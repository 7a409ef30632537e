"""Batched M3TSZ encoder — hybrid host/device write-seal hot loop.

Byte-exact with the scalar oracle (``m3tsz_scalar.Encoder``) and hence
wire-compatible with the reference encoder
(ref: src/dbnode/encoding/m3tsz/{encoder.go:89-249,
timestamp_encoder.go:67-213, float_encoder_iterator.go:47-113,
int_sig_bits_tracker.go:35-91} and src/dbnode/encoding/scheme.go:28-63).

Why hybrid: this TPU platform emulates f64, and the emulation is lossy
at the *transfer* boundary — a float64 loses low mantissa bits the
moment it is device_put (measured: 1.2654214710460525 does not round-
trip).  Byte-exact encoding therefore cannot consume device-resident
f64 values at all.  The split that follows from that hardware truth:

  host (numpy, exact IEEE f64):  the value grammar — int/float
      conversion (m3tsz.go:78-118), significant-bit tracker, XOR
      control — a branchy, precision-critical state machine over
      cheap elementwise ops.  Vectorized across all L series per
      time step (T-step Python loop, ~30 numpy ops per step).
  device (jit, pure integer ops — exact under X64 emulation):
      timestamp delta-of-delta fields (dod = diff(diff(ts)) —
      elementwise, no scan) and the bit-packing of the [L, 2+3T]
      variable-width field matrix into wire words via exclusive
      prefix-sum + 3-word scatter-add.  This is the throughput-bound
      part and it is scan-free: the whole device program is flat
      vectorized integer code.

Scope: int-optimized streams at one fixed time unit with no
annotations — the production batch-seal shape.  Exotic streams
(mid-stream time-unit changes, annotations) take the scalar path at
the wire edge.
"""

from __future__ import annotations

import subprocess
import threading

import jax
import jax.numpy as jnp
import numpy as np

from m3_tpu.ops import m3tsz_scalar as tsz
from m3_tpu.ops.bitstream import PAD_WORDS, unpack_stream
from m3_tpu.ops.kernel_telemetry import instrument_kernel
from m3_tpu.utils import instrument, xtime

U64 = jnp.uint64
I64 = jnp.int64
U32 = jnp.uint32
I32 = jnp.int32

_SECOND = xtime.Unit.SECOND.nanos
_MAX_BITS_FIRST = 64 + 36 + 17 + 64  # start64 + t + ctl + pay
_MAX_BITS_NEXT = 36 + 17 + 64
_EOS_BITS = tsz.MARKER_OPCODE_BITS + tsz.MARKER_VALUE_BITS  # 11

_U = np.uint64
_ONE = _U(1)


def _u64(x) -> jax.Array:
    return jnp.asarray(x, dtype=U64)


# ---------------------------------------------------------------------------
# host-side vectorized bit helpers (numpy, exact)
# ---------------------------------------------------------------------------


def _np_popcount64(x: np.ndarray) -> np.ndarray:
    x = x - ((x >> _U(1)) & _U(0x5555555555555555))
    x = (x & _U(0x3333333333333333)) + ((x >> _U(2)) & _U(0x3333333333333333))
    x = (x + (x >> _U(4))) & _U(0x0F0F0F0F0F0F0F0F)
    return ((x * _U(0x0101010101010101)) >> _U(56)).astype(np.int32)


def _np_clz64(x: np.ndarray) -> np.ndarray:
    y = x.copy()
    for s in (1, 2, 4, 8, 16, 32):
        y |= y >> _U(s)
    return 64 - _np_popcount64(y)


def _np_ctz64(x: np.ndarray) -> np.ndarray:
    """ctz(0) == 0, matching the reference's LeadingAndTrailingZeros
    (ref: src/dbnode/encoding/encoding.go:35-43)."""
    lsb = x & (~x + _ONE)
    return np.where(x == 0, 0, 63 - _np_clz64(lsb)).astype(np.int32)


def _np_nsb64(x: np.ndarray) -> np.ndarray:
    """Significant bits of uint64 (0 for 0) — ref: encoding.go:29."""
    return (64 - _np_clz64(x)).astype(np.int32)


def _np_float_bits(v: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(v, dtype=np.float64).view(np.uint64)


# ---------------------------------------------------------------------------
# convert_to_int_float, vectorized numpy (ref: m3tsz.go:78-118)
# ---------------------------------------------------------------------------

_MULTIPLIERS = np.asarray(tsz.MULTIPLIERS, dtype=np.float64)


def _np_convert_to_int_float(v: np.ndarray, cur_max_mult: np.ndarray):
    """Elementwise (val, mult, is_float).  NaN/huge values go float."""
    with np.errstate(invalid="ignore", over="ignore"):
        tr = np.trunc(v)
        fast = (cur_max_mult == 0) & (v < tsz.MAX_INT64) & (v - tr == 0)

        sign = np.where(v < 0, -1.0, 1.0)
        mult_pow = _MULTIPLIERS[np.clip(cur_max_mult, 0, tsz.MAX_MULT)]
        val = np.abs(v) * mult_pow
        mult = cur_max_mult.astype(np.int32)

        found = fast.copy()
        res_val = np.where(fast, tr, 0.0)
        res_mult = np.zeros_like(mult)
        for _ in range(tsz.MAX_MULT + 1):
            active = (~found) & (mult <= tsz.MAX_MULT) & (val < tsz.MAX_OPT_INT)
            ip = np.trunc(val)
            frac = val - ip
            nxt = ip + 1
            c1 = frac == 0
            c2 = (frac < 0.1) & (np.nextafter(val, 0.0) <= ip)
            c3 = (frac > 0.9) & (np.nextafter(val, np.inf) >= nxt)
            hit = active & (c1 | c2 | c3)
            hit_val = np.where(c1 | c2, sign * ip, sign * nxt)
            res_val = np.where(hit, hit_val, res_val)
            res_mult = np.where(hit, mult, res_mult)
            found |= hit
            step = active & ~hit
            val = np.where(step, val * 10.0, val)
            mult = np.where(step, mult + 1, mult)

    is_float = ~found
    res_val = np.where(is_float, v, res_val)
    res_mult = np.where(is_float, 0, res_mult)
    return res_val, res_mult.astype(np.int32), is_float


# ---------------------------------------------------------------------------
# host-side field builders (numpy mirrors of the wire grammar)
# ---------------------------------------------------------------------------


def _np_sig_mult_fields(num_sig, sig, max_mult, mult, float_changed):
    """Sig-bit + multiplier update prefix (ref: encoder.go:206-238)."""
    sig_changed = num_sig != sig
    s6 = (sig - 1).astype(_U) & _U(0x3F)
    f1_bits = np.where(
        sig_changed, np.where(sig == 0, _U(0b10), (_U(0b11) << _U(6)) | s6), _U(0)
    )
    f1_n = np.where(sig_changed, np.where(sig == 0, 2, 8), 1).astype(np.int32)

    up = mult > max_mult
    rewrite = (~up) & (max_mult == mult) & float_changed
    f2_bits = np.where(
        up,
        _U(0b1000) | mult.astype(_U),
        np.where(rewrite, _U(0b1000) | max_mult.astype(_U), _U(0)),
    )
    f2_n = np.where(up | rewrite, 4, 1).astype(np.int32)
    new_max_mult = np.where(up, mult, max_mult)

    bits = (f1_bits << f2_n.astype(_U)) | f2_bits
    return bits, f1_n + f2_n, new_max_mult


def _np_track_sig(num_sig, chl, nlow, nsb):
    """Hysteresis tracker step (ref: int_sig_bits_tracker.go:68-91)."""
    gt = nsb > num_sig
    dropbig = (~gt) & (num_sig - nsb >= tsz.SIG_DIFF_THRESHOLD)
    new_chl = np.where(dropbig & ((nlow == 0) | (nsb > chl)), nsb, chl)
    nlow1 = np.where(dropbig, nlow + 1, np.where(gt, nlow, 0)).astype(np.int32)
    fire = dropbig & (nlow1 >= tsz.SIG_REPEAT_THRESHOLD)
    tracked = np.where(gt, nsb, np.where(fire, new_chl, num_sig)).astype(np.int32)
    new_nlow = np.where(fire, 0, nlow1).astype(np.int32)
    return tracked, new_chl.astype(np.int32), new_nlow


def _np_xor_fields(prev_xor, xor):
    """Float XOR control + payload (ref: float_encoder_iterator.go:63-113)."""
    xz = xor == 0
    pl, pt = _np_clz64(prev_xor), _np_ctz64(prev_xor)
    lead, trail = _np_clz64(xor), _np_ctz64(xor)
    contained = (lead >= pl) & (trail >= pt)
    m_prev = (64 - pl - pt).astype(np.int32)
    m_cur = (64 - lead - trail).astype(np.int32)
    ctl_bits = np.where(
        xz,
        _U(0),
        np.where(
            contained,
            _U(0b10),
            (_U(0b11) << _U(12)) | (lead.astype(_U) << _U(6)) | (m_cur - 1).astype(_U),
        ),
    )
    ctl_n = np.where(xz, 1, np.where(contained, 2, 14)).astype(np.int32)
    pay_bits = np.where(
        xz, _U(0), np.where(contained, xor >> pt.astype(_U), xor >> trail.astype(_U))
    )
    pay_n = np.where(xz, 0, np.where(contained, m_prev, m_cur)).astype(np.int32)
    return ctl_bits, ctl_n, pay_bits, pay_n


# ---------------------------------------------------------------------------
# host value-grammar state machine
# ---------------------------------------------------------------------------


def prepare_value_fields(values: np.ndarray, n_valid: np.ndarray):
    """Run the value grammar for L series over T steps on the host.

    values:  [L, T] float64 (host numpy — never routed via the device)
    n_valid: [L] int32

    Returns (ctl_bits, ctl_n, pay_bits, pay_n), each [L, T]
    (uint64/int32), the per-step value control + payload fields to be
    interleaved with the device-computed time fields and bit-packed.
    Mirrors _encode_first_value / _encode_next_value of the original
    all-device kernel (oracle-verified), now in exact host arithmetic.
    """
    values = np.asarray(values, dtype=np.float64)
    n_valid = np.asarray(n_valid, dtype=np.int32)
    L, T = values.shape

    prev_float = np.zeros(L, _U)
    prev_xor = np.zeros(L, _U)
    int_val = np.zeros(L, np.float64)
    num_sig = np.zeros(L, np.int32)
    chl = np.zeros(L, np.int32)
    nlow = np.zeros(L, np.int32)
    max_mult = np.zeros(L, np.int32)
    is_float = np.zeros(L, bool)

    ctl_bits = np.zeros((L, T), _U)
    ctl_n = np.zeros((L, T), np.int32)
    pay_bits = np.zeros((L, T), _U)
    pay_n = np.zeros((L, T), np.int32)

    def put(t, valid, cb, cn, pb, pn):
        ctl_bits[:, t] = np.where(valid, cb, _U(0))
        ctl_n[:, t] = np.where(valid, cn, 0)
        pay_bits[:, t] = np.where(valid, pb, _U(0))
        pay_n[:, t] = np.where(valid, pn, 0)

    def merge(valid, new, old):
        return np.where(valid, new, old)

    # --- first datapoint (ref: encoder.go:111-145) ---
    v = values[:, 0]
    valid = n_valid > 0
    val, mult, go_float = _np_convert_to_int_float(v, np.zeros_like(max_mult))
    fb = _np_float_bits(v)
    with np.errstate(invalid="ignore"):
        mag = np.minimum(np.abs(val), 2.0**63)
        mag = np.where(np.isnan(mag), 2.0**63, mag).astype(_U)
    sig_first = _np_nsb64(mag)
    sm_bits, sm_n, mm_int = _np_sig_mult_fields(
        num_sig, sig_first, max_mult, mult, np.zeros_like(go_float)
    )
    with np.errstate(invalid="ignore"):
        add = (val >= 0).astype(_U)
    ctl_int = (sm_bits << _ONE) | add  # '0' mode bit + sig/mult + sign
    n_ctl_int = 1 + sm_n + 1
    put(
        0,
        valid,
        np.where(go_float, _U(1), ctl_int),
        np.where(go_float, 1, n_ctl_int),
        np.where(go_float, fb, mag),
        np.where(go_float, 64, sig_first),
    )
    prev_float = merge(valid & go_float, fb, prev_float)
    prev_xor = merge(valid & go_float, fb, prev_xor)
    int_val = merge(valid & ~go_float, val, int_val)
    num_sig = merge(valid & ~go_float, sig_first, num_sig)
    max_mult = merge(valid & ~go_float, mm_int, max_mult)
    is_float = merge(valid, go_float, is_float)

    # --- remaining datapoints (ref: encoder.go:147-204) ---
    for t in range(1, T):
        v = values[:, t]
        valid = t < n_valid
        val, mult, isf = _np_convert_to_int_float(v, max_mult)
        with np.errstate(invalid="ignore"):
            diff = int_val - val
            go_float = isf | (diff >= tsz.MAX_INT64) | (diff <= -tsz.MAX_INT64)
            go_float |= np.isnan(diff)

        fb = _np_float_bits(val)
        b_trans = go_float & ~is_float  # int -> float: '001' + raw64
        same_bits = fb == prev_float
        b_frep = go_float & is_float & same_bits  # '01'
        b_fxor = go_float & is_float & ~same_bits  # '1' + xor
        xor = prev_float ^ fb
        xc_bits, xc_n, xp_bits, xp_n = _np_xor_fields(prev_xor, xor)

        b_int = ~go_float
        rep_i = b_int & (diff == 0) & ~is_float & (mult == max_mult)  # '01'
        with np.errstate(invalid="ignore"):
            add = (diff < 0).astype(_U)
            mag = np.where(np.isnan(diff), 0.0, np.abs(diff)).astype(_U)
        nsb = _np_nsb64(mag)
        tracked, chl2, nlow2 = _np_track_sig(num_sig, chl, nlow, nsb)
        float_changed = is_float
        need_up = (mult > max_mult) | (num_sig != tracked) | float_changed
        sm_bits, sm_n, mm_up = _np_sig_mult_fields(
            num_sig, tracked, max_mult, mult, float_changed
        )
        ctl_up = (sm_bits << _ONE) | add  # '000' + sigmult + sign
        n_up = 3 + sm_n + 1
        ctl_nu = _U(0b10) | add  # '1' + sign
        b_iup = b_int & ~rep_i & need_up
        b_inu = b_int & ~rep_i & ~need_up

        cb = np.where(
            b_trans,
            _U(0b001),
            np.where(
                b_frep | rep_i,
                _U(0b01),
                np.where(
                    b_fxor,
                    (_ONE << xc_n.astype(_U)) | xc_bits,
                    np.where(b_iup, ctl_up, ctl_nu),
                ),
            ),
        )
        cn = np.where(
            b_trans,
            3,
            np.where(
                b_frep | rep_i, 2, np.where(b_fxor, 1 + xc_n, np.where(b_iup, n_up, 2))
            ),
        )
        pb = np.where(b_trans, fb, np.where(b_fxor, xp_bits, mag))
        pn = np.where(
            b_trans,
            64,
            np.where(
                b_fxor, xp_n, np.where(b_iup, tracked, np.where(b_inu, num_sig, 0))
            ),
        )
        put(t, valid, cb, cn, pb, pn)

        int_emit = b_iup | b_inu | rep_i
        prev_float = merge(valid & (b_trans | b_fxor), fb, prev_float)
        prev_xor = merge(valid & b_trans, fb, merge(valid & b_fxor, xor, prev_xor))
        int_val = merge(valid & int_emit, val, int_val)
        num_sig = merge(valid & (b_iup | b_inu), tracked, num_sig)
        chl = merge(valid & (b_iup | b_inu), chl2, chl)
        nlow = merge(valid & (b_iup | b_inu), nlow2, nlow)
        max_mult = merge(
            valid & b_trans, mult, merge(valid & b_iup, mm_up, max_mult)
        )
        is_float = merge(valid & b_trans, True, merge(valid & (b_iup | b_inu), False, is_float))

    return ctl_bits, ctl_n, pay_bits, pay_n


# ---------------------------------------------------------------------------
# device kernel: time fields + bit packing (pure integer ops, scan-free)
# ---------------------------------------------------------------------------


def _time_fields(timestamps: jax.Array, start: jax.Array, n_valid: jax.Array):
    """[L, T] delta-of-delta records, elementwise (no scan).

    ref: timestamp_encoder.go:174-213, scheme.go:42-52
    (second/millisecond default bucket = 32 bits).
    """
    L, T = timestamps.shape
    prev_t = jnp.concatenate([start[:, None], timestamps[:, :-1]], axis=1)
    delta = timestamps - prev_t
    prev_delta = jnp.concatenate([jnp.zeros((L, 1), I64), delta[:, :-1]], axis=1)
    raw_dod = delta - prev_delta
    unit = I64(_SECOND)
    dod = jnp.where(raw_dod < 0, -((-raw_dod) // unit), raw_dod // unit)

    d = dod.astype(U64)
    z = dod == 0
    in7 = (dod >= -64) & (dod <= 63)
    in9 = (dod >= -256) & (dod <= 255)
    in12 = (dod >= -2048) & (dod <= 2047)
    bits = jnp.where(
        z,
        _u64(0),
        jnp.where(
            in7,
            (_u64(0b10) << 7) | (d & _u64(0x7F)),
            jnp.where(
                in9,
                (_u64(0b110) << 9) | (d & _u64(0x1FF)),
                jnp.where(
                    in12,
                    (_u64(0b1110) << 12) | (d & _u64(0xFFF)),
                    (_u64(0b1111) << 32) | (d & _u64(0xFFFFFFFF)),
                ),
            ),
        ),
    )
    nbits = jnp.where(
        z, I32(1), jnp.where(in7, I32(9), jnp.where(in9, I32(12), jnp.where(in12, I32(16), I32(36))))
    )
    valid = jnp.arange(T, dtype=I32)[None, :] < n_valid[:, None]
    return jnp.where(valid, bits, _u64(0)), jnp.where(valid, nbits, 0)


def _pack_fields(bits: jax.Array, nbits: jax.Array, n_words: int):
    """Scatter [L, F] (bits, nbits) fields into [L, W] uint32 words.

    The vectorized OStream (ref: src/dbnode/encoding/ostream.go:180
    WriteBits): exclusive prefix-sum gives each field its absolute bit
    offset; each field touches at most 3 consecutive 32-bit words.
    """
    L, F = bits.shape
    n64 = nbits.astype(U64)
    offs = (jnp.cumsum(nbits, axis=1) - nbits).astype(I32)
    total = offs[:, -1] + nbits[:, -1]

    aligned = jnp.where(nbits > 0, bits << (_u64(64) - n64), _u64(0))
    b = (offs & 31).astype(U64)
    w0 = (offs >> 5).astype(I32)
    main = aligned >> b
    spill = jnp.where(b > 0, aligned << (_u64(64) - b), _u64(0))
    v0 = (main >> 32).astype(U32)
    v1 = main.astype(U32)
    v2 = (spill >> 32).astype(U32)

    lane = jnp.arange(L, dtype=I32)[:, None]
    base = lane * n_words + w0
    flat = jnp.zeros((L * n_words,), U32)
    flat = flat.at[base.ravel()].add(v0.ravel())
    flat = flat.at[(base + 1).ravel()].add(v1.ravel())
    flat = flat.at[(base + 2).ravel()].add(v2.ravel())
    return flat.reshape(L, n_words), total


def pack_encode(
    timestamps: jax.Array,
    start: jax.Array,
    n_valid: jax.Array,
    ctl_bits: jax.Array,
    ctl_n: jax.Array,
    pay_bits: jax.Array,
    pay_n: jax.Array,
):
    """Device half of the encoder: time fields + wire packing.

    All operands and every op are integer-typed, so the result is exact
    on emulated-X64 accelerator backends (unlike anything f64).

    Returns (words [L, W] uint32 big-endian, nbits [L] int32 — exact bit
    length including the EOS marker; byte length = ceil(nbits/8)).
    """
    L, T = timestamps.shape
    has_any = n_valid > 0
    t_bits, t_n = _time_fields(timestamps, start, n_valid)

    start_bits = start.astype(U64)[:, None]
    start_n = jnp.where(has_any, I32(64), I32(0))[:, None]
    rec_bits = jnp.stack([t_bits, ctl_bits, pay_bits], axis=2).reshape(L, 3 * T)
    rec_n = jnp.stack([t_n, ctl_n, pay_n], axis=2).reshape(L, 3 * T)
    eos_bits = jnp.full(
        (L, 1), (tsz.MARKER_OPCODE << tsz.MARKER_VALUE_BITS) | tsz.MARKER_EOS, U64
    )
    eos_n = jnp.where(has_any, I32(_EOS_BITS), I32(0))[:, None]

    fields = jnp.concatenate([start_bits, rec_bits, eos_bits], axis=1)
    fields_n = jnp.concatenate([start_n, rec_n, eos_n], axis=1)
    return _pack_fields(fields, fields_n, n_words_for(T))


# the seal's device program, under kernel telemetry as `pack_encode`;
# its one production caller (encode_to_streams) copies the result to
# the host at once, so the wrapper's fence moves no synchronisation
_pack_encode_jit = instrument_kernel("pack_encode")(jax.jit(pack_encode))


# compile-cache fingerprint memo behind
# m3_encode_compile_cache_{hits,misses}_total (the query planner's
# pattern, query/plan.py).  jax.jit already caches programs by abstract
# shape; the memo adds observability — a miss is a fresh XLA compile of
# the pack kernel (seconds on a cold shape), a hit a table lookup.  The
# seal path buckets (L, T) to powers of two precisely to keep this set
# small, and the counters make a bucketing regression visible on a
# dashboard instead of as mystery seal-tail latency.  Bounded: on
# overflow the epoch resets (counters stay monotonic; a handful of
# "misses" re-count — the jit cache itself is unaffected).
_FP_CAP = 1024
_FP_LOCK = threading.Lock()
_FP_SEEN: set = set()  # allow-unbounded-cache: epoch-reset at _FP_CAP


def note_encode_fingerprint(fp) -> bool:
    """Record an encode-shape fingerprint; True = compile-cache hit
    (an equal shape already compiled this process)."""
    with _FP_LOCK:
        hit = fp in _FP_SEEN
        if hit:
            instrument.counter(
                "m3_encode_compile_cache_hits_total").inc()
        else:
            if len(_FP_SEEN) >= _FP_CAP:
                _FP_SEEN.clear()
            _FP_SEEN.add(fp)
            instrument.counter(
                "m3_encode_compile_cache_misses_total").inc()
    # device-ledger inventory: /debug/device lists encode shape
    # buckets with hit counts and last-use for manual eviction
    from m3_tpu import observe
    led = observe.device_ledger()
    led.compile_cache_register_evictor("encode", _evict_encode_cache)
    led.compile_cache_note(
        "encode", repr(fp), bucket="x".join(str(d) for d in fp[1:]),
        hit=hit)
    return hit


def _evict_encode_cache() -> int:
    """Registered /debug/device evictor: drops the fingerprint memo
    AND the jitted pack kernel's compiled programs."""
    with _FP_LOCK:
        n = len(_FP_SEEN)
        _FP_SEEN.clear()
    _pack_encode_jit.clear_cache()
    return n


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


def _prepare(values: np.ndarray, n_valid: np.ndarray):
    """Production prepare: threaded C++ (native/m3tsz_prepare.cc) with
    the numpy state machine as fallback when the toolchain is absent.
    Both emit identical fields (asserted in tests)."""
    try:
        from m3_tpu.utils.native import prepare_value_fields_native

        return prepare_value_fields_native(values, n_valid)
    except (OSError, subprocess.CalledProcessError, FileNotFoundError):
        return prepare_value_fields(values, n_valid)


def n_words_for(n_dp: int) -> int:
    max_bits = _MAX_BITS_FIRST + max(n_dp - 1, 0) * _MAX_BITS_NEXT + _EOS_BITS
    return (max_bits + 31) // 32 + PAD_WORDS + 1


def encode_batched(
    timestamps, values, start, n_valid
) -> tuple[jax.Array, jax.Array]:
    """Encode L series in parallel into M3TSZ wire streams.

    timestamps: [L, T] int64 unix-nanos (second-aligned, ascending)
    values:     [L, T] float64 — HOST data (numpy); float64 routed
                through an emulated-f64 accelerator loses mantissa
                bits in transfer, so values never touch the device
    start:      [L] int64 stream (block) start unix-nanos
    n_valid:    [L] int32 — datapoints per lane (left-aligned ragged)

    Returns (words [L, W] uint32 big-endian, nbits [L] int32).
    """
    values = np.asarray(values, dtype=np.float64)
    n_valid_np = np.asarray(n_valid, dtype=np.int32)
    note_encode_fingerprint(("batched",) + values.shape)
    cb, cn, pb, pn = _prepare(values, n_valid_np)
    ts = np.asarray(timestamps, np.int64)
    st = np.asarray(start, np.int64)
    from m3_tpu import observe
    scratch = (ts.nbytes + st.nbytes + n_valid_np.nbytes + cb.nbytes
               + cn.nbytes + pb.nbytes + pn.nbytes)
    # scoped device-ledger borrow: the encode argument upload is
    # resident for exactly the duration of the pack kernel
    with observe.device_ledger().borrow("encode_scratch", scratch,
                                        count=7):
        return _pack_encode_jit(
            jnp.asarray(ts),
            jnp.asarray(st),
            jnp.asarray(n_valid_np),
            jnp.asarray(cb),
            jnp.asarray(cn),
            jnp.asarray(pb),
            jnp.asarray(pn),
        )


def encode_to_streams(
    timestamps: np.ndarray, values: np.ndarray, start: np.ndarray, n_valid: np.ndarray
) -> list[bytes]:
    """Host convenience: hybrid batched encode -> per-lane wire bytes."""
    words, nbits = encode_batched(timestamps, values, start, n_valid)
    words = np.asarray(words)
    nbits = np.asarray(nbits)
    capacity = (words.shape[1] - PAD_WORDS - 1) * 32
    if nbits.size and int(nbits.max()) > capacity:
        # the device scatter CLIPS out-of-range word indexes, so an
        # overflow would silently truncate a stream instead of failing
        instrument.invariant_violated(
            "encoded stream exceeds word capacity",
            max_bits=int(nbits.max()), capacity=capacity)
    return [
        unpack_stream(words[i], ((int(nbits[i]) + 7) // 8) * 8) for i in range(words.shape[0])
    ]
