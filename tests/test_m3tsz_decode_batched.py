"""Batched (device) decoder vs the scalar oracle.

Every grammar path the fast kernel claims to support must decode
identically to the wire-verified scalar codec; unsupported constructs
must flag and fall back, never corrupt.
"""

import functools
import math
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from m3_tpu.ops import m3tsz_decode as dec
from m3_tpu.ops import m3tsz_scalar as tsz
from m3_tpu.ops.bitstream import I32, U64, pack_streams, take_top
from m3_tpu.ops.m3tsz_decode import decode_streams
from m3_tpu.utils import xtime

SEC = xtime.SECOND
START = 1_600_000_000 * SEC


def encode_all(series, int_optimized=True, start=START):
    return [
        tsz.encode_series(ts, vs, start, int_optimized=int_optimized)
        for ts, vs in series
    ]


def check(series, int_optimized=True, start=START, max_dp=None):
    streams = encode_all(series, int_optimized=int_optimized, start=start)
    max_dp = max_dp or max(len(ts) for ts, _ in series)
    # exercise BOTH serving tiers on the CPU suite: the XLA kernel
    # (prefer_native=False — the TPU path; it must not lose coverage to
    # the CPU-native routing) and whatever the auto-dispatch picks
    for prefer_native in (False, None):
        got_ts, got_vs, valid = decode_streams(
            streams, max_dp, int_optimized=int_optimized,
            prefer_native=prefer_native,
        )
        for lane, (ts, vs) in enumerate(series):
            n = min(len(ts), max_dp)
            assert valid[lane, :n].all(), f"lane {lane} invalid early"
            assert not valid[lane, n:].any(), f"lane {lane} valid past end"
            np.testing.assert_array_equal(
                got_ts[lane, :n], ts[:n], err_msg=f"lane {lane} ts")
            want = np.asarray(vs[:n])
            got = got_vs[lane, :n]
            same = (got == want) | (np.isnan(got) & np.isnan(want))
            assert same.all(), (
                f"lane {lane} values: {got[~same][:4]} != {want[~same][:4]}")


def gauge(n, seed, step=10):
    rng = random.Random(seed)
    ts, vs = [], []
    t, v = START, float(rng.randint(0, 1000))
    for _ in range(n):
        t += step * SEC
        v = max(0.0, v + rng.choice([-2.0, -1.0, 0.0, 0.0, 1.0, 2.0]))
        ts.append(t)
        vs.append(v)
    return ts, vs


def test_int_gauges_roundtrip():
    check([gauge(60, s) for s in range(8)])


def test_single_point_lanes():
    check([([START + 10 * SEC], [5.0]), ([START + 20 * SEC], [7.5])])


def test_ragged_lengths():
    check([gauge(n, n) for n in (1, 3, 17, 64, 100)])


def test_float_values_int_optimized():
    ts = [START + i * 10 * SEC for i in range(50)]
    vs = [math.sin(i / 7.0) * 100 for i in range(50)]
    check([(ts, vs)])


def test_mode_transitions():
    ts = [START + i * 10 * SEC for i in range(12)]
    vs = [1.0, 2.0, math.pi, math.pi, math.e, 5.0, 5.0, 6.5, 7.0, math.sqrt(2), 9.0, 9.0]
    check([(ts, vs)])


def test_repeats_and_zero_sig():
    ts = [START + i * 10 * SEC for i in range(30)]
    check([(ts, [42.0] * 30)])


def test_decimal_multipliers():
    ts = [START + i * 10 * SEC for i in range(40)]
    vs = [round(1.5 + 0.001 * i, 3) for i in range(40)]
    check([(ts, vs)])


def test_negative_values():
    ts = [START + i * 10 * SEC for i in range(20)]
    vs = [(-1.0) ** i * i * 100 for i in range(20)]
    check([(ts, vs)])


def test_all_time_buckets():
    deltas = [10, 10, 70, 3, 500, 500, 2000, 100000, 1, 10, 10]
    ts = [START]
    for d in deltas:
        ts.append(ts[-1] + d * SEC)
    check([(ts, [float(i) for i in range(len(ts))])])


def test_nan_inf():
    ts = [START + i * 10 * SEC for i in range(6)]
    vs = [1.0, math.nan, math.inf, -math.inf, 2.0, 3.0]
    check([(ts, vs)])


def test_float_only_mode():
    ts = [START + i * 10 * SEC for i in range(50)]
    vs = [math.sin(i / 3.0) * 10 for i in range(50)]
    check([(ts, vs)], int_optimized=False)
    check([gauge(30, 3)], int_optimized=False)


def test_max_dp_truncation():
    check([gauge(100, 1)], max_dp=40)


def test_fallback_on_annotation():
    enc = tsz.Encoder(START)
    enc.encode(START + 10 * SEC, 1.0, annotation=b"schema")
    enc.encode(START + 20 * SEC, 2.0)
    streams = [enc.finalize(), encode_all([gauge(5, 9)])[0]]
    got_ts, got_vs, valid = decode_streams(streams, 5)
    assert valid[0, :2].all() and not valid[0, 2:].any()
    np.testing.assert_array_equal(got_ts[0, :2], [START + 10 * SEC, START + 20 * SEC])
    np.testing.assert_array_equal(got_vs[0, :2], [1.0, 2.0])
    assert valid[1, :5].all()


def test_fallback_on_unaligned_start():
    # unaligned start writes a time-unit marker first -> fast path flags it
    start = START + 123
    ts = [start + 1 + i * 10 * SEC for i in range(5)]
    vs = [float(i) for i in range(5)]
    streams = [tsz.encode_series(ts, vs, start)]
    got_ts, got_vs, valid = decode_streams(streams, 5)
    assert valid[0, :5].all()
    np.testing.assert_array_equal(got_ts[0, :5], ts)


def test_truncated_stream_lane_isolated():
    good = encode_all([gauge(20, 5)])[0]
    bad = good[: len(good) // 3]
    got_ts, got_vs, valid = decode_streams([bad, good], 20)
    assert valid[1, :20].all()  # neighbor unaffected
    # truncated lane keeps only its cleanly-decoded prefix
    assert valid[0].sum() < 20


def test_generative_vs_oracle():
    rng = random.Random(99)
    series = []
    for _ in range(20):
        n = rng.randint(1, 120)
        t = START
        ts, vs = [], []
        for _ in range(n):
            t += rng.choice([1, 10, 10, 10, 60, 300]) * SEC
            ts.append(t)
            r = rng.random()
            if r < 0.45:
                vs.append(float(rng.randint(0, 10**9)))
            elif r < 0.65:
                vs.append(round(rng.uniform(0, 100), rng.randint(0, 4)))
            elif r < 0.85:
                vs.append(rng.uniform(-1e6, 1e6))
            else:
                vs.append(vs[-1] if vs else 0.0)
        series.append((ts, vs))
    # oracle-equivalence: compare to what the scalar decoder produces
    streams = encode_all(series)
    max_dp = max(len(ts) for ts, _ in series)
    got_ts, got_vs, valid = decode_streams(streams, max_dp)
    for lane, blob in enumerate(streams):
        want_t, want_v = tsz.decode_series(blob)
        n = len(want_t)
        assert valid[lane, :n].all()
        np.testing.assert_array_equal(got_ts[lane, :n], want_t)
        np.testing.assert_array_equal(got_vs[lane, :n], want_v)


# --- the word window and the first record's peel (PR 39) ---
#
# decode_batched reads a stream's first record in place, before its
# scan, and past WIN_WORDS words a row takes the later records' reads
# from a per-row word window refilled every WIN_STEPS steps.  The
# parent's step is kept here in its plain form: every step reads the
# whole [rows, words] tensor and builds both plans, a first record's
# and a later one's, picked by a `started` flag.  The two are the same
# function of the bits, and on sound rows the scalar oracle's.

MS = xtime.Unit.MILLISECOND


def _plain_window128(words, cursor):
    """The parent's read: five words at each row's cursor by a one-hot
    OR-reduce over the whole [rows, words] tensor."""
    base = cursor >> 5
    off = (cursor & 31).astype(U64)
    diff = jnp.arange(words.shape[1], dtype=I32)[None, :] - base[:, None]
    w64 = words.astype(U64)
    z = jnp.zeros((), U64)
    a = jnp.where(diff == 0, w64 << U64(32), z) | jnp.where(diff == 1, w64, z)
    b = jnp.where(diff == 2, w64 << U64(32), z) | jnp.where(diff == 3, w64, z)
    c = jnp.where(diff == 4, w64 << U64(32), z)
    w01, w23, w45 = jax.lax.reduce(
        (a, b, c), (z, z, z),
        lambda acc, x: tuple(p | q for p, q in zip(acc, x)), (1,))
    aligned = off == 0
    inv = U64(64) - jnp.where(aligned, U64(1), off)
    hi = jnp.where(aligned, w01, (w01 << off) | (w23 >> inv))
    lo = jnp.where(aligned, w23, (w23 << off) | (w45 >> inv))
    return hi, lo


@functools.partial(jax.jit, static_argnames=(
    "n_steps", "int_optimized", "unit_nanos", "flag_truncation"))
def _plain_decode_batched(words, nbits, n_steps, int_optimized=True,
                          unit_nanos=SEC, flag_truncation=False):
    """decode_batched as the parent had it (one scan, whole-row reads,
    two plans a step), on the module's own record grammar."""
    words = words.astype(jnp.uint32)

    def step(carry, _):
        st, started = carry
        hi, lo = _plain_window128(words, st.cursor)
        t, d, t_len, eos, bad = dec._parse_timestamp(hi, st, unit_nanos)
        active = ~st.done & ~st.error
        emit = active & ~eos & ~bad
        st2 = st._replace(
            error=st.error | (bad & active), done=st.done | (eos & active),
            prev_time=jnp.where(emit, t, st.prev_time),
            prev_delta=jnp.where(emit, d, st.prev_delta))
        cwin = hi << jnp.minimum(t_len, 63).astype(U64)
        plan = jax.tree.map(
            lambda n, f: jnp.where(started, n, f),
            dec._plan_value(cwin, st2, int_optimized, first=False),
            dec._plan_value(cwin, st2, int_optimized, first=True))
        payload = take_top(dec._mid_window(hi, lo, t_len + plan.ctrl),
                           plan.payload_len)
        st3 = dec._merge(st2, dec._apply_value(st2, plan, payload), emit)
        st3 = st3._replace(cursor=st2.cursor + jnp.where(
            emit, t_len + plan.ctrl + plan.payload_len, 0))
        st3 = st3._replace(
            error=st3.error | ((st3.cursor > nbits) & ~st3.done))
        valid = emit & ~st3.error
        return (st3, started | emit), (st3.prev_time, dec._emit_value(st3),
                                       valid)

    scan_len = n_steps + 1 if flag_truncation else n_steps
    st = dec._init_state(words.T, nbits)
    (st, _), (ts, vs, valid) = jax.lax.scan(
        step, (st, jnp.zeros(nbits.shape, jnp.bool_)), None, length=scan_len)
    ts, vs, valid = (jnp.moveaxis(x, 0, 1)[:, :n_steps]
                     for x in (ts, vs, valid))
    error = st.error | ~st.done if flag_truncation else st.error
    return ts, vs, valid, valid.sum(axis=1, dtype=I32), error


def dense_series(n, seed):
    """The longest records the encoder writes: random float64 bit
    patterns (XOR records with no shared zero bits) at millisecond
    stamps whose deltas swing over 30 bits (the 32-bit time bucket): a
    record near 100 bits at every step."""
    rng = np.random.default_rng(seed)
    vs = rng.integers(0, 1 << 64, n, dtype=np.uint64).view(np.float64)
    ts = START + np.cumsum(rng.integers(1, 1 << 30, n)) * MS.nanos
    return [int(t) for t in ts], [float(v) for v in vs]


def counter(n, seed):
    """A counter at 10 s with increments uniform on 0..99 (the benchmark
    fleets' value law): some 11 bits a sample, int-optimised."""
    rng = np.random.default_rng(seed)
    ts = [START + (i + 1) * 10 * SEC for i in range(n)]
    return ts, [float(v) for v in np.cumsum(rng.integers(0, 100, n))]


def dense_streams(counts, seed=0):
    return [tsz.encode_series(*dense_series(n, seed + i), START, unit=MS)
            for i, n in enumerate(counts)]


def _full_floats():
    streams = dense_streams([300] * 4 + [1, 40], seed=50)
    # a record is 103 bits (a contained XOR) or 114-115 (its own lead and
    # length fields) of the 116 the grammar allows
    bits = (len(streams[0]) * 8 - 64) / 300
    assert 0.85 * dec.MAX_RECORD_BITS < bits <= dec.MAX_RECORD_BITS, bits
    return dict(streams=streams, n_steps=300, unit_nanos=MS.nanos,
                flag_truncation=True, error=[False] * 6)


def _counters():
    return dict(streams=encode_all([counter(450, s) for s in range(5)]),
                n_steps=450, flag_truncation=True, error=[False] * 5)


def _mode_change():
    """Ints, then floats, then ints again, twice over: the float/int
    mode bit flips mid-stream, far enough in to lie in a later window."""
    ts = [START + (i + 1) * 10 * SEC for i in range(400)]
    vs = [float(i) if (i // 90) % 2 == 0 else math.sin(i / 7.0) * 100
          for i in range(400)]
    return dict(streams=encode_all([(ts, vs), counter(400, 4)]),
                n_steps=400, flag_truncation=True, error=[False] * 2)


def _ends():
    """Streams that end inside a window, on the last word of the batch
    (the widest row) and within their first window."""
    streams = encode_all([counter(n, n) for n in (450, 200, 5, 449)])
    widest = max(range(4), key=lambda i: len(streams[i]))
    assert widest in (0, 3)
    return dict(streams=streams, n_steps=450, flag_truncation=True,
                width=-(-max(len(s) for s in streams) // 4),
                error=[False] * 4)


def _ragged_and_padding():
    streams = encode_all([counter(n, n) for n in (1, 3, 450, 17, 500, 64)])
    streams[1:1] = [b"", b""]          # padding rows: nbits 0
    streams.append(b"")
    return dict(streams=streams, n_steps=500, flag_truncation=True,
                error=[False] * 9)


def _flagged_among_sound():
    """A corrupt marker (an annotation's), a tail of zeros and a stream
    cut short, each between sound rows."""
    enc = tsz.Encoder(START)
    for i in range(450):
        enc.encode(START + (i + 1) * 10 * SEC, float(i % 7),
                   annotation=b"schema" if i == 200 else None)
    sound = encode_all([counter(450, s) for s in range(3)])
    corrupt = bytearray(sound[0])
    corrupt[len(corrupt) // 2:] = bytes(len(corrupt) - len(corrupt) // 2)
    cut = sound[1][: len(sound[1]) // 3]
    streams = [sound[0], enc.finalize(), sound[1], bytes(corrupt), cut,
               sound[2]]
    return dict(streams=streams, n_steps=450, flag_truncation=True,
                error=[False, True, False, True, True, False])


def _truncated_at(offset):
    """n_steps a multiple of WIN_STEPS plus `offset`; streams of
    n_steps - 1, n_steps and n_steps + 1 records: the last alone is
    truncated, and says so."""
    n_steps = 3 * dec.WIN_STEPS + 1 + offset
    streams = dense_streams([n_steps - 1, n_steps, n_steps + 1])
    return dict(streams=streams, n_steps=n_steps, unit_nanos=MS.nanos,
                flag_truncation=True, error=[False, False, True])


def _width(extra_words):
    """A batch exactly `extra_words` words wider than the window."""
    n = (dec.WIN_WORDS + dec.WIN_BLOCK // 2) * 32 // 12
    streams = encode_all([counter(n, s) for s in range(6)])
    return dict(streams=streams, n_steps=n, flag_truncation=True,
                width=dec.WIN_WORDS + extra_words, error=[False] * 6)


def _cursors_far_apart():
    """One long-record row beside short-record rows: after 300 records
    its cursor is 30,000 bits in and theirs 600 to 3,300."""
    dense = dense_streams([300], seed=7)[0]
    ts = [START + (i + 1) * 1000 * MS.nanos for i in range(300)]
    flat = tsz.encode_series(ts, [5.0] * 300, START, unit=MS)
    rng = np.random.default_rng(11)
    ints = tsz.encode_series(
        ts, [float(v) for v in np.cumsum(rng.integers(0, 100, 300))],
        START, unit=MS)
    assert len(dense) > 8 * len(ints) > 8 * len(flat)
    return dict(streams=[flat, dense, ints, flat], n_steps=300,
                unit_nanos=MS.nanos, flag_truncation=True,
                error=[False] * 4)


def _float_only_grammar():
    ts = [START + (i + 1) * 10 * SEC for i in range(300)]
    streams = [tsz.encode_series(ts, [math.sin(i / k) * 10 for i in
                                      range(300)], START,
                                 int_optimized=False) for k in (3.0, 5.0)]
    return dict(streams=streams, n_steps=300, int_optimized=False,
                flag_truncation=True, error=[False] * 2)


_WINDOW_CASES = {
    "full-64-bit-floats": _full_floats,
    "int-optimised-counters": _counters,
    "float-int-mode-change-mid-stream": _mode_change,
    "ends-inside-a-window-and-on-the-last-word": _ends,
    "ragged-rows-and-padding-rows": _ragged_and_padding,
    "corrupt-marker-zeros-and-cut-among-sound": _flagged_among_sound,
    "truncation-one-step-short-of-a-refill": lambda: _truncated_at(-1),
    "truncation-on-a-refill": lambda: _truncated_at(0),
    "truncation-one-step-past-a-refill": lambda: _truncated_at(1),
    "no-truncation-flag": lambda: {**_truncated_at(1),
                                   "flag_truncation": False,
                                   "error": [False] * 3},
    "one-block-wider-than-the-window": lambda: _width(dec.WIN_BLOCK),
    "width-no-multiple-of-the-block": lambda: _width(dec.WIN_BLOCK + 3),
    "cursors-far-apart": _cursors_far_apart,
    "float-only-grammar": _float_only_grammar,
}


@pytest.mark.parametrize("case", _WINDOW_CASES)
def test_window_and_peel_equal_the_plain_step_and_the_oracle(case):
    kw = _WINDOW_CASES[case]()
    streams, want_error = kw.pop("streams"), kw.pop("error")
    width = kw.pop("width", None)
    words, nbits = pack_streams(streams)
    if width is not None:   # more zero words a row, or none of the pad's
        assert not words[:, width:].any()
        words = np.pad(words, ((0, 0), (0, max(0, width - words.shape[1])))
                       )[:, :width]
    n_steps = kw["n_steps"]
    scan_len = n_steps + kw["flag_truncation"]
    # the window is engaged, and refilled as often as the static
    # schedule says: the first record is read in place, the others
    # WIN_STEPS to a refill
    refills = dec.decode_refills(scan_len, words.shape[1])
    assert words.shape[1] > dec.WIN_WORDS
    assert refills == -(-(scan_len - 1) // dec.WIN_STEPS) > 0
    # which is no fewer than the streams' bit lengths ask for: a refill
    # brings at most a window's words less the read's own
    longest = int(nbits.max()) - 64
    fresh = (dec.WIN_WORDS - dec._READ_WORDS) * 32
    if not any(want_error):
        assert refills >= longest / fresh
    got = [np.asarray(x) for x in dec.decode_batched(words, nbits, **kw)]
    plain = [np.asarray(x) for x in _plain_decode_batched(words, nbits, **kw)]
    for name, a, b in zip(("ts", "vs", "valid", "count", "error"),
                          got, plain):
        if a.dtype == np.float64:     # bit for bit, NaN payloads too
            a, b = a.view(np.uint64), b.view(np.uint64)
        np.testing.assert_array_equal(a, b, err_msg=f"{case}: {name}")
    np.testing.assert_array_equal(got[4], want_error)
    # and both are the scalar oracle's answer on the rows not flagged
    unit = MS if kw.get("unit_nanos") == MS.nanos else xtime.Unit.SECOND
    for lane in np.nonzero(~got[4])[0]:
        want_t, want_v = dec._scalar_decode(
            streams[lane], kw.get("int_optimized", True), unit)
        n = min(len(want_t), n_steps)
        assert got[3][lane] == n
        assert got[2][lane, :n].all() and not got[2][lane, n:].any()
        np.testing.assert_array_equal(got[0][lane, :n], want_t[:n])
        np.testing.assert_array_equal(
            got[1][lane, :n].view(np.uint64),
            np.asarray(want_v[:n], dtype=np.float64).view(np.uint64))


@pytest.mark.parametrize("n_words", [dec.WIN_WORDS, dec.WIN_WORDS - 3, 12])
def test_a_row_no_longer_than_the_window_is_its_own(n_words):
    """Up to WIN_WORDS words a row no refill runs (the count says 0) and
    every step reads the row: the same answers."""
    n = max(1, (n_words - 4) * 32 // 12)
    streams = encode_all([counter(n, s) for s in range(3)] + [gauge(3, 1)])
    words, nbits = pack_streams(streams)
    assert words.shape[1] <= n_words
    words = np.pad(words, ((0, 0), (0, n_words - words.shape[1])))
    assert dec.decode_refills(n + 1, n_words) == 0
    got = dec.decode_batched(words, nbits, n, flag_truncation=True)
    plain = _plain_decode_batched(words, nbits, n, flag_truncation=True)
    for a, b in zip(got, plain):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.asarray(got[4]).any()
    assert list(np.asarray(got[3])) == [n, n, n, 3]


def test_window_invariant_follows_from_the_grammar():
    """MAX_RECORD_BITS is the sum of the grammar's own constants, and the
    committed WIN_STEPS, WIN_BLOCK, WIN_WORDS keep every read of a
    window's steps inside it: the cursor's word starts at most
    WIN_BLOCK - 1 words in (and 31 bits into that word), WIN_STEPS - 1
    records move it, the read takes five words."""
    # the catch-all bucket's opcode (1111) is as long as the last
    # bucket's (1110)
    time_bits = (max(op_bits for _, op_bits, _ in tsz.TIME_BUCKETS)
                 + tsz.DEFAULT_VALUE_BITS[xtime.Unit.SECOND])
    assert tsz.DEFAULT_VALUE_BITS[MS] == tsz.DEFAULT_VALUE_BITS[
        xtime.Unit.SECOND]
    control_bits = (3                                  # update, repeat, float
                    + 2 + tsz.NUM_SIG_BITS_FIELD       # sig block
                    + 1 + tsz.NUM_MULT_BITS            # mult block
                    + 1)                               # sign
    assert dec.MAX_RECORD_BITS == time_bits + control_bits + 64 == 116
    K, G, C = dec.WIN_STEPS, dec.WIN_BLOCK, dec.WIN_WORDS
    assert C % G == 0 and K >= 1
    moved = -(-(K - 1) * dec.MAX_RECORD_BITS // 32)
    assert G - 1 + moved + 1 + dec._READ_WORDS <= C
    # and WIN_STEPS is the most that does
    assert G - 1 + -(-K * dec.MAX_RECORD_BITS // 32) + 1 \
        + dec._READ_WORDS > C
    # the window engages at the benchmark cells' word buckets (256 and,
    # the fused planner's, 512) and their steps divide into whole
    # windows (768 and 1,024 after the first record); a row no wider
    # than the window is its own
    assert dec.decode_refills(769, 256) == 768 // K
    assert dec.decode_refills(1025, 512) == 1024 // K
    assert dec.decode_refills(770, 256) == 768 // K + 1
    assert dec.decode_refills(769, C) == 0 < dec.decode_refills(769, C + 1)
    assert dec.decode_refills(1, 256) == 0


def test_a_read_past_the_window_is_flagged_not_misread(monkeypatch):
    """The behaviour stays total: with more steps to a refill than the
    invariant allows, a dense row's read leaves its window; the row is
    flagged (the engine's scalar fallback serves it) and its samples up
    to there are right, while a sparse row beside it decodes whole."""
    streams = dense_streams([120], seed=9) + [
        tsz.encode_series(
            [START + (i + 1) * 1000 * MS.nanos for i in range(120)],
            [float(i) for i in range(120)], START, unit=MS)]
    words, nbits = pack_streams(streams)
    monkeypatch.setattr(dec, "WIN_STEPS", 2 * dec.WIN_STEPS)
    ts, vs, valid, count, error = (np.asarray(x) for x in jax.jit(
        functools.partial(dec.decode_batched.__wrapped__, n_steps=120,
                          unit_nanos=MS.nanos, flag_truncation=True))(
                              words, nbits))
    assert list(error) == [True, False] and count[1] == 120
    want_t, _ = tsz.decode_series(streams[0], unit=MS)
    assert 0 < count[0] < 120
    np.testing.assert_array_equal(ts[0, :count[0]], want_t[:count[0]])


def test_decode_batched_refuses_an_empty_grid():
    words, nbits = pack_streams(encode_all([gauge(5, 1)]))
    with pytest.raises(ValueError, match="n_steps"):
        dec.decode_batched(words, nbits, 0)
