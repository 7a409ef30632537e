"""Batched branchless M3TSZ decode — the TPU read-path hot loop.

Replaces the reference's per-series iterator goroutines
(ref: src/dbnode/encoding/m3tsz/iterator.go:64 Next; parallelized per
series at src/query/ts/m3db/encoded_step_iterator_generic.go:120
nextParallel) with one data-parallel kernel: L series decode in lockstep,
one datapoint per scan step, every control-flow branch of the bit grammar
turned into arithmetic selects.

TPU-first design notes (the numbers: one TPU v5e, PERF.md PR 39):
- Per-lane variable-position bitstream access is a one-hot masked
  OR-reduce over the word tensor (the TPU compiler runs an
  element-indexed gather an element at a time).  The scan reads the
  tensor laid ``[W, L]``, a lane's words down the MAJOR axis: the
  compiler lays an array by the axis it is reduced over, and with the
  lanes across a register the reduce is an OR from register to
  register, its cost in proportion to the words read.  One fused pass
  per step yields a 160-bit window per lane, from which the timestamp
  record (<=36 bits), value control bits (<=16) and value payload
  (<=64) are all carved with shifts: a record is at most
  MAX_RECORD_BITS from the window base, so one window per datapoint
  suffices.
- A step reads only what the record at a lane's cursor can need: past
  WIN_WORDS words a row, the windows come from a per-lane word window
  of WIN_WORDS words, refilled for every lane at once, each from the
  block that holds its cursor, every WIN_STEPS steps (_refill: one
  masked OR-reduce at block grain); and the first record, whose layout
  is its own and whose place is the same in every row, is decoded once,
  before the scan, so the scan's body plans a later record alone.
- Per-lane decode state is the same ~10 scalars the reference iterator
  keeps (SURVEY.md §8.1), all integer registers, exact on every backend.
  The final f64 emission is bit-exact on CPU; on TPU float64 is emulated
  at reduced precision so float-mode values can land 1 ulp off there —
  irrelevant for aggregation, and the exact integer state is what
  downstream device kernels consume.

Constructs that cannot appear in sealed numeric blocks written with a
fixed time unit — annotations, mid-stream time-unit changes — set a
per-lane `error` flag; `decode_streams` re-decodes those lanes with the
scalar oracle so behavior stays total.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from m3_tpu.ops import decode_counter, m3tsz_scalar
from m3_tpu.ops.bitstream import (
    I32,
    I64,
    U64,
    bitcast_i64,
    clz64,
    ctz64,
    pack_streams,
    take_top,
)
from m3_tpu.utils import xtime

MULT_DIVISORS = np.array([10.0**i for i in range(m3tsz_scalar.MAX_MULT + 1)])

# the longest record the grammar can write in the units this kernel
# decodes: a timestamp in the catch-all bucket (its opcode is as long as
# the last bucket's), the longest value control (the update, repeat and
# float bits, a sig block with its 6-bit field, a mult block, the sign)
# and a whole 64-bit payload
MAX_RECORD_BITS = (
    m3tsz_scalar.TIME_BUCKETS[-1][1]
    + m3tsz_scalar.DEFAULT_VALUE_BITS[xtime.Unit.SECOND]
    + 3 + (2 + m3tsz_scalar.NUM_SIG_BITS_FIELD)
    + (1 + m3tsz_scalar.NUM_MULT_BITS) + 1
    + 64)
_READ_WORDS = 5  # _window128 reads five words from the cursor's

# The per-lane word window of the scan (decode_batched): WIN_WORDS words a
# lane, whole blocks of WIN_BLOCK words from the one that holds the lane's
# cursor, refilled every WIN_STEPS steps.  WIN_STEPS is the most the
# grammar allows: the cursor's word lies at most WIN_BLOCK - 1 words into
# a fresh window, WIN_STEPS - 1 records of MAX_RECORD_BITS move it on, and
# the last read still ends inside (tests/test_m3tsz_decode_batched.py).
# Chosen on the chip (TPU v5e, `decode_batched` alone, PERF.md PR 39): a
# step costs in proportion to WIN_WORDS, a refill one sweep of the rows.
WIN_BLOCK = 8
WIN_WORDS = 40
WIN_STEPS = 1 + ((WIN_WORDS - WIN_BLOCK - _READ_WORDS) * 32
                 // MAX_RECORD_BITS)


def decode_refills(scan_len: int, n_words: int) -> int:
    """Refills of the per-lane word window in one decode_batched call of
    `scan_len` steps over rows `n_words` wide: the first record is read
    in place, the others WIN_STEPS to a refill; 0 where a row is no
    longer than the window and every step reads the row.  The schedule
    is the grammar's worst case, so a function of the static shapes
    alone."""
    if n_words <= WIN_WORDS:
        return 0
    return -(-(scan_len - 1) // WIN_STEPS)


class DecodeState(NamedTuple):
    cursor: jax.Array  # i32[L] bit position
    done: jax.Array  # bool[L] saw end-of-stream
    error: jax.Array  # bool[L] unsupported construct / corrupt
    prev_time: jax.Array  # i64[L] unix nanos
    prev_delta: jax.Array  # i64[L] nanos
    prev_float: jax.Array  # u64[L] float64 bit pattern
    prev_xor: jax.Array  # u64[L]
    int_val: jax.Array  # i64[L]
    sig: jax.Array  # i32[L]
    mult: jax.Array  # i32[L]
    is_float: jax.Array  # bool[L]


class ValuePlan(NamedTuple):
    """Geometry + routing of one value record, before its payload is read."""

    ctrl: jax.Array  # i32[L] control bits (incl. sign bit for int diffs)
    payload_len: jax.Array  # i32[L]
    full_float: jax.Array  # bool[L] payload is a raw 64-bit float
    int_active: jax.Array  # bool[L] payload is an int diff
    xor_active: jax.Array  # bool[L] payload is XOR meaningful bits
    xor_zero: jax.Array  # bool[L] XOR == 0 record
    add: jax.Array  # bool[L] int diff sign (True = add)
    trail: jax.Array  # i32[L] XOR trailing-zero shift
    new_sig: jax.Array  # i32[L]
    new_mult: jax.Array  # i32[L]
    set_float: jax.Array  # bool[L] is_float after this record
    sig_mult_active: jax.Array  # bool[L] commit new_sig/new_mult


def _bit_at(win: jax.Array, pos: jax.Array) -> jax.Array:
    """Bit at per-lane position `pos` (0 = MSB) of each 64-bit window."""
    return ((win >> (U64(63) - pos.astype(U64))) & U64(1)).astype(jnp.bool_)


def _field_at(win: jax.Array, pos: jax.Array, width: int) -> jax.Array:
    """`width` bits starting at per-lane position `pos` (0 = MSB)."""
    shift = U64(64 - width) - pos.astype(U64)
    return (win >> shift) & U64((1 << width) - 1)


def _sext(win: jax.Array, skip: int, nbits: int) -> jax.Array:
    """Sign-extended nbits field after `skip` bits from the window top."""
    return bitcast_i64(win << U64(skip)) >> I64(64 - nbits)


def _window128(words: jax.Array, cursor: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(hi, lo) u64 pair: 128 stream bits starting at each lane's cursor.

    `words` is u32[W, L], a lane's words down the MAJOR axis: the whole
    row, or the lane's word window (decode_batched).  Five consecutive
    words from the cursor's base word are picked by ONE variadic masked
    OR-reduce over that axis: no gather, and the five u32s ride three
    u64 operands of a single `lax.reduce`, so the W words of a lane are
    read once a step; words past W read zero.  Reduced over the major
    axis the lanes lie across a register and the reduce is an OR from
    register to register; over the minor axis (the words across the
    register) a step cost the same at 128 words as at 256.
    """
    base = cursor >> 5
    off = (cursor & 31).astype(U64)
    diff = jnp.arange(words.shape[0], dtype=I32)[:, None] - base[None, :]
    w64 = words.astype(U64)
    z = jnp.zeros((), U64)
    a = jnp.where(diff == 0, w64 << U64(32), z) | jnp.where(diff == 1, w64, z)
    b = jnp.where(diff == 2, w64 << U64(32), z) | jnp.where(diff == 3, w64, z)
    c = jnp.where(diff == 4, w64 << U64(32), z)

    def _or3(acc, x):
        return (acc[0] | x[0], acc[1] | x[1], acc[2] | x[2])

    w01, w23, w45 = jax.lax.reduce((a, b, c), (z, z, z), _or3, (0,))
    aligned = off == 0
    inv = U64(64) - jnp.where(aligned, U64(1), off)  # dodge shift-by-64
    hi = jnp.where(aligned, w01, (w01 << off) | (w23 >> inv))
    lo = jnp.where(aligned, w23, (w23 << off) | (w45 >> inv))
    return hi, lo


def _mid_window(hi: jax.Array, lo: jax.Array, skip: jax.Array) -> jax.Array:
    """64 bits starting `skip` (1..63) bits into the 128-bit (hi, lo) pair."""
    s = skip.astype(U64)
    safe = jnp.where(s == 0, U64(1), s)
    return jnp.where(s == 0, hi, (hi << safe) | (lo >> (U64(64) - safe)))


def _parse_timestamp(hi, st: DecodeState, unit_nanos: int):
    """One delta-of-delta timestamp record incl. marker look-ahead.

    Returns (new_time, new_delta, consumed_bits, eos, bad_marker).
    Grammar: docs/m3tsz_format.md; ref: timestamp_iterator.go:136-284.
    """
    is_marker = (hi >> U64(55)) == U64(0x100)
    marker_val = (hi >> U64(53)) & U64(3)
    eos = is_marker & (marker_val == 0)
    bad_marker = is_marker & (marker_val != 0)

    lead_ones = clz64(~hi)
    dod_units = jnp.where(
        lead_ones == 0,
        I64(0),
        jnp.where(
            lead_ones == 1,
            _sext(hi, 2, 7),
            jnp.where(
                lead_ones == 2,
                _sext(hi, 3, 9),
                jnp.where(lead_ones == 3, _sext(hi, 4, 12), _sext(hi, 4, 32)),
            ),
        ),
    )
    consumed = jnp.where(
        lead_ones == 0,
        I32(1),
        jnp.where(
            lead_ones == 1,
            I32(9),
            jnp.where(lead_ones == 2, I32(12), jnp.where(lead_ones == 3, I32(16), I32(36))),
        ),
    )
    dod_units = jnp.where(is_marker, I64(0), dod_units)
    consumed = jnp.where(is_marker, I32(0), consumed)

    new_delta = st.prev_delta + dod_units * I64(unit_nanos)
    new_time = st.prev_time + new_delta
    return new_time, new_delta, consumed, eos, bad_marker


def _parse_sig_mult(cwin, base: jax.Array, sig, mult):
    """sig/mult update block + sign bit (ref: iterator.go:145-168).

    `base` is the per-lane bit offset of the block inside cwin.
    Returns (new_sig, new_mult, add_flag, total_len_including_sign).
    """
    s_upd = _bit_at(cwin, base)
    s_nonzero = _bit_at(cwin, base + 1)
    sig_field = _field_at(cwin, base + 2, m3tsz_scalar.NUM_SIG_BITS_FIELD)
    k = jnp.where(s_upd, jnp.where(s_nonzero, I32(8), I32(2)), I32(1))
    new_sig = jnp.where(
        s_upd, jnp.where(s_nonzero, sig_field.astype(I32) + 1, I32(0)), sig
    )
    m_upd = _bit_at(cwin, base + k)
    mult_field = _field_at(cwin, base + k + 1, m3tsz_scalar.NUM_MULT_BITS)
    m = jnp.where(m_upd, I32(4), I32(1))
    new_mult = jnp.where(m_upd, mult_field.astype(I32), mult)
    add = _bit_at(cwin, base + k + m)
    return new_sig, new_mult, add, base + k + m + 1


def _parse_xor(xwin, prev_xor):
    """Float XOR record geometry, opcode at window bit 0.
    Returns (ctrl_len, payload_len, trail, is_zero).
    Ref: float_encoder_iterator.go:117-166."""
    zero_pos = jnp.zeros(prev_xor.shape, I32)
    x0 = _bit_at(xwin, zero_pos)
    x1 = _bit_at(xwin, zero_pos + 1)
    prev_lead = clz64(prev_xor)
    prev_trail = ctz64(prev_xor)
    contained_len = I32(64) - prev_lead - prev_trail
    u_lead = _field_at(xwin, zero_pos + 2, 6).astype(I32)
    u_mlen = _field_at(xwin, zero_pos + 8, 6).astype(I32) + 1
    u_trail = I32(64) - u_lead - u_mlen

    is_zero = ~x0
    is_contained = x0 & ~x1
    ctrl = jnp.where(is_zero, I32(1), jnp.where(is_contained, I32(2), I32(14)))
    payload = jnp.where(is_zero, I32(0), jnp.where(is_contained, contained_len, u_mlen))
    trail = jnp.where(is_contained, prev_trail, u_trail)
    return ctrl, payload, trail, is_zero


def _false(shape_like) -> jax.Array:
    return jnp.zeros(shape_like.shape, jnp.bool_)


def _plan_value(cwin, st: DecodeState, int_optimized: bool, first: bool) -> ValuePlan:
    """Parse a value record's control bits (cwin top-aligned at the record)."""
    L = st.cursor
    zero = jnp.zeros(L.shape, I32)

    if not int_optimized:
        if first:
            return ValuePlan(
                ctrl=zero,
                payload_len=zero + 64,
                full_float=~_false(L),
                int_active=_false(L),
                xor_active=_false(L),
                xor_zero=_false(L),
                add=_false(L),
                trail=zero,
                new_sig=st.sig,
                new_mult=st.mult,
                set_float=~_false(L),
                sig_mult_active=_false(L),
            )
        ctrl_x, payload_x, trail_x, x_zero = _parse_xor(cwin, st.prev_xor)
        return ValuePlan(
            ctrl=ctrl_x,
            payload_len=payload_x,
            full_float=_false(L),
            int_active=_false(L),
            xor_active=~_false(L),
            xor_zero=x_zero,
            add=_false(L),
            trail=trail_x,
            new_sig=st.sig,
            new_mult=st.mult,
            set_float=~_false(L),
            sig_mult_active=_false(L),
        )

    if first:
        # mode bit, then raw float or sig/mult + signed diff
        # (ref: iterator.go:88-106)
        mode_float = _bit_at(cwin, zero)
        sig_a, mult_a, add_a, ctrl_a = _parse_sig_mult(cwin, zero + 1, st.sig, st.mult)
        return ValuePlan(
            ctrl=jnp.where(mode_float, I32(1), ctrl_a),
            payload_len=jnp.where(mode_float, I32(64), sig_a),
            full_float=mode_float,
            int_active=~mode_float,
            xor_active=_false(L),
            xor_zero=_false(L),
            add=add_a,
            trail=zero,
            new_sig=sig_a,
            new_mult=mult_a,
            set_float=mode_float,
            sig_mult_active=~mode_float,
        )

    # --- next value, int-optimized (ref: iterator.go:108-143) ---
    c_update = ~_bit_at(cwin, zero)  # bit 0 == opcodeUpdate(0)
    c_repeat = _bit_at(cwin, zero + 1)
    c_float = _bit_at(cwin, zero + 2)

    a_repeat = c_update & c_repeat
    a_float = c_update & ~c_repeat & c_float
    a_int = c_update & ~c_repeat & ~c_float
    b_float = ~c_update & st.is_float
    b_int = ~c_update & ~st.is_float

    sig_a, mult_a, add_a, ctrl_a = _parse_sig_mult(cwin, zero + 3, st.sig, st.mult)

    xwin = cwin << U64(1)  # XOR record starts after the no-update bit
    ctrl_x, payload_x, trail_x, x_zero = _parse_xor(xwin, st.prev_xor)
    ctrl_x = ctrl_x + 1

    add_b = _bit_at(cwin, zero + 1)

    ctrl = jnp.where(
        a_repeat,
        I32(2),
        jnp.where(
            a_float,
            I32(3),
            jnp.where(a_int, ctrl_a, jnp.where(b_float, ctrl_x, I32(2))),
        ),
    )
    payload_len = jnp.where(
        a_repeat,
        I32(0),
        jnp.where(
            a_float,
            I32(64),
            jnp.where(a_int, sig_a, jnp.where(b_float, payload_x, st.sig)),
        ),
    )
    return ValuePlan(
        ctrl=ctrl,
        payload_len=payload_len,
        full_float=a_float,
        int_active=a_int | b_int,
        xor_active=b_float,
        xor_zero=x_zero & b_float,
        add=jnp.where(a_int, add_a, add_b),
        trail=trail_x,
        new_sig=sig_a,
        new_mult=mult_a,
        set_float=jnp.where(a_float, True, jnp.where(a_int, False, st.is_float)),
        sig_mult_active=a_int,
    )


def _apply_value(st: DecodeState, plan: ValuePlan, payload: jax.Array) -> DecodeState:
    """Commit one value record given its payload bits."""
    diff = bitcast_i64(payload)
    new_int = jnp.where(
        plan.int_active,
        st.int_val + jnp.where(plan.add, diff, -diff),
        st.int_val,
    )
    xor = jnp.where(
        plan.xor_zero, U64(0), payload << jnp.maximum(plan.trail, 0).astype(U64)
    )
    new_float = jnp.where(
        plan.full_float,
        payload,
        jnp.where(plan.xor_active, st.prev_float ^ xor, st.prev_float),
    )
    new_xor = jnp.where(
        plan.full_float, payload, jnp.where(plan.xor_active, xor, st.prev_xor)
    )
    return st._replace(
        prev_float=new_float,
        prev_xor=new_xor,
        int_val=new_int,
        sig=jnp.where(plan.sig_mult_active, plan.new_sig, st.sig),
        mult=jnp.where(plan.sig_mult_active, plan.new_mult, st.mult),
        is_float=plan.set_float,
    )


def _emit_value(st: DecodeState) -> jax.Array:
    """Current datapoint value as float64 (ref: iterator.go:183-197)."""
    float_val = jax.lax.bitcast_convert_type(st.prev_float, jnp.float64)
    # 10 ** clip(mult, 0, MAX_MULT), a select a power: no gather in the step
    divisor = jnp.full(st.mult.shape, MULT_DIVISORS[0])
    for i in range(1, m3tsz_scalar.MAX_MULT + 1):
        divisor = jnp.where(st.mult >= i, MULT_DIVISORS[i], divisor)
    int_val = st.int_val.astype(jnp.float64) / divisor
    return jnp.where(st.is_float, float_val, int_val)


def _merge(st: DecodeState, new_st: DecodeState, emit) -> DecodeState:
    """Commit per-lane updates only on lanes that emitted a datapoint."""
    return jax.tree.map(lambda new, old: jnp.where(emit, new, old), new_st, st)


def _init_state(words: jax.Array, nbits: jax.Array) -> DecodeState:
    """State before any datapoint: cursor past the raw 64-bit stream start
    (a static two-word slice of `words` u32[W, L]: uniform position, no
    window pass needed)."""
    L = words.shape[1]
    start = (words[0].astype(U64) << U64(32)) | words[1].astype(U64)
    return DecodeState(
        cursor=jnp.full((L,), 64, I32),
        # Streams too small for start + EOS marker are immediately done.
        done=nbits < 64 + 11,
        error=jnp.zeros((L,), jnp.bool_),
        prev_time=bitcast_i64(start),
        prev_delta=jnp.zeros((L,), I64),
        prev_float=jnp.zeros((L,), U64),
        prev_xor=jnp.zeros((L,), U64),
        int_val=jnp.zeros((L,), I64),
        sig=jnp.zeros((L,), I32),
        mult=jnp.zeros((L,), I32),
        is_float=jnp.zeros((L,), jnp.bool_),
    )


def _decode_step(words, nbits, st: DecodeState, int_optimized: bool,
                 unit_nanos: int, first: bool = False):
    """Decode one datapoint on every lane, from `words` u32[W, L].

    Returns (state', (time i64[L], value f64[L], valid bool[L])).
    `first` is static: a stream's first record has a layout of its own
    (a mode bit instead of the update structure) and is the record of
    step 0 on every lane at once.  A lane that emits nothing at step 0
    met its end marker or an error and is never active again, so no
    later step needs that plan.
    """
    hi, lo = _window128(words, st.cursor)  # the ONE window pass
    t, d, t_len, eos, bad = _parse_timestamp(hi, st, unit_nanos)
    active = ~st.done & ~st.error
    emit = active & ~eos & ~bad
    st2 = st._replace(
        error=st.error | (bad & active),
        done=st.done | (eos & active),
        prev_time=jnp.where(emit, t, st.prev_time),
        prev_delta=jnp.where(emit, d, st.prev_delta),
    )
    cwin = hi << jnp.minimum(t_len, 63).astype(U64)
    plan = _plan_value(cwin, st2, int_optimized, first=first)
    payload = take_top(_mid_window(hi, lo, t_len + plan.ctrl), plan.payload_len)
    st3 = _merge(st2, _apply_value(st2, plan, payload), emit)
    st3 = st3._replace(
        cursor=st2.cursor + jnp.where(emit, t_len + plan.ctrl + plan.payload_len, 0),
    )
    st3 = st3._replace(error=st3.error | ((st3.cursor > nbits) & ~st3.done))
    valid = emit & ~st3.error
    return st3, (st3.prev_time, _emit_value(st3), valid)


def _refill(blocks: jax.Array, block: jax.Array) -> jax.Array:
    """u32[WIN_WORDS, L]: each lane's WIN_WORDS // WIN_BLOCK consecutive
    blocks of WIN_BLOCK words from block `block` i32[L], out of `blocks`
    u32[W / WIN_BLOCK, WIN_BLOCK, L], the lanes' words seen block by
    block.

    _window128's trick at block grain: the blocks are picked by ONE
    variadic masked OR-reduce over the block axis, so a refill costs one
    sweep of the rows: no gather, no per-lane dynamic slice, no rotation
    of the row.  Blocks past a lane's end read zero, as words past W do
    in _window128."""
    n = WIN_WORDS // WIN_BLOCK
    diff = (jnp.arange(blocks.shape[0], dtype=I32)[:, None]
            - block[None, :])[:, None, :]
    z = jnp.zeros((), jnp.uint32)
    picked = jax.lax.reduce(
        tuple(jnp.where(diff == j, blocks, z) for j in range(n)),
        (z,) * n, lambda acc, x: tuple(a | b for a, b in zip(acc, x)), (0,))
    return jnp.concatenate(picked, axis=0)


def _windowed_steps(blocks, nbits, st: DecodeState, n_steps: int,
                    int_optimized: bool, unit_nanos: int):
    """One refill, then `n_steps` (at most WIN_STEPS) later records on
    every lane, their reads taken from the lane's word window: a step
    then costs WIN_WORDS words a lane and not the row.  The window is an
    invariant of the steps' loop.  _decode_step runs on it as it does on
    the row, the cursor and the stream's length rebased to the window's
    first bit; the state between refills carries the true cursor.

    A lane whose read would leave its window sets `error`, like any
    construct the kernel does not handle: WIN_STEPS is the most steps
    after which no record the grammar allows can, so only a cursor that
    a corrupt stream moved backwards (a contained XOR after a zero one,
    which no encoder writes) does."""
    with jax.named_scope("refill"):
        block = (st.cursor >> 5) // WIN_BLOCK
        win = _refill(blocks, block)
    origin = block * (32 * WIN_BLOCK)
    rel_bits = nbits - origin

    def step(st: DecodeState, _):
        word = st.cursor >> 5
        outside = ((word < 0) | (word + _READ_WORDS > WIN_WORDS)) & ~st.done
        return _decode_step(win, rel_bits, st._replace(error=st.error | outside),
                            int_optimized, unit_nanos)

    st, outs = jax.lax.scan(
        step, st._replace(cursor=st.cursor - origin), None, length=n_steps)
    return st._replace(cursor=st.cursor + origin), outs


def _later_steps(words, nbits, st: DecodeState, n_steps: int,
                 int_optimized: bool, unit_nanos: int):
    """The `n_steps` records after a stream's first, on every lane of
    `words` u32[W, L] -> (state', (times, values, valid) [n_steps, L])."""
    W, L = words.shape
    if W <= WIN_WORDS:  # a row no longer than the window is its own
        return jax.lax.scan(
            lambda st, _: _decode_step(words, nbits, st, int_optimized,
                                       unit_nanos),
            st, None, length=n_steps)
    blocks = jnp.pad(words, ((0, -W % WIN_BLOCK), (0, 0))).reshape(
        -1, WIN_BLOCK, L)
    run = functools.partial(_windowed_steps, blocks, nbits,
                            int_optimized=int_optimized,
                            unit_nanos=unit_nanos)
    # whole windows (their WIN_STEPS results land as one block: eight
    # rows are a register tile's), then one of the steps left over: a
    # step past n_steps could reach an end of stream that the scan must
    # not see (flag_truncation)
    whole, left = divmod(n_steps, WIN_STEPS)
    st, outs = jax.lax.scan(
        lambda st, _: run(st, n_steps=WIN_STEPS), st, None, length=whole)
    outs = tuple(x.reshape((-1,) + x.shape[2:]) for x in outs)
    if left:
        st, last = run(st, n_steps=left)
        outs = tuple(jnp.concatenate(pair) for pair in zip(outs, last))
    return st, outs


@functools.partial(
    jax.jit,
    static_argnames=("n_steps", "int_optimized", "unit_nanos",
                     "flag_truncation"),
)
def decode_batched(
    words: jax.Array,
    nbits: jax.Array,
    n_steps: int,
    int_optimized: bool = True,
    unit_nanos: int = xtime.SECOND,
    flag_truncation: bool = False,
):
    """Decode up to n_steps datapoints from each of L streams.

    Returns (timestamps i64[L, n_steps], values f64[L, n_steps],
    valid bool[L, n_steps], count i32[L], error bool[L]).

    With `flag_truncation`, a stream that did NOT reach its end-of-
    stream marker within n_steps records is reported in `error` —
    callers that size the decode grid from an expected sample count
    (e.g. the device query pipeline's per-block `n_dp`) would otherwise
    silently drop the tail with error=False.
    """
    if unit_nanos not in (xtime.SECOND, 1_000_000):
        raise ValueError("fast path supports second/millisecond units")
    if n_steps < 1:
        raise ValueError("n_steps must be at least 1")
    words = words.astype(jnp.uint32).T  # [W, L]: see _window128
    st = _init_state(words, nbits)
    # the EOS marker is consumed by the step AFTER the last datapoint,
    # so truncation detection needs one extra (discarded) scan step for
    # a stream holding exactly n_steps records to reach done=True
    scan_len = n_steps + 1 if flag_truncation else n_steps
    # the first record: its own layout, and at bit 64 of every row, so
    # its read is a static slice and its plan stays out of the scan
    st, first = _decode_step(words[:2 + _READ_WORDS], nbits, st,
                             int_optimized, unit_nanos, first=True)
    st, later = _later_steps(words, nbits, st, scan_len - 1, int_optimized,
                             unit_nanos)
    ts, vs, valid = (jnp.concatenate([f[None], x])
                     for f, x in zip(first, later))
    ts = jnp.moveaxis(ts, 0, 1)[:, :n_steps]
    vs = jnp.moveaxis(vs, 0, 1)[:, :n_steps]
    valid = jnp.moveaxis(valid, 0, 1)[:, :n_steps]
    count = valid.sum(axis=1, dtype=I32)
    error = st.error
    if flag_truncation:
        error = error | ~st.done
    return ts, vs, valid, count, error


@functools.partial(
    jax.jit,
    static_argnames=("n_steps", "window", "int_optimized", "unit_nanos", "full_agg"),
)
def decode_downsample_fused(
    words: jax.Array,
    nbits: jax.Array,
    n_steps: int,
    window: int,
    int_optimized: bool = True,
    unit_nanos: int = xtime.SECOND,
    full_agg: bool = False,
):
    """Fused decode + windowed aggregation: never materializes the
    [L, n_steps] grid — the scan runs per *window*, decoding `window`
    datapoints inline and emitting only the accumulators.

    This is the memory-traffic-optimal form of the read hot path: HBM
    sees the compressed words plus [L, n_windows] aggregates only.

    Returns (agg: WindowedAgg of [L, n_windows] — sum/count always
    populated; min/max/sum_sq/last only when full_agg — count i32[L],
    error bool[L]).
    """
    from m3_tpu.ops.downsample import WindowedAgg

    if n_steps % window:
        raise ValueError(f"n_steps {n_steps} not divisible by window {window}")
    words = words.astype(jnp.uint32).T  # [W, L]: see _window128
    L = words.shape[1]
    st = _init_state(words, nbits)

    def dp_step(carry, _=None, first=False):
        st, s, ssq, cnt, vmin, vmax, last, has_last = carry
        st, (_t, v, valid) = _decode_step(words, nbits, st, int_optimized,
                                          unit_nanos, first=first)
        contrib = valid & ~jnp.isnan(v)
        vz = jnp.where(contrib, v, 0.0)
        s = s + vz
        cnt = cnt + valid
        if full_agg:
            ssq = ssq + vz * vz
            vmin = jnp.where(contrib, jnp.minimum(vmin, v), vmin)
            vmax = jnp.where(contrib, jnp.maximum(vmax, v), vmax)
            last = jnp.where(valid, v, last)
            has_last = has_last | valid
        return (st, s, ssq, cnt, vmin, vmax, last, has_last), None

    def win_step(st: DecodeState, _=None, first=False):
        carry = (
            st,
            jnp.zeros((L,), jnp.float64),
            jnp.zeros((L,), jnp.float64),
            jnp.zeros((L,), I64),
            jnp.full((L,), jnp.inf, jnp.float64),
            jnp.full((L,), -jnp.inf, jnp.float64),
            jnp.full((L,), jnp.nan, jnp.float64),
            jnp.zeros((L,), jnp.bool_),
        )
        if first:  # a stream's first record opens the first window
            carry, _n = dp_step(carry, first=True)
        if window <= 8:  # unroll small windows; nest a scan for large ones
            for _ in range(window - first):
                carry, _n = dp_step(carry)
        else:
            carry, _n = jax.lax.scan(dp_step, carry, None,
                                     length=window - first)
        st, s, ssq, cnt, vmin, vmax, last, has_last = carry
        if full_agg:
            any_c = vmin != jnp.inf
            out = (
                s,
                ssq,
                cnt,
                jnp.where(any_c, vmin, jnp.nan),
                jnp.where(any_c, vmax, jnp.nan),
                jnp.where(has_last, last, jnp.nan),
            )
        else:
            out = (s, cnt)
        return st, out

    st, head = win_step(st, first=True)
    st, outs = jax.lax.scan(win_step, st, None, length=n_steps // window - 1)
    outs = tuple(jnp.concatenate([h[None], x]) for h, x in zip(head, outs))
    tr = lambda x: jnp.moveaxis(x, 0, 1)  # noqa: E731
    if full_agg:
        agg = WindowedAgg(
            sum=tr(outs[0]),
            sum_sq=tr(outs[1]),
            count=tr(outs[2]),
            min=tr(outs[3]),
            max=tr(outs[4]),
            last=tr(outs[5]),
        )
    else:
        # Fields not computed in the cheap mode are NaN, preserving
        # WindowedAgg's NaN-for-unset invariant (rollup/value_of key on it).
        nan = jnp.full_like(tr(outs[0]), jnp.nan)
        agg = WindowedAgg(
            sum=tr(outs[0]), sum_sq=nan, count=tr(outs[1]), min=nan, max=nan, last=nan
        )
    total = agg.count.sum(axis=1).astype(I32)
    return agg, total, st.error


def _scalar_decode(stream: bytes, int_optimized: bool, unit: xtime.Unit):
    """Scalar-oracle decode of one stream -> (times, values) lists.
    A truncated or corrupt tail keeps the clean prefix (the shared
    fallback for lanes the fast paths flag)."""
    got_t: list[int] = []
    got_v: list[float] = []
    try:
        for dp in m3tsz_scalar.Decoder(
                bytes(stream), int_optimized=int_optimized,
                default_unit=unit):
            got_t.append(dp.t_nanos)
            got_v.append(dp.value)
    except (EOFError, ValueError):
        pass
    return got_t, got_v


def decode_streams_merged(
    streams: list[bytes],
    slots: np.ndarray,
    n_lanes: int,
    int_optimized: bool = True,
    unit: xtime.Unit = xtime.Unit.SECOND,
    counts: np.ndarray | None = None,
):
    """Fused decode+merge for the warm-read hot path: count pass →
    exact per-lane sizing → decode each block stream DIRECTLY into its
    packed [n_lanes, N] position (native/m3tsz_ref.cc) → tail padding.
    The read path is memory-bandwidth-bound on the host; skipping the
    intermediate per-stream grids halves the traffic of
    decode_streams_adaptive + merge_grids.

    Contract: same-lane streams appear in ascending time order (the
    engine's emission order).  Returns (times [n_lanes, N] +inf-pad,
    values [n_lanes, N] NaN-pad, lane_counts [n_lanes]) or None when
    the preconditions do not hold (out-of-order timestamps inside or
    across streams, no native toolchain, float-only grammar) — callers
    then take the general decode + sorting-merge path."""
    if not int_optimized or not len(streams):
        return None
    decode_counter.bump(len(streams))
    try:
        from m3_tpu.utils.native import (blob_offsets, count_batch_native,
                                         decode_merged_native,
                                         pad_lane_tails_native)

        packed = blob_offsets(streams)  # shared by count + decode pass
        if counts is not None:
            # v2 filesets store per-stream dp counts: skip the
            # count-only decode pass (a full bitstream walk) entirely
            counts = np.ascontiguousarray(counts, dtype=np.int64)
        else:
            counts = count_batch_native(streams, unit_nanos=unit.nanos,
                                        packed=packed)
    except Exception:  # toolchain unavailable
        return None
    slots = np.ascontiguousarray(slots, dtype=np.int64)
    if len(slots) > 1 and not bool(np.all(slots[1:] >= slots[:-1])):
        return None  # not grouped: adjacency order check would not cover
    bad = np.nonzero(counts < 0)[0]
    bad_data: dict[int, tuple[list, list]] = {}
    for lane in bad:
        got_t, got_v = _scalar_decode(streams[lane], int_optimized, unit)
        bad_data[int(lane)] = (got_t, got_v)
        counts[lane] = len(got_t)
    lane_counts = np.bincount(slots, weights=counts,
                              minlength=n_lanes).astype(np.int64)
    n_cap = max(int(lane_counts.max(initial=0)), 1)
    # flat destination offsets: per-lane running position in row order
    # (slots are grouped ascending — checked above — so a global cumsum
    # re-based at each group start gives the within-lane positions)
    cum = np.concatenate(([0], np.cumsum(counts)[:-1]))
    first = np.concatenate(([True], slots[1:] != slots[:-1]))
    group_idx = np.cumsum(first) - 1
    pos_in_lane = cum - cum[np.nonzero(first)[0]][group_idx]
    row_dst = slots * n_cap + pos_in_lane
    out_t = np.empty((n_lanes, n_cap), dtype=np.int64)
    out_v = np.empty((n_lanes, n_cap), dtype=np.float64)
    row_n, row_first, row_last, row_sorted = decode_merged_native(
        streams, row_dst, counts, out_t.reshape(-1), out_v.reshape(-1),
        unit_nanos=unit.nanos, packed=packed)
    for lane, (got_t, got_v) in bad_data.items():
        dst = row_dst[lane]
        flat_t, flat_v = out_t.reshape(-1), out_v.reshape(-1)
        flat_t[dst:dst + len(got_t)] = got_t
        flat_v[dst:dst + len(got_v)] = got_v
        row_n[lane] = len(got_t)
        row_first[lane] = got_t[0] if got_t else np.iinfo(np.int64).max
        row_last[lane] = got_t[-1] if got_t else np.iinfo(np.int64).min
        row_sorted[lane] = int(all(
            a <= b for a, b in zip(got_t, got_t[1:])))
    # order validation (cheap [M] vector ops): every row internally
    # sorted, and adjacent same-lane rows non-overlapping in time
    if not row_sorted.all():
        return None
    if len(streams) > 1:
        same = slots[1:] == slots[:-1]
        if not bool(np.all(~same | (row_last[:-1] <= row_first[1:]))):
            return None
    if not bool((row_n == counts).all()):
        return None  # count/decode disagreement: be safe, repack
    pad_lane_tails_native(out_t, out_v, lane_counts)
    return out_t, out_v, lane_counts


def decode_streams_adaptive(
    streams: list[bytes],
    int_optimized: bool = True,
    unit: xtime.Unit = xtime.Unit.SECOND,
    counts: np.ndarray | None = None,
):
    """decode_streams with automatic width escalation.

    A stream's datapoint count is not recoverable from its byte length:
    int-optimized gauge walks compress to ~4.5 bits/dp while float-mode
    streams run 12-26 bits/dp, and the wire carries no count.  Sizing
    the grid for the dense case up front would cost 4-6x the memory for
    typical data, so: start at a 12 bits/dp estimate, detect lanes that
    FILLED the grid (possible truncation — this silently dropped 60% of
    tightly-compressed samples before round 5), and re-decode only
    those lanes 4x wider, down to the grammar's 2 bits/dp floor.
    Returns (ts [L, T], vs [L, T], valid [L, T]) with T = the widest
    round's width."""
    if not streams:
        return (np.zeros((0, 1), dtype=np.int64),
                np.zeros((0, 1)), np.zeros((0, 1), dtype=bool))
    max_len = max(len(s) for s in streams)
    hard_cap = 1 + max_len * 8 // 2  # grammar floor: 1b time + 1b value
    if counts is not None:
        # stored (v2-fileset) counts: size the grid exactly with no
        # count pass.  Decode at width+1 so a stale/understated count
        # is DETECTABLE (the extra column catches any lane with more
        # datapoints than claimed); any per-lane disagreement discards
        # the stored counts and retries with a real count pass.
        counts = np.asarray(counts, dtype=np.int64)
        width = int(counts.max(initial=0)) + 1
        ts, vs, valid = decode_streams(streams, max(width, 1),
                                       int_optimized=int_optimized,
                                       unit=unit)
        if bool((valid.sum(axis=1) == counts).all()):
            return ts, vs, valid
        return decode_streams_adaptive(streams,
                                       int_optimized=int_optimized,
                                       unit=unit)
    if int_optimized:
        try:
            # exact sizing: one threaded count-only pass, then a single
            # decode at precisely the widest stream's dp count — no
            # re-decode rounds, no over-allocation
            from m3_tpu.utils.native import count_batch_native

            counts = count_batch_native(streams, unit_nanos=unit.nanos)
            width = int(counts.max(initial=0))
            for lane in np.nonzero(counts < 0)[0]:
                # unsupported constructs: the scalar oracle both counts
                # here and re-decodes inside decode_streams below
                got_t, _ = _scalar_decode(
                    streams[lane], int_optimized, unit)
                width = max(width, len(got_t))
            return decode_streams(streams, max(width, 1),
                                  int_optimized=int_optimized, unit=unit)
        except Exception:  # toolchain unavailable: escalation loop below
            pass
    est = min(1 + max_len * 8 // 12, hard_cap)
    todo = np.arange(len(streams))
    rounds: list[tuple[np.ndarray, tuple]] = []
    while True:
        sub = [streams[i] for i in todo]
        ts, vs, valid = decode_streams(
            sub, est, int_optimized=int_optimized, unit=unit)
        if est >= hard_cap:
            rounds.append((todo, (ts, vs, valid)))
            break
        sat = valid[:, -1]  # grid filled: may be truncated
        done = ~sat
        if done.any():
            rounds.append((todo[done], (ts[done], vs[done], valid[done])))
        if not sat.any():
            break
        todo = todo[sat]
        est = min(est * 4, hard_cap)
    width = max(r[1][0].shape[1] for r in rounds)
    L = len(streams)
    out_t = np.zeros((L, width), dtype=np.int64)
    out_v = np.zeros((L, width))
    out_m = np.zeros((L, width), dtype=bool)
    for idx, (ts, vs, valid) in rounds:
        w = ts.shape[1]
        out_t[idx, :w] = ts
        out_v[idx, :w] = vs
        out_m[idx, :w] = valid
    return out_t, out_v, out_m


def decode_streams(
    streams: list[bytes],
    max_datapoints: int,
    int_optimized: bool = True,
    unit: xtime.Unit = xtime.Unit.SECOND,
    prefer_native: bool | None = None,
):
    """Host entry: pack → device decode → scalar-oracle fallback for lanes
    the fast path flagged (annotations, time-unit changes, corruption).

    Returns (timestamps i64[L, T], values f64[L, T], valid bool[L, T]).

    On a CPU backend (``prefer_native=None`` auto-detects) the batch
    routes through the threaded native decoder instead: the branchless
    one-hot XLA kernel is shaped for the TPU's vector units and runs
    ~7x slower than the scalar C++ state machine on a host core.  Both
    paths are bit-exact against the same scalar oracle (native parity:
    tests/test_native_decoder.py)."""
    decode_counter.bump(len(streams))
    if prefer_native is None:
        # the C++ decoder speaks the int-optimized grammar only (the
        # storage write path always encodes int-optimized; float-only
        # streams appear via external/imported data)
        prefer_native = int_optimized and jax.default_backend() == "cpu"
    if prefer_native and streams:
        try:
            from m3_tpu.utils.native import decode_batch_native

            ts, vs, counts = decode_batch_native(
                streams, max_datapoints, unit_nanos=unit.nanos)
        except Exception:
            pass  # toolchain unavailable: XLA path below
        else:
            for lane in np.nonzero(counts < 0)[0]:
                got_t, got_v = _scalar_decode(
                    streams[lane], int_optimized, unit)
                n = min(len(got_t), max_datapoints)
                ts[lane, :n] = got_t[:n]
                vs[lane, :n] = got_v[:n]
                counts[lane] = n
            valid = np.arange(max_datapoints)[None, :] < counts[:, None]
            return ts, vs, valid
    words, nbits = pack_streams(streams)
    ts, vs, valid, count, error = decode_batched(
        jnp.asarray(words),
        jnp.asarray(nbits),
        max_datapoints,
        int_optimized=int_optimized,
        unit_nanos=unit.nanos,
    )
    err_lanes = np.nonzero(np.asarray(error))[0]
    if len(err_lanes):
        # writable copies: the scalar-oracle fallback patches lanes
        ts, vs, valid = np.array(ts), np.array(vs), np.array(valid)
    else:
        # clean fast path: zero-copy views of the device buffers (CPU
        # backend) — the [L, T] copies were a measured hotspot at
        # 50k-series fan-out reads (~350MB per array)
        ts, vs, valid = (np.asarray(ts), np.asarray(vs),
                         np.asarray(valid))
    for lane in err_lanes:
        got_t, got_v = _scalar_decode(streams[lane], int_optimized, unit)
        n = min(len(got_t), max_datapoints)
        ts[lane, :n] = got_t[:n]
        vs[lane, :n] = got_v[:n]
        valid[lane, :] = False
        valid[lane, :n] = True
    return ts, vs, valid
