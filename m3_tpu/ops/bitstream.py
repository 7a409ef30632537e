"""Batched bitstream layout + device peek primitives.

A batch of L compressed series is a ``[L, W]`` uint32 tensor: stream bit 0
is the MSB of word 0 (big-endian byte packing), so a 64-bit window at any
bit cursor is built from three consecutive words with shifts — a fully
vectorized replacement for the reference's per-stream buffered reader
(ref: src/dbnode/encoding/istream.go:97 ReadBits).

Two zero words of tail padding let every peek gather safely past the end
of the shortest stream.
"""

from __future__ import annotations

import sys
from collections.abc import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

PAD_WORDS = 2

U64 = jnp.uint64
I64 = jnp.int64
U32 = jnp.uint32
I32 = jnp.int32


def pack_streams(
        streams: Sequence[bytes | bytearray | memoryview],
        pad: Callable[[int], int] | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Pack byte streams into ``([L, W] uint32 big-endian words, [L] bit lengths)``.

    ``streams`` holds ``bytes``, ``bytearray`` or ``memoryview`` payloads
    (sealed blocks in memory, the fileset reader's zero-copy views).
    ``W`` is the longest stream's words plus ``PAD_WORDS``.  ``pad``,
    where given, maps a count of rows and a count of words to the count
    to lay out (a jit shape bucket): rows and words past the streams'
    own are zero, as are their bit lengths, so the padded shape costs
    no second copy.

    Every payload byte is copied once, by ONE ``join`` of the streams
    with a slice of a shared zero row after each, and swapped to the
    host's byte order in place.  No array has an element per input byte
    but the result, so the cost is the output's bytes (0.3-0.4 ns each
    on a desktop core) plus some 0.1 us a stream: 9-12 ms for the
    25,000 streams of 1 KB that a fleet-wide panel packs.  One stream
    far longer than the rest widens every row, and is paid for in
    those output bytes, once."""
    n = len(streams)
    lens = [len(s) for s in streams]
    rows = n
    width = (max(lens, default=0) + 3) // 4 + PAD_WORDS
    if pad is not None:
        rows, width = pad(rows), pad(width)
    nbits = np.zeros(rows, dtype=np.int32)
    nbits[:n] = np.asarray(lens, dtype=np.int64) * 8
    zero_row = memoryview(bytes(width * 4))
    tails = {k: zero_row[k:] for k in set(lens)}
    parts = [None] * (2 * n)
    parts[0::2] = streams
    parts[1::2] = [tails[k] for k in lens]
    parts.append(bytes((rows - n) * width * 4))
    words = np.frombuffer(bytearray().join(parts), dtype=np.uint32)
    if sys.byteorder == "little":
        words.byteswap(inplace=True)
    return words.reshape(rows, width), nbits


def unpack_stream(words: np.ndarray, nbits: int) -> bytes:
    """Inverse of pack_streams for one lane."""
    nbytes = (int(nbits) + 7) // 8
    return np.asarray(words, dtype=">u4").tobytes()[:nbytes]


def bitcast_i64(x: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(x.astype(U64), I64)


# NOTE: there is deliberately no device-side f64->bits helper here.  On
# this TPU platform f64 is emulated and *lossy at the transfer boundary*
# (a float64 loses low mantissa bits on device_put), so any kernel that
# needs exact IEEE-754 bit patterns must receive them from the host as
# integer tensors (see m3tsz_encode.prepare_value_fields).  The exact
# direction that does work on-device is u64 -> f64 (decode's rebind).


def bitcast_u64(x: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(x.astype(I64), U64)


def peek64(words: jax.Array, cursor: jax.Array) -> jax.Array:
    """``[L]`` uint64 windows: the 64 bits starting at each lane's cursor.

    words: [L, W] uint32 (with >= PAD_WORDS zero words of tail padding)
    cursor: [L] int32 bit positions
    """
    word_idx = (cursor >> 5).astype(I32)
    bit_off = (cursor & 31).astype(U64)
    w = words.shape[1]
    idx = jnp.clip(word_idx[:, None] + jnp.arange(3, dtype=I32)[None, :], 0, w - 1)
    gathered = jnp.take_along_axis(words, idx, axis=1).astype(U64)  # [L, 3]
    w0, w1, w2 = gathered[:, 0], gathered[:, 1], gathered[:, 2]
    hi = (w0 << U64(32)) | w1
    # bit_off == 0 makes the w2 shift 32 — safe on a uint64 operand.
    return (hi << bit_off) | (w2 >> (U64(32) - bit_off))


def take_top(window: jax.Array, n: jax.Array | int) -> jax.Array:
    """Top ``n`` bits of a 64-bit window, right-aligned; n == 0 yields 0.

    n may be a per-lane array (0..64).
    """
    n = jnp.asarray(n, dtype=U64)
    shifted = window >> jnp.where(n == 0, U64(0), U64(64) - n)
    return jnp.where(n == 0, U64(0), shifted)


def sign_extend_top(window: jax.Array, skip: int, nbits: int) -> jax.Array:
    """Sign-extended int64 of ``nbits`` bits located after ``skip`` bits
    from the top of the window (static widths)."""
    return bitcast_i64(window << U64(skip)) >> I64(64 - nbits)


def clz64(x: jax.Array) -> jax.Array:
    """Leading-zero count of uint64 (clz(0) == 64)."""
    return jax.lax.clz(bitcast_i64(x)).astype(I32)


def ctz64(x: jax.Array) -> jax.Array:
    """Trailing-zero count of uint64 (ctz(0) == 0, matching the reference's
    LeadingAndTrailingZeros which reports (64, 0) for zero —
    ref: src/dbnode/encoding/encoding.go:35-43)."""
    lsb = x & (~x + U64(1))
    return jnp.where(x == 0, I32(0), I32(63) - clz64(lsb))
