"""`ops/bitstream.pack_streams`: the `[L, W]` word matrix every device
read path decodes from, held bit for bit against a plain per-stream
reference written here, and its memory against the output's size."""

import tracemalloc

import numpy as np
import pytest

from m3_tpu.ops.bitstream import PAD_WORDS, pack_streams, unpack_stream


def _payloads(n, lo, hi, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, int(k), dtype=np.uint8).tobytes()
            for k in rng.integers(lo, hi + 1, n)]


def _skewed():
    streams = _payloads(1_000, 90, 110, 3)
    streams[500] = _payloads(1, 2_000, 2_000, 4)[0]
    return streams


def _linear64(n):
    return max(64, -(-n // 64) * 64)


def _pow2(n):
    return max(64, 1 << (n - 1).bit_length())


CASES = {
    "empty_list": lambda: [],
    "all_empty_streams": lambda: [b""] * 5,
    "one_empty_among_full": lambda: [b"\x01\x02\x03", b"", b"\xff" * 7],
    "lengths_1_to_9": lambda: [bytes(range(1, k + 1)) for k in range(1, 10)],
    "even_1000_rows": lambda: _payloads(1_000, 1_000, 1_000, 1),
    "ragged_1000_rows": lambda: _payloads(1_000, 960, 1_020, 2),
    "one_stream_20x": _skewed,
    "memoryview": lambda: [memoryview(s) for s in _payloads(64, 1, 300, 5)],
    "bytearray": lambda: [bytearray(s) for s in _payloads(64, 1, 300, 6)],
    "mixed_types": lambda: [t(s) for t, s in zip(
        (bytes, bytearray, memoryview) * 7, _payloads(21, 0, 40, 7))],
    "fleet_25000_x_1kb": lambda: _payloads(25_000, 960, 1_020, 8),
}


def _reference(streams, pad):
    """One row at a time: the stream's bytes, zero-filled to whole
    words, read as big-endian uint32."""
    n = len(streams)
    width = max(((len(s) + 3) // 4 for s in streams), default=0) + PAD_WORDS
    rows, cols = (n, width) if pad is None else (pad(n), pad(width))
    words = np.zeros((rows, cols), dtype=np.uint32)
    nbits = np.zeros(rows, dtype=np.int32)
    for i, s in enumerate(streams):
        row = np.zeros(width * 4, dtype=np.uint8)
        row[:len(s)] = np.frombuffer(s, dtype=np.uint8)
        words[i, :width] = row.view(">u4")
        nbits[i] = 8 * len(s)
    return words, nbits


@pytest.mark.parametrize("pad", [None, _linear64, _pow2],
                         ids=["natural", "linear64", "pow2"])
@pytest.mark.parametrize("case", CASES)
def test_pack_streams_matches_reference(case, pad):
    streams = CASES[case]()
    want_words, want_nbits = _reference(streams, pad)
    words, nbits = pack_streams(streams, pad=pad)
    assert words.dtype == np.uint32 and nbits.dtype == np.int32
    assert words.shape == want_words.shape
    assert nbits.shape == want_nbits.shape
    assert np.array_equal(words, want_words)
    assert np.array_equal(nbits, want_nbits)
    if pad is None:
        # a peek at the longest stream's end reads PAD_WORDS past it
        assert not words[:, words.shape[1] - PAD_WORDS:].any()
    step = max(1, len(streams) // 200)
    for i in range(0, len(streams), step):
        assert unpack_stream(words[i], nbits[i]) == bytes(streams[i])


def test_pack_streams_peak_memory_is_bounded_by_its_output():
    """No array with an element per input byte beside the result: a
    per-byte row and column index (int64 each) read 32x here."""
    streams = _payloads(5_000, 960, 1_020, 9)
    pack_streams(streams[:10])
    tracemalloc.start()
    try:
        before = tracemalloc.get_traced_memory()[0]
        tracemalloc.reset_peak()
        words, _ = pack_streams(streams)
        peak = tracemalloc.get_traced_memory()[1] - before
    finally:
        tracemalloc.stop()
    assert peak < 4 * words.nbytes, (peak, words.nbytes)
