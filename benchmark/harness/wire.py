"""The load generator's side of the Prometheus remote-write wire.

Copied from chip_smoke.py (PR 22) so that a later change to the smoke
cannot change the yardstick.  Nothing here imports the program.
"""

from __future__ import annotations

import numpy as np

def _uvarint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _len_delim(field: int, body: bytes) -> bytes:
    return bytes([(field << 3) | 2]) + _uvarint(len(body)) + body


def label_bytes(labels: dict[bytes, bytes]) -> bytes:
    """TimeSeries.labels (field 1), sorted by name."""
    return b"".join(
        _len_delim(1, _len_delim(1, k) + _len_delim(2, labels[k]))
        for k in sorted(labels))


def sample_bytes(ts_ms: np.ndarray, values: np.ndarray) -> np.ndarray:
    """Vectorized TimeSeries.samples (field 2): [S, T] float64 values at
    shared timestamps ts_ms [T] -> uint8 [S, T*18].  A Sample is
    `09 <f64 LE> 10 <varint ts_ms>`; ms timestamps of this century take
    a 6-byte varint, so every sample is 18 bytes on the wire."""
    if not (int(ts_ms.min()) >= 1 << 35 and int(ts_ms.max()) < 1 << 42):
        raise ValueError("timestamps outside the 6-byte varint range")
    S, T = values.shape
    out = np.empty((S, T, 18), dtype=np.uint8)
    out[:, :, 0] = 0x12
    out[:, :, 1] = 16
    out[:, :, 2] = 0x09
    out[:, :, 3:11] = np.ascontiguousarray(
        values, dtype="<f8").view(np.uint8).reshape(S, T, 8)
    out[:, :, 11] = 0x10
    t = ts_ms.astype(np.uint64)
    for k in range(6):
        byte = ((t >> np.uint64(7 * k)) & np.uint64(0x7F)).astype(np.uint8)
        if k < 5:
            byte |= 0x80
        out[:, :, 12 + k] = byte[None, :]
    return out.reshape(S, T * 18)


def snappy_literal(data: bytes) -> bytes:
    """Spec-valid snappy block stream made of literal elements only."""
    out = bytearray(_uvarint(len(data)))
    for lo in range(0, len(data), 65536):
        chunk = data[lo:lo + 65536]
        out.append(61 << 2)                      # literal, 2-byte length
        out += (len(chunk) - 1).to_bytes(2, "little")
        out += chunk
    return bytes(out)


def write_request(label_blobs: list[bytes], ts_ms: np.ndarray,
                  values: np.ndarray) -> bytes:
    """One snappy-framed WriteRequest: row i of `values` under
    label_blobs[i], every row at the shared timestamps."""
    samples = sample_bytes(ts_ms, values)
    return snappy_literal(b"".join(
        _len_delim(1, lb + samples[row].tobytes())
        for row, lb in enumerate(label_blobs)))
