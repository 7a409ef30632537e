"""The copied wire encoder round-trips through the program's native
parser (native/prom_wire.cc)."""

import numpy as np

from harness import wire


def test_write_request_round_trips_through_native_parser():
    from m3_tpu.query import remote_write
    from m3_tpu.utils import snappy
    from m3_tpu.utils.native import decode_write_request_native

    rng = np.random.default_rng(7)
    ts_ms = (1_790_000_000 + np.arange(0, 7200, 10, dtype=np.int64)) * 1000
    values = np.cumsum(rng.integers(0, 100, size=(25, len(ts_ms))),
                       axis=1).astype(np.float64)
    labels = [{b"__name__": b"http_requests_total", b"job": b"job-001",
               b"zone": b"zone-%d" % (i % 10), b"instance": b"inst-%04d" % i}
              for i in range(25)]
    body = wire.write_request([wire.label_bytes(ls) for ls in labels],
                              ts_ms, values)
    raw = snappy.decompress(body)
    ls, ss, off, blob, got_ts, got_vals = decode_write_request_native(raw)
    assert len(ss) - 1 == 25
    assert np.array_equal(np.asarray(got_vals).reshape(25, -1), values)
    assert np.array_equal(np.asarray(got_ts).reshape(25, -1),
                          np.broadcast_to(ts_ms, values.shape))
    for i in range(25):
        assert remote_write.labels_from_offsets(
            off, blob, int(ls[i]), int(ls[i + 1])) == labels[i]
