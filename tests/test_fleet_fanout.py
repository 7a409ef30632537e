"""The fleet-wide panel on the normal path: `sum by (job|zone)(rate())`
over every series of a metric, through the HTTP front end with the
device tier on, at lane counts past one chunk of
query_pipeline._MERGE_LANES.  600 series pack to 640 lanes (512 and a
last chunk that overlaps its neighbour), 1,024 to exactly two chunks.
Each answer is held against the host tier and against the benchmark's
independent numpy reference; counters with resets and lanes with gaps
lie among the lanes that two chunks both compute.

Sizes are short on purpose (60 samples a block, 36 steps): XLA:CPU runs
the windowed selection slowly at a cell's own."""

import json
import pathlib
import sys
import urllib.parse
import urllib.request

import numpy as np
import pytest

from m3_tpu.models import query_pipeline
from m3_tpu.ops import kernel_telemetry, m3tsz_decode
from m3_tpu.query import slowlog
from m3_tpu.query.engine import Engine
from m3_tpu.query.http import CoordinatorServer
from m3_tpu.storage import (Database, DatabaseOptions, NamespaceOptions,
                            RetentionOptions)
from m3_tpu.utils import instrument, xtime

BENCHMARK = pathlib.Path(__file__).resolve().parents[1] / "benchmark"
if str(BENCHMARK) not in sys.path:
    sys.path.insert(0, str(BENCHMARK))

from harness import loadgen, reference  # noqa: E402

SEC = xtime.SECOND
BLOCK = 2 * xtime.HOUR
T0 = (1_600_000_000 * SEC // BLOCK) * BLOCK
CADENCE_S = 120
PER_BLOCK = BLOCK // (CADENCE_S * SEC)          # 60
N_SAMPLES = 2 * PER_BLOCK
ZONES = 10
RANGE_S, STEP_S = 600, 300
# the lanes both merge chunks of the 640-lane program compute (128..511),
# and both windowed chunks of any program whose lanes do not divide
GAPPED = (130, 300, 511, 512, 599)              # samples 40..69 missing
RESETS = (129, 300, 400, 510, 598)              # the counter drops twice


def _fleet(n_series: int, per_job: int, seed: int = 7):
    """-> (ts_s [T], values [n_series, T], present bool [n_series, T],
    labels)."""
    rng = np.random.default_rng(seed)
    ts = T0 // SEC + CADENCE_S * np.arange(N_SAMPLES)
    values = np.cumsum(rng.integers(0, 100, (n_series, N_SAMPLES)),
                       axis=1).astype(np.float64)
    for i in RESETS:
        values[i, 33:] -= values[i, 32]
        values[i, 90:] -= values[i, 89]
    present = np.ones((n_series, N_SAMPLES), dtype=bool)
    for i in GAPPED:
        present[i, 40:70] = False
    labels = [{b"__name__": b"http_requests_total",
               b"job": b"job-%03d" % (i // per_job),
               b"zone": b"zone-%d" % (i % per_job % ZONES),
               b"instance": b"inst-%04d" % (i % per_job)}
              for i in range(n_series)]
    return ts, values, present, labels


def _serve(tmp_path, n_series: int, per_job: int):
    db = Database(DatabaseOptions(path=str(tmp_path), num_shards=4,
                                  commit_log_enabled=False))
    db.create_namespace(NamespaceOptions(
        name="default", retention=RetentionOptions(block_size=BLOCK)))
    ts, values, present, labels = _fleet(n_series, per_job)
    for i in range(n_series):
        keep = present[i]
        db.write_batch("default", [b"s%05d" % i] * int(keep.sum()),
                       [labels[i]] * int(keep.sum()),
                       (ts[keep] * SEC).tolist(), values[i, keep].tolist())
    db.tick(now_nanos=T0 + 2 * BLOCK + 11 * 60 * SEC)
    db.flush()
    return db, ts, values, present


def _reference_rates(ts, values, present, steps):
    """[series, steps] by the benchmark's reference, which takes series
    that share their timestamps: one call a pattern of presence."""
    out = np.empty((len(values), len(steps)))
    patterns, which = np.unique(present, axis=0, return_inverse=True)
    for p, keep in enumerate(patterns):
        rows = np.flatnonzero(which.ravel() == p)
        out[rows] = reference.rate(ts[keep], values[rows][:, keep], steps,
                                   RANGE_S)
    return out


def _get(port: int, **params):
    url = (f"http://127.0.0.1:{port}/api/v1/query_range?"
           + urllib.parse.urlencode(params))
    with urllib.request.urlopen(url, timeout=120) as resp:
        return json.loads(resp.read())


@pytest.fixture(scope="module", params=[(600, 200), (1024, 256)],
                ids=["600_lanes_overlapping_chunk", "1024_lanes_two_chunks"])
def served(request, tmp_path_factory):
    n_series, per_job = request.param
    db, ts, values, present = _serve(
        tmp_path_factory.mktemp(f"fleet{n_series}"), n_series, per_job)
    srv = CoordinatorServer(db, port=0, engine=Engine(
        db, "default", device_serving=True)).start()
    try:
        yield {"db": db, "port": srv.port, "ts": ts, "values": values,
               "present": present, "n_series": n_series, "per_job": per_job}
    finally:
        srv.stop()
        db.close()


@pytest.mark.parametrize("by", ["job", "zone"])
def test_fleet_wide_panel_over_http_equals_host_tier_and_reference(served,
                                                                   by):
    n, per_job, ts = served["n_series"], served["per_job"], served["ts"]
    query = f"sum by ({by})(rate(http_requests_total[10m]))"
    start, end = int(ts[0]) + 1200, int(ts[-1])
    steps = np.arange(start, end + 1, STEP_S)
    compiles = kernel_telemetry.snapshot()[
        "device_grouped_pipeline"]["compiles"]
    lanes_total = instrument.counter("m3_query_lanes_total").value
    got = loadgen.rows_of(_get(served["port"], query=query, start=start,
                               end=end, step=STEP_S))

    # the record: the device tier served it, at this fan-out
    rec = next(r for r in slowlog.log().records() if r["expr"] == query)
    assert rec["device_serving"] and "device_declines" not in rec
    lanes_pad = -(-n // 64) * 64
    chunks = -(-lanes_pad // query_pipeline._MERGE_LANES)
    assert chunks == 2
    assert (rec["lanes"], rec["lanes_pad"], rec["lane_chunks"],
            rec["rows"]) == (n, lanes_pad, chunks, 2 * n)
    assert rec["window_form"] == "select"
    assert (instrument.counter("m3_query_lanes_total").value
            - lanes_total) == n
    # the decode scan, from the buckets alone: 60 samples a block pack
    # into 64 words a row and are decoded in the bucket of 128, so the
    # per-row word window is refilled 128 / WIN_STEPS times a call
    assert rec["decode_refills"] == query_pipeline.decode_refills(
        128, 64) == 128 // m3tsz_decode.WIN_STEPS > 0
    # the program this call compiled gave the compiler's account of its
    # peak: no less than its arguments and result
    st = kernel_telemetry.snapshot()["device_grouped_pipeline"]
    assert st["compiles"] == compiles + 1
    assert st["hbm_peak_bytes"] >= (2 * n * 64 * 4 + 16 * 64 * 8)
    assert instrument.gauge("m3_kernel_hbm_peak_bytes",
                            kernel="device_grouped_pipeline").value == (
        st["hbm_peak_bytes"])

    # the host tier of the same engine: equal
    _, mat = Engine(served["db"], "default",
                    device_serving=False).query_range(
        query, start * SEC, end * SEC, STEP_S * SEC)
    host = reference.drop_nan(steps, {
        tuple(sorted((k.decode(), v.decode()) for k, v in ls.items())): row
        for ls, row in zip(mat.labels, np.asarray(mat.values))})
    assert reference.max_rel_gap(got, host) == 0.0

    # the benchmark's reference, from the generator's arrays: within the
    # 1 ns by which the program opens a window (PERF.md section 2)
    series = np.arange(n)
    groups, name = {"job": (series // per_job, lambda g: f"job-{g:03d}"),
                    "zone": (series % per_job % ZONES,
                             lambda g: f"zone-{g}")}[by]
    rates = _reference_rates(ts, served["values"], served["present"], steps)
    # the gapped and the reset lanes do answer, and differently from
    # their neighbours: a reset corrected, a gap left NaN
    assert np.isnan(rates[list(GAPPED)]).any(axis=1).all()
    assert not np.isnan(rates[[129, 400]]).any()
    want = reference.drop_nan(steps, {
        ((by, name(g)),): row
        for g, row in reference.sum_by(groups, rates).items()})
    assert len(want) == (n // per_job if by == "job" else ZONES)
    assert reference.max_rel_gap(got, want) <= 1e-11


def test_record_says_how_often_the_decode_scan_refilled_its_window(tmp_path):
    """At the benchmark cells' buckets (a 2 h block at 10 s: 720 samples,
    some 910 bytes, 256 words a row, 768 samples decoded) the decode scan
    reads a per-row word window, refilled every WIN_STEPS steps after the
    first record, and the query's record and the counter say how often,
    as a function of the buckets alone; a row no wider than the window is
    its own (0).  The answer is the host tier's."""
    K, C = m3tsz_decode.WIN_STEPS, m3tsz_decode.WIN_WORDS
    assert query_pipeline.decode_refills(768, 256) == -(-768 // K) > 0
    assert query_pipeline.decode_refills(1024, 512) == -(-1024 // K)
    assert query_pipeline.decode_refills(768, C) == 0

    n, per_block = 8, BLOCK // (10 * SEC)
    db = Database(DatabaseOptions(path=str(tmp_path), num_shards=2,
                                  commit_log_enabled=False))
    db.create_namespace(NamespaceOptions(
        name="default", retention=RetentionOptions(block_size=BLOCK)))
    rng = np.random.default_rng(3)
    ts = (T0 // SEC + 10 * np.arange(per_block)) * SEC
    for i in range(n):
        values = np.cumsum(rng.integers(0, 100, per_block)).astype(float)
        db.write_batch(
            "default", [b"s%02d" % i] * per_block,
            [{b"__name__": b"http_requests_total", b"job": b"job-%d" % (i % 2),
              b"instance": b"inst-%d" % i}] * per_block,
            ts.tolist(), values.tolist())
    db.tick(now_nanos=T0 + BLOCK + 11 * 60 * SEC)
    db.flush()
    try:
        query = "sum by (job)(rate(http_requests_total[5m]))"
        span = ((T0 // SEC + 600) * SEC, (T0 // SEC + 7000) * SEC, 300 * SEC)
        _, got = Engine(db, "default", device_serving=True).query_range(
            query, *span)
        rec = next(r for r in slowlog.log().records() if r["expr"] == query)
        assert rec["device_serving"] and rec["rows"] == n
        assert rec["decode_refills"] == -(-768 // K)
        _, want = Engine(db, "default", device_serving=False).query_range(
            query, *span)
        # a host-tier record carries the field and counts nothing
        host = next(r for r in slowlog.log().records() if r["expr"] == query)
        assert not host["device_serving"] and host["decode_refills"] == 0
        np.testing.assert_allclose(np.asarray(got.values),
                                   np.asarray(want.values), rtol=1e-12)
        assert np.isfinite(np.asarray(got.values)).all()
    finally:
        db.close()
