"""The latency row (configuration m3query-histogram, cell dash-p99) on
the CPU, held by the suite the driver runs.

The cell's controls and its traced run at rehearsal size, through the
whole served path (benchmark/run.py --rehearse in this process): the
cases of benchmark/tests/test_control_hq.py.  The cell's query, its
aggregated `sum by (le)` form and the mean-latency ratio through the
engine against the plain reference (benchmark/harness/reference_hq.py)
on the generator's seeded arrays, with what the record says of the
quantile; the reference against the Prometheus documentation's rules
written out a point at a time; the comparison's tie rule; the
generator's law; the manifest's new entries."""

import json
import pathlib
import sys
import time

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCHMARK = ROOT / "benchmark"
for path in (BENCHMARK, BENCHMARK / "tests"):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))

import lint_manifest  # noqa: E402
from harness import fleet_histogram, reference, reference_hq  # noqa: E402
from test_control_hq import *  # noqa: E402,F401,F403 - its cases are run here
from traffic_kinds import query_hq_loop  # noqa: E402

SEC = 10**9
CONFIG = json.loads((BENCHMARK / "configs" / "m3query-histogram.json")
                    .read_text())
MIX = json.loads((BENCHMARK / "traffic" / "panels-p99-4c.json").read_text())
UBS = [float(le) for le in fleet_histogram.LE]
LIMIT = MIX["limits"]["panel_max_rel_gap"]


def _fleet(seed=4701, jobs=None) -> fleet_histogram.HistogramFleet:
    cfg = dict(CONFIG, **CONFIG["rehearse"])
    if jobs:
        cfg["jobs"] = jobs
    return fleet_histogram.HistogramFleet(
        cfg, seed, int(time.time()), cfg["hours"] * 3600 // cfg["block_s"])


@pytest.fixture(scope="module")
def sealed(tmp_path_factory):
    """The configuration's fleet at its rehearsal size (2 jobs x 10
    instances x 14 series, 4 h), written block by block and sealed by
    the database's own tick and flush: the float `_sum` series through
    the codec's XOR path.  -> (database, fleet)."""
    from m3_tpu.storage.database import Database, DatabaseOptions
    from m3_tpu.storage.namespace import NamespaceOptions, RetentionOptions

    fleet = _fleet()
    db = Database(DatabaseOptions(path=str(tmp_path_factory.mktemp("hq")),
                                  num_shards=4, commit_log_enabled=False))
    db.create_namespace(NamespaceOptions(
        name="default", retention=RetentionOptions(
            retention_period=48 * 3600 * SEC,
            block_size=CONFIG["block_s"] * SEC)))
    ids = [b"s%04d" % i for i in range(fleet.n_series)]
    tags = [fleet.labels(i) for i in range(fleet.n_series)]
    for k in range(fleet.n_blocks):
        ts = (fleet.block_ts(k) * SEC).tolist()
        vals = fleet.block_values(0, fleet.n_series, k)
        for i in range(fleet.n_series):
            db.write_batch("default", [ids[i]] * len(ts),
                           [tags[i]] * len(ts), ts, vals[i].tolist())
    db.tick()
    db.flush()
    yield db, fleet
    db.close()


def _served(db, fleet, query):
    """`query` over the cell's range through both tiers -> (the device
    tier's matrix, its record, the host tier's matrix, the steps)."""
    from m3_tpu.query import slowlog
    from m3_tpu.query.engine import Engine

    start = (fleet.t0 + MIX["start_offset_s"]) * SEC
    end = (fleet.seal_end - MIX["step_s"]) * SEC
    _, got = Engine(db, "default", lookback_nanos=300 * SEC,
                    device_serving=True).query_range(
                        query, start, end, MIX["step_s"] * SEC)
    rec = slowlog.log().records(limit=1)[0]
    assert rec["expr"] == query
    _, host = Engine(db, "default", lookback_nanos=300 * SEC,
                     device_serving=False).query_range(
                         query, start, end, MIX["step_s"] * SEC)
    steps = np.arange(fleet.t0 + MIX["start_offset_s"],
                      fleet.seal_end - MIX["step_s"] + 1, MIX["step_s"])
    return got, rec, host, steps


def _by_instance(matrix):
    return np.asarray(matrix.values)[np.argsort(
        [ls[b"instance"] for ls in matrix.labels])]


def _gap(got, want):
    assert got.shape == want.shape
    assert np.array_equal(np.isnan(got), np.isnan(want))
    return float(np.nanmax(np.abs(got - want) / np.abs(want)))


def test_cells_query_equals_the_reference_and_records_its_quantile(sealed):
    """`histogram_quantile(0.99, rate(.._bucket{job=J}[5m]))`, a row an
    instance, served whole by the fused program: equal to the plain
    reference and to the host evaluator; the record carries the
    quantile's groups and buckets, and the counter counts them."""
    from m3_tpu.utils import instrument

    db, fleet = sealed
    counted = instrument.counter("m3_query_hq_groups_total").value
    query = MIX["query"].replace("<J>", fleet.job_name(1))
    got, rec, host, steps = _served(db, fleet, query)
    assert rec["device_serving"] is True
    assert rec["device_tier"]["host_nodes"] == 0
    assert (rec["hq_groups"], rec["hq_buckets"]) == (10, 12)
    assert (rec["lanes"], rec["lanes_pad"], rec["rows_out"]) == (120, 128, 10)
    assert (rec["groups"], rec["topk_k"]) == (0, 0)
    assert query_hq_loop.without_hq([rec], 10, 12) == 0
    assert query_hq_loop.without_hq([rec], 100, 12) == 1
    # a parent's record, from before the fields, is not held to them
    old = {k: v for k, v in rec.items() if not k.startswith("hq_")}
    assert query_hq_loop.without_hq([old], 100, 12) == 0
    # the device tier's record and the host's: the quantile counted once
    assert instrument.counter(
        "m3_query_hq_groups_total").value == counted + 10
    assert {tuple(sorted(ls)) for ls in got.labels} == {
        (b"instance", b"job", b"zone")}

    ts, buckets, _, _ = fleet.job_histograms(1)
    rates = reference_hq.bucket_rates(ts, buckets, steps, MIX["range_s"])
    want = reference_hq.quantile(MIX["q"], UBS, rates)
    assert not np.isnan(want).any()
    assert not reference_hq.tied(MIX["q"], rates, LIMIT).any()
    assert _gap(_by_instance(got), want) < LIMIT
    assert _gap(_by_instance(host), want) < LIMIT
    # the 99th percentile falls in another bucket from instance to
    # instance, in +Inf (answered by the highest finite bound) for the
    # slowest: the panel is not decided by noise
    at_end = np.searchsorted(UBS, want[:, -1])
    assert len(set(at_end)) >= 5 and (want[np.argmax(fleet.median[1])]
                                      == 10.0).all()
    # the kind's own comparison on the same reply, as HTTP would hand it
    rows = {tuple(sorted((k.decode(), v.decode()) for k, v in ls.items())):
            (steps.astype(np.float64), np.asarray(got.values)[i])
            for i, ls in enumerate(got.labels)}
    found = query_hq_loop.compare_job(fleet, MIX, 1, rows, steps)
    assert found["max_rel_gap"] < LIMIT
    assert {k: found[k] for k in ("points_nan_mismatch", "rows_unknown",
                                  "rows_missing", "points_tied")} == {
        "points_nan_mismatch": 0, "rows_unknown": 0, "rows_missing": 0,
        "points_tied": 0}


def test_aggregated_form_and_mean_latency_equal_the_reference(sealed):
    """`histogram_quantile(0.99, sum by (le)(rate(..)))` (one row a
    job: a grouped reduce under the quantile) and the mean-latency
    ratio (the only reader of the float `_sum` series, sealed through
    the codec's XOR path and decoded on the device tier)."""
    db, fleet = sealed
    ts, buckets, sums, counts = fleet.job_histograms(0)
    job = fleet.job_name(0)

    query = (f'histogram_quantile(0.99, sum by (le)(rate('
             f'{fleet.metric}_bucket{{job="{job}"}}[5m])))')
    got, rec, host, steps = _served(db, fleet, query)
    assert rec["device_tier"]["host_nodes"] == 0
    assert (rec["hq_groups"], rec["hq_buckets"], rec["groups"]) == (1, 12, 12)
    rates = reference_hq.bucket_rates(ts, buckets, steps, MIX["range_s"])
    want = reference_hq.quantile(0.99, UBS, rates.sum(axis=0, keepdims=True))
    assert _gap(np.asarray(got.values), want) < LIMIT
    assert _gap(np.asarray(host.values), want) < LIMIT

    query = (f'sum(rate({fleet.metric}_sum{{job="{job}"}}[5m])) / '
             f'sum(rate({fleet.metric}_count{{job="{job}"}}[5m]))')
    got, rec, host, steps = _served(db, fleet, query)
    assert rec["device_serving"] and rec["device_tier"]["host_nodes"] == 0
    assert rec["hq_groups"] == 0
    want = reference_hq.mean_latency(ts, sums, counts, steps,
                                     MIX["range_s"])[None, :]
    assert 0.5 < want.mean() < 1.5      # seconds: log-normal means
    assert _gap(np.asarray(got.values), want) < LIMIT
    assert _gap(np.asarray(host.values), want) < LIMIT
    # a float counter's samples survive the seal bit for bit
    from m3_tpu.query.engine import Engine
    _, raw = Engine(db, "default", device_serving=False).query_range(
        f'{fleet.metric}_sum{{job="{job}",instance="inst-0003"}}',
        fleet.t0 * SEC, (fleet.seal_end - 10) * SEC, 10 * SEC)
    assert np.array_equal(np.asarray(raw.values)[0], sums[3])


def _by_the_rules(q, ubs, counts):
    """The documentation's rules, a point at a time."""
    if np.isnan(counts).any():
        return np.nan
    c = list(counts)
    for b in range(1, len(c)):          # made monotonic first
        c[b] = max(c[b], c[b - 1])
    if not c[-1] > 0:                   # no observations
        return np.nan
    rank = q * c[-1]
    b = next(i for i, x in enumerate(c) if x >= rank)
    if b == len(c) - 1:                 # in +Inf: the highest finite bound
        return ubs[-2]
    if b == 0 and ubs[0] <= 0:
        return ubs[0]
    lower, below = (0.0, 0.0) if b == 0 else (ubs[b - 1], c[b - 1])
    return lower + (ubs[b] - lower) * (rank - below) / (c[b] - below)


@pytest.mark.parametrize("q", [0.25, 0.5, 0.9, 0.99, 1.0])
def test_reference_follows_the_documented_rules_point_by_point(q):
    rng = np.random.default_rng(int(q * 100))
    ubs = [-1.0, 0.5, 1.0, 2.5, np.inf]
    counts = np.cumsum(rng.integers(0, 4, (40, 5, 30)), axis=1).astype(float)
    counts[3, 2, :] -= 1.0                  # not monotonic over le
    counts[4, :, :5] = 0.0                  # no observations
    counts[5, 1, 7] = np.nan                # a bucket without a rate
    counts[6, :3, :] = 0.0                  # all of it above the third bound
    for bounds in (ubs, UBS[:4] + [np.inf]):
        got = reference_hq.quantile(q, bounds, counts)
        want = np.array([[_by_the_rules(q, bounds, counts[g, :, s])
                          for s in range(30)] for g in range(40)])
        assert np.array_equal(np.isnan(got), np.isnan(want))
        assert np.allclose(got, want, rtol=1e-15, atol=0, equal_nan=True)
    assert np.isnan(got[4, :5]).all() and np.isnan(got[5, 7])
    for bad, value in ((-0.1, -np.inf), (1.5, np.inf)):
        assert (reference_hq.quantile(bad, ubs, counts) == value).all()
    assert np.isnan(reference_hq.quantile(np.nan, ubs, counts)).all()
    with pytest.raises(ValueError):
        reference_hq.quantile(q, ubs[:-1], counts[:, :-1])


def test_comparison_holds_every_point_but_a_tie():
    """A rank that equals a bucket's count which the next bucket
    repeats may be answered from either bucket; everything else is
    held: a value off by float32, a point on one side only, a row of
    other labels, a row that is not there."""
    ubs = [1.0, 2.0, 4.0, np.inf]
    steps = np.arange(0, 180, 60)
    # row 0: plain; row 1: rank 0.5 x 8 = 4 = the first bucket's count,
    # which the second repeats (a tie: 1.0 or 2.0); row 2: no rate at
    # the last step
    counts = np.array([[[1.0, 1, 1], [3, 3, 3], [6, 6, 6], [8, 8, 8]],
                       [[4.0, 4, 4], [4, 4, 4], [6, 6, 6], [8, 8, 8]],
                       [[2.0, 2, np.nan], [3, 3, np.nan], [5, 5, np.nan],
                        [8, 8, np.nan]]])
    want = reference_hq.quantile(0.5, ubs, counts)
    free = reference_hq.tied(0.5, counts, LIMIT)
    assert free.tolist() == [[False] * 3, [True] * 3, [False] * 3]
    assert want[1].tolist() == [1.0] * 3 and np.isnan(want[2, 2])
    keys = [(("instance", f"i{g}"),) for g in range(3)]

    def reply(matrix):
        return {keys[g]: (steps[~np.isnan(row)].astype(np.float64),
                          row[~np.isnan(row)])
                for g, row in enumerate(matrix)}

    def found(matrix):
        return reference_hq.compare(reply(matrix), keys, steps, want, free,
                                    LIMIT)

    clean = {"max_rel_gap": 0.0, "points_nan_mismatch": 0,
             "rows_unknown": 0, "rows_missing": 0, "points_tied": 3}
    assert found(want) == clean
    other = want.copy()
    other[1] = 2.0                      # the tie's other side
    assert found(other) == clean
    other = want.copy()
    other[0, 1] = float(np.float32(want[0, 1] * (1 + 3e-7)))
    assert 1e-7 < found(other)["max_rel_gap"] < 1e-6
    other = want.copy()
    other[2, 2], other[0, 0] = 1.0, np.nan
    assert found(other)["points_nan_mismatch"] == 2
    stranger = {(("zone", "z"),): (steps[:1] * 1.0, np.array([1.0]))}
    assert reference_hq.compare({**reply(want), **stranger}, keys, steps,
                                want, free, LIMIT)["rows_unknown"] == 1
    assert found(want[:2])["rows_missing"] == 1


def test_histogram_fleet_keeps_its_law_and_regenerates_a_block():
    fleet, again = _fleet(4702, jobs=3), _fleet(4702, jobs=3)
    width = fleet_histogram.RUN * fleet_histogram.PER_INSTANCE
    assert (fleet.per_job, fleet.n_series, width) == (140, 420, 28)
    # any (series range, block) on demand, bit for bit: a later block
    # first, which draws the one before it for its totals
    for lo, hi, k in ((140, 168, 1), (0, 28, 0), (392, 420, 1), (0, 140, 1)):
        assert np.array_equal(fleet.block_values(lo, hi, k),
                              again.block_values(lo, hi, k))
    assert not np.array_equal(fleet.rank, _fleet(4703, jobs=3).rank)
    for j in range(3):
        assert sorted(fleet.rank[j]) == list(range(10))
    assert np.allclose(np.sort(fleet.median[0]),
                       0.010 * 256 ** (np.arange(10) / 9))
    ts, buckets, sums, counts = fleet.job_histograms(2)
    assert len(ts) == 1440 and buckets.shape == (10, 12, 1440)
    # buckets cumulative over le, +Inf equal to _count at every sample,
    # no counter ever resets, block 1 goes on from block 0's totals
    assert (np.diff(buckets, axis=1) >= 0).all()
    assert np.array_equal(buckets[:, -1], counts)
    for series in (buckets.reshape(120, 1440), sums, counts):
        assert (np.diff(series, axis=1) >= 0).all()
    n = np.diff(counts, axis=1)
    assert n.min() == 0 and n.max() == 99 and 48 < n.mean() < 51
    assert (counts == np.rint(counts)).all()
    assert (buckets == np.rint(buckets)).all()
    # _sum is a full-precision float counter whose mean duration is the
    # log-normal's: the median x exp(sigma^2 / 2)
    assert (sums != np.rint(sums)).mean() > 0.99
    mean = sums[:, -1] / counts[:, -1]
    assert np.allclose(mean, fleet.median[2] * np.exp(0.5), rtol=0.05)
    # the share of requests at or under a bound is the log-normal's
    share = buckets[:, :-1, -1] / counts[:, -1:]
    z = np.log(np.asarray(UBS[:-1])[None, :] / fleet.median[2][:, None])
    from math import erf
    cdf = 0.5 * (1 + np.vectorize(erf)(z / np.sqrt(2)))
    assert np.abs(share - cdf).max() < 0.01
    # the low buckets of a slow instance do not move between scrapes
    slow = int(np.argmax(fleet.median[2]))
    assert (np.diff(buckets[slow, 0]) == 0).mean() > 0.99
    labels = fleet.labels(2 * 140 + 7 * 14 + 3)
    assert labels == {b"__name__": b"http_request_duration_seconds_bucket",
                      b"job": b"job-002", b"zone": b"zone-7",
                      b"instance": b"inst-0007", b"le": b"0.05"}
    assert fleet.labels(2 * 140 + 7 * 14 + 11)[b"le"] == b"+Inf"
    assert [fleet.labels(13)[b"__name__"], fleet.labels(12)[b"__name__"]] == [
        b"http_request_duration_seconds_count",
        b"http_request_duration_seconds_sum"]
    assert b"le" not in fleet.labels(12)
    assert fleet.block_requests(0) == [(lo, lo + 28)
                                       for lo in range(0, 420, 28)]


def test_rate_of_a_float_counter_in_the_reference_is_the_plain_one():
    """reference.rate over the generator's float `_sum` series: the
    increase over the window's samples, extrapolated as Prometheus
    does, equals the sum of the increments between them."""
    fleet = _fleet(4704, jobs=1)
    ts, _, sums, _ = fleet.job_histograms(0)
    steps = np.array([ts[100]])
    got = reference.rate(ts, sums, steps, 300)[:, 0]
    first, last = 100 - 30, 100
    plain = (sums[:, last] - sums[:, first]) * (300 / 300) / 300
    assert np.allclose(got, plain, rtol=1e-12)


def test_manifest_holds_the_cell_and_lints():
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = next(c for c in man["configs"] if c["name"] == "m3query-histogram")
    assert cfg["reduced"] == ["hours", "jobs", "query_fanout_series"]
    assert len(cfg["source"]) <= 200 and cfg["source"] == CONFIG["source"]
    assert (len(CONFIG["le"]), CONFIG["series_per_instance"]) == (12, 14)
    assert "le" not in CONFIG["reduced"]
    assert (CONFIG["jobs"] * CONFIG["instances_per_job"]
            * CONFIG["series_per_instance"]) == 12_600
    assert CONFIG["query_fanout_series"] == 1_200
    cell = man["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        "dash-p99", "m3query-histogram", "panels-p99-4c", 1)
    assert len(man["configs"]) == len(man["workloads"]) == 6
    judged = [m["name"] for m in man["end_to_end"]
              if "dash-p99" in m.get("workloads", ["dash-p99"])]
    assert "setup_s" in judged and "panel_ms_p95" in judged
    assert sorted(MIX["end_to_end"] + ["setup_s"]) == sorted(judged)
    assert (MIX["kind"], MIX["clients"], MIX["step_s"], MIX["ramp_s"],
            MIX["start_offset_s"], MIX["trace_slice_s"]) == (
        "query_hq_loop", 4, 60, 3.0, 600, 3.0)
    assert MIX["query"] == ('histogram_quantile(0.99, rate(http_request_'
                            'duration_seconds_bucket{job="<J>"}[5m]))')
    layered = {m["name"]: m for m in man["per_layer"]
               if m.get("workloads") == ["dash-p99"]}
    assert set(layered) == {f"{name}.hq" for name in (
        "fused_served_pct", "plan_ms", "fetch_ms", "pack_ms", "device_ms",
        "device_wait_ms", "device_queue_depth", "d2h_ms", "reply_ms",
        "engine_cpu_ms", "interp_wait_ms", "program_ms",
        "program_hbm_peak_mb", "panel_median_ms", "rows_per_reply",
        "hq_share_pct", "hq_groups", "hq_buckets", "program_roofline_pct",
        "frontend_ms", "reply_native_pct")}
    assert all(m["moves"] in judged for m in layered.values())
    assert lint_manifest.lint() == []
