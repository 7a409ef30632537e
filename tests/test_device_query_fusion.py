"""Whole-query device fusion: fused pipeline vs host tier, bit-for-bit.

query/plan.py lowers a PromQL op-tree into ONE jitted program
(models/query_pipeline.device_expr_pipeline).  These tests pin its
contract against the host evaluator:

- bit-identity (np.array_equal, equal_nan) for the exact family —
  arithmetic, comparisons, abs/ceil/floor/sqrt/sgn/round/clamp/
  timestamp, sum/avg/min/max/count/group, and the rate family — which
  this container's XLA:CPU lowers to the same bit patterns as numpy;
- 1e-12 relative closeness for transcendental-containing expressions
  (exp/ln/log2/log10/^ are ulp-loose on XLA) and 1e-9 for the loose
  agg family (stddev/stdvar/quantile), matching the tolerance keying
  the host differential suite already applies to the per-node tier;
- the padded-lanes-are-NaN invariant under `^` (NaN^0 == 1.0 would
  leak padding rows into aggregations without per-node re-masking);
- the DecodedBlockCache arrays bridge: warm queries feed the fused
  pipeline decoded grids with ZERO M3TSZ decode calls;
- compile-cache behavior: a varied-cardinality sweep inside one pow2
  shape bucket reuses the compiled program (zero recompiles);
- split-at-unsupported: a set-op wrapper evaluates on the host while
  its supported subtrees still device-serve, result unchanged.

Every fused case asserts ``stats["device_fused"] is True`` so a
silent decline to the per-node paths cannot masquerade as a pass.
"""

import random

import numpy as np
import pytest

from m3_tpu.cache import CacheOptions
from m3_tpu.ops import decode_counter
from m3_tpu.query import slowlog
from m3_tpu.query.engine import Engine
from m3_tpu.storage.database import Database, DatabaseOptions
from m3_tpu.storage.namespace import NamespaceOptions, RetentionOptions
from m3_tpu.utils import xtime

SEC = xtime.SECOND
BLOCK = 2 * xtime.HOUR
T0 = (1_600_000_000 * SEC // BLOCK) * BLOCK
LOOKBACK = 5 * 60 * SEC
START = T0 + 10 * 60 * SEC
END = T0 + 50 * 60 * SEC
STEP = 60 * SEC

JOBS = ("api", "db", "web")
DCS = ("east", "west")


def _write_series(db, metric, job, dc, rng, counter=False):
    ts, vs = [], []
    t = T0 + rng.randrange(1, 30) * SEC
    acc = 0.0
    while t < T0 + 3600 * SEC:
        if counter:
            acc += rng.uniform(0, 5)
            if rng.random() < 0.03:
                acc = rng.uniform(0, 2)  # counter reset
            vs.append(round(acc, 2))
        else:
            vs.append(round(rng.uniform(-50, 50), 2))
        ts.append(t)
        gap = rng.choice([1, 1, 1, 2, 3])
        if rng.random() < 0.04:
            gap = 40  # > lookback: series goes stale mid-range
        t += 10 * SEC * gap
    sid = ("%s|%s|%s" % (metric, job, dc)).encode()
    tags = {b"__name__": metric.encode(), b"job": job.encode(),
            b"dc": dc.encode()}
    db.write_batch("default", [sid] * len(ts), [tags] * len(ts), ts, vs)


@pytest.fixture(scope="module")
def fused_db(tmp_path_factory):
    rng = random.Random(20260805)
    db = Database(DatabaseOptions(
        path=str(tmp_path_factory.mktemp("fuseddb")), num_shards=4,
        commit_log_enabled=False))
    db.create_namespace(NamespaceOptions(
        name="default", retention=RetentionOptions(block_size=BLOCK)))
    for metric, counter in (("http_req", True), ("http_lim", True),
                            ("mem_use", False)):
        for job in JOBS:
            for dc in DCS:
                if metric == "mem_use" and rng.random() < 0.2:
                    continue  # absent series: matching must cope
                _write_series(db, metric, job, dc, rng, counter=counter)
    db.tick(now_nanos=T0 + 2 * BLOCK)
    db.flush()
    yield db
    db.close()


@pytest.fixture(scope="module")
def engines(fused_db):
    host = Engine(fused_db, "default", lookback_nanos=LOOKBACK,
                  device_serving=False)
    dev = Engine(fused_db, "default", lookback_nanos=LOOKBACK,
                 device_serving=True)
    return host, dev


def _run_both(host, dev, expr):
    _, mh = host.query_range(expr, START, END, STEP)
    dev.last_fetch_stats = None
    _, md = dev.query_range(expr, START, END, STEP)
    return mh, md, (dev.last_fetch_stats or {})


def _assert_same_shape(mh, md, expr):
    assert mh.labels == md.labels, expr
    assert mh.values.shape == md.values.shape, expr
    np.testing.assert_array_equal(np.isnan(mh.values),
                                  np.isnan(md.values), err_msg=expr)


# ops whose device lowering is the same bit pattern as the host numpy
# form on this backend: gauge temporal fns, arith/cmp/scalar fns, the
# core agg family.  The rate family (rate/increase/irate/...) does the
# extrapolation divide in a different association order and lands
# within a few ulps instead — those ride RATE_EXPRS at the 1e-12 gate
# the host differential suite already applies to the per-node tier.
EXACT_EXPRS = (
    "abs(delta(mem_use[5m])) + sqrt(abs(mem_use))",
    "max by (dc)(max_over_time(mem_use[5m]))"
    " - min by (dc)(min_over_time(mem_use[5m]))",
    "floor(mem_use) % 3 == bool 0",
    "round(avg by (job)(mem_use), 0.5) + 0",
    "timestamp(mem_use) - 1600000000",
    "sum(count_over_time(http_req[5m])) + count(mem_use)",
)

RATE_EXPRS = (
    "sum by (dc)(rate(http_req[5m])) / sum by (dc)(rate(http_lim[5m]))",
    "sum by (job)(rate(http_req[5m]))"
    " / on(job) sum by (job)(rate(http_lim[5m]))",
    "sum by (job, dc)(irate(http_req[5m]))"
    " - on(job) group_left sum by (job)(rate(http_lim[5m]))",
    "clamp(sum by (dc)(increase(http_req[10m])), 10, 1000)",
    "(rate(http_req[5m]) > 0.5) * 60",
    "sum by (dc)(rate(http_req[5m]) >= bool 0.2)",
)


def test_fused_bit_identical_exact_family(engines):
    """The exact-op family must match the host tier BIT-FOR-BIT: same
    labels, same NaN mask, np.array_equal on values."""
    host, dev = engines
    for expr in EXACT_EXPRS:
        mh, md, stats = _run_both(host, dev, expr)
        assert stats.get("device_fused") is True, (
            expr, dev._cost().fused_error)
        _assert_same_shape(mh, md, expr)
        assert np.array_equal(mh.values, md.values, equal_nan=True), expr


def test_fused_rate_family_strict_close(engines):
    """Counter-reset data: the rate family's extrapolation divide is
    ulp-reassociated on device, so the gate is the differential
    suite's strict 1e-12 — with labels and NaN masks still exact."""
    host, dev = engines
    for expr in RATE_EXPRS:
        mh, md, stats = _run_both(host, dev, expr)
        assert stats.get("device_fused") is True, (
            expr, dev._cost().fused_error)
        _assert_same_shape(mh, md, expr)
        np.testing.assert_allclose(
            np.nan_to_num(md.values), np.nan_to_num(mh.values),
            rtol=1e-12, atol=1e-12, err_msg=expr)


def test_fused_transcendental_within_ulp(engines):
    """exp/ln/log2/log10/^ lower ulp-loose on XLA:CPU — 1e-12 relative
    (the host differential suite's strict gate) must still hold."""
    host, dev = engines
    for expr in (
        "exp(ln(abs(mem_use) + 1)) - abs(mem_use)",
        "log2(abs(mem_use) + 2) + log10(abs(mem_use) + 2)",
        "sum by (dc)(rate(http_req[5m])) ^ 2",
    ):
        mh, md, stats = _run_both(host, dev, expr)
        assert stats.get("device_fused") is True, expr
        _assert_same_shape(mh, md, expr)
        np.testing.assert_allclose(
            np.nan_to_num(md.values), np.nan_to_num(mh.values),
            rtol=1e-12, atol=1e-12, err_msg=expr)


def test_fused_loose_agg_family(engines):
    """stddev/stdvar/quantile: cancellation-prone forms keyed loose
    (1e-9) in the differential suites; the fused tier inherits that
    gate, and the stats agg field must expose the loose op."""
    host, dev = engines
    for expr, agg in (
        ("stddev by (dc)(mem_use) + 0", "stddev"),
        ("quantile(0.9, mem_use) * 1", "quantile"),
    ):
        mh, md, stats = _run_both(host, dev, expr)
        assert stats.get("device_fused") is True, expr
        assert stats.get("agg") == agg, expr
        _assert_same_shape(mh, md, expr)
        np.testing.assert_allclose(
            np.nan_to_num(md.values), np.nan_to_num(mh.values),
            rtol=1e-9, atol=1e-9, err_msg=expr)


def test_padded_lanes_stay_nan_under_pow(engines):
    """NaN^0 == 1.0: without per-node re-masking, `^ 0` would turn
    padding lanes into 1.0 rows and sum() would count them."""
    host, dev = engines
    expr = "sum(rate(http_req[5m]) ^ 0)"
    mh, md, stats = _run_both(host, dev, expr)
    assert stats.get("device_fused") is True
    _assert_same_shape(mh, md, expr)
    np.testing.assert_allclose(
        np.nan_to_num(md.values), np.nan_to_num(mh.values),
        rtol=1e-12, atol=1e-12, err_msg=expr)


def test_fused_split_at_unsupported_node(engines):
    """Set ops have no fused form (label-data-dependent): the engine
    evaluates the `and` on the host and retries fusion on each side —
    which must still device-serve — and the final result is
    unchanged."""
    host, dev = engines
    ratio = ("sum by (job)(rate(http_req[5m]))"
             " / on(job) sum by (job)(rate(http_lim[5m]))")
    expr = "(%s) and on(job) (%s)" % (ratio, ratio)
    _, mh = host.query_range(expr, START, END, STEP)
    slowlog.log().clear()
    _, md = dev.query_range(expr, START, END, STEP)
    _assert_same_shape(mh, md, expr)
    np.testing.assert_array_equal(np.isnan(mh.values),
                                  np.isnan(md.values))
    np.testing.assert_allclose(  # rate family: ulp-reassociated
        np.nan_to_num(mh.values), np.nan_to_num(md.values),
        rtol=1e-12, atol=1e-12)
    # both side subtrees fused (device_tier recorded) while the set op
    # stayed host-side (host_nodes >= 1), and the split cause landed
    # in the per-query accounting
    rec = slowlog.log().records()[0]
    tier = rec.get("device_tier")
    assert tier is not None
    assert tier["device_nodes"] >= 3
    assert tier["host_nodes"] >= 1
    assert tier.get("host_splits", {}).get("set_op", 0) >= 1
    assert tier["compile_cache"] in ("hit", "miss")


def test_slowlog_device_tier_fields(engines):
    """Fused queries leave a device_tier cost phase in the slow-query
    ring: compile-cache disposition, compile seconds, node split, and
    the single device->host transfer size."""
    host, dev = engines
    slowlog.log().clear()
    _run_both(host, dev, RATE_EXPRS[0])
    rec = slowlog.log().records()[0]
    tier = rec.get("device_tier")
    assert tier is not None
    assert tier["compile_cache"] in ("hit", "miss")
    assert tier["compile_s"] >= 0.0
    # 2 selectors + 2 rate calls + 2 aggs + 1 binop = 7 AST nodes
    assert tier["device_nodes"] == 7
    assert tier["host_nodes"] == 0
    assert tier["transfer_bytes"] > 0
    assert rec["cache"].get("device_bridge_misses", 0) >= 1  # words path


def test_compile_cache_20_query_sweep(tmp_path):
    """The acceptance sweep: 20 grouped-rate-ratio queries at varied
    cardinality (different matchers select 2..6 of the series) whose
    shapes land in shared pow2 buckets must reuse ONE compiled
    program after the first query — compile-cache hit ratio >= 0.9,
    <= 4 distinct compiles."""
    from m3_tpu.ops import kernel_telemetry
    from m3_tpu.utils import instrument

    db = Database(DatabaseOptions(path=str(tmp_path), num_shards=4,
                                  commit_log_enabled=False))
    db.create_namespace(NamespaceOptions(
        name="default", retention=RetentionOptions(block_size=BLOCK)))
    # uniform spacing/length: per-stream dp counts and word widths are
    # near-identical, so every cardinality subset shares shape buckets
    rng = random.Random(11)
    for metric in ("http_req", "http_lim"):
        for job in JOBS:
            for dc in DCS:
                ts = list(range(T0 + 10 * SEC, T0 + 3600 * SEC,
                                10 * SEC))
                acc, vs = 0.0, []
                for _ in ts:
                    acc += rng.uniform(0, 5)
                    vs.append(round(acc, 2))
                sid = ("u|%s|%s|%s" % (metric, job, dc)).encode()
                tags = {b"__name__": metric.encode(),
                        b"job": job.encode(), b"dc": dc.encode()}
                db.write_batch("default", [sid] * len(ts),
                               [tags] * len(ts), ts, vs)
    db.tick(now_nanos=T0 + 2 * BLOCK)
    db.flush()
    host = Engine(db, "default", lookback_nanos=LOOKBACK,
                  device_serving=False)
    dev = Engine(db, "default", lookback_nanos=LOOKBACK,
                 device_serving=True)
    shape = ("sum by (dc)(rate(http_req%s[5m]))"
             " / sum by (dc)(rate(http_lim%s[5m]))")
    filters = ("", '{job="api"}', '{job="db"}', '{job="web"}',
               '{job!="api"}', '{job!="db"}', '{dc="east"}',
               '{dc="west"}', '{dc!="east"}', '{job!="web"}')
    sweep = [shape % (f, g) for f, g in
             zip(filters, tuple(filters[1:]) + (filters[0],))]
    sweep += [shape % (f, f) for f in filters]
    assert len(sweep) == 20
    ker_before = kernel_telemetry.kernels().get("device_expr_pipeline")
    compiles_before = (ker_before.stats()["compiles"]
                       if ker_before else 0)
    hits_before = instrument.counter(
        "m3_query_compile_cache_hits_total").value
    n_hit = 0
    for expr in sweep:
        mh, md, stats = _run_both(host, dev, expr)
        assert stats.get("device_fused") is True, expr
        n_hit += stats.get("compile_cache") == "hit"
        _assert_same_shape(mh, md, expr)
        np.testing.assert_allclose(  # rate family: ulp-reassociated
            np.nan_to_num(md.values), np.nan_to_num(mh.values),
            rtol=1e-12, atol=1e-12, err_msg=expr)
    ker = kernel_telemetry.kernels()["device_expr_pipeline"]
    assert ker.stats()["compiles"] - compiles_before <= 4
    assert n_hit >= 18, n_hit  # >= 0.9 hit ratio
    hits_after = instrument.counter(
        "m3_query_compile_cache_hits_total").value
    assert hits_after - hits_before >= n_hit
    db.close()


def test_pack_streams_memoized_per_query(engines, monkeypatch):
    """A tree that repeats a selector (x/x) must pack its streams
    ONCE: the pack memo rides the per-query gather memo."""
    import m3_tpu.ops.bitstream as bitstream

    host, dev = engines
    calls = []
    real = bitstream.pack_streams

    def counting(streams, **kwargs):
        calls.append(len(streams))
        return real(streams, **kwargs)

    monkeypatch.setattr(bitstream, "pack_streams", counting)
    expr = ("sum by (dc)(rate(http_req[5m]))"
            " / sum by (dc)(rate(http_req[5m]))")
    mh, md, stats = _run_both(host, dev, expr)
    assert stats.get("device_fused") is True
    assert np.array_equal(mh.values, md.values, equal_nan=True)
    # one pack for the device engine; the host engine never packs
    assert len(calls) == 1, calls


def test_pack_memo_is_keyed_by_the_bucket_too(engines):
    """The memoized words come padded to their jit buckets, and the
    per-node tier's linear bucket and the fused planner's power of two
    differ: one gather packed for both must give each its own shape,
    and each of them once."""
    from m3_tpu.ops.bitstream import pack_streams
    from m3_tpu.query.plan import _bucket_pow2

    _, dev = engines
    streams = [bytes([i + 1]) * (5 + i % 50) for i in range(130)]
    matchers, lo, hi = ("m",), 0, 10
    dev._qrange_local.gather_cache = {(matchers, lo, hi): {}}
    try:
        packs = {b: dev._pack_streams_cached(matchers, lo, hi, streams, b)
                 for b in (Engine._bucket, _bucket_pow2)}
        for b, (words, nbits) in packs.items():
            assert dev._pack_streams_cached(
                matchers, lo, hi, streams, b)[0] is words
            assert words.shape == (b(130, 64), 64)
            assert len(nbits) == b(130, 64)
            want, want_bits = pack_streams(streams)
            assert np.array_equal(words[:130, :want.shape[1]], want)
            assert np.array_equal(nbits[:130], want_bits)
            assert not words[130:].any() and not nbits[130:].any()
        assert [len(nbits) for _, nbits in packs.values()] == [192, 256]
    finally:
        dev._qrange_local.gather_cache = None


def test_warm_arrays_bridge_zero_decode(tmp_path):
    """DecodedBlockCache -> device bridge: a warm repeat feeds the
    fused pipeline decoded grids — zero M3TSZ decode calls — and a
    warm SINGLE-op query fuses too (arrays have no per-node device
    form), all bit-identical to the host tier."""
    rng = random.Random(7)
    db = Database(DatabaseOptions(
        path=str(tmp_path), num_shards=4, commit_log_enabled=False,
        cache=CacheOptions(decoded_policy="lru")))
    db.create_namespace(NamespaceOptions(
        name="default", retention=RetentionOptions(block_size=BLOCK)))
    for job in JOBS:
        for dc in DCS:
            _write_series(db, "http_req", job, dc, rng, counter=True)
    db.tick(now_nanos=T0 + 2 * BLOCK)
    db.flush()
    for shard in db._ns("default").shards.values():
        shard._sealed.clear()  # reads must hit the filesets
    host = Engine(db, "default", lookback_nanos=LOOKBACK,
                  device_serving=False)
    dev = Engine(db, "default", lookback_nanos=LOOKBACK,
                 device_serving=True)
    expr = ("sum by (dc)(rate(http_req[5m]))"
            " / sum by (dc)(rate(http_req[5m]))")
    _, mh = host.query_range(expr, START, END, STEP)  # warms the cache
    dev.last_fetch_stats = None
    _, md1 = dev.query_range(expr, START, END, STEP)
    assert (dev.last_fetch_stats or {}).get("device_fused") is True
    before = decode_counter.value()
    slowlog.log().clear()
    dev.last_fetch_stats = None
    _, md2 = dev.query_range(expr, START, END, STEP)
    stats = dev.last_fetch_stats or {}
    assert stats.get("device_fused") is True
    assert decode_counter.value() == before, \
        "warm fused query must perform ZERO M3TSZ decode calls"
    for md in (md1, md2):
        assert mh.labels == md.labels
        assert np.array_equal(mh.values, md.values, equal_nan=True)
    rec = slowlog.log().records()[0]
    assert rec["cache"].get("device_bridge_hits", 0) >= 1
    # single-op: no per-node device form for arrays, fusion takes it
    _, mh3 = host.query_range("rate(http_req[5m])", START, END, STEP)
    dev.last_fetch_stats = None
    _, md3 = dev.query_range("rate(http_req[5m])", START, END, STEP)
    assert (dev.last_fetch_stats or {}).get("device_fused") is True
    assert mh3.labels == md3.labels
    np.testing.assert_array_equal(np.isnan(mh3.values),
                                  np.isnan(md3.values))
    np.testing.assert_allclose(  # rate family: ulp-reassociated
        np.nan_to_num(md3.values), np.nan_to_num(mh3.values),
        rtol=1e-12, atol=1e-12)
    db.close()


def test_multi_tier_stitch_matches_host(tmp_path):
    """Raw + aggregated namespaces with overlapping retention: the
    fused pipeline's multi-tier leaf (per-slot tier cut on device)
    must agree with the host tier's stitched evaluation."""
    db = Database(DatabaseOptions(path=str(tmp_path), num_shards=2,
                                  commit_log_enabled=False))
    db.create_namespace(NamespaceOptions(
        name="default", retention=RetentionOptions(block_size=BLOCK)))
    db.create_namespace(NamespaceOptions(
        name="agg", aggregated=True, aggregation_resolution=60 * SEC,
        retention=RetentionOptions(block_size=BLOCK)))
    rng = np.random.default_rng(31)
    for i in range(12):
        sid = b"t|h%02d" % i
        tags = {b"__name__": b"t", b"host": b"h%02d" % i,
                b"dc": b"east" if i % 2 else b"west"}
        n_agg = int(rng.integers(5, 30))
        ts_a = [T0 + (k + 1) * 60 * SEC for k in range(n_agg)]
        db.write_batch("agg", [sid] * n_agg, [tags] * n_agg, ts_a,
                       (rng.random(n_agg) * 10).tolist())
        if i % 4:
            n_raw = int(rng.integers(5, 60))
            off = int(rng.integers(0, 40))
            ts_r = [T0 + (off + k + 1) * 10 * SEC for k in range(n_raw)]
            db.write_batch("default", [sid] * n_raw, [tags] * n_raw,
                           ts_r, (rng.random(n_raw) * 10).tolist())
    db.tick(now_nanos=T0 + 2 * BLOCK)
    db.flush()
    host = Engine(db, "default", lookback_nanos=LOOKBACK,
                  device_serving=False)
    dev = Engine(db, "default", lookback_nanos=LOOKBACK,
                 device_serving=True)
    expr = ("sum by (dc)(sum_over_time(t[10m]))"
            " - min by (dc)(min_over_time(t[10m]))")
    start, end = T0 + 10 * 60 * SEC, T0 + 80 * 60 * SEC
    _, mh = host.query_range(expr, start, end, STEP)
    dev.last_fetch_stats = None
    _, md = dev.query_range(expr, start, end, STEP)
    stats = dev.last_fetch_stats or {}
    assert stats.get("device_fused") is True, \
        dev._cost().fused_error
    assert mh.labels == md.labels
    np.testing.assert_array_equal(np.isnan(mh.values),
                                  np.isnan(md.values))
    # a window spanning the tier cut accumulates in a different order
    # on device than the host's stitched fragments: ulp-close, and the
    # stitch itself (which samples land where) must be exact — pinned
    # by the NaN-mask equality above plus the strict gate here
    np.testing.assert_allclose(
        np.nan_to_num(md.values), np.nan_to_num(mh.values),
        rtol=1e-12, atol=1e-12)
    db.close()


def test_fused_topk_record_carries_its_phases_and_shape(engines):
    """A top-k panel is one fused program: its record has the planning
    stamped apart from the call, the staging inside it (numpy leaves:
    h2d_s 0.0), and the shape the program ran at."""
    from m3_tpu.models.query_pipeline import band_width as qp_band_width

    host, dev = engines
    expr = "topk(2, sum by (job)(rate(http_req[5m])))"
    slowlog.log().clear()
    mh, md, stats = _run_both(host, dev, expr)
    assert stats["device_fused"] is True
    _assert_same_shape(mh, md, expr)
    np.testing.assert_allclose(np.nan_to_num(mh.values),
                               np.nan_to_num(md.values), rtol=1e-12)
    rec = next(r for r in slowlog.log().records()
               if r["device_serving"])
    assert rec["expr"] == expr
    ph = rec["phases"]
    assert ph["plan_s"] > 0.0 and ph["h2d_s"] == 0.0
    assert 0.0 < ph["d2h_s"] < ph["device_s"]
    tiling = ("parse_s", "plan_s", "fetch_s", "open_read_s", "pack_s",
              "decode_s", "merge_s", "device_s", "self_s")
    assert sum(ph[k] for k in tiling) == pytest.approx(ph["total_s"],
                                                       rel=1e-9)
    # 6 series of http_req in one 64-lane bucket, 3 jobs, k = 2; every
    # job is in the top two at some step of these 41
    assert (rec["lanes"], rec["lanes_pad"], rec["groups"],
            rec["topk_k"]) == (6, 64, 3, 2)
    assert rec["rows_out"] == rec["series"] == len(md.labels)
    assert 2 <= rec["rows_out"] <= 3
    assert rec["window_form"] == "select" and rec["rows"] >= 6
    assert rec["merge_form"] == "rotate"     # the one leaf's, as words
    # the leaf's windowed stage said how its one lane chunk was searched:
    # at 64 lanes x 256 samples, 41 steps, no band is taken
    assert qp_band_width(rec["n_cap"], rec["steps_pad"]) is None
    assert rec["band_served_pct"] == 0.0
    # the decode scan of the one leaf, from its buckets alone (the fused
    # planner's: 256 samples a row, 128 words): its window's refills
    from m3_tpu.models import query_pipeline as qp
    assert rec["decode_refills"] == qp.decode_refills(256, 128) > 0
    assert rec["device_tier"]["host_nodes"] == 0
    assert "device_declines" not in rec
    # a tree without a top-k or a rate: no k, no window form
    slowlog.log().clear()
    _run_both(host, dev, EXACT_EXPRS[3])
    rec = next(r for r in slowlog.log().records()
               if r["device_serving"])
    assert rec["topk_k"] == 0 and rec["window_form"] is None
    assert rec["merge_form"] == "rotate"
    assert rec["groups"] == 3 and rec["rows_out"] == 3
