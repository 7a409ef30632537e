"""Compile the served path's device programs for a DESCRIBED TPU v5e.

No chip is attached here: the TPU compiler that ships with jax lowers
each program for `v5e:2x2` device 0 from `jax.ShapeDtypeStruct`s at the
shapes `chip_smoke.py` dispatches (50,000 series, 10 s cadence, 2 h
blocks, 6 h span, 60 s steps), so anything the chip's compiler refuses
— a missing X64 rewrite, a program that does not fit 16 GB of HBM —
fails a CPU test run instead of a chip call.  Nothing executes; a pass
is not a chip run.

Rules this file keeps (a second process cannot load libtpu, and xdist
workers must all collect the same tests): the topology is described in
a module-scoped fixture, never at import / skipif / parametrize time;
no child process; every compile happens in the test's own process with
the persistent compilation cache off (a described-device executable
can be written to it but never read back).

The TPU compiler takes 1-4 minutes per query program whatever its size,
so the tier-1 run keeps the seal kernel and the rate pipeline (every
query program shares its decode + merge front half) and the rest are
`slow`: run them all before a chip call with
`pytest tests/test_tpu_aot_compile.py -m "slow or not slow"`.
"""

import os

import numpy as np
import pytest

import jax
from jax.sharding import SingleDeviceSharding

from m3_tpu.models import query_pipeline as qp
from m3_tpu.ops import m3tsz_encode
from m3_tpu.query import plan as qplan
from m3_tpu.query.engine import Engine
from m3_tpu.storage.database import Database, DatabaseOptions
from m3_tpu.storage.namespace import NamespaceOptions, RetentionOptions
from m3_tpu.storage.shard import _pow2_at_least
from m3_tpu.utils import xtime

SEC = xtime.SECOND
BLOCK = 2 * xtime.HOUR
T0 = (1_600_000_000 * SEC // BLOCK) * BLOCK

# chip_smoke.py's deployment (BASELINE.json config 4)
SERIES = 50_000
HOURS = 4
SHARDS = 64           # deploy/config/coordinator.yml num_shards
DP_PER_BLOCK = 720    # 2 h at 10 s
JOBS = 32
V5E_HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def build_tiny_db(path: str, hours: int = HOURS):
    """64 series x the smoke's real span: per-stream shapes (words per
    block, samples per block and lane, steps) come out at their real
    size and only the series-proportional dims need rescaling."""
    db = Database(DatabaseOptions(path=path, num_shards=4,
                                  commit_log_enabled=False))
    db.create_namespace(NamespaceOptions(
        name="default", retention=RetentionOptions(block_size=BLOCK)))
    rng = np.random.default_rng(0)
    n = hours * 360
    ts = (T0 + np.arange(n) * 10 * SEC).tolist()
    for i in range(64):
        tags = {b"__name__": b"http_requests_total",
                b"job": b"job-%02d" % (i % JOBS),
                b"host": b"host-%05d" % i}
        vs = np.cumsum(rng.integers(0, 100, n)).astype(np.float64)
        db.write_batch("default", [b"s%05d" % i] * n, [tags] * n, ts,
                       vs.tolist())
    db.tick(now_nanos=T0 + (hours // 2 + 2) * BLOCK)
    db.flush()
    return db


@pytest.fixture(scope="module")
def tiny_db(tmp_path_factory):
    db = build_tiny_db(str(tmp_path_factory.mktemp("aotdb")))
    yield db
    db.close()


def _capture(monkeypatch, tiny_db, kernel: str, expr: str,
             fused: bool = False):
    """Run `expr` through the engine's device tier on the CPU backend
    and return the (args, kwargs) it handed `kernel`."""
    seen = []
    orig = getattr(qp, kernel)

    def spy(*a, **k):
        seen.append((a, k))
        return orig(*a, **k)

    monkeypatch.setattr(qp, kernel, spy)
    eng = Engine(tiny_db, "default", lookback_nanos=300 * SEC,
                 device_serving=True)
    start, end = T0 + 600 * SEC, T0 + HOURS * 3600 * SEC - 60 * SEC
    steps = np.arange(start, end + 1, 60 * SEC, dtype=np.int64)
    if fused:
        # the plan compiler itself, past serve_fused's engagement gate
        # (which leaves a lone agg-over-rate to the per-node kernels)
        from m3_tpu.query import promql
        node = promql.parse(expr)
        counts = {"ops": 0, "fns": [], "aggs": [], "new": False}
        sym = qplan._extract(node, counts, root=True)
        assert qplan.run_sym(eng, sym, steps, counts, 3) is not None
    else:
        eng.query_range(expr, start, end, 60 * SEC)
    assert len(seen) == 1, f"{kernel} not dispatched once for {expr}"
    monkeypatch.undo()
    return seen[0]


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _per_node_args(args, kw, sharding):
    """Rescale a per-node pipeline call (words, nbits, slots, steps +
    statics) from 64 series to SERIES with the engine's own bucketing."""
    words, nbits, slots, steps = args
    blocks = words.shape[0] // 64            # streams per series
    m_pad = Engine._bucket(SERIES * blocks, 64)
    lanes_pad = Engine._bucket(SERIES, 64)
    if m_pad > SERIES * blocks and lanes_pad == SERIES:
        lanes_pad = Engine._bucket(SERIES + 1, 64)
    w_pad = words.shape[1]
    new_args = (_sds((m_pad, w_pad), words.dtype, sharding),
                _sds((m_pad,), nbits.dtype, sharding),
                _sds((m_pad,), slots.dtype, sharding),
                _sds(steps.shape, steps.dtype, sharding))
    new_kw = dict(kw)
    new_kw["n_lanes"] = lanes_pad
    return new_args, new_kw


def _rescale_plan(plan, leaves, params, sharding):
    """Rewrite a fused plan captured at 64 series to SERIES: every
    series-proportional static (lanes, stream rows, per-host groups)
    is re-bucketed with the plan compiler's own pow2 quantizer, and
    leaves/params become ShapeDtypeStructs of the matching shapes."""
    new_leaves = [None] * len(leaves)
    new_params = [None] * len(params)

    def scalars(p):
        return tuple(_sds((), np.asarray(x).dtype, sharding) for x in p)

    def walk(node):
        """-> (new node, padded row count of its output)."""
        tag = node[0]
        if tag == "leaf":
            (_, idx, pidx, kind, fn, _lanes, n_cap, n_dp, n_tiers,
             m_tiny, w_pad, s_pad, sf, tf) = node
            assert kind == "words"
            blocks = -(-m_tiny // 64)
            lanes_pad = qplan._bucket_pow2(SERIES, 64)
            m_pad = qplan._bucket_pow2(SERIES * HOURS // 2, 64)
            assert blocks >= HOURS // 2
            lf = leaves[idx]
            new_leaves[idx] = {
                "words": _sds((m_pad, w_pad), lf["words"].dtype, sharding),
                "nbits": _sds((m_pad,), lf["nbits"].dtype, sharding),
                "slots": _sds((m_pad,), lf["slots"].dtype, sharding),
                "tiers": _sds((m_pad,), lf["tiers"].dtype, sharding),
                "steps": _sds((s_pad,), lf["steps"].dtype, sharding),
                "rng": _sds((), np.int64, sharding),
                "valid": _sds((lanes_pad,), np.bool_, sharding),
            }
            new_params[pidx] = scalars(params[pidx])
            return (("leaf", idx, pidx, kind, fn, lanes_pad, n_cap, n_dp,
                     n_tiers, m_pad, w_pad, s_pad, sf, tf), lanes_pad)
        if tag == "agg":
            _, op, g_tiny, pidx, child = node
            new_child, rows = walk(child)
            # by (job) keeps its 32 groups; by (host) grows with SERIES
            g_pad = (g_tiny if g_tiny <= JOBS
                     else qplan._bucket_pow2(SERIES, 8))
            groups, gvalid, tval = params[pidx]
            new_params[pidx] = (
                _sds((rows,), groups.dtype, sharding),
                _sds((g_pad,), gvalid.dtype, sharding),
                _sds((), np.asarray(tval).dtype, sharding))
            return ("agg", op, g_pad, pidx, new_child), g_pad
        if tag == "topk":
            _, op, k, g_pad, pidx, child = node
            new_child, rows = walk(child)
            (groups,) = params[pidx]
            new_params[pidx] = (_sds((rows,), groups.dtype, sharding),)
            return ("topk", op, k, g_pad, pidx, new_child), rows
        raise AssertionError(f"unexpected plan node {tag!r}")

    new_plan, _ = walk(plan)
    return new_plan, tuple(new_leaves), tuple(new_params)


def _compile(jitted, *args, **kw):
    compiled = jitted.lower(*args, **kw).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    print(f"\n{getattr(jitted, '__name__', jitted)}: args "
          f"{mem.argument_size_in_bytes} out {mem.output_size_in_bytes} "
          f"temp {mem.temp_size_in_bytes} total {total}")
    assert total < V5E_HBM_BYTES
    return compiled


def test_seal_pack_encode_compiles(one_chip):
    """The seal path's device half at the bucket storage/shard.py picks
    for one of 64 shards' 2 h block: ~781 lanes -> 1024, 720 -> 1024."""
    L = _pow2_at_least(-(-SERIES // SHARDS), 8)
    T = 1 << (DP_PER_BLOCK - 1).bit_length()
    assert (L, T) == (1024, 1024)
    i64 = lambda *s: _sds(s, np.int64, one_chip)      # noqa: E731
    i32 = lambda *s: _sds(s, np.int32, one_chip)      # noqa: E731
    u64 = lambda *s: _sds(s, np.uint64, one_chip)     # noqa: E731
    _compile(m3tsz_encode._pack_encode_jit, i64(L, T), i64(L), i32(L),
             u64(L, T), i32(L, T), u64(L, T), i32(L, T))


def test_headline_decode_downsample_compiles(one_chip):
    """README's headline shape: 1,000,000 series x 360 dp (1 h at 10 s)
    -> 1 m means; 57 words is what such integer gauges pack to."""
    from m3_tpu.models.read_pipeline import decode_downsample
    _compile(decode_downsample,
             _sds((1_000_000, 57), np.uint32, one_chip),
             _sds((1_000_000,), np.int32, one_chip), 360, 6)


@pytest.mark.parametrize("kernel,expr", [
    ("device_temporal_pipeline", "rate(http_requests_total[5m])"),
    pytest.param("device_temporal_pipeline",
                 "count_over_time(http_requests_total[5m])",
                 marks=pytest.mark.slow),
])
def test_per_node_pipeline_compiles(one_chip, tiny_db, monkeypatch,
                                    kernel, expr):
    args, kw = _capture(monkeypatch, tiny_db, kernel, expr)
    new_args, new_kw = _per_node_args(args, kw, one_chip)
    assert new_kw["n_lanes"] >= SERIES
    _compile(getattr(qp, kernel), *new_args, **new_kw)


def test_grouped_pipeline_compiles_at_the_fleet_cells_shape(one_chip):
    """The benchmark cell `fanout-fleet`: 25,000 sealed streams of every
    series a node holds -> 12,544 lanes x 1,536 in 25 chunks, 256
    steps, 25 jobs.  At this shape the chip's compiler takes seconds
    (5.4 s here, PR 34), so it is tier-1; the chunk loops keep the
    program's temporaries at a chunk's size, 0.57 GB of the 16 (at
    [lanes, n_cap, steps] the selection alone would be 39 GB)."""
    M, W, L, S = 25_024, 256, 12_544, 256
    assert qp.lane_chunks(L) == 25
    sds = lambda shape, dt: _sds(shape, dt, one_chip)   # noqa: E731
    compiled = _compile(
        qp.device_grouped_pipeline, sds((M, W), np.uint32),
        sds((M,), np.int32), sds((M,), np.int64), sds((S,), np.int64),
        sds((L,), np.int64), n_lanes=L, n_groups=32, n_cap=1536, n_dp=768,
        range_nanos=sds((), np.int64))
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 1 << 30
    # a chunk's windows are searched in a band of the lane (PR 48: a
    # span of 640 of its 1,536 samples a block of 64 steps), the
    # full-width body under the band's conditional inside the chunk
    # loop; the spans are a chunk's too, and the program's peak stays
    # within 5% of the 642.5 MB it had without them
    assert qp.band_width(1536, S) == 640
    assert "conditional(" in compiled.as_text()
    assert getattr(memory, "peak_memory_in_bytes", 0) < 1.05 * 642.5e6


def test_grouped_pipeline_compiles_at_the_two_day_cells_shape(one_chip):
    """The benchmark cell `dash-2d`: 11,000 sealed streams of one job's
    500 series over the 22 blocks a 48 h retention holds -> 512 lanes x
    15,872 samples, 1,344 steps: the windowed stage's gather form.  Its
    first request has 60 s (the mix's `request_timeout_s`), and the
    rate family's reset prefix sum as ONE reduce_window a lane wide took
    the compiler 168.5 s of the program's 149 at this shape (PR 45: the
    first panel gave up on the chip); as a scan past _PREFIX_MAX_N the
    whole program takes 6-9 s here.  The two bounds fuse: at
    [lanes, n_cap, steps] one of them would be 10.9 GB."""
    import time

    M, W, L, S, n_cap = 11_008, 256, 512, 1_344, 15_872
    assert qp.window_form(n_cap) == "gather" and n_cap - 1 > qp._PREFIX_MAX_N
    assert qp.merge_form(n_cap, 768) == "window"
    sds = lambda shape, dt: _sds(shape, dt, one_chip)   # noqa: E731
    t0 = time.perf_counter()
    compiled = _compile(
        qp.device_grouped_pipeline, sds((M, W), np.uint32),
        sds((M,), np.int32), sds((M,), np.int64), sds((S,), np.int64),
        sds((L,), np.int64), n_lanes=L, n_groups=16, n_cap=n_cap, n_dp=768,
        range_nanos=sds((), np.int64))
    assert time.perf_counter() - t0 < 60.0
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
    # the long lane's bounds go through the band (PR 48: a span of 1,024
    # of 15,872 samples), the parent's two full-width counts under its
    # conditional; the reads of the windows' ends are still the gathers
    # the cell's check holds its records to
    assert qp.band_width(n_cap, S) == 1024
    text = compiled.as_text()
    assert "conditional(" in text and "gather(" in text


def test_fused_hq_pipeline_compiles_at_the_latency_cells_shape(one_chip):
    """The benchmark cell `dash-p99`: `histogram_quantile(0.99,
    rate(.._bucket{job=J}[5m]))` of one job's 100 instances x 12 `le`,
    2,400 sealed streams at the fused planner's pow2 buckets -> 4,096
    rows x 256 words, 2,048 lanes x 2,048 samples in four chunks of
    `_MERGE_LANES`, 256 steps, then the `hq` node over 100 groups x 12
    buckets in [128, 16, 256].  Its first request has 60 s (the mix's
    `request_timeout_s`); the chunk loops keep the temporaries at a
    chunk's size."""
    import time

    M, W, L, S, n_cap, n_dp, g_pad, b_pad = (4096, 256, 2048, 256, 2048,
                                             1024, 128, 16)
    assert qp.lane_chunks(L) == 4 and qp.window_form(n_cap) == "select"
    sds = lambda shape, dt: _sds(shape, dt, one_chip)   # noqa: E731
    leaf = {"words": sds((M, W), np.uint32), "nbits": sds((M,), np.int32),
            "slots": sds((M,), np.int64), "tiers": sds((M,), np.int64),
            "steps": sds((S,), np.int64), "rng": sds((), np.int64),
            "valid": sds((L,), np.bool_)}
    plan = ("hq", g_pad, b_pad, 1,
            ("leaf", 0, 0, "words", "rate", L, n_cap, n_dp, 1, M, W, S,
             0.5, 0.5))
    params = ((sds((), np.float64), sds((), np.float64)),
              (sds((g_pad, b_pad), np.int64), sds((g_pad, b_pad), np.float64),
               sds((g_pad,), np.float64), sds((g_pad,), np.bool_),
               sds((), np.float64)))
    t0 = time.perf_counter()
    compiled = _compile(qp.device_expr_pipeline, plan, (leaf,), params,
                        sds((S,), np.int64))
    assert time.perf_counter() - t0 < 60.0
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.slow
def test_grouped_pipeline_compiles(one_chip, tiny_db, monkeypatch):
    """sum by (job)(rate(...)) as the engine dispatches it: the
    per-node grouped kernel (the fusion gate leaves a lone
    agg-over-rate to it)."""
    args, kw = _capture(monkeypatch, tiny_db, "device_grouped_pipeline",
                        "sum by (job)(rate(http_requests_total[5m]))")
    *head, groups = args
    new_head, new_kw = _per_node_args(tuple(head), kw, one_chip)
    new_groups = _sds((new_kw["n_lanes"],), groups.dtype, one_chip)
    _compile(qp.device_grouped_pipeline, *new_head, new_groups, **new_kw)


@pytest.mark.slow
@pytest.mark.parametrize("expr,fused", [
    ("sum by (job)(rate(http_requests_total[5m]))", True),
    ("topk(5, sum by (host)(rate(http_requests_total[5m])))", False),
])
def test_fused_expr_pipeline_compiles(one_chip, tiny_db, monkeypatch,
                                      expr, fused):
    (plan, leaves, params, steps), _ = _capture(
        monkeypatch, tiny_db, "device_expr_pipeline", expr, fused=fused)
    new_plan, new_leaves, new_params = _rescale_plan(
        plan, leaves, params, one_chip)
    _compile(qp.device_expr_pipeline, new_plan, new_leaves, new_params,
             _sds(steps.shape, steps.dtype, one_chip))
