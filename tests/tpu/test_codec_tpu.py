"""Real-accelerator lane: jit-compile + run the codec hot paths on
``jax.devices()[0]`` with the platform left alone (no CPU override).

Guards TPU-only lowering failures (e.g. the f64->u64 bitcast-convert
has no X64 rewrite on TPU), which are invisible to the CPU-backend
suite.  Run it on the chip: `M3_TPU_LANE=1 pytest tests/tpu -q`.

Precision contract (documented drift bounds): 64-bit integer/bit-domain
work is emulated with u32 pairs and must be EXACT — timestamps,
int-optimized values, and the encoded stream bytes of integer-valued
series.  float64 *values* may be emulated at reduced precision
(f32-pair, ~49 mantissa bits) on accelerator backends, so decoded
general floats are asserted within relative 2**-44 of the true f64.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import m3_tpu  # noqa: F401 - enables x64 before any kernel builds
from m3_tpu.models import decode_downsample
from m3_tpu.ops import m3tsz_scalar as tsz
from m3_tpu.ops.bitstream import pack_streams, unpack_stream
from m3_tpu.ops.m3tsz_decode import decode_batched
from m3_tpu.ops.m3tsz_encode import encode_batched
from m3_tpu.utils import xtime

pytestmark = pytest.mark.tpu

SEC = xtime.SECOND
START = 1_600_000_000 * SEC


def _dev():
    """The accelerator device.  In this lane a missing chip is a
    failure, not a skip: the lane exists to run on the TPU."""
    dev = jax.devices()[0]
    assert dev.platform == "tpu", f"TPU lane on {dev.platform!r}"
    return dev


def _int_gauge_grids(n_lanes: int, n_dp: int):
    rng = np.random.default_rng(7)
    ts = np.zeros((n_lanes, n_dp), dtype=np.int64)
    vs = np.zeros((n_lanes, n_dp), dtype=np.float64)
    for u in range(n_lanes):
        t, v = START, float(rng.integers(0, 1000))
        for i in range(n_dp):
            t += 10 * SEC
            v = max(0.0, v + float(rng.integers(-2, 3)))
            ts[u, i] = t
            vs[u, i] = v
    return ts, vs


def _oracle_streams(ts, vs, int_optimized=True):
    out = []
    for lane_t, lane_v in zip(ts, vs):
        enc = tsz.Encoder(START, int_optimized=int_optimized)
        for t, v in zip(lane_t, lane_v):
            enc.encode(int(t), float(v))
        out.append(enc.finalize())
    return out


def test_encode_batched_device_byte_exact_int_gauges():
    """The seal hot loop's device half (time fields + bit pack) compiles
    and the hybrid encode is byte-exact for integer-valued series (the
    BASELINE config-1 shape).  Values are prepared host-side — lossy
    f64 transfer makes device-resident values unusable — so the device
    program is pure integer ops and must be EXACT."""
    _dev()  # skip when the backend is unavailable
    ts, vs = _int_gauge_grids(8, 24)
    want = _oracle_streams(ts, vs)
    starts = np.full(len(ts), START, dtype=np.int64)
    nv = np.full(len(ts), ts.shape[1], dtype=np.int32)
    words, nbits = encode_batched(ts, vs, starts, nv)
    words = np.asarray(words)
    nbits = np.asarray(nbits)
    got = [
        unpack_stream(words[i], ((int(nbits[i]) + 7) // 8) * 8)
        for i in range(len(ts))
    ]
    assert got == want


def test_encode_batched_device_byte_exact_floats():
    """Hybrid encode is byte-exact on the accelerator even for general
    float values: the XOR grammar runs on host bit patterns; nothing
    float-typed ever crosses the transfer boundary."""
    _dev()
    rng = np.random.default_rng(3)
    n_lanes, n_dp = 4, 16
    ts = START + (np.arange(n_dp, dtype=np.int64) + 1)[None, :] * 10 * SEC
    ts = np.repeat(ts, n_lanes, axis=0)
    vs = rng.normal(100.0, 10.0, size=(n_lanes, n_dp))
    want = _oracle_streams(ts, vs)
    starts = np.full(n_lanes, START, dtype=np.int64)
    nv = np.full(n_lanes, n_dp, dtype=np.int32)
    words, nbits = encode_batched(ts, vs, starts, nv)
    words = np.asarray(words)
    nbits = np.asarray(nbits)
    got = [
        unpack_stream(words[i], ((int(nbits[i]) + 7) // 8) * 8)
        for i in range(n_lanes)
    ]
    assert got == want


def test_decode_batched_device_exact_int_gauges():
    dev = _dev()  # FIRST: jnp.asarray would init the (possibly wedged)
    # default backend before the bounded probe ever ran
    ts, vs = _int_gauge_grids(8, 24)
    words_np, nbits_np = pack_streams(_oracle_streams(ts, vs))
    words = jax.device_put(jnp.asarray(words_np), dev)
    nbits = jax.device_put(jnp.asarray(nbits_np), dev)
    dts, dvs, valid, count, error = decode_batched(words, nbits, ts.shape[1])
    assert not np.asarray(error).any()
    assert (np.asarray(count) == ts.shape[1]).all()
    assert (np.asarray(dts) == ts).all()
    assert (np.asarray(dvs) == vs).all()  # integers: exact under emulation


def test_decode_downsample_device_golden():
    dev = _dev()
    n_dp, window = 24, 6
    ts, vs = _int_gauge_grids(8, n_dp)
    words_np, nbits_np = pack_streams(_oracle_streams(ts, vs))
    words = jax.device_put(jnp.asarray(words_np), dev)
    nbits = jax.device_put(jnp.asarray(nbits_np), dev)
    out, count, error = decode_downsample(words, nbits, n_dp, window)
    assert not np.asarray(error).any()
    assert (np.asarray(count) == n_dp).all()
    want = vs.reshape(len(vs), n_dp // window, window).mean(axis=2)
    np.testing.assert_allclose(np.asarray(out), want, rtol=2**-40, atol=0)


def test_decode_float_mode_drift_bound():
    """General float values: bit-domain decode is exact; only the final
    u64->f64 rebind may round to the emulated representation."""
    dev = _dev()
    rng = np.random.default_rng(11)
    n_lanes, n_dp = 4, 16
    ts = START + (np.arange(n_dp, dtype=np.int64) + 1)[None, :] * 10 * SEC
    ts = np.repeat(ts, n_lanes, axis=0)
    vs = rng.normal(100.0, 10.0, size=(n_lanes, n_dp))
    words_np, nbits_np = pack_streams(_oracle_streams(ts, vs, int_optimized=False))
    words = jax.device_put(jnp.asarray(words_np), dev)
    nbits = jax.device_put(jnp.asarray(nbits_np), dev)
    dts, dvs, valid, count, error = decode_batched(
        words, nbits, n_dp, int_optimized=False
    )
    assert not np.asarray(error).any()
    assert (np.asarray(dts) == ts).all()
    err = np.abs(np.asarray(dvs) - vs) / np.abs(vs)
    assert err.max() <= 2**-44, err.max()


def test_ingest_pipeline_device_half_exact():
    """Round-4 path: the FULL sharded ingest step
    (models/ingest_pipeline.encode_rollup_sharded — shard_map wrapper,
    pack_encode body, psum/psum_scatter/all_gather rollup, accounting)
    must lower and run on the REAL accelerator (1x1 mesh of the probed
    device), with byte-exact encode output — integer-domain, so
    u32-pair emulation must be exact like the encode lane above."""
    dev = _dev()
    from m3_tpu.models.ingest_pipeline import (encode_rollup_sharded,
                                               shard_ingest_inputs)
    from m3_tpu.ops.m3tsz_encode import _prepare
    from m3_tpu.parallel import make_mesh

    n_lanes, n_dp, window = 32, 60, 6
    ts, vs = _int_gauge_grids(n_lanes, n_dp)
    starts = np.full(n_lanes, START, dtype=np.int64)
    nv = np.full(n_lanes, n_dp, dtype=np.int32)
    cb, cn, pb, pn = _prepare(vs, nv)
    mesh = make_mesh(n_series_shards=1, n_window_shards=1, devices=[dev])
    step = encode_rollup_sharded(mesh, n_dp, window)
    args = shard_ingest_inputs(mesh, ts, starts, nv, cb, cn, pb, pn, vs)
    words, nbits, rolled, fleet, total_bytes = step(*args)
    words, nbits = np.asarray(words), np.asarray(nbits)
    want = _oracle_streams(ts, vs)
    for i in range(n_lanes):
        got = unpack_stream(words[i], int(nbits[i]))
        assert got == want[i], f"lane {i} bytes diverge on device"
    ref_rolled = vs.reshape(n_lanes, n_dp // window, window).mean(axis=2)
    np.testing.assert_allclose(np.asarray(rolled), ref_rolled,
                               rtol=2**-44)
    np.testing.assert_allclose(np.asarray(fleet), ref_rolled.sum(axis=0),
                               rtol=2**-40)
    assert int(total_bytes) == sum(len(b) for b in want)


def test_quantile_downsample_device():
    """Round-4 aggregation surface on device: quantile-typed
    decode+downsample (the padded-sort path) lowers and matches the
    host computation within the documented f64-emulation drift."""
    _dev()
    from m3_tpu.ops import downsample as ds

    n_lanes, n_dp, window = 16, 36, 6
    ts, vs = _int_gauge_grids(n_lanes, n_dp)
    streams = _oracle_streams(ts, vs)
    words, nbits = pack_streams(streams)
    out, count, err = decode_downsample(
        jnp.asarray(words), jnp.asarray(nbits), n_dp, window,
        agg_type=ds.AggregationType.P50)
    out = np.asarray(out)
    assert not np.asarray(err).any()
    # nearest-rank-below quantiles (the implementation's and the
    # reference CM stream's definition — no linear interpolation)
    want = np.quantile(
        vs.reshape(n_lanes, n_dp // window, window), 0.5, axis=2,
        method="lower")
    np.testing.assert_allclose(out, want, rtol=2**-40)


def test_adaptive_decode_full_width_on_device():
    """Round-5 regression surface, on device: the read path sizes the
    decode grid from a native COUNT pass (a stream's dp count is not
    derivable from its byte length — dense int gauges run ~4.5 bits/dp
    and the old 12 bits/dp estimate silently truncated 60% of their
    samples).  The XLA decode at the exact width must return EVERY
    datapoint bit-exactly for dense 720-dp blocks."""
    _dev()
    from m3_tpu.ops.m3tsz_decode import decode_streams_adaptive

    n_lanes, n_dp = 32, 720  # a full 2h block at 10s cadence
    ts, vs = _int_gauge_grids(n_lanes, n_dp)
    streams = _oracle_streams(ts, vs)
    # the truncation regression shape: tight streams, well under
    # 12 bits/dp
    assert max(len(s) for s in streams) * 8 // n_dp < 8
    got_ts, got_vs, valid = decode_streams_adaptive(streams)
    assert valid.shape[1] >= n_dp
    counts = valid.sum(axis=1)
    np.testing.assert_array_equal(counts, np.full(n_lanes, n_dp))
    np.testing.assert_array_equal(got_ts[:, :n_dp], ts)
    np.testing.assert_array_equal(got_vs[:, :n_dp], vs)  # int-exact


def test_merged_read_batch_on_device_backend():
    """Round-5 read path under the accelerator backend: the fused
    CPU-native merge is gated OFF on non-CPU backends, so the engine's
    fallback (XLA decode at counted width + merge_grids) must serve a
    multi-block fan-out correctly with the device doing the decode."""
    _dev()
    from m3_tpu.ops import consolidate as cons
    from m3_tpu.ops.m3tsz_decode import decode_streams_adaptive

    n_series, blocks = 12, 3
    ts, vs = _int_gauge_grids(n_series * blocks, 120)
    streams = _oracle_streams(ts, vs)
    slots = np.repeat(np.arange(n_series), blocks).astype(np.int64)
    dts, dvs, valid = decode_streams_adaptive(streams)
    times2, values2, counts = cons.merge_grids(
        slots, dts, dvs, valid, n_series, use_native=False)
    assert counts.sum() == n_series * blocks * 120
    # every lane's merged samples are time-sorted and value-exact
    for lane in range(n_series):
        n = int(counts[lane])
        t_lane = times2[lane, :n]
        assert (np.diff(t_lane) >= 0).all()


def test_device_temporal_pipeline_rate_on_device():
    """Round-5 frontier on hardware: the fused decode->merge->rate
    pipeline (models/query_pipeline.py) — one jit, the
    [streams, samples] intermediate resident in HBM — must lower, run,
    and match the host serving tier.  Counter rates divide f64 deltas,
    so the documented emulation drift applies (int-exact decode state,
    ~2**-44-relative f64 arithmetic); timestamps and NaN masks are
    exact."""
    dev = _dev()
    from m3_tpu.models.query_pipeline import device_temporal_pipeline
    from m3_tpu.ops import consolidate as cons

    n_lanes, blocks_per, dp = 8, 3, 60
    ts, vs = _int_gauge_grids(n_lanes * blocks_per, dp)
    # re-base each lane's blocks to be consecutive in time
    frags, streams, slots = [], [], []
    for lane in range(n_lanes):
        for b in range(blocks_per):
            row = lane * blocks_per + b
            base = START + b * dp * 10 * SEC
            t = base + (np.arange(dp, dtype=np.int64) + 1) * 10 * SEC
            v = vs[row]
            enc = tsz.Encoder(base)
            for ti, vi in zip(t, v):
                enc.encode(int(ti), float(vi))
            streams.append(enc.finalize())
            slots.append(lane)
            frags.append((lane, t, v))
    words_np, nbits_np = pack_streams(streams)
    steps = START + 600 * SEC + np.arange(12, dtype=np.int64) * 120 * SEC
    range_nanos = 10 * 60 * SEC
    rate, err = device_temporal_pipeline(
        jax.device_put(jnp.asarray(words_np), dev),
        jax.device_put(jnp.asarray(nbits_np), dev),
        jax.device_put(jnp.asarray(np.asarray(slots, dtype=np.int64)), dev),
        jax.device_put(jnp.asarray(steps), dev),
        n_lanes=n_lanes, n_cap=blocks_per * dp,
        fn="rate", range_nanos=range_nanos, n_dp=dp)
    assert not np.asarray(err).any()
    t_ref, v_ref, _ = cons.merge_packed(frags, n_lanes)
    want = cons.extrapolated_rate(t_ref, v_ref, steps, range_nanos,
                                  True, True)
    got = np.asarray(rate)
    np.testing.assert_array_equal(np.isnan(want), np.isnan(got))
    np.testing.assert_allclose(np.nan_to_num(got), np.nan_to_num(want),
                               rtol=1e-9, atol=1e-12)


def test_device_temporal_pipeline_reducers_on_device():
    """The *_over_time device pipeline (NaN-masked prefix sums over the
    merged batch) must lower and match the host window_reduce on
    hardware within the documented f64-emulation drift; count/present
    are integer-exact."""
    dev = _dev()
    from m3_tpu.models.query_pipeline import (DEVICE_REDUCERS,
                                              device_temporal_pipeline)
    from m3_tpu.ops import consolidate as cons

    n_lanes, blocks_per, dp = 6, 2, 48
    frags, streams, slots = [], [], []
    ts, vs = _int_gauge_grids(n_lanes * blocks_per, dp)
    for lane in range(n_lanes):
        for b in range(blocks_per):
            row = lane * blocks_per + b
            base = START + b * dp * 10 * SEC
            t = base + (np.arange(dp, dtype=np.int64) + 1) * 10 * SEC
            v = vs[row]
            enc = tsz.Encoder(base)
            for ti, vi in zip(t, v):
                enc.encode(int(ti), float(vi))
            streams.append(enc.finalize())
            slots.append(lane)
            frags.append((lane, t, v))
    words_np, nbits_np = pack_streams(streams)
    steps = START + 600 * SEC + np.arange(10, dtype=np.int64) * 120 * SEC
    range_nanos = 10 * 60 * SEC
    from m3_tpu.ops.consolidate import merge_packed
    t_ref, v_ref, _ = merge_packed(frags, n_lanes)
    for reducer in DEVICE_REDUCERS:
        out, err = device_temporal_pipeline(
            jax.device_put(jnp.asarray(words_np), dev),
            jax.device_put(jnp.asarray(nbits_np), dev),
            jax.device_put(jnp.asarray(np.asarray(slots, np.int64)), dev),
            jax.device_put(jnp.asarray(steps), dev),
            n_lanes=n_lanes, n_cap=blocks_per * dp,
            range_nanos=range_nanos, fn=reducer, n_dp=dp)
        assert not np.asarray(err).any(), reducer
        if reducer == "last_over_time":
            want = cons.step_consolidate(t_ref, v_ref, steps, range_nanos)
        elif reducer in ("irate", "idelta"):
            from m3_tpu.query.engine import Engine
            want = Engine._instant_delta(t_ref, v_ref, steps, range_nanos,
                                         is_rate=reducer == "irate")
        elif reducer in ("changes", "resets"):
            want = cons.window_changes(t_ref, v_ref, steps, range_nanos,
                                       resets_only=reducer == "resets")
        elif reducer == "deriv":
            want, _, _ = cons.window_linreg(t_ref, v_ref, steps,
                                            range_nanos)
        else:
            want = cons.window_reduce(t_ref, v_ref, steps, range_nanos,
                                      reducer)
        got = np.asarray(out)
        np.testing.assert_array_equal(np.isnan(want), np.isnan(got),
                                      err_msg=reducer)
        np.testing.assert_allclose(np.nan_to_num(got),
                                   np.nan_to_num(want), rtol=1e-9,
                                   atol=1e-12, err_msg=reducer)


def test_device_grouped_pipeline_on_device():
    """Grouped serving on hardware: `agg by (...) (rate(x[r]))` fused
    into one jit — decode, merge, windowed rate, and the segment
    reduction over lanes all in HBM, only the [groups, steps] result
    transferred back.  Segment sum/min/max must match the host two-
    stage reference within the f64-emulation drift; count is
    integer-exact."""
    dev = _dev()
    from m3_tpu.models.query_pipeline import (DEVICE_GROUP_AGGS,
                                              device_grouped_pipeline)
    from m3_tpu.ops import consolidate as cons

    n_lanes, blocks_per, dp = 8, 2, 48
    frags, streams, slots = [], [], []
    ts, vs = _int_gauge_grids(n_lanes * blocks_per, dp)
    for lane in range(n_lanes):
        for b in range(blocks_per):
            row = lane * blocks_per + b
            base = START + b * dp * 10 * SEC
            t = base + (np.arange(dp, dtype=np.int64) + 1) * 10 * SEC
            v = vs[row]
            enc = tsz.Encoder(base)
            for ti, vi in zip(t, v):
                enc.encode(int(ti), float(vi))
            streams.append(enc.finalize())
            slots.append(lane)
            frags.append((lane, t, v))
    words_np, nbits_np = pack_streams(streams)
    steps = START + 600 * SEC + np.arange(10, dtype=np.int64) * 120 * SEC
    range_nanos = 10 * 60 * SEC
    groups = np.arange(n_lanes, dtype=np.int64) % 3
    t_ref, v_ref, _ = cons.merge_packed(frags, n_lanes)
    want_rate = cons.extrapolated_rate(t_ref, v_ref, steps, range_nanos,
                                       True, True)
    from tests.test_query_pipeline_device import _host_grouped
    for agg in DEVICE_GROUP_AGGS:
        out, err = device_grouped_pipeline(
            jax.device_put(jnp.asarray(words_np), dev),
            jax.device_put(jnp.asarray(nbits_np), dev),
            jax.device_put(jnp.asarray(np.asarray(slots, np.int64)), dev),
            jax.device_put(jnp.asarray(steps), dev),
            jax.device_put(jnp.asarray(groups), dev),
            n_lanes=n_lanes, n_groups=3, n_cap=blocks_per * dp,
            range_nanos=range_nanos, fn="rate", agg=agg, n_dp=dp)
        assert not np.asarray(err).any(), agg
        want = _host_grouped(want_rate, groups, 3, agg)
        got = np.asarray(out)
        np.testing.assert_array_equal(np.isnan(want), np.isnan(got),
                                      err_msg=agg)
        np.testing.assert_allclose(np.nan_to_num(got),
                                   np.nan_to_num(want), rtol=1e-9,
                                   atol=1e-10, err_msg=agg)


def test_device_multitier_pipeline_on_device():
    """Multi-tier serving on hardware: the stitch cut (_tier_cut's
    int64 segment_min cascade + comparison masking) must lower through
    the TPU X64 emulation and reproduce the host stitch — the same
    risk class as the f64 psum_scatter rewrite gap the lane caught in
    round 5 session 2."""
    dev = _dev()
    from m3_tpu.models.query_pipeline import device_temporal_pipeline
    from m3_tpu.ops import consolidate as cons

    n_lanes, dp_fine, dp_coarse = 6, 40, 20
    streams, slots, tiers, frags = [], [], [], []
    rng = np.random.default_rng(13)
    for lane in range(n_lanes):
        # coarse tier (rank 1): older 60s-resolution data from T0
        t_c = START + (np.arange(dp_coarse, dtype=np.int64) + 1) * 60 * SEC
        v_c = np.cumsum(rng.integers(0, 4, dp_coarse)).astype(np.float64)
        # fine tier (rank 0): 10s data overlapping the coarse tail
        off = int(rng.integers(0, 60))
        t_f = (START + (off + 10) * 60 * SEC
               + (np.arange(dp_fine, dtype=np.int64) + 1) * 10 * SEC)
        v_f = np.cumsum(rng.integers(0, 4, dp_fine)).astype(np.float64)
        # merge contract: coarsest tier first within a slot
        for t, v, rank in ((t_c, v_c, 1), (t_f, v_f, 0)):
            enc = tsz.Encoder(int(t[0] - 10 * SEC))
            for ti, vi in zip(t, v):
                enc.encode(int(ti), float(vi))
            streams.append(enc.finalize())
            slots.append(lane)
            tiers.append(rank)
        cut = int(t_f.min())
        keep = t_c < cut
        tt = np.concatenate([t_c[keep], t_f])
        vv = np.concatenate([v_c[keep], v_f])
        frags.append((lane, tt, vv))
    words_np, nbits_np = pack_streams(streams)
    steps = START + 600 * SEC + np.arange(10, dtype=np.int64) * 300 * SEC
    range_nanos = 20 * 60 * SEC
    rate, err = device_temporal_pipeline(
        jax.device_put(jnp.asarray(words_np), dev),
        jax.device_put(jnp.asarray(nbits_np), dev),
        jax.device_put(jnp.asarray(np.asarray(slots, np.int64)), dev),
        jax.device_put(jnp.asarray(steps), dev),
        n_lanes=n_lanes, n_cap=dp_fine + dp_coarse,
        fn="rate", range_nanos=range_nanos,
        tiers=jax.device_put(
            jnp.asarray(np.asarray(tiers, np.int64)), dev),
        n_tiers=2)
    assert not np.asarray(err).any()
    t_ref, v_ref, _ = cons.merge_packed(frags, n_lanes)
    want = cons.extrapolated_rate(t_ref, v_ref, steps, range_nanos,
                                  True, True)
    got = np.asarray(rate)
    np.testing.assert_array_equal(np.isnan(want), np.isnan(got))
    np.testing.assert_allclose(np.nan_to_num(got), np.nan_to_num(want),
                               rtol=1e-9, atol=1e-10)


def test_device_extra_arg_temporals_on_device():
    """The session-4 family completions on hardware: holt_winters
    (affine-map composition — non-commutative combines through
    associative_scan and the lifting tables, the orientation class the
    CPU suite caught a reverse-scan bug in) and quantile_over_time
    (window materialization + per-window f64 sort under X64
    emulation).  Neither rides the DEVICE_REDUCERS family iteration
    (extra args), so they get their own lane test."""
    dev = _dev()
    from m3_tpu.models.query_pipeline import device_temporal_pipeline
    from m3_tpu.ops import consolidate as cons

    n_lanes, dp = 5, 96
    rng = np.random.default_rng(29)
    streams, frags = [], []
    for lane in range(n_lanes):
        t = START + (np.arange(dp, dtype=np.int64) + 1) * 10 * SEC
        v = np.round(np.cumsum(rng.standard_normal(dp)) + 30, 2)
        v[rng.random(dp) < 0.2] = np.nan
        enc = tsz.Encoder(START)
        for ti, vi in zip(t, v):
            enc.encode(int(ti), float(vi))
        streams.append(enc.finalize())
        frags.append((lane, t, v))
    words_np, nbits_np = pack_streams(streams)
    steps = START + 600 * SEC + np.arange(8, dtype=np.int64) * 60 * SEC
    range_nanos = 5 * 60 * SEC
    t_ref, v_ref, _ = cons.merge_packed(frags, n_lanes)
    slots = jax.device_put(
        jnp.asarray(np.arange(n_lanes, dtype=np.int64)), dev)
    args = (jax.device_put(jnp.asarray(words_np), dev),
            jax.device_put(jnp.asarray(nbits_np), dev), slots,
            jax.device_put(jnp.asarray(steps), dev))
    out, err = device_temporal_pipeline(
        *args, n_lanes=n_lanes, n_cap=dp, range_nanos=range_nanos,
        fn="holt_winters", hw_sf=0.3, hw_tf=0.1)
    assert not np.asarray(err).any()
    want = cons.window_holt_winters(t_ref, v_ref, steps, range_nanos,
                                    0.3, 0.1)
    got = np.asarray(out)
    np.testing.assert_array_equal(np.isnan(want), np.isnan(got))
    np.testing.assert_allclose(np.nan_to_num(got), np.nan_to_num(want),
                               rtol=1e-9, atol=1e-10)
    out, err = device_temporal_pipeline(
        *args, n_lanes=n_lanes, n_cap=dp, range_nanos=range_nanos,
        fn="quantile_over_time", phi=0.9)
    assert not np.asarray(err).any()
    want = cons.window_quantile(t_ref, v_ref, steps, range_nanos, 0.9)
    got = np.asarray(out)
    np.testing.assert_array_equal(np.isnan(want), np.isnan(got))
    np.testing.assert_allclose(np.nan_to_num(got), np.nan_to_num(want),
                               rtol=1e-9, atol=1e-10)
