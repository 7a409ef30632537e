"""Device-resident query pipeline (decode -> merge -> rate in one jit)
vs the host serving tier: exact parity on the CPU backend, plus the
series-sharded variant on the virtual 8-device mesh with its psum
fleet aggregate (the round-6 device read path, validated the same way
every device kernel here was before hardware)."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from m3_tpu.models.query_pipeline import (device_grouped_pipeline,
                                          device_temporal_pipeline)
from m3_tpu.ops import consolidate as cons
from m3_tpu.ops import m3tsz_scalar as tsz
from m3_tpu.ops.bitstream import pack_streams
from m3_tpu.utils import xtime

SEC = xtime.SECOND
T0 = 1_600_000_000 * SEC


def _mk_streams(n_lanes, blocks_per, dp, seed=9):
    rng = np.random.default_rng(seed)
    streams, slots, host_frags = [], [], []
    for lane in range(n_lanes):
        for b in range(blocks_per):
            base = T0 + b * dp * 10 * SEC
            t = base + (np.arange(dp) + 1) * 10 * SEC
            v = np.cumsum(rng.random(dp) * 3)
            enc = tsz.Encoder(base)
            for ti, vi in zip(t, v):
                enc.encode(int(ti), float(vi))
            streams.append(enc.finalize())
            slots.append(lane)
            host_frags.append((lane, t, v))
    return streams, np.asarray(slots, dtype=np.int64), host_frags


def _host_reference(host_frags, n_lanes, steps, range_nanos):
    times, values, _ = cons.merge_packed(host_frags, n_lanes)
    return cons.extrapolated_rate(times, values, steps, range_nanos,
                                  True, True)


def test_device_pipeline_matches_host():
    n_lanes, blocks_per, dp = 12, 3, 40
    streams, slots, frags = _mk_streams(n_lanes, blocks_per, dp)
    words, nbits = pack_streams(streams)
    steps = T0 + np.arange(9, dtype=np.int64) * 120 * SEC + 600 * SEC
    range_nanos = 10 * 60 * SEC
    n_cap = blocks_per * dp
    rate, err = device_temporal_pipeline(
        jnp.asarray(words), jnp.asarray(nbits), jnp.asarray(slots),
        jnp.asarray(steps), n_lanes=n_lanes, n_cap=n_cap,
        fn="rate", range_nanos=range_nanos)
    assert not np.asarray(err).any()
    want = _host_reference(frags, n_lanes, steps, range_nanos)
    got = np.asarray(rate)
    np.testing.assert_array_equal(np.isnan(want), np.isnan(got))
    np.testing.assert_allclose(np.nan_to_num(got), np.nan_to_num(want),
                               rtol=1e-12, atol=1e-12)


def test_device_pipeline_block_width_decode():
    """n_dp < n_cap: decode grids sized to one BLOCK while lanes hold
    all of a series' blocks — the memory/work shape the config-4 device
    leg runs at.  Must be value-identical to the full-width decode."""
    n_lanes, blocks_per, dp = 10, 3, 32
    streams, slots, frags = _mk_streams(n_lanes, blocks_per, dp, seed=21)
    words, nbits = pack_streams(streams)
    steps = T0 + np.arange(8, dtype=np.int64) * 120 * SEC + 600 * SEC
    range_nanos = 10 * 60 * SEC
    n_cap = blocks_per * dp
    rate, err = device_temporal_pipeline(
        jnp.asarray(words), jnp.asarray(nbits), jnp.asarray(slots),
        jnp.asarray(steps), n_lanes=n_lanes, n_cap=n_cap,
        fn="rate", range_nanos=range_nanos, n_dp=dp)
    assert not np.asarray(err).any()
    want = _host_reference(frags, n_lanes, steps, range_nanos)
    got = np.asarray(rate)
    np.testing.assert_array_equal(np.isnan(want), np.isnan(got))
    np.testing.assert_allclose(np.nan_to_num(got), np.nan_to_num(want),
                               rtol=1e-12, atol=1e-12)


def test_device_pipeline_truncation_flagged():
    """Under-provisioned n_dp (a stream longer than its decode budget)
    must surface in `error`, never as a silently short lane."""
    n_lanes, blocks_per, dp = 4, 2, 24
    streams, slots, _ = _mk_streams(n_lanes, blocks_per, dp, seed=5)
    words, nbits = pack_streams(streams)
    steps = T0 + np.arange(4, dtype=np.int64) * 120 * SEC + 600 * SEC
    _, err = device_temporal_pipeline(
        jnp.asarray(words), jnp.asarray(nbits), jnp.asarray(slots),
        jnp.asarray(steps), n_lanes=n_lanes, n_cap=blocks_per * dp,
        fn="rate", range_nanos=10 * 60 * SEC, n_dp=dp - 1)  # one short
    assert np.asarray(err).all()
    # and at the exact width nothing is flagged
    _, err_ok = device_temporal_pipeline(
        jnp.asarray(words), jnp.asarray(nbits), jnp.asarray(slots),
        jnp.asarray(steps), n_lanes=n_lanes, n_cap=blocks_per * dp,
        fn="rate", range_nanos=10 * 60 * SEC, n_dp=dp)
    assert not np.asarray(err_ok).any()


@pytest.mark.parametrize("blocks_per,form", [(3, "rotate"), (6, "window")])
def test_device_pipeline_lane_overflow_flagged(blocks_per, form):
    """A lane whose streams exceed its n_cap budget must flag every
    contributing stream — and must NOT spill samples into the next
    lane's merged region."""
    from m3_tpu.models.query_pipeline import merge_form

    n_lanes, dp = 3, 24
    assert merge_form((blocks_per - 1) * dp, dp) == form
    streams, slots, frags = _mk_streams(n_lanes, blocks_per, dp, seed=8)
    words, nbits = pack_streams(streams)
    steps = T0 + np.arange(5, dtype=np.int64) * 120 * SEC + 600 * SEC
    range_nanos = 10 * 60 * SEC
    # budget holds all blocks but the last; streams are exactly dp long
    # so per-stream truncation does NOT fire — only the lane overflow
    n_cap = (blocks_per - 1) * dp
    rate, err = device_temporal_pipeline(
        jnp.asarray(words), jnp.asarray(nbits), jnp.asarray(slots),
        jnp.asarray(steps), n_lanes=n_lanes, n_cap=n_cap,
        fn="rate", range_nanos=range_nanos, n_dp=dp)
    assert np.asarray(err).all()
    # no cross-lane corruption: each lane's merged samples are its own
    # blocks but the last, so rates equal the host reference on that
    # subset
    seen: dict[int, int] = {}
    kept = []
    for f in frags:
        seen[f[0]] = seen.get(f[0], 0) + 1
        if seen[f[0]] < blocks_per:
            kept.append(f)
    t_ref, v_ref, _ = cons.merge_packed(kept, n_lanes)
    want = cons.extrapolated_rate(t_ref, v_ref, steps, range_nanos,
                                  True, True)
    got = np.asarray(rate)
    np.testing.assert_array_equal(np.isnan(want), np.isnan(got))
    np.testing.assert_allclose(np.nan_to_num(got), np.nan_to_num(want),
                               rtol=1e-12, atol=1e-12)


def test_device_pipeline_range_is_not_a_compile_key():
    """range_nanos must be a traced operand: arbitrary per-query window
    durations (rate(x[93s])) must not each force an XLA recompile of
    the serving pipeline."""
    n_lanes, blocks_per, dp = 4, 2, 16
    streams, slots, _ = _mk_streams(n_lanes, blocks_per, dp, seed=13)
    words, nbits = pack_streams(streams)
    steps = T0 + np.arange(4, dtype=np.int64) * 120 * SEC + 600 * SEC
    device_temporal_pipeline._clear_cache()
    for rng_s in (300, 93, 607):
        device_temporal_pipeline(
            jnp.asarray(words), jnp.asarray(nbits), jnp.asarray(slots),
            jnp.asarray(steps), n_lanes=n_lanes, n_cap=blocks_per * dp,
            fn="rate", range_nanos=rng_s * SEC, n_dp=dp)
    assert device_temporal_pipeline._cache_size() == 1


@pytest.mark.parametrize("blocks_per,form", [(2, "rotate"), (4, "window")])
def test_device_pipeline_unsorted_lane_flagged(blocks_per, form):
    """Overlapping blocks (out-of-order across a slot's streams) break
    the searchsorted window-selection assumption — the pipeline must
    flag the lane's streams, not return silently wrong windows.  The
    host tier detects the same condition and re-sorts (the engine falls
    back on the flag)."""
    from m3_tpu.models.query_pipeline import merge_form

    n_lanes, dp = 3, 20
    assert merge_form(blocks_per * dp, dp) == form
    streams, slots, frags = [], [], []
    for lane in range(n_lanes):
        for b in range(blocks_per):
            # lane 1's last two blocks OVERLAP (same base); others stack
            base = (T0 + (blocks_per - 2) * dp * 10 * SEC
                    if lane == 1 and b == blocks_per - 1
                    else T0 + b * dp * 10 * SEC)
            t = base + (np.arange(dp, dtype=np.int64) + 1) * 10 * SEC
            v = np.arange(dp, dtype=np.float64) + lane
            enc = tsz.Encoder(base)
            for ti, vi in zip(t, v):
                enc.encode(int(ti), float(vi))
            streams.append(enc.finalize())
            slots.append(lane)
            frags.append((lane, t, v))
    words, nbits = pack_streams(streams)
    steps = T0 + np.arange(4, dtype=np.int64) * 120 * SEC + 600 * SEC
    _, err = device_temporal_pipeline(
        jnp.asarray(words), jnp.asarray(nbits),
        jnp.asarray(np.asarray(slots, dtype=np.int64)),
        jnp.asarray(steps), n_lanes=n_lanes, n_cap=blocks_per * dp,
        fn="rate", range_nanos=10 * 60 * SEC, n_dp=dp)
    err = np.asarray(err).reshape(n_lanes, blocks_per)
    assert err[1].all(), "overlapping lane's streams must flag"
    assert not err[[0, 2]].any(), "clean lanes must not flag"


def test_device_temporal_pipeline_matches_host():
    """*_over_time on device (NaN-masked prefix sums) vs the host
    window_reduce / step_consolidate references — exact on CPU."""
    from m3_tpu.models.query_pipeline import (DEVICE_REDUCERS,
                                              device_temporal_pipeline)

    n_lanes, blocks_per, dp = 10, 2, 36
    streams, slots, frags = _mk_streams(n_lanes, blocks_per, dp, seed=17)
    words, nbits = pack_streams(streams)
    steps = T0 + np.arange(9, dtype=np.int64) * 120 * SEC + 600 * SEC
    range_nanos = 10 * 60 * SEC
    t_ref, v_ref, _ = cons.merge_packed(frags, n_lanes)
    for reducer in DEVICE_REDUCERS:
        out, err = device_temporal_pipeline(
            jnp.asarray(words), jnp.asarray(nbits), jnp.asarray(slots),
            jnp.asarray(steps), n_lanes=n_lanes,
            n_cap=blocks_per * dp, range_nanos=range_nanos,
            fn=reducer, n_dp=dp)
        assert not np.asarray(err).any(), reducer
        if reducer == "last_over_time":
            want = cons.step_consolidate(t_ref, v_ref, steps,
                                         range_nanos)
        elif reducer in ("irate", "idelta"):
            from m3_tpu.query.engine import Engine
            want = Engine._instant_delta(t_ref, v_ref, steps,
                                         range_nanos,
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


def test_inf_samples_agree_across_tiers():
    """±Inf is a legal f64 sample (M3TSZ encodes it); sum/avg over a
    window containing +Inf must be +Inf on BOTH tiers (upstream
    semantics), and an Inf + -Inf window must be NaN on both — guards
    the host _masked() clamp regression (nan_to_num turned Inf into
    ±1.8e308 on the host tier only)."""
    from m3_tpu.models.query_pipeline import device_temporal_pipeline

    n_lanes, dp = 2, 12
    streams, frags = [], []
    for lane in range(n_lanes):
        t = T0 + (np.arange(dp, dtype=np.int64) + 1) * 10 * SEC
        v = np.full(dp, 2.0)
        v[3] = np.inf
        if lane == 1:
            v[4] = -np.inf
        enc = tsz.Encoder(T0)  # int-optimized grammar: Inf rides the
        for ti, vi in zip(t, v):  # per-value float-fallback control bit
            enc.encode(int(ti), float(vi))
        streams.append(enc.finalize())
        frags.append((lane, t, v))
    words, nbits = pack_streams(streams)
    steps = np.asarray([T0 + dp * 10 * SEC], dtype=np.int64)
    rng = dp * 10 * SEC
    t_ref, v_ref, _ = cons.merge_packed(frags, n_lanes)
    host = cons.window_reduce(t_ref, v_ref, steps, rng, "sum_over_time")
    out, err = device_temporal_pipeline(
        jnp.asarray(words), jnp.asarray(nbits),
        jnp.asarray(np.arange(n_lanes, dtype=np.int64)),
        jnp.asarray(steps), n_lanes=n_lanes, n_cap=dp,
        range_nanos=rng, fn="sum_over_time")
    assert not np.asarray(err).any()
    dev = np.asarray(out)
    assert host[0, 0] == np.inf and dev[0, 0] == np.inf
    assert np.isnan(host[1, 0]) and np.isnan(dev[1, 0])


def test_device_minmax_nan_and_wide_windows():
    """min/max_over_time device form (two-level range-max): NaN-riddled
    lanes, an all-NaN window, ±Inf samples, and window widths that
    exercise every decomposition case — same-block, adjacent blocks
    (empty sparse mid-range), and wide multi-block ranges."""
    from m3_tpu.models.query_pipeline import device_temporal_pipeline

    rng = np.random.default_rng(71)
    n_lanes, dp = 6, 150  # not a multiple of the 32-sample block
    streams, frags = [], []
    for lane in range(n_lanes):
        t = T0 + (np.arange(dp, dtype=np.int64) + 1) * 10 * SEC
        v = np.round(rng.standard_normal(dp) * 50, 1)
        v[rng.random(dp) < 0.3] = np.nan  # heavy NaN sprinkle
        if lane == 1:
            v[:] = np.nan  # every window all-NaN -> NaN
        if lane == 2:
            v[10] = np.inf
            v[11] = -np.inf
        enc = tsz.Encoder(T0)
        for ti, vi in zip(t, v):
            enc.encode(int(ti), float(vi))
        streams.append(enc.finalize())
        frags.append((lane, t, v))
    words, nbits = pack_streams(streams)
    t_ref, v_ref, _ = cons.merge_packed(frags, n_lanes)
    # ranges: 50s (same block), 400s (adjacent), 1490s (all blocks)
    for range_s in (50, 400, 1490):
        range_nanos = range_s * SEC
        steps = T0 + np.arange(12, dtype=np.int64) * 120 * SEC + 60 * SEC
        for reducer in ("min_over_time", "max_over_time"):
            out, err = device_temporal_pipeline(
                jnp.asarray(words), jnp.asarray(nbits),
                jnp.asarray(np.arange(n_lanes, dtype=np.int64)),
                jnp.asarray(steps), n_lanes=n_lanes, n_cap=dp,
                range_nanos=range_nanos, fn=reducer)
            assert not np.asarray(err).any(), (range_s, reducer)
            want = cons.window_reduce(t_ref, v_ref, steps, range_nanos,
                                      reducer)
            got = np.asarray(out)
            np.testing.assert_array_equal(
                np.isnan(want), np.isnan(got),
                err_msg=f"{reducer}/{range_s}")
            np.testing.assert_array_equal(
                np.nan_to_num(got, posinf=1e308, neginf=-1e308),
                np.nan_to_num(want, posinf=1e308, neginf=-1e308),
                err_msg=f"{reducer}/{range_s}")


def test_device_stdvar_stability_and_windows():
    """stddev/stdvar_over_time device form (mergeable-Welford range
    structure): every window-decomposition case (same-block, adjacent
    blocks with an empty mid-range, wide multi-block), NaN-riddled and
    all-NaN lanes (host contract: nonempty-but-all-NaN window -> 0.0),
    AND the catastrophic-cancellation regime the design exists for —
    1e9-offset samples with unit-scale spread, where the prefix-sum
    E[x^2]-E[x]^2 form would read a wildly wrong (even negative)
    variance."""
    from m3_tpu.models.query_pipeline import device_temporal_pipeline

    rng = np.random.default_rng(93)
    n_lanes, dp = 6, 150  # not a multiple of the 32-sample block
    streams, frags = [], []
    for lane in range(n_lanes):
        t = T0 + (np.arange(dp, dtype=np.int64) + 1) * 10 * SEC
        v = np.round(rng.standard_normal(dp) * 50, 1)
        v[rng.random(dp) < 0.3] = np.nan
        if lane == 1:
            v[:] = np.nan  # all-NaN: every nonempty window -> 0.0
        if lane == 2:
            # counter regime: 1e9 offset, spread ~1.  Naive two-sided
            # prefix form loses all 9 leading digits; the Welford
            # merges must hold ~1e-6 relative accuracy here
            v = 1.5e9 + np.round(rng.standard_normal(dp), 3)
        enc = tsz.Encoder(T0)
        for ti, vi in zip(t, v):
            enc.encode(int(ti), float(vi))
        streams.append(enc.finalize())
        frags.append((lane, t, v))
    words, nbits = pack_streams(streams)
    t_ref, v_ref, _ = cons.merge_packed(frags, n_lanes)
    # ranges: 50s (same block), 400s (adjacent), 1490s (all blocks)
    for range_s in (50, 400, 1490):
        range_nanos = range_s * SEC
        steps = T0 + np.arange(12, dtype=np.int64) * 120 * SEC + 60 * SEC
        for reducer in ("stdvar_over_time", "stddev_over_time"):
            out, err = device_temporal_pipeline(
                jnp.asarray(words), jnp.asarray(nbits),
                jnp.asarray(np.arange(n_lanes, dtype=np.int64)),
                jnp.asarray(steps), n_lanes=n_lanes, n_cap=dp,
                range_nanos=range_nanos, fn=reducer)
            assert not np.asarray(err).any(), (range_s, reducer)
            want = cons.window_reduce(t_ref, v_ref, steps, range_nanos,
                                      reducer)
            got = np.asarray(out)
            np.testing.assert_array_equal(
                np.isnan(want), np.isnan(got),
                err_msg=f"{reducer}/{range_s}")
            np.testing.assert_allclose(
                np.nan_to_num(got), np.nan_to_num(want), rtol=1e-5,
                atol=1e-9, err_msg=f"{reducer}/{range_s}")
            # the cancellation canary: lane 2's spread is ~1, so any
            # window with >=2 samples must read an O(1) stddev, never
            # 0 or a 1e9-scale artifact
            if reducer == "stddev_over_time":
                w2 = got[2][~np.isnan(got[2])]
                multi = w2[w2 > 0]
                if multi.size:
                    assert float(multi.max()) < 10.0, multi
                    assert float(multi.min()) > 1e-3, multi


def test_device_holt_winters_matches_host():
    """holt_winters device form (affine-map composition over the
    block-scan + binary-lifting structure, windows rebased at the first
    present sample): NaN-riddled lanes, an all-NaN lane, sparse lanes
    sitting at the cnt==2 boundary, several (sf, tf) pairs, and window
    widths covering same-block, adjacent, and wide multi-block
    decompositions — vs the host window_holt_winters reference."""
    from m3_tpu.models.query_pipeline import device_temporal_pipeline

    rng = np.random.default_rng(87)
    n_lanes, dp = 6, 150
    streams, frags = [], []
    for lane in range(n_lanes):
        t = T0 + (np.arange(dp, dtype=np.int64) + 1) * 10 * SEC
        v = np.round(np.cumsum(rng.standard_normal(dp)), 2)
        v[rng.random(dp) < 0.3] = np.nan
        if lane == 1:
            v[:] = np.nan
        if lane == 3:  # very sparse: many windows at the cnt<2 edge
            keep = rng.random(dp) < 0.06
            v = np.where(keep, v, np.nan)
        enc = tsz.Encoder(T0)
        for ti, vi in zip(t, v):
            enc.encode(int(ti), float(vi))
        streams.append(enc.finalize())
        frags.append((lane, t, v))
    words, nbits = pack_streams(streams)
    t_ref, v_ref, _ = cons.merge_packed(frags, n_lanes)
    for range_s in (50, 400, 1490):
        range_nanos = range_s * SEC
        steps = T0 + np.arange(12, dtype=np.int64) * 120 * SEC + 60 * SEC
        for sf, tf in ((0.3, 0.1), (0.8, 0.6)):
            out, err = device_temporal_pipeline(
                jnp.asarray(words), jnp.asarray(nbits),
                jnp.asarray(np.arange(n_lanes, dtype=np.int64)),
                jnp.asarray(steps), n_lanes=n_lanes, n_cap=dp,
                range_nanos=range_nanos, fn="holt_winters",
                hw_sf=sf, hw_tf=tf)
            assert not np.asarray(err).any(), (range_s, sf, tf)
            want = cons.window_holt_winters(t_ref, v_ref, steps,
                                            range_nanos, sf, tf)
            got = np.asarray(out)
            np.testing.assert_array_equal(
                np.isnan(want), np.isnan(got),
                err_msg=f"{range_s}/{sf}/{tf}")
            np.testing.assert_allclose(
                np.nan_to_num(got), np.nan_to_num(want), rtol=1e-9,
                atol=1e-12, err_msg=f"{range_s}/{sf}/{tf}")


def test_device_quantile_over_time_matches_host():
    """quantile_over_time device form (direct window materialization +
    per-window sort): phi endpoints and interior values, NaN-riddled
    and all-NaN lanes, every window-width class — vs the host
    window_quantile reference.  phi is traced: the sweep must not grow
    the jit cache."""
    from m3_tpu.models.query_pipeline import device_temporal_pipeline

    rng = np.random.default_rng(19)
    n_lanes, dp = 5, 150
    streams, frags = [], []
    for lane in range(n_lanes):
        t = T0 + (np.arange(dp, dtype=np.int64) + 1) * 10 * SEC
        v = np.round(rng.standard_normal(dp) * 30, 2)
        v[rng.random(dp) < 0.3] = np.nan
        if lane == 1:
            v[:] = np.nan
        enc = tsz.Encoder(T0)
        for ti, vi in zip(t, v):
            enc.encode(int(ti), float(vi))
        streams.append(enc.finalize())
        frags.append((lane, t, v))
    words, nbits = pack_streams(streams)
    t_ref, v_ref, _ = cons.merge_packed(frags, n_lanes)
    device_temporal_pipeline._clear_cache()
    for range_s in (50, 400, 1490):
        range_nanos = range_s * SEC
        steps = T0 + np.arange(12, dtype=np.int64) * 120 * SEC + 60 * SEC
        for phi in (0.0, 0.25, 0.5, 0.95, 1.0):
            out, err = device_temporal_pipeline(
                jnp.asarray(words), jnp.asarray(nbits),
                jnp.asarray(np.arange(n_lanes, dtype=np.int64)),
                jnp.asarray(steps), n_lanes=n_lanes, n_cap=dp,
                range_nanos=range_nanos, fn="quantile_over_time",
                phi=phi)
            assert not np.asarray(err).any(), (range_s, phi)
            want = cons.window_quantile(t_ref, v_ref, steps,
                                        range_nanos, phi)
            got = np.asarray(out)
            np.testing.assert_array_equal(
                np.isnan(want), np.isnan(got),
                err_msg=f"{range_s}/{phi}")
            np.testing.assert_allclose(
                np.nan_to_num(got), np.nan_to_num(want), rtol=1e-9,
                atol=1e-12, err_msg=f"{range_s}/{phi}")
    assert device_temporal_pipeline._cache_size() == 1


def _host_grouped(per_lane, groups, n_groups, agg, phi=0.5):
    """Numpy reference for the grouped lane reduction — the same masked
    math as Engine._eval_agg (NaN = absent, empty group-step = NaN,
    mean-shifted two-pass stddev, nanquantile at phi)."""
    G, S = n_groups, per_lane.shape[1]
    m = ~np.isnan(per_lane)
    vz = np.where(m, per_lane, 0.0)
    sums = np.zeros((G, S))
    counts = np.zeros((G, S))
    mins = np.full((G, S), np.inf)
    maxs = np.full((G, S), -np.inf)
    for i, g in enumerate(groups):
        sums[g] += vz[i]
        counts[g] += m[i]
        mins[g][m[i]] = np.minimum(mins[g][m[i]], per_lane[i][m[i]])
        maxs[g][m[i]] = np.maximum(maxs[g][m[i]], per_lane[i][m[i]])
    n = np.maximum(counts, 1)
    if agg == "sum":
        out = sums
    elif agg == "avg":
        out = sums / n
    elif agg == "count":
        out = counts
    elif agg == "min":
        out = mins
    elif agg == "max":
        out = maxs
    elif agg == "group":
        out = np.ones((G, S))
    elif agg in ("stddev", "stdvar"):
        mean = sums / n
        sq = np.zeros((G, S))
        for i, g in enumerate(groups):
            d = np.where(m[i], per_lane[i] - mean[g], 0.0)
            sq[g] += d * d
        var = sq / n
        out = np.sqrt(var) if agg == "stddev" else var
    elif agg == "quantile":  # same masked form as Engine._eval_agg
        out = np.full((G, S), np.nan)
        for g in range(G):
            sub = per_lane[[i for i, gg in enumerate(groups) if gg == g]]
            any_m = ~np.isnan(sub).all(axis=0)
            with np.errstate(invalid="ignore"):
                q = np.nanquantile(np.where(any_m[None, :], sub, 0.0),
                                   phi, axis=0)
            out[g] = np.where(any_m, q, np.nan)
    return np.where(counts == 0, np.nan, out)


def test_device_grouped_pipeline_matches_host():
    """agg by (...) (fn(x[range])) fused on device: every aggregation
    over both a rate-family and a reduce-family temporal, vs the
    two-stage host reference — exact on CPU (segment reductions sum in
    lane order)."""
    from m3_tpu.models.query_pipeline import (DEVICE_GROUP_AGGS,
                                              device_grouped_pipeline)

    n_lanes, blocks_per, dp = 12, 2, 36
    streams, slots, frags = _mk_streams(n_lanes, blocks_per, dp, seed=33)
    words, nbits = pack_streams(streams)
    steps = T0 + np.arange(9, dtype=np.int64) * 120 * SEC + 600 * SEC
    range_nanos = 10 * 60 * SEC
    groups = np.arange(n_lanes, dtype=np.int64) % 3
    t_ref, v_ref, _ = cons.merge_packed(frags, n_lanes)
    want_rate = cons.extrapolated_rate(t_ref, v_ref, steps, range_nanos,
                                       True, True)
    want_sot = cons.window_reduce(t_ref, v_ref, steps, range_nanos,
                                  "sum_over_time")
    for fn, per_lane in (("rate", want_rate), ("sum_over_time", want_sot)):
        for agg in DEVICE_GROUP_AGGS:
            out, err = device_grouped_pipeline(
                jnp.asarray(words), jnp.asarray(nbits),
                jnp.asarray(slots), jnp.asarray(steps),
                jnp.asarray(groups), n_lanes=n_lanes, n_groups=3,
                n_cap=blocks_per * dp, range_nanos=range_nanos,
                fn=fn, agg=agg, n_dp=dp)
            assert not np.asarray(err).any(), (fn, agg)
            want = _host_grouped(per_lane, groups, 3, agg)
            got = np.asarray(out)
            np.testing.assert_array_equal(np.isnan(want), np.isnan(got),
                                          err_msg=f"{fn}/{agg}")
            np.testing.assert_allclose(
                np.nan_to_num(got), np.nan_to_num(want), rtol=1e-9,
                atol=1e-12, err_msg=f"{fn}/{agg}")


def test_device_grouped_padding_lanes_inert():
    """jit-padding lanes (no streams -> all-NaN rows) parked on group 0
    must not perturb any aggregate — including count and min/max."""
    from m3_tpu.models.query_pipeline import device_grouped_pipeline

    n_lanes, blocks_per, dp = 6, 2, 24
    streams, slots, frags = _mk_streams(n_lanes, blocks_per, dp, seed=7)
    words, nbits = pack_streams(streams)
    steps = T0 + np.arange(5, dtype=np.int64) * 120 * SEC + 600 * SEC
    range_nanos = 10 * 60 * SEC
    groups = np.arange(n_lanes, dtype=np.int64) % 2
    t_ref, v_ref, _ = cons.merge_packed(frags, n_lanes)
    want_rate = cons.extrapolated_rate(t_ref, v_ref, steps, range_nanos,
                                       True, True)
    # pad lanes to 64 (all parked on group 0) like the engine does
    lanes_pad = 64
    groups_p = np.zeros(lanes_pad, dtype=np.int64)
    groups_p[:n_lanes] = groups
    for agg in ("sum", "count", "min", "max", "avg"):
        out, err = device_grouped_pipeline(
            jnp.asarray(words), jnp.asarray(nbits), jnp.asarray(slots),
            jnp.asarray(steps), jnp.asarray(groups_p),
            n_lanes=lanes_pad, n_groups=2, n_cap=blocks_per * dp,
            range_nanos=range_nanos, fn="rate", agg=agg, n_dp=dp)
        assert not np.asarray(err).any(), agg
        want = _host_grouped(want_rate, groups, 2, agg)
        np.testing.assert_allclose(
            np.nan_to_num(np.asarray(out)), np.nan_to_num(want),
            rtol=1e-9, atol=1e-12, err_msg=agg)


def test_device_grouped_quantile_phi_sweep():
    """quantile by (...) on device (per-step lane sort + interpolated
    gather): phi endpoints and interior values, groups with NaN-riddled
    and all-NaN lanes, and jit padding lanes parked on group 0 — vs
    np.nanquantile (the host _eval_agg form).  phi is traced: the sweep
    must not grow the jit cache."""
    from m3_tpu.models.query_pipeline import device_grouped_pipeline

    rng = np.random.default_rng(55)
    n_lanes, blocks_per, dp = 9, 2, 30
    streams, slots, frags = [], [], []
    for lane in range(n_lanes):
        for b in range(blocks_per):
            base = T0 + b * dp * 10 * SEC
            t = base + (np.arange(dp) + 1) * 10 * SEC
            v = np.round(rng.standard_normal(dp) * 20, 2)
            v[rng.random(dp) < 0.25] = np.nan
            if lane == 4:
                v[:] = np.nan  # an all-NaN lane inside a live group
            enc = tsz.Encoder(base)
            for ti, vi in zip(t, v):
                enc.encode(int(ti), float(vi))
            streams.append(enc.finalize())
            slots.append(lane)
            frags.append((lane, t, v))
    slots = np.asarray(slots, dtype=np.int64)
    words, nbits = pack_streams(streams)
    steps = T0 + np.arange(7, dtype=np.int64) * 120 * SEC + 600 * SEC
    range_nanos = 10 * 60 * SEC
    groups = np.arange(n_lanes, dtype=np.int64) % 3
    t_ref, v_ref, _ = cons.merge_packed(frags, n_lanes)
    per_lane = cons.window_reduce(t_ref, v_ref, steps, range_nanos,
                                  "avg_over_time")
    # pad lanes to 64 on group 0 like the engine does
    lanes_pad = 64
    groups_p = np.zeros(lanes_pad, dtype=np.int64)
    groups_p[:n_lanes] = groups
    device_grouped_pipeline._clear_cache()
    for phi in (0.0, 0.25, 0.5, 0.9, 1.0):
        out, err = device_grouped_pipeline(
            jnp.asarray(words), jnp.asarray(nbits), jnp.asarray(slots),
            jnp.asarray(steps), jnp.asarray(groups_p),
            n_lanes=lanes_pad, n_groups=3, n_cap=blocks_per * dp,
            range_nanos=range_nanos, fn="avg_over_time",
            agg="quantile", n_dp=dp, phi=phi)
        assert not np.asarray(err).any(), phi
        want = _host_grouped(per_lane, groups, 3, "quantile", phi=phi)
        got = np.asarray(out)
        np.testing.assert_array_equal(np.isnan(want), np.isnan(got),
                                      err_msg=str(phi))
        np.testing.assert_allclose(np.nan_to_num(got),
                                   np.nan_to_num(want), rtol=1e-9,
                                   atol=1e-12, err_msg=str(phi))
    assert device_grouped_pipeline._cache_size() == 1


def _sharded_case(seed, blocks_per=2, n_dp=None):
    """16 lanes, 2 a shard of the 8-device mesh, the slots local to
    their shard; -> (mesh, the entry points' positional arguments for
    the mesh and for one chip, keywords, frags, steps, range)."""
    from m3_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(n_series_shards=8, n_window_shards=1)
    n_lanes, dp = 16, 30
    streams, slots, frags = _mk_streams(n_lanes, blocks_per, dp, seed=seed)
    words, nbits = pack_streams(streams)
    steps = T0 + np.arange(7, dtype=np.int64) * 120 * SEC + 600 * SEC
    range_nanos = 10 * 60 * SEC
    head = (jnp.asarray(words), jnp.asarray(nbits))
    local = head + (jnp.asarray(slots % (n_lanes // 8)), jnp.asarray(steps))
    whole = head + (jnp.asarray(slots), jnp.asarray(steps))
    kw = dict(n_lanes=n_lanes, n_cap=blocks_per * dp,
              range_nanos=range_nanos, n_dp=n_dp)
    return mesh, local, whole, kw, frags, steps, range_nanos


@pytest.mark.parametrize("agg", ["sum", "avg", "min", "max", "count",
                                 "group", "stddev", "stdvar", "quantile"])
def test_device_grouped_meshed_equals_one_chip(agg):
    """Each aggregation of the grouped form, given the mesh (the lane
    reduction through its collective), equals the one-chip program's
    answer and the host's."""
    if jax.device_count() < 8:
        pytest.skip("needs the virtual 8-device mesh")
    mesh, local, whole, kw, frags, steps, range_nanos = _sharded_case(41)
    groups = jnp.arange(16, dtype=jnp.int64) % 4  # span shards
    kw.update(n_groups=4, fn="rate", agg=agg, phi=0.3)
    out, err = device_grouped_pipeline(*local, groups, mesh=mesh, **kw)
    one, err_one = device_grouped_pipeline(*whole, groups, **kw)
    assert not np.asarray(err).any() and not np.asarray(err_one).any()
    np.testing.assert_allclose(np.asarray(out), np.asarray(one),
                               rtol=1e-12, atol=1e-12, equal_nan=True)
    t_ref, v_ref, _ = cons.merge_packed(frags, 16)
    want = _host_grouped(
        cons.extrapolated_rate(t_ref, v_ref, steps, range_nanos, True, True),
        np.asarray(groups), 4, agg, phi=0.3)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-9,
                               atol=1e-12, equal_nan=True)


def test_device_grouped_on_mesh_collectives():
    if jax.device_count() < 8:
        pytest.skip("needs the virtual 8-device mesh")
    from m3_tpu.models.query_pipeline import DEVICE_GROUP_AGGS

    mesh, local, _, kw, frags, steps, range_nanos = _sharded_case(41)
    groups = np.arange(16, dtype=np.int64) % 4  # span shards
    t_ref, v_ref, _ = cons.merge_packed(frags, 16)
    want_rate = cons.extrapolated_rate(t_ref, v_ref, steps, range_nanos,
                                       True, True)
    for agg in DEVICE_GROUP_AGGS:
        out, err = device_grouped_pipeline(
            *local, jnp.asarray(groups), n_groups=4, fn="rate", agg=agg,
            mesh=mesh, **kw)
        assert not np.asarray(err).any(), agg
        want = _host_grouped(want_rate, groups, 4, agg)
        got = np.asarray(out)
        np.testing.assert_array_equal(np.isnan(want), np.isnan(got),
                                      err_msg=agg)
        np.testing.assert_allclose(
            np.nan_to_num(got), np.nan_to_num(want), rtol=1e-9,
            atol=1e-12, err_msg=agg)
    # a parameterized temporal has no grouped form, on a mesh or off it
    for m in (mesh, None):
        with pytest.raises(ValueError, match="no grouped device form"):
            device_grouped_pipeline(
                *local, jnp.asarray(groups), n_groups=4,
                fn="holt_winters", agg="sum", mesh=m, **kw)


@pytest.mark.parametrize("blocks_per,n_dp,form", [
    (2, None, "rotate"), (5, 30, "window")])
def test_device_pipeline_sharded_psum(blocks_per, n_dp, form):
    """The temporal form given the mesh: per-series results sharded by
    series, no collective; the fleet sum over ICI is the grouped form
    with one group (one psum).  In either form of the merge."""
    from m3_tpu.models.query_pipeline import merge_form

    if jax.device_count() < 8:
        pytest.skip("needs the virtual 8-device mesh")
    mesh, local, whole, kw, frags, steps, range_nanos = _sharded_case(
        9, blocks_per, n_dp)
    assert merge_form(kw["n_cap"], n_dp) == form
    rate, err = device_temporal_pipeline(*local, fn="rate", mesh=mesh, **kw)
    assert not np.asarray(err).any()
    want = _host_reference(frags, 16, steps, range_nanos)
    got = np.asarray(rate)
    np.testing.assert_allclose(np.nan_to_num(got), np.nan_to_num(want),
                               rtol=1e-12, atol=1e-12)
    fleet, err = device_grouped_pipeline(
        *local, jnp.zeros(16, dtype=jnp.int64), n_groups=1, fn="rate",
        agg="sum", mesh=mesh, **kw)
    assert not np.asarray(err).any()
    # (a step no lane is present at is absent there, 0 in the nansum)
    np.testing.assert_allclose(np.nan_to_num(np.asarray(fleet)[0]),
                               np.nansum(want, axis=0), rtol=1e-12)
    # a parameterized temporal through the mesh: its traced parameter
    # reaches every shard
    q_mesh, _ = device_temporal_pipeline(
        *local, fn="quantile_over_time", phi=0.9, mesh=mesh, **kw)
    q_one, _ = device_temporal_pipeline(
        *whole, fn="quantile_over_time", phi=0.9, **kw)
    np.testing.assert_array_equal(np.asarray(q_mesh), np.asarray(q_one))


@pytest.mark.parametrize("entry", ["temporal", "grouped"])
@pytest.mark.parametrize("with_open", [False, True])
def test_one_chip_program_holds_no_collective(entry, with_open):
    """Without a mesh either entry point traces to the one-chip
    program: no shard_map and no collective in its jaxpr, with and
    without open rows.  (With the mesh the same entry point holds
    both.)"""
    n_lanes, dp = 4, 16
    streams, slots, _ = _mk_streams(n_lanes, 1, dp, seed=3)
    words, nbits = pack_streams(streams)
    steps = T0 + np.arange(4, dtype=np.int64) * 60 * SEC + 60 * SEC
    open_rows = None
    if with_open:
        open_rows = (jnp.zeros((2, dp), jnp.int64), jnp.zeros((2, dp)),
                     jnp.zeros(2, jnp.int32), jnp.zeros(2, jnp.int64),
                     jnp.arange(n_lanes + 2))
    args = [jnp.asarray(words), jnp.asarray(nbits), jnp.asarray(slots),
            jnp.asarray(steps)]
    kw = dict(n_lanes=n_lanes, n_cap=2 * dp, range_nanos=300 * SEC,
              n_dp=dp, fn="rate", open_rows=open_rows)
    if entry == "grouped":
        fn = functools.partial(device_grouped_pipeline, n_groups=2,
                               agg="stddev", **kw)
        args.append(jnp.arange(n_lanes, dtype=jnp.int64) % 2)
    else:
        fn = functools.partial(device_temporal_pipeline, **kw)
    text = str(jax.make_jaxpr(fn)(*args))
    for word in ("shard_map", "psum", "pmin", "pmax", "all_gather"):
        assert word not in text, word
    if jax.device_count() >= 8 and not with_open:
        from m3_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(n_series_shards=4, n_window_shards=1)
        meshed = str(jax.make_jaxpr(functools.partial(fn, mesh=mesh))(*args))
        assert "shard_map" in meshed
        assert ("psum" in meshed) == (entry == "grouped")


@pytest.mark.parametrize("fn", ["rate", "increase", "delta"])
def test_rate_family_through_the_temporal_form_is_rate_device(fn):
    """rate / increase / delta have no entry point of their own: the
    temporal form's answer is _rate_device's on the same merged lanes,
    bit for bit."""
    from m3_tpu.models.query_pipeline import _decode_merge, _rate_device

    n_lanes, blocks_per, dp = 6, 2, 24
    streams, slots, _ = _mk_streams(n_lanes, blocks_per, dp, seed=17)
    words, nbits = pack_streams(streams)
    steps = jnp.asarray(T0 + np.arange(6, dtype=np.int64) * 90 * SEC
                        + 300 * SEC)
    args = (jnp.asarray(words), jnp.asarray(nbits), jnp.asarray(slots))
    out, err = device_temporal_pipeline(
        *args, steps, n_lanes=n_lanes, n_cap=blocks_per * dp, fn=fn,
        range_nanos=5 * 60 * SEC, n_dp=dp)
    assert not np.asarray(err).any()
    times, values, _ = jax.jit(
        _decode_merge, static_argnums=(3, 4, 5, 6))(
            *args, n_lanes, blocks_per * dp, dp, SEC)
    want, _ = jax.jit(_rate_device, static_argnames=("is_counter", "is_rate"))(
        times, values, steps, 5 * 60 * SEC,
        is_counter=fn != "delta", is_rate=fn == "rate")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    assert np.isfinite(np.asarray(out)).any()


# -- the merge and the window bounds alone, against numpy ------------------

_INF = np.iinfo(np.int64).max


def _merge_reference(ts, vs, valid, slots, n_lanes, n_cap):
    """The merge's contract, a cell at a time: a slot's valid cells in
    row order fill its lane from the left, cells past n_cap are dropped
    and still counted."""
    out_t = np.full((n_lanes, n_cap), _INF, dtype=np.int64)
    out_v = np.full((n_lanes, n_cap), np.nan)
    counts = np.zeros(n_lanes, dtype=np.int64)
    for r, lane in enumerate(slots):
        for c in np.flatnonzero(valid[r]):
            if counts[lane] < n_cap:
                out_t[lane, counts[lane]] = ts[r, c]
                out_v[lane, counts[lane]] = vs[r, c]
            counts[lane] += 1
    return out_t, out_v, counts


def _merge_layout(rows_of, T, m_pad, pad_slot, seed):
    """rows_of[lane] = the sample counts of the lane's rows, in order.
    -> ts, vs, valid, slots with `m_pad` rows, the padding rows (count
    0) parked on `pad_slot`; cells past a row's count hold stale decode
    state (garbage), never fill values."""
    rng = np.random.default_rng(seed)
    slots = [lane for lane, rows in enumerate(rows_of) for _ in rows]
    counts = [c for rows in rows_of for c in rows]
    n_real = len(slots)
    assert n_real <= m_pad
    slots = np.asarray(slots + [pad_slot] * (m_pad - n_real), np.int64)
    counts = np.asarray(counts + [0] * (m_pad - n_real), np.int64)
    ts = rng.integers(1, 10**6, (m_pad, T)).astype(np.int64)
    vs = rng.random((m_pad, T))
    t_next: dict[int, int] = {}
    for r in range(n_real):            # ascending within a lane
        t0 = t_next.get(int(slots[r]), T0)
        ts[r, :counts[r]] = t0 + (np.arange(counts[r]) + 1) * SEC
        t_next[int(slots[r])] = t0 + counts[r] * SEC
    valid = np.arange(T)[None, :] < counts[:, None]
    return ts, vs, valid, slots


def _random_rows(rng, n_lanes, T, lo, hi, p_empty_lane, p_zero_row):
    return [[] if rng.random() < p_empty_lane else
            [0 if rng.random() < p_zero_row else int(rng.integers(1, T + 1))
             for _ in range(rng.integers(lo, hi + 1))]
            for _ in range(n_lanes)]


def _merge_cases():
    rng = np.random.default_rng(77)
    T = 12
    yield "rows_1_to_5", _random_rows(rng, 9, T, 1, 5, 0.0, 0.0) + [[]], \
        T, 40, 9, 64
    yield "zero_count_rows", [[0, 5, 0, 0, 3], [0], [4, 0], [0, 0, 7]] \
        + [[]], T, 16, 4, 40
    yield "empty_lanes", _random_rows(rng, 12, T, 1, 3, 0.7, 0.1) + [[]], \
        T, 32, 12, 40
    yield "no_rows_at_all", [[], [], []], T, 8, 2, 16
    # the sharded re-lay parks padding on a lane that holds real rows
    yield "padding_on_a_real_lane", [[3, 12], [12, 12, 1], [6, 2]], \
        T, 16, 2, 40
    yield "overflow", [[12, 12, 12], [12, 5], [12, 12, 12, 12, 12],
                       [7]] + [[]], T, 16, 4, 30
    yield "row_wider_than_cap", [[12, 12], [3], [9, 9]] + [[]], T, 8, 3, 8
    yield "one_full_row_each", [[16]] * 7 + [[]], 16, 8, 7, 16
    yield from _long_lane_cases()


def _long_lane_cases():
    """What a lane many rows wide brings (merge_form "window" at these
    shapes): the offset q * T + r with r at both ends of its range, a
    lane's width no multiple of the rows', a row cut at the lane's end,
    rows so short that several meet in one block."""
    rng = np.random.default_rng(78)
    T = 8
    # r = 0 after every full row; r = T - 1 after the row of 7
    yield "offsets_at_both_ends", [[8, 8, 8, 8], [7, 8, 8], [8, 7, 1, 8],
                                   [1, 7, 8], []], T, 16, 4, 37
    # 5 blocks, the last of 4 cells: rows straddle it and are cut in
    # it, one lands wholly past it, one ends on the lane's last cell
    yield "row_cut_at_the_cap", [[8, 8, 8, 7, 8], [8, 8, 8, 8, 4, 8],
                                 [8, 8, 8, 8, 8, 8, 8], [6, 8, 8, 8, 6],
                                 [8, 8, 8, 8, 3, 8], [2]], T, 40, 5, 36
    yield "short_rows_meet_in_a_block", \
        _random_rows(rng, 6, T, 6, 14, 0.0, 0.2) + [[1] * 20, []], \
        T, 96, 7, 45
    # the two-day panel's 22 rows a lane, beside lanes of 0 and 1 rows
    yield "lane_of_23_rows", [[8] * 22 + [5], [], [3], [7] * 23,
                              [8] * 23, [], [4, 8]], T, 80, 6, 180
    yield "rows_1_to_9_cap_not_a_multiple", \
        _random_rows(rng, 10, T, 1, 9, 0.1, 0.1) + [[]], T, 64, 10, 61


_FORMS = {"rotate": 1 << 30, "window": 0}  # _WINDOW_MIN_ROWS that forces it


@pytest.mark.parametrize("form", list(_FORMS))
@pytest.mark.parametrize("chunk", [None, 4], ids=["one_chunk", "chunks_of_4"])
@pytest.mark.parametrize(
    "rows_of,T,m_pad,pad_slot,n_cap",
    [pytest.param(*c[1:], id=c[0]) for c in _merge_cases()])
def test_merge_matches_numpy_reference(rows_of, T, m_pad, pad_slot, n_cap,
                                       chunk, form, monkeypatch):
    """_merge_device moves whole rows; the reference moves cells.  Bit
    for bit: times, the values' bit patterns, the fill, the counts —
    also when the lanes go in chunks, the last one overlapping, and in
    either form (merge_form), whichever the shapes would take."""
    from m3_tpu.models import query_pipeline as qp

    if chunk:
        monkeypatch.setattr(qp, "_MERGE_LANES", chunk)
    monkeypatch.setattr(qp, "_WINDOW_MIN_ROWS", _FORMS[form])
    assert qp.merge_form(n_cap, T) == form
    n_lanes = len(rows_of)
    ts, vs, valid, slots = _merge_layout(rows_of, T, m_pad, pad_slot, 3)
    got_t, got_v, got_c = jax.jit(      # a new function: a new trace
        lambda *a: qp._merge_device(*a, n_lanes, n_cap))(
        jnp.asarray(ts), jnp.asarray(vs), jnp.asarray(valid),
        jnp.asarray(slots))
    want_t, want_v, want_c = _merge_reference(ts, vs, valid, slots,
                                              n_lanes, n_cap)
    np.testing.assert_array_equal(np.asarray(got_t), want_t)
    np.testing.assert_array_equal(np.asarray(got_v).view(np.uint64),
                                  want_v.view(np.uint64))
    np.testing.assert_array_equal(np.asarray(got_c), want_c)


@pytest.mark.parametrize(
    "T,n_cap", [pytest.param(c[2], c[5], id=c[0])
                for c in _long_lane_cases()])
def test_long_lane_cases_take_the_window_form_by_their_shapes(T, n_cap):
    from m3_tpu.models.query_pipeline import merge_form

    assert merge_form(n_cap, T) == "window"


def _parents_merge_device(ts, vs, valid, slots, n_lanes, n_cap, order=None):
    """_merge_device as it stood before merge_form (PR 45's tree), kept
    to compare against: every row rotated over the lane's whole width."""
    from m3_tpu.models import query_pipeline as qp
    from m3_tpu.ops.bitstream import I32

    M, T = ts.shape
    B = min(n_lanes, qp._MERGE_LANES)
    row_counts = valid.sum(axis=1, dtype=I32)
    if order is not None:
        slots, row_counts = slots[order], row_counts[order]
    first = jnp.searchsorted(slots, jnp.arange(n_lanes + 1), side="left",
                             method="scan_unrolled")
    used = jnp.max(jnp.where(row_counts > 0, jnp.arange(1, M + 1), 0))
    n_rows = jnp.minimum(first[1:], used) - first[:-1]
    col = jnp.arange(n_cap, dtype=I32)
    fit = ((0, 0), (0, max(n_cap - T, 0)))

    def place(x, row, off):
        x = jnp.pad(x[:, :n_cap].at[row].get(mode="promise_in_bounds"), fit)
        for b in range((n_cap - 1).bit_length()):
            x = jnp.where((off >> b & 1)[:, None] == 1,
                          jnp.roll(x, 1 << b, axis=1), x)
        return x

    def chunk(c, outs):
        lo = jnp.minimum(c * B, n_lanes - B)
        first_c = jax.lax.dynamic_slice_in_dim(first, lo, B)
        n_rows_c = jax.lax.dynamic_slice_in_dim(n_rows, lo, B)

        def body(k, carry):
            out_t, out_v, counts = carry
            row = jnp.minimum(first_c + k, M - 1)
            cnt = jnp.where(k < n_rows_c, row_counts[row], 0)
            if order is not None:
                row = order[row]
            off = jnp.minimum(counts, n_cap)
            take = (col >= off[:, None]) & (col < (off + cnt)[:, None])
            return (jnp.where(take, place(ts, row, off), out_t),
                    jnp.where(take, place(vs, row, off), out_v),
                    counts + cnt)

        done = jax.lax.fori_loop(0, jnp.max(n_rows_c), body, (
            jnp.full((B, n_cap), qp._INF, dtype=jnp.int64),
            jnp.full((B, n_cap), jnp.nan, dtype=vs.dtype),
            jnp.zeros((B,), I32)))
        return tuple(jax.lax.dynamic_update_slice_in_dim(o, d, lo, 0)
                     for o, d in zip(outs, done))

    return jax.lax.fori_loop(0, qp.lane_chunks(n_lanes), chunk, (
        jnp.empty((n_lanes, n_cap), jnp.int64),
        jnp.empty((n_lanes, n_cap), vs.dtype),
        jnp.empty((n_lanes,), I32)))


@pytest.mark.parametrize("n_lanes,T,rows,n_cap,chunk", [
    (40, 128, 5, 128 * 5 + 37, None),   # a partial last block
    (21, 96, 22, 96 * 21, 8),           # 22 rows overflow 21 blocks
    (9, 64, 6, 64 * 4, 4),              # the crossing itself, overflowing
])
def test_window_merge_equals_the_parents_rotation(n_lanes, T, rows, n_cap,
                                                  chunk, monkeypatch):
    """The long lane's merge against the parent's, at rows wide enough
    for the window's halves to be whole tiles: bit for bit, the counts
    past n_cap included."""
    from m3_tpu.models import query_pipeline as qp

    if chunk:
        monkeypatch.setattr(qp, "_MERGE_LANES", chunk)
    assert qp.merge_form(n_cap, T) == "window"
    rng = np.random.default_rng(n_lanes)
    rows_of = _random_rows(rng, n_lanes - 1, T, 0, rows, 0.1, 0.1) + [[]]
    rows_of[0] = [T] * rows
    m_pad = sum(map(len, rows_of)) + 5
    args = [jnp.asarray(a) for a in _merge_layout(
        rows_of, T, m_pad, n_lanes - 1, 11)]
    got = jax.jit(lambda *a: qp._merge_device(*a, n_lanes, n_cap))(*args)
    want = jax.jit(lambda *a: _parents_merge_device(*a, n_lanes, n_cap))(
        *args)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(
            np.asarray(g).view(np.uint8), np.asarray(w).view(np.uint8))
    assert int(np.asarray(got[2]).max()) > n_cap - T


@pytest.mark.parametrize("n_cap,n_dp,form", [
    (1536, 768, "rotate"),     # dash-sealed, fanout-fleet
    (1920, 768, "rotate"),     # dash-live: two sealed rows and an open one
    (2048, 1024, "rotate"),    # dash-topk, the fused planner's pow2 buckets
    (15872, 768, "window"),    # dash-2d: 22 rows a lane
    (3071, 768, "rotate"), (3072, 768, "window"),
    (1536, None, "rotate"),    # a row as wide as the lane
])
def test_merge_form_at_the_cells_shapes(n_cap, n_dp, form):
    """Which form a call takes is read off its static buckets: the four
    4 h cells keep the rotation over the lane, the two-day panel takes
    the window (PERF.md, PR 46)."""
    from m3_tpu.models.query_pipeline import merge_form

    assert merge_form(n_cap, n_dp) == form


def test_accepted_cells_program_lowers_to_what_it_lowered_to(monkeypatch):
    """`device_grouped_pipeline` at dash-sealed's static shapes, lowered
    with the parent's merge in _merge_device's place and as it stands:
    the same text (metadata apart, which as_text leaves out), so the
    cells on "rotate" run the program they ran."""
    from m3_tpu.models import query_pipeline as qp

    args, kw = _grouped_program_at("dash-sealed")
    assert qp.merge_form(kw["n_cap"], kw["n_dp"]) == "rotate"
    fn = qp.device_grouped_pipeline.__wrapped__     # the jitted function
    fn.clear_cache()
    now = fn.lower(*args, **kw).as_text()
    monkeypatch.setattr(qp, "_merge_device", _parents_merge_device)
    fn.clear_cache()                                # a new trace
    before = fn.lower(*args, **kw).as_text()
    fn.clear_cache()
    assert "stablehlo.while" in now and now == before


@pytest.mark.parametrize("n_cap,form", [(24, "rotate"), (43, "window")])
def test_merge_two_tiers_matches_numpy_reference(n_cap, form):
    """Two tiers: the coarse rows come first in a slot and the cut keeps
    a prefix of each, so the rows still land as contiguous runs and the
    lane stays ascending."""
    from m3_tpu.models.query_pipeline import (_merge_device, _tier_cut,
                                              merge_form)

    n_lanes, T = 5, 10
    assert merge_form(n_cap, T) == form
    slots, tiers, rows_t = [], [], []
    for lane in range(n_lanes - 1):
        # coarse block (60 s apart) then two fine ones (10 s) that
        # start inside the coarse one's span
        fine0 = T0 + (3 + lane) * 60 * SEC
        rows_t += [T0 + (np.arange(T) + 1) * 60 * SEC,
                   fine0 + np.arange(T) * 10 * SEC,
                   fine0 + (T + np.arange(T)) * 10 * SEC]
        slots += [lane] * 3
        tiers += [1, 0, 0]
    m_pad = 16
    pad = m_pad - len(slots)
    ts = np.stack(rows_t + [np.zeros(T, np.int64)] * pad).astype(np.int64)
    vs = np.random.default_rng(5).random((m_pad, T))
    valid = np.ones((m_pad, T), bool)
    valid[len(slots):] = False
    slots = np.asarray(slots + [n_lanes - 1] * pad, np.int64)
    tiers = np.asarray(tiers + [0] * pad, np.int64)

    @jax.jit
    def run(ts, vs, valid, slots, tiers):
        cut = _tier_cut(ts, valid, slots, tiers, n_lanes, 2)
        return cut, _merge_device(ts, vs, cut, slots, n_lanes, n_cap)

    cut, (got_t, got_v, got_c) = run(*map(jnp.asarray,
                                          (ts, vs, valid, slots, tiers)))
    cut = np.asarray(cut)
    assert (cut[0::3][:n_lanes - 1].sum(axis=1) < T).all(), "cut is idle"
    want_t, want_v, want_c = _merge_reference(ts, vs, cut, slots,
                                              n_lanes, n_cap)
    np.testing.assert_array_equal(np.asarray(got_t), want_t)
    np.testing.assert_array_equal(np.asarray(got_v).view(np.uint64),
                                  want_v.view(np.uint64))
    np.testing.assert_array_equal(np.asarray(got_c), want_c)
    assert (np.diff(np.asarray(got_t), axis=1) >= 0).all()


@pytest.mark.parametrize("case", ["duplicates", "inf_padding",
                                  "padded_steps", "steps_on_samples"])
def test_window_bounds_match_searchsorted(case):
    """left / right are np.searchsorted(side="right") of the window's
    exclusive start and its end on each lane."""
    from m3_tpu.models.query_pipeline import _window_bounds_device

    rng = np.random.default_rng(11)
    L, N, S = 7, 40, 9
    range_nanos = 90 * SEC
    gaps = rng.integers(0 if case == "duplicates" else 1, 4, (L, N))
    times = T0 + np.cumsum(gaps, axis=1) * 10 * SEC
    if case == "inf_padding":
        fill = np.arange(N)[None, :] >= rng.integers(0, N + 1, (L, 1))
        fill[0] = True                      # an empty lane
        fill[1] = False                     # a full one
        times = np.where(fill, _INF, times)
    steps = T0 + (np.arange(S, dtype=np.int64) + 2) * 120 * SEC
    if case == "padded_steps":              # the engine repeats the last
        steps[S - 3:] = steps[S - 4]
    if case == "steps_on_samples":          # both window edges inclusive
        steps = times[2, 10:10 + S].copy()
        range_nanos = int(steps[3] - times[2, 4])
    starts, left, right, _ = jax.jit(_window_bounds_device)(
        jnp.asarray(times), jnp.asarray(steps), jnp.int64(range_nanos))
    np.testing.assert_array_equal(np.asarray(starts),
                                  steps - range_nanos - 1)
    for lane in range(L):
        np.testing.assert_array_equal(
            np.asarray(left)[lane], np.searchsorted(
                times[lane], steps - range_nanos - 1, side="right"))
        np.testing.assert_array_equal(
            np.asarray(right)[lane],
            np.searchsorted(times[lane], steps, side="right"))


@pytest.fixture
def small_band(monkeypatch):
    """The band's constants at a test's size: blocks of 8 steps, tiles
    of 8 cells, 8 cells of slack; at [*, 256] x 40 a span of 72."""
    from m3_tpu.models import query_pipeline as qp

    for name in ("_BAND_STEPS", "_BAND_TILE", "_BAND_SLACK"):
        monkeypatch.setattr(qp, name, 8)
    assert qp.band_width(256, 40) == 72
    return qp


def _bounds_and_windows(qp, times, steps, range_nanos):
    """-> the bounds as numpy and [the lane chunks searched at the full
    width, all of them]."""
    starts, left, right, full = jax.jit(qp._window_bounds_device)(
        jnp.asarray(times), jnp.asarray(steps), jnp.int64(range_nanos))
    return (np.asarray(starts), np.asarray(left), np.asarray(right),
            [int(full), 1])


def _band_case(case, seed=13):
    """-> (times [7, 256], steps [40], range_nanos, the lane chunks at
    the full width) of one batch that holds `case`: lanes at a 10 s
    cadence, steps 20 s apart (a block of 8 reaches 16 samples), a
    range of 50 s."""
    rng = np.random.default_rng(seed)
    L, N, S = 7, 256, 40
    range_nanos, full = 50 * SEC, 0
    gaps = np.full((L, N), 10)
    gaps[:, 0] = rng.integers(0, 10, L)
    if case == "duplicates":
        gaps = rng.integers(0, 3, (L, N)) * 10
    times = T0 + np.cumsum(gaps, axis=1) * SEC
    steps = T0 + (np.arange(S, dtype=np.int64) * 20 + 600) * SEC
    if case == "inf_padding":
        fill = np.arange(N)[None, :] >= rng.integers(0, N + 1, (L, 1))
        fill[0], fill[1] = True, False      # an empty lane, a full one
        times = np.where(fill, _INF, times)
    if case == "padded_steps":              # the engine repeats the last
        steps[S - 11:] = steps[S - 12]
    if case == "steps_on_samples":          # both window edges inclusive
        steps = times[2, 70:70 + 2 * S:2].copy()
        range_nanos = int(steps[3] - times[2, 72])
    if case == "late_lane":                 # began inside the range
        times[3] = np.where(np.arange(N) < 150, times[3] + 900 * SEC, _INF)
    if case == "ended_lane":                # before the last step block
        times[4, 100:] = _INF
        assert times[4, 99] < steps[-8] - range_nanos
    if case == "skewed_lanes":              # by more than a span's width
        times[1] = np.where(np.arange(N) < 130, times[1] + 1200 * SEC, _INF)
        times[5] -= 1100 * SEC
        assert abs(np.searchsorted(times[1], steps[-1])
                   - np.searchsorted(times[5], steps[-1])) > 72
    if case == "burst_lane":                # 1 s apart: 160 samples a block
        times[2] = T0 + (600 + np.arange(N)) * SEC
        full = 1
    if case == "long_range":                # longer than a block of steps
        range_nanos = 200 * SEC
        assert range_nanos > steps[7] - steps[0]
    return times, steps, range_nanos, full


@pytest.mark.parametrize("case", [
    "duplicates", "inf_padding", "padded_steps", "steps_on_samples",
    "late_lane", "ended_lane", "skewed_lanes", "burst_lane", "long_range"])
def test_window_bounds_through_the_band_match_searchsorted(case, small_band):
    """The bounds counted inside a (lane, step block)'s span are
    np.searchsorted's, lane by lane: a lane's span is its own (a lane
    that began late, one that ended early and lanes far apart share a
    chunk in the band), and a lane that does not fit its span sends the
    chunk to the full width, which says so."""
    times, steps, range_nanos, full = _band_case(case)
    starts, left, right, windows = _bounds_and_windows(
        small_band, times, steps, range_nanos)
    assert windows == [full, 1]
    np.testing.assert_array_equal(starts, steps - range_nanos - 1)
    for lane in range(len(times)):
        np.testing.assert_array_equal(left[lane], np.searchsorted(
            times[lane], steps - range_nanos - 1, side="right"))
        np.testing.assert_array_equal(right[lane], np.searchsorted(
            times[lane], steps, side="right"))


def test_window_bounds_at_a_cells_width_take_the_band():
    """The constants as they stand, at dash-sealed's lane width and
    steps: a regular fleet, a third of it started late, stays in the
    band; one lane scraped every second sends its chunk to the full
    width; either way np.searchsorted's integers."""
    from m3_tpu.models import query_pipeline as qp

    L, N, S = 8, 1536, 256
    assert qp.band_width(N, S) == 640
    rng = np.random.default_rng(17)
    times = T0 + (np.arange(N)[None, :] * 10 + rng.integers(0, 9, (L, 1))) * SEC
    times = np.where(np.arange(N) < 1470, times, _INF)
    times[2::3] = np.where(np.arange(N) < 900, times[2::3] + 5700 * SEC, _INF)
    steps = T0 + (300 + np.arange(S, dtype=np.int64) * 60) * SEC
    for burst, full in ((False, 0), (True, 1)):
        if burst:
            times[1] = T0 + (3000 + np.arange(N)) * SEC
        _, left, right, windows = _bounds_and_windows(qp, times, steps,
                                                      300 * SEC)
        assert windows == [full, 1]
        for lane in range(L):
            np.testing.assert_array_equal(left[lane], np.searchsorted(
                times[lane], steps - 300 * SEC - 1, side="right"))
            np.testing.assert_array_equal(right[lane], np.searchsorted(
                times[lane], steps, side="right"))


@pytest.mark.parametrize("n_cap,n_steps,width", [
    (1536, 256, 640), (1920, 256, 768), (2048, 256, 768),
    (15872, 1344, 1024), (1536, 1344, 384), (1536, 8, None),
    (40, 9, None), (32, 4, None), (1500, 256, None)],
    ids=["dash-sealed_fanout-fleet", "dash-live", "dash-topk_dash-p99",
         "dash-2d", "a_short_step", "few_steps", "a_stage_test", "tiny",
         "no_whole_tiles"])
def test_band_width_at_the_cells_shapes(n_cap, n_steps, width):
    """The span a block of steps is searched in, from the static
    buckets alone: whole tiles of 128, under half the lane or the full
    width is the only body (the tests' shapes: the parent's program)."""
    from m3_tpu.models.query_pipeline import band_width

    assert band_width(n_cap, n_steps) == width


def _window_case(case, N, seed=23):
    """(times, values, steps, range_nanos) of one lane batch that holds
    `case` at N samples a lane: what _rate_device reads at a window's
    ends, at its worst."""
    rng = np.random.default_rng(seed)
    L, S = 6, 9
    range_nanos = 90 * SEC
    gaps = rng.integers(0 if case == "duplicates" else 1, 4, (L, N))
    times = T0 + np.cumsum(gaps, axis=1) * 10 * SEC
    values = np.cumsum(rng.integers(0, 50, (L, N)), axis=1).astype(float)
    steps = times[0, -1] - (S - 1 - np.arange(S, dtype=np.int64)) * 70 * SEC
    if case == "padding":                 # _INF / NaN past a lane's count
        fill = np.arange(N)[None, :] >= rng.integers(0, N + 1, (L, 1))
        fill[0], fill[1], fill[2, 1:] = True, False, True
        times = np.where(fill, _INF, times)
        values = np.where(fill, np.nan, values)
    if case == "nan_inf_samples":         # real samples, not padding
        odd = np.array([np.nan, np.inf, -np.inf, -0.0, 1e300, -1e300])
        values = np.where(rng.random((L, N)) < 0.5,
                          odd[rng.integers(0, len(odd), (L, N))], values)
    if case == "left_is_n":               # windows that open past the end
        steps = times.max() + (np.arange(S, dtype=np.int64) + 2) * 100 * SEC
    if case == "right_is_0":              # steps before the first sample
        steps = T0 - (np.arange(S, dtype=np.int64)[::-1] + 1) * 100 * SEC
    if case == "empty_and_one_sample":    # 0 or 1 sample a window
        range_nanos = 5 * SEC
    if case == "counter_resets":
        for lane in range(L):
            cut = rng.integers(1, N)
            values[lane, cut:] -= values[lane, cut] - 1.0
    if case == "steps_on_samples":        # both window edges inclusive
        steps = times[2, N - S:].copy()
        range_nanos = int(steps[3] - times[2, N - S - 2])
    return times, values, steps, range_nanos


_WINDOW_CASES = ["duplicates", "padding", "nan_inf_samples", "left_is_n",
                 "right_is_0", "empty_and_one_sample", "counter_resets",
                 "steps_on_samples"]


def _window_sizes():
    """A lane width on each side of the constant, by the form it takes."""
    from m3_tpu.models.query_pipeline import _SELECT_MAX_N
    return [pytest.param(40, id="select"),
            pytest.param(_SELECT_MAX_N, id="select_at_the_constant"),
            pytest.param(_SELECT_MAX_N + 8, id="gather")]


@pytest.mark.parametrize("N", _window_sizes())
@pytest.mark.parametrize("case", _WINDOW_CASES)
def test_take_at_is_take_along_axis(case, N):
    """_take_at_device hands back the very elements, in either form:
    nothing added, nothing rounded, no padding leaked, a NaN or an
    infinity that was picked still one."""
    from m3_tpu.models.query_pipeline import (_SELECT_MAX_N,
                                              _take_at_device,
                                              _window_bounds_device,
                                              window_form)

    times, values, steps, range_nanos = _window_case(case, N)
    assert window_form(N) == ("gather" if N > _SELECT_MAX_N else "select")
    _, left, right, _ = jax.jit(_window_bounds_device)(
        jnp.asarray(times), jnp.asarray(steps), jnp.int64(range_nanos))
    cum = np.cumsum(np.nan_to_num(values, posinf=7.0, neginf=-7.0) % 1e3,
                    axis=1)
    xs = (times, values, cum)
    idxs = (np.clip(np.asarray(left), 0, N - 1),
            np.clip(np.asarray(right) - 1, 0, N - 1))
    for idx, got in zip(idxs, jax.jit(_take_at_device)(
            tuple(map(jnp.asarray, xs)), tuple(map(jnp.asarray, idxs)))):
        assert len(got) == len(xs)
        for g, x in zip(got, xs):
            want = np.take_along_axis(x, idx, axis=1)
            assert np.asarray(g).dtype == x.dtype
            assert np.array_equal(np.asarray(g), want, equal_nan=True)
            assert np.array_equal(np.signbit(np.asarray(g)),
                                  np.signbit(want))
    if case == "left_is_n":
        assert (np.asarray(left) == N).all()
    if case == "right_is_0":
        assert (np.asarray(right) == 0).all()
    if case == "empty_and_one_sample":
        assert set(np.unique(np.asarray(right) - np.asarray(left))) <= {0, 1}


@pytest.mark.parametrize("fn", ["rate", "increase", "delta"])
@pytest.mark.parametrize("case", _WINDOW_CASES)
def test_rate_family_by_selection_equals_by_gather(case, fn, monkeypatch):
    """The windowed answer does not depend on the form of the reads:
    the same inputs through the selection and through the gathers (the
    program as it was before the selection) agree bit for bit, NaN for
    NaN."""
    from m3_tpu.models import query_pipeline as qp

    times, values, steps, range_nanos = _window_case(case, 48)
    args = (jnp.asarray(times), jnp.asarray(values), jnp.asarray(steps),
            jnp.int64(range_nanos))
    assert qp.window_form(48) == "select"
    selected = np.asarray(jax.jit(functools.partial(
        qp._temporal_eval, fn))(*args)[0])
    monkeypatch.setattr(qp, "_SELECT_MAX_N", 0)
    assert qp.window_form(48) == "gather"
    gathered = np.asarray(jax.jit(functools.partial(
        qp._temporal_eval, fn))(*args)[0])
    assert np.array_equal(selected, gathered, equal_nan=True)
    real = ~np.isnan(selected)          # a computed NaN's sign is no one's
    assert np.array_equal(np.signbit(selected[real]),
                          np.signbit(gathered[real]))
    if case in ("duplicates", "counter_resets", "steps_on_samples"):
        assert np.isfinite(selected).any()


def _rate_and_windows(qp, fn, args, band=True):
    """The windowed `fn` over `args` and what its stage said of its
    lane chunks: [those searched at the full width, all of them]."""
    out, windows = jax.jit(functools.partial(
        qp._temporal_eval, fn, band=band))(*args)
    return np.asarray(out), [int(n) for n in windows]


@pytest.mark.parametrize("fn", ["rate", "increase", "delta"])
@pytest.mark.parametrize("chunks", [1, 3], ids=["one_chunk", "chunked"])
@pytest.mark.parametrize("case", _WINDOW_CASES)
def test_rate_family_through_the_band_equals_the_full_width(
        case, chunks, fn, monkeypatch):
    """The band finds the same bounds and selects the same elements
    from fewer cells: rate / increase / delta through it and through
    the full-width body (the parent's program) agree bit for bit, NaN
    for NaN, in one chunk of lanes and in three; the windows' reads are
    selections either way."""
    from m3_tpu.models import query_pipeline as qp

    N = 96
    times, values, steps, range_nanos = _window_case(case, N)
    if chunks > 1:
        monkeypatch.setattr(qp, "_MERGE_LANES", 2)
        assert qp.lane_chunks(len(times)) == chunks
    monkeypatch.setattr(qp, "_BAND_STEPS", 3)
    monkeypatch.setattr(qp, "_BAND_TILE", 4)
    monkeypatch.setattr(qp, "_BAND_SLACK", 4)
    assert qp.band_width(N, len(steps)) == 40 and qp.window_form(N) == "select"
    args = (jnp.asarray(times), jnp.asarray(values), jnp.asarray(steps),
            jnp.int64(range_nanos))
    banded, windows = _rate_and_windows(qp, fn, args)
    assert windows == [0, chunks], "the band was not taken"
    full, windows = _rate_and_windows(qp, fn, args, band=False)
    assert windows == [chunks, chunks]
    assert np.array_equal(banded, full, equal_nan=True)
    real = ~np.isnan(banded)            # a computed NaN's sign is no one's
    assert np.array_equal(banded[real].view(np.uint64),
                          full[real].view(np.uint64))
    if case in ("duplicates", "counter_resets", "steps_on_samples"):
        assert np.isfinite(banded).any()


def test_a_burst_lane_sends_its_chunk_alone_to_the_full_width(monkeypatch):
    """Three chunks of lanes, a lane scraped ten times as often in the
    second: that chunk is served by the full-width body and the others
    by the band, the program says 1 of 3, and every lane's answer is
    the full width's bit for bit."""
    from m3_tpu.models import query_pipeline as qp

    rng = np.random.default_rng(31)
    L, N, S = 24, 256, 40
    times = T0 + (np.arange(N)[None, :] * 10 + rng.integers(0, 9, (L, 1))) * SEC
    times[11] = T0 + (600 + np.arange(N)) * SEC
    values = np.cumsum(rng.integers(0, 50, (L, N)), axis=1).astype(float)
    values[::5, 100:] -= values[::5, 100:101]     # counter resets
    steps = T0 + (np.arange(S, dtype=np.int64) * 20 + 600) * SEC
    args = (jnp.asarray(times), jnp.asarray(values), jnp.asarray(steps),
            jnp.int64(50 * SEC))
    for name in ("_BAND_STEPS", "_BAND_TILE", "_BAND_SLACK", "_MERGE_LANES"):
        monkeypatch.setattr(qp, name, 8)
    banded, windows = _rate_and_windows(qp, "rate", args)
    assert windows == [1, 3]
    full, windows = _rate_and_windows(qp, "rate", args, band=False)
    assert windows == [3, 3]
    assert np.array_equal(banded.view(np.uint64), full.view(np.uint64))
    assert np.isfinite(banded).any(axis=1).all()


@pytest.mark.parametrize("fn", ["rate", "increase", "delta"])
@pytest.mark.parametrize("lanes,at_a_time", [(20, 8), (16, 8), (9, 4)])
def test_rate_lanes_at_a_time_equals_one_chunk(fn, lanes, at_a_time,
                                               monkeypatch):
    """Past _MERGE_LANES lanes the windowed rate goes a chunk of lanes
    at a time (even chunks; the last overlaps its neighbour where they
    do not divide): the same answer, lane for lane, as all at once."""
    from m3_tpu.models import query_pipeline as qp

    rng = np.random.default_rng(29)
    N, S = 48, 9
    times = T0 + np.cumsum(rng.integers(1, 4, (lanes, N)), axis=1) * 10 * SEC
    values = np.cumsum(rng.integers(0, 50, (lanes, N)), axis=1).astype(float)
    values[::3, 20:] -= values[::3, 20:21]        # counter resets
    fill = np.arange(N)[None, :] >= rng.integers(N // 2, N + 1, (lanes, 1))
    times, values = np.where(fill, _INF, times), np.where(fill, np.nan, values)
    steps = T0 + (np.arange(S, dtype=np.int64) + 3) * 100 * SEC
    args = (jnp.asarray(times), jnp.asarray(values), jnp.asarray(steps),
            jnp.int64(120 * SEC))
    whole = np.asarray(jax.jit(functools.partial(
        qp._temporal_eval, fn))(*args)[0])
    monkeypatch.setattr(qp, "_MERGE_LANES", at_a_time)
    lowered = jax.jit(functools.partial(qp._temporal_eval, fn)).lower(*args)
    assert "stablehlo.while" in lowered.as_text()
    chunked = np.asarray(lowered.compile()(*args)[0])
    assert np.array_equal(whole, chunked, equal_nan=True)
    assert np.isfinite(whole).any(axis=1).all()


def _walk_eqns(jaxpr, scope=""):
    """(equation, named-scope path) of every equation, inner jits,
    loops and branches included."""
    for eqn in jaxpr.eqns:
        path = f"{scope}/{eqn.source_info.name_stack}"
        yield eqn, path
        stack = list(eqn.params.values())
        while stack:
            v = stack.pop()
            if isinstance(v, (tuple, list)):
                stack.extend(v)
            elif hasattr(v, "eqns") or hasattr(v, "jaxpr"):
                yield from _walk_eqns(getattr(v, "jaxpr", v), path)


def _walk_jaxpr(jaxpr, scope=""):
    """(primitive, named-scope path) of every equation."""
    for eqn, path in _walk_eqns(jaxpr, scope):
        yield eqn.primitive.name, path


def _stage_addressing(jaxpr):
    """What the windowed stage of a program addresses by index, having
    checked its shape: -> (its gathers of ELEMENTS, every slice one
    cell: what this chip runs a cell at a time; its conds).  A gather
    whose slices are wider (contiguous spans) would be another thing,
    and the stage holds none: a band's spans are selected as whole
    tiles (_spans_device).  A cond is a band's: its two branches both
    count bounds, one inside the spans and one over the lane (the
    parent's body, kept under it for the lanes that do not fit)."""
    stage = [(e, s) for e, s in _walk_eqns(jaxpr) if "m3.temporal" in s]
    gathers = [(e, s) for e, s in stage if e.primitive.name == "gather"]
    assert all(set(e.params["slice_sizes"]) == {1} for e, _ in gathers)
    conds = [e for e, _ in stage if e.primitive.name == "cond"]
    for cond in conds:
        assert len(cond.params["branches"]) == 2
        widths = []
        for branch in cond.params["branches"]:
            ops = list(_walk_eqns(branch.jaxpr))
            counts = [e for e, s in ops if e.primitive.name == "reduce_sum"
                      and "bounds" in s]
            assert len(counts) == 2, "a branch counts both bounds"
            # the sample axis a count reduces over: a span's, the lane's
            widths.append(counts[0].invars[0].aval.shape[-1])
            assert not [e for e, _ in ops
                        if e.primitive.name in ("while", "scan", "cond")]
        assert min(widths) * 2 <= max(widths), widths
    return gathers, conds


# M, W, L, S, n_cap, n_dp, n_groups of the grouped program: the size the
# stage tests run at, and the benchmark cells' (lowered, never run)
_STRUCTURE_SHAPES = {
    "tiny": (16, 8, 8, 4, 32, 16, 4),
    "dash-sealed": (1024, 256, 512, 256, 1536, 768, 16),
    "fanout-fleet": (25024, 256, 12544, 256, 1536, 768, 32),
    # 22 rows a lane and the windowed stage's gather form (PR 45)
    "dash-2d": (11008, 256, 512, 1344, 15872, 768, 16),
}


def _grouped_program_at(shape):
    """-> device_grouped_pipeline's arguments, as shapes, and keywords
    at one of _STRUCTURE_SHAPES."""
    M, W, L, S, n_cap, n_dp, n_groups = _STRUCTURE_SHAPES[shape]
    sds = jax.ShapeDtypeStruct
    args = (sds((M, W), np.uint32), sds((M,), np.int32),
            sds((M,), np.int64), sds((S,), np.int64), sds((L,), np.int64))
    return args, dict(n_lanes=L, n_groups=n_groups, n_cap=n_cap, n_dp=n_dp,
                      range_nanos=jnp.int64(300 * SEC))


def _decode_loops(ops, n_dp, n_words):
    """The loops the decode stage holds at this bucket, having checked
    what is under `m3.decode`: the first record is decoded before any
    loop; a row no longer than the word window goes through ONE loop of
    steps; a longer one through the loop over refills, a window's steps
    inside it, and the steps left over where n_dp does not divide.  No
    gather and no scatter: the words a step reads are picked by a
    masked reduce, a window's blocks by ONE more under `refill`."""
    from m3_tpu.ops.m3tsz_decode import WIN_STEPS, WIN_WORDS, decode_refills

    decode = [(p, s) for p, s in ops if "m3.decode" in s]
    assert not [(p, s) for p, s in decode
                if p == "gather" or p.startswith("scatter")]
    refills = decode_refills(n_dp + 1, n_words)
    assert (refills > 0) == (n_words > WIN_WORDS)
    left = n_dp % WIN_STEPS > 0
    want = 2 + left if refills else 1
    assert [p for p, _ in decode if p in ("while", "scan")] == ["scan"] * want
    refill = [p for p, s in decode if "refill" in s]
    assert refill.count("reduce") == (1 + left if refills else 0)
    assert not set(refill) & {"gather", "while", "scan", "dynamic_slice"}
    return want


@pytest.mark.parametrize("shape", _STRUCTURE_SHAPES)
def test_grouped_program_has_no_per_element_addressing(shape):
    """The TPU compiler runs an element-indexed scatter or gather one
    element at a time (PERF.md, PR 26: 134 of the program's 213 ms were
    two scatters, 59 ms two binary searches).  The grouped program
    keeps its scatters to the [n_groups, S] reduction and its windowed
    stage free of gathers (PR 33: twelve were 16.0 of 21.2 ms) and of
    loops but the one over lane chunks, which a fan-out past
    _MERGE_LANES brings; the decode scan reads a per-row word window
    past WIN_WORDS words a row (PR 39) and fills it without an indexed
    access a row; a later edit that brings one back fails here, on the
    CPU, at a dashboard row's shape and at the whole fleet's."""
    from m3_tpu.models.query_pipeline import (band_width,
                                              device_grouped_pipeline,
                                              lane_chunks, window_form)

    M, W, L, S, n_cap, n_dp, n_groups = _STRUCTURE_SHAPES[shape]
    chunked = lane_chunks(L) > 1
    assert chunked == (shape == "fanout-fleet")
    gathers = window_form(n_cap) == "gather"
    assert gathers == (shape == "dash-2d")
    args, kw = _grouped_program_at(shape)
    sds = jax.ShapeDtypeStruct
    fn = device_grouped_pipeline.__wrapped__     # the jitted function
    ops = list(_walk_jaxpr(
        jax.make_jaxpr(functools.partial(fn, **kw))(*args).jaxpr))
    scatters = [(p, s) for p, s in ops if p.startswith("scatter")]
    assert scatters and all("m3.group" in s for _, s in scatters), scatters
    # the windowed stage reads its windows' ends by a selection up to
    # _SELECT_MAX_N samples a lane; past it (the two-day panel) by six
    # gathers, all of them under m3.temporal/take, and the bounds under
    # m3.temporal/bounds hold none
    jaxpr = jax.make_jaxpr(functools.partial(fn, **kw))(*args).jaxpr
    in_stage, conds = _stage_addressing(jaxpr)
    assert len(in_stage) == (6 if gathers else 0)
    assert all("m3.temporal/take" in s for _, s in in_stage), in_stage
    bounds = [p for p, s in ops if "m3.temporal" in s and "bounds" in s]
    assert "reduce_sum" in bounds and "gather" not in bounds
    # at a cell's shape the bounds are searched in a band of the lane,
    # the full-width body under the band's cond (once a chunk: inside
    # the chunk loop where there is one); the stage test's shape is too
    # narrow for a band and runs the parent's program
    banded = band_width(n_cap, S) is not None
    assert banded == (shape != "tiny")
    assert len(conds) == banded
    # the loops: the decode scan's (the refills and a window's steps at
    # the cells' 256 words a row, the steps alone at the tiny shape's 8);
    # the merge's lane -> first row search, its chunks and a chunk's
    # rows; the windowed stage's chunks
    decode_loops = _decode_loops(ops, n_dp, W)
    assert decode_loops == (1 if shape == "tiny" else 2)
    loops = sorted((s.strip("/").split("/")[0], p) for p, s in ops
                   if p in ("while", "scan"))
    assert loops == sorted(
        [("m3.decode", "scan")] * decode_loops
        + [("m3.merge", "scan"), ("m3.merge", "scan"), ("m3.merge", "while")]
        + [("m3.temporal", "scan")] * chunked), loops
    # and the lowered text agrees: no scatter but the reduction's, the
    # search unrolled, and the windowed stage alone lowers without a
    # loop until its lanes pass one chunk
    text = fn.lower(*args, **kw).as_text()
    assert text.count('"stablehlo.scatter"(') == len(scatters)
    assert text.count("stablehlo.while") == 2 + decode_loops + chunked
    from m3_tpu.models.query_pipeline import _temporal_eval
    stage = jax.jit(functools.partial(_temporal_eval, "rate")).lower(
        sds((L, n_cap), np.int64), sds((L, n_cap), np.float64), args[3],
        kw["range_nanos"])
    text = stage.as_text()
    assert text.count("stablehlo.while") == chunked
    assert text.count("stablehlo.case") == banded
    assert "stablehlo.scatter" not in text
    # (take_along_axis lowers to one function a dtype, called six times)
    assert ("stablehlo.gather" in text) == gathers
    # the reset prefix sum is the stage's own operation under the
    # stage's scope (inside a chunk loop's body names are relative to
    # the loop's), not jnp.cumsum's cached function, which has none
    # (past _PREFIX_MAX_N samples a lane it is a log-step scan, PR 45:
    # the TPU compiler takes minutes over one window that wide)
    from m3_tpu.models.query_pipeline import _PREFIX_MAX_N
    scanned = n_cap - 1 > _PREFIX_MAX_N
    assert scanned == (shape == "dash-2d")
    assert text.count("stablehlo.reduce_window") == (not scanned)
    assert "@cumsum" not in text
    named = stage.as_text(debug_info=True)
    assert ("m3.temporal/reduce_window_sum" in named) == (
        not chunked and not scanned)
    assert "m3.temporal/while/body" in named or not chunked


def _rate_at(n_cap, L=8, S=256):
    """The windowed rate alone and abstract arguments at n_cap samples
    a lane."""
    from m3_tpu.models.query_pipeline import _temporal_eval
    sds = jax.ShapeDtypeStruct
    return functools.partial(_temporal_eval, "rate"), (
        sds((L, n_cap), np.int64), sds((L, n_cap), np.float64),
        sds((S,), np.int64), jnp.int64(300 * SEC))


@pytest.mark.parametrize("n_cap", [1536, 1920],
                         ids=["dash-sealed", "dash-live"])
def test_rate_at_the_cells_width_has_no_per_element_addressing(n_cap):
    """The same disease, the gathers (PERF.md, PR 33: twelve of 1.33 ms
    were 16.0 of the program's 21.2 ms): at the benchmark cells' lane
    width the windowed rate lowers without a gather, and still without
    a loop or a scatter."""
    from m3_tpu.models.query_pipeline import band_width, window_form

    assert window_form(n_cap) == "select"
    rate, args = _rate_at(n_cap)
    assert band_width(n_cap, 256) is not None
    text = jax.jit(rate).lower(*args).as_text()
    for op in ("stablehlo.gather", "stablehlo.dynamic_gather",
               "stablehlo.while", "stablehlo.scatter"):
        assert op not in text, op
    # the band and, for the lanes that do not fit it, the parent's body
    assert text.count("stablehlo.case") == 1
    gathers, conds = _stage_addressing(jax.make_jaxpr(rate)(*args).jaxpr)
    assert not gathers and len(conds) == 1


def test_rate_above_the_constant_keeps_its_six_gathers():
    """Selection costs L x N x S and the gather L x S: past
    _SELECT_MAX_N samples a lane the six reads are gathers again."""
    from m3_tpu.models.query_pipeline import (_SELECT_MAX_N, band_width,
                                              window_form)

    n_cap = _SELECT_MAX_N + 128
    assert window_form(n_cap) == "gather"
    assert band_width(n_cap, 1344) == 896
    rate, args = _rate_at(n_cap, S=1344)
    jaxpr = jax.make_jaxpr(rate)(*args).jaxpr
    ops = [p for p, _ in _walk_jaxpr(jaxpr)]
    assert ops.count("gather") == 6
    assert not {"while", "scan", "scatter", "scatter-add"} & set(ops)
    # the reads stay gathers of elements, outside the cond under which
    # the long lane's BOUNDS alone are searched in the band
    gathers, conds = _stage_addressing(jaxpr)
    assert len(gathers) == 6 and len(conds) == 1
    assert all("take" in s and "cond" not in s for _, s in gathers)


def test_open_rows_keep_the_grouped_programs_structure():
    """Rows that arrive as arrays (open buffers) go through the same
    merge: still no scatter outside m3.group and no loop or gather in
    the windowed stage, the rows are laid under m3.open, and a call without
    them lowers to the program it was before they existed."""
    from m3_tpu.models.query_pipeline import device_grouped_pipeline

    M, W, L, S, R, T = 16, 8, 8, 4, 8, 16
    sds = jax.ShapeDtypeStruct
    args = (sds((M, W), np.uint32), sds((M,), np.int32),
            sds((M,), np.int64), sds((S,), np.int64), sds((L,), np.int64))
    kw = dict(n_lanes=L, n_groups=4, n_cap=32, n_dp=T,
              range_nanos=jnp.int64(300 * SEC))
    open_rows = (sds((R, T), np.int64), sds((R, T), np.float64),
                 sds((R,), np.int32), sds((R,), np.int64),
                 sds((M + R,), np.int64))
    fn = device_grouped_pipeline.__wrapped__
    ops = list(_walk_jaxpr(jax.make_jaxpr(functools.partial(
        fn, **kw))(*args, open_rows=open_rows).jaxpr))
    scatters = [(p, s) for p, s in ops if p.startswith("scatter")]
    assert scatters and all("m3.group" in s for _, s in scatters), scatters
    assert not [(p, s) for p, s in ops
                if p in ("while", "scan", "gather") and "m3.temporal" in s]
    assert any("m3.open" in s for _, s in ops)
    with_rows = fn.lower(*args, open_rows=open_rows, **kw).as_text()
    assert with_rows.count('"stablehlo.scatter"(') == len(scatters)
    without = fn.lower(*args, **kw).as_text()
    assert without == fn.lower(*args, open_rows=None, **kw).as_text()
    assert "m3.open" not in fn.lower(*args, **kw).as_text(debug_info=True)
    assert "m3.open" in fn.lower(*args, open_rows=open_rows, **kw).as_text(
        debug_info=True)


@pytest.mark.parametrize("n_cap,form", [(12, "rotate"), (18, "window")])
def test_open_rows_merge_behind_the_decoded_rows_of_their_lane(n_cap, form):
    """_merge_device reaches rows through `order`: decoded rows and
    open rows laid end to end land in each lane block-ascending."""
    from m3_tpu.models.query_pipeline import _merge_device, merge_form

    T, n_lanes = 4, 3
    assert merge_form(n_cap, T) == form
    # decoded rows: lane 0 twice, lane 2 once; open rows: lanes 0 and 1
    ts = np.array([[1, 2, 3, 0], [4, 5, 0, 0], [1, 2, 0, 0],
                   [6, 7, 8, 9], [3, 0, 0, 0]], dtype=np.int64)
    valid = np.array([[1, 1, 1, 0], [1, 1, 0, 0], [1, 1, 0, 0],
                      [1, 1, 1, 1], [1, 0, 0, 0]], dtype=bool)
    slots = np.array([0, 0, 2, 0, 1], dtype=np.int64)
    order = np.argsort(np.array([1, 3, 5, 4, 4]), kind="stable")
    times, values, counts = jax.jit(
        _merge_device, static_argnames=("n_lanes", "n_cap"))(
        jnp.asarray(ts), jnp.asarray(ts * 10.0), jnp.asarray(valid),
        jnp.asarray(slots), n_lanes=n_lanes, n_cap=n_cap,
        order=jnp.asarray(order))
    assert np.asarray(counts).tolist() == [9, 1, 2]
    assert np.asarray(times)[0, :9].tolist() == [1, 2, 3, 4, 5, 6, 7, 8, 9]
    assert np.asarray(values)[0, :9].tolist() == [
        10.0 * t for t in range(1, 10)]
    assert np.asarray(times)[1, 0] == 3 and np.asarray(times)[2, :2].tolist(
    ) == [1, 2]


@pytest.mark.parametrize("n_cap,form", [(16, "rotate"), (40, "window")])
def test_tier_cut_that_keeps_no_prefix_is_flagged(n_cap, form):
    """A coarse row out of time order can leave the cut a kept cell
    behind a dropped one.  The merge moves a row's first `count` cells,
    so such a row must flag (the engine falls back to the host tier),
    and a clean two-tier lane beside it must not."""
    from m3_tpu.models.query_pipeline import merge_form

    assert merge_form(n_cap, 8) == form

    def stream(t):
        enc = tsz.Encoder(T0)
        for ti in t:
            enc.encode(int(ti), 1.0)
        return enc.finalize()

    fine = T0 + (15 + np.arange(6)) * 10 * SEC           # from 150 s on
    streams = [stream(T0 + np.asarray([60, 150, 120]) * SEC),   # lane 0
               stream(fine),
               stream(T0 + np.asarray([60, 120, 180]) * SEC),   # lane 1
               stream(fine)]
    words, nbits = pack_streams(streams)
    _, err = device_temporal_pipeline(
        jnp.asarray(words), jnp.asarray(nbits),
        jnp.asarray(np.asarray([0, 0, 1, 1], dtype=np.int64)),
        jnp.asarray(T0 + np.asarray([240], dtype=np.int64) * SEC),
        n_lanes=2, n_cap=n_cap, fn="rate", range_nanos=300 * SEC, n_dp=8,
        tiers=jnp.asarray(np.asarray([1, 0, 1, 0], dtype=np.int64)),
        n_tiers=2)
    assert np.asarray(err).tolist() == [True, False, False, False]


def test_fused_topk_program_names_its_stages_at_the_cells_shape():
    """`topk(5, sum by (instance)(rate(..[5m])))` of one job as
    `dash-topk` runs it (lowered, never run): 1,024 streams x 512 words
    at the fused planner's pow2 buckets, 512 lanes x 2,048 samples, 256
    steps, 100 groups in 128 rows.  Each stage lowers under its own
    scope and the op-tree's `m3.expr` wraps none of them: a device
    trace is read by the first `m3.*` of an operation's name, so a scope
    around the whole interpreter would hide the others."""
    from m3_tpu.models.query_pipeline import device_expr_pipeline

    M, W, L, S, n_cap, n_dp, g_pad = 1024, 512, 512, 256, 2048, 1024, 128
    sds = jax.ShapeDtypeStruct
    leaf = {"words": sds((M, W), np.uint32), "nbits": sds((M,), np.int32),
            "slots": sds((M,), np.int64), "tiers": sds((M,), np.int64),
            "steps": sds((S,), np.int64), "rng": sds((), np.int64),
            "valid": sds((L,), np.bool_)}
    plan = ("topk", "topk", 5, 8, 2,
            ("agg", "sum", g_pad, 1,
             ("leaf", 0, 0, "words", "rate", L, n_cap, n_dp, 1, M, W, S,
              0.5, 0.5)))
    params = ((sds((), np.float64), sds((), np.float64)),
              (sds((L,), np.int64), sds((g_pad,), np.bool_),
               sds((), np.float64)),
              (sds((g_pad,), np.int64),))
    low = device_expr_pipeline.lower(plan, (leaf,), params,
                                     sds((S,), np.int64))
    text = low.as_text(debug_info=True)
    for scope in ("m3.decode", "m3.merge", "m3.temporal", "m3.group",
                  "m3.topk"):
        assert f"/{scope}/" in text, scope
    assert "m3.expr" not in text        # no node of this tree is its own
    for outer in ("m3.expr", "m3.topk", "m3.group"):
        for inner in ("m3.decode", "m3.merge", "m3.temporal"):
            assert f"{outer}/{inner}" not in text, (outer, inner)
    # the selection is two sorts over the group axis, under its scope
    ops = list(_walk_jaxpr(jax.make_jaxpr(functools.partial(
        device_expr_pipeline.__wrapped__, plan))((leaf,), params,
                                                 sds((S,), np.int64)).jaxpr))
    sorts = [s for p, s in ops if p == "sort"]
    assert len(sorts) == 2 and all("m3.topk" in s for s in sorts), sorts
    # 512 words a row: the decode scan reads the per-row word window,
    # 1,024 steps after the first record in 128 whole windows
    assert _decode_loops(ops, n_dp, W) == 2


def test_fused_hq_program_names_its_stages_at_the_cells_shape():
    """`histogram_quantile(0.99, rate(.._bucket{job=J}[5m]))` of one job
    as `dash-p99` runs it (lowered, never run): 4,096 streams x 256
    words at the fused planner's pow2 buckets, 2,048 lanes x 2,048
    samples in four chunks of `_MERGE_LANES`, 256 steps, 100 groups x
    12 buckets in [128, 16].  The quantile's own operations (the
    bucket-row gather, the running maximum over `le`, the
    interpolation's element gathers) lower under `m3.hq`, in place of
    `m3.expr` and around no stage of the leaf."""
    from m3_tpu.models.query_pipeline import (device_expr_pipeline,
                                              lane_chunks)

    M, W, L, S, n_cap, n_dp, g_pad, b_pad = (4096, 256, 2048, 256, 2048,
                                             1024, 128, 16)
    assert lane_chunks(L) == 4
    sds = jax.ShapeDtypeStruct
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
    low = device_expr_pipeline.lower(plan, (leaf,), params,
                                     sds((S,), np.int64))
    text = low.as_text(debug_info=True)
    for scope in ("m3.decode", "m3.merge", "m3.temporal", "m3.hq"):
        assert f"/{scope}/" in text, scope
    assert "m3.expr" not in text        # the tree's one node is the quantile
    for inner in ("m3.decode", "m3.merge", "m3.temporal"):
        assert f"m3.hq/{inner}" not in text, inner
    ops = list(_walk_jaxpr(jax.make_jaxpr(functools.partial(
        device_expr_pipeline.__wrapped__, plan))((leaf,), params,
                                                 sds((S,), np.int64)).jaxpr))
    # the bucket rows' gather and the interpolation's four element
    # gathers, and the running maximum over le: all the quantile's
    under = [p for p, s in ops if "m3.hq" in s]
    assert under.count("gather") == 5 and "cummax" in under, under
    assert not [s for p, s in ops if p == "cummax" and "m3.hq" not in s]


@pytest.mark.parametrize("n", [1, 2, 40, 1535, 4096, 4097, 5000, 15871])
def test_prefix_sum_by_scan_equals_the_one_window(n):
    """The reset prefix sum is one reduce_window up to _PREFIX_MAX_N
    samples a lane (what every 4 h cell lowers to) and a log-step scan
    above (the TPU compiler takes minutes over one window that wide,
    PR 45): the same sums either way, exactly, on counters'
    integer-valued resets."""
    from m3_tpu.models.query_pipeline import (_PREFIX_MAX_N,
                                              _prefix_sum_device)

    rng = np.random.default_rng(n)
    x = rng.integers(1, 10**9, (5, n)).astype(np.float64)
    x[rng.random((5, n)) < 0.9] = 0.0           # a reset now and then
    x[3] = 0.0                                  # a counter that never resets
    fn = jax.jit(_prefix_sum_device)
    got = np.asarray(fn(jnp.asarray(x)))
    assert got.dtype == x.dtype and got.shape == x.shape
    assert np.array_equal(got, np.cumsum(x, axis=1))
    text = fn.lower(jnp.asarray(x)).as_text()
    assert ("stablehlo.reduce_window" in text) == (n <= _PREFIX_MAX_N)
