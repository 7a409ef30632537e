#!/usr/bin/env python3
"""The controls of dash-topk, each of which must fail `correct`.

Float32, the precision below the float64 that the deployment states,
planted in the served program (test_control_dash.planted_float32: the
fused program reduces its groups through the same `_grouped_reduce`),
must fail `panel_max_rel_gap`.  Two breaks of the selection itself,
planted on `masked_topk` as the fused interpreter calls it:

  last_step   the selection of the last step served at every step (a
              top-k computed once, as an instant query would): rows
              that were in the top k earlier and are not at the end go
              missing
  next_group  the (k+1)-th group served in place of the k-th

must fail the count and the rank of harness/reference_topk.compare.

    python benchmark/tests/test_control_topk.py --planted --seeds 1 2

on the chip, at the cell's own size, prints each run's lines.  The
pytest cases hold all three at the rehearsal size (10 instances a job,
of which six contest the fifth place at every step).
"""

from __future__ import annotations

import argparse
import pathlib
import sys
import time

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent))

from test_control_dash import planted_float32  # noqa: E402


def last_step_alone(query_pipeline):
    """-> a `masked_topk` that serves, at every step, the lanes the real
    one selects at the last."""
    import jax.numpy as jnp

    real = query_pipeline.masked_topk

    def broken(values, groups, n_groups, k, bottom):
        out, _present, rank = real(values, groups, n_groups, k, bottom)
        at_end = ~jnp.isnan(out[:, -1])
        return jnp.where(at_end[:, None], values, jnp.nan), at_end, rank

    return broken


def next_group(query_pipeline):
    """-> a `masked_topk` that serves the (k+1)-th lane of a cell in
    place of the k-th."""
    import jax.numpy as jnp

    real = query_pipeline.masked_topk

    def broken(values, groups, n_groups, k, bottom):
        wide, _, rank = real(values, groups, n_groups, k + 1, bottom)
        at_k, _, _ = real(values, groups, n_groups, k, bottom)
        under, _, _ = real(values, groups, n_groups, k - 1, bottom)
        out = jnp.where(jnp.isnan(at_k) | ~jnp.isnan(under), wide, jnp.nan)
        return out, (~jnp.isnan(out)).any(axis=1), rank

    return broken


BREAKS = {"last_step": last_step_alone, "next_group": next_group}


@pytest.fixture
def run_cell(run_cell, monkeypatch):
    """conftest's run_cell, with run.py's reading of the process's start
    taken anew: the kind's watchdog counts `open_within_s` from it."""
    import run as bench_run

    def go(*args, **kwargs):
        monkeypatch.setattr(bench_run, "T_PROCESS", time.perf_counter())
        return run_cell(*args, **kwargs)

    return go


def test_served_program_in_float32_is_not_correct(run_cell, monkeypatch):
    from m3_tpu.models import query_pipeline

    monkeypatch.setattr(query_pipeline, "_grouped_reduce",
                        planted_float32(query_pipeline))
    query_pipeline.device_expr_pipeline.clear_cache()
    try:
        line = run_cell("dash-topk", 61)
        assert line["correct"] is False
        assert not line["checks"]["panel_max_rel_gap"]["ok"]
        assert line["checks"]["failed_requests"]["ok"]
        assert line["checks"][
            "records_not_served_whole_by_the_fused_program"]["ok"]
    finally:
        query_pipeline.device_expr_pipeline.clear_cache()


@pytest.mark.parametrize("which", sorted(BREAKS))
def test_sound_then_the_selection_broken(run_cell, monkeypatch, which):
    from m3_tpu.models import query_pipeline

    assert run_cell("dash-topk", 62)["correct"] is True
    monkeypatch.setattr(query_pipeline, "masked_topk",
                        BREAKS[which](query_pipeline))
    query_pipeline.device_expr_pipeline.clear_cache()
    try:
        line = run_cell("dash-topk", 62)
        assert line["correct"] is False
        assert not line["checks"]["topk_points_misranked"]["ok"]
        # the values served are still the reference's
        assert line["checks"]["panel_max_rel_gap"]["ok"]
        assert line["checks"]["failed_requests"]["ok"]
    finally:
        query_pipeline.device_expr_pipeline.clear_cache()


def test_traced_run_reports_its_layers(run_cell):
    line = run_cell("dash-topk", 63, trace=1)
    assert line["correct"] is True
    # all but the roofline share, which needs a chip's peaks
    assert {"fused_served_pct.topk", "plan_ms.topk", "fetch_ms.topk",
            "pack_ms.topk", "d2h_ms.topk", "device_ms.topk",
            "device_queue_depth.topk", "program_ms.topk",
            "topk_share_pct.topk", "program_hbm_peak_mb.topk",
            "reply_ms.topk", "rows_per_reply.topk",
            "panel_median_ms.topk"} <= set(line["metrics"])
    assert line["metrics"]["fused_served_pct.topk"]["value"] == 100.0
    assert 5.0 <= line["metrics"]["rows_per_reply.topk"]["value"] <= 10.0
    assert line["metrics"]["plan_ms.topk"]["value"] > 0
    assert line["device"]["busy_s"] > 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--planted", action="store_true")
    ap.add_argument("--break", dest="which", choices=sorted(BREAKS))
    ap.add_argument("--seconds", default="8")
    args = ap.parse_args()
    sys.path.insert(0, str(HERE.parent.parent))
    import run as bench_run
    from m3_tpu.models import query_pipeline
    if args.planted:
        query_pipeline._grouped_reduce = planted_float32(query_pipeline)
    if args.which:
        query_pipeline.masked_topk = BREAKS[args.which](query_pipeline)
    for seed in args.seeds:
        sys.argv = ["run.py", "--workload", "dash-topk", "--seed",
                    str(seed), "--seconds", args.seconds, "--trace", "0"]
        bench_run.main()
