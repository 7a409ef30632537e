#!/usr/bin/env python3
"""The controls of fanout-fleet.  Float32, the precision below the
float64 that the deployment states, planted in the served program
(test_control_dash.planted_float32), must fail `panel_max_rel_gap`; and
a run in which the program leaves its last chunk of lanes unmerged (the
panel then sums the other chunks' series alone) must not be correct.

    python benchmark/tests/test_control_fleet.py --planted --seeds 1 2
    python benchmark/tests/test_control_fleet.py --dropped --seeds 3

on the chip, at the cell's own size, prints each run's lines; the
`check_done` line has the gap of each query.  The pytest cases hold
both at the rehearsal size (600 series, 640 lanes, two chunks).
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


def drop_last_chunk(query_pipeline):
    """-> a `lane_chunks` for m3_tpu.models.query_pipeline that counts
    one round too few, so that the merge never reaches its last chunk
    of lanes."""
    real = query_pipeline.lane_chunks

    def short(n_lanes: int) -> int:
        return max(real(n_lanes) - 1, 1)

    return short


@pytest.fixture
def run_cell(run_cell, monkeypatch):
    """conftest's run_cell, with run.py's reading of the process's start
    taken anew: the kind's watchdog counts `open_within_s` from it, and
    a test session's process started long before its runs do."""
    import run as bench_run

    def go(*args, **kwargs):
        monkeypatch.setattr(bench_run, "T_PROCESS", time.perf_counter())
        return run_cell(*args, **kwargs)

    return go


def test_served_program_in_float32_is_not_correct(run_cell, monkeypatch):
    from m3_tpu.models import query_pipeline

    monkeypatch.setattr(query_pipeline, "_grouped_reduce",
                        planted_float32(query_pipeline))
    query_pipeline.device_grouped_pipeline.clear_cache()
    try:
        line = run_cell("fanout-fleet", 51)
        assert line["correct"] is False
        assert not line["checks"]["panel_max_rel_gap"]["ok"]
        assert line["checks"]["failed_requests"]["ok"]
    finally:
        query_pipeline.device_grouped_pipeline.clear_cache()


def test_sound_then_a_chunks_lanes_dropped(run_cell, monkeypatch):
    from m3_tpu.models import query_pipeline

    assert run_cell("fanout-fleet", 52)["correct"] is True
    monkeypatch.setattr(query_pipeline, "lane_chunks",
                        drop_last_chunk(query_pipeline))
    query_pipeline.device_grouped_pipeline.clear_cache()
    try:
        line = run_cell("fanout-fleet", 52)
        assert line["correct"] is False
        assert not line["checks"]["panel_max_rel_gap"]["ok"]
    finally:
        query_pipeline.device_grouped_pipeline.clear_cache()


def test_traced_run_reports_its_layers(run_cell):
    line = run_cell("fanout-fleet", 53, trace=1)
    assert line["correct"] is True
    # all but the roofline share, which needs a chip's peaks
    assert {"device_served_pct.fan", "fetch_ms.fan", "pack_ms.fan",
            "h2d_ms.fan", "device_ms.fan", "d2h_ms.fan",
            "device_queue_depth.fan", "program_ms.fan", "reply_ms.fan",
            "lanes_per_panel.fan", "program_hbm_peak_mb.fan",
            "panel_p95_ms.fan"} <= set(
        line["metrics"])
    assert line["metrics"]["device_served_pct.fan"]["value"] == 100.0
    assert line["metrics"]["lanes_per_panel.fan"]["value"] == 600.0
    assert line["metrics"]["program_hbm_peak_mb.fan"]["value"] > 0
    assert line["device"]["busy_s"] > 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--planted", action="store_true")
    ap.add_argument("--dropped", action="store_true")
    ap.add_argument("--seconds", default="8")
    args = ap.parse_args()
    sys.path.insert(0, str(HERE.parent.parent))
    import run as bench_run
    from m3_tpu.models import query_pipeline
    if args.planted:
        query_pipeline._grouped_reduce = planted_float32(query_pipeline)
    if args.dropped:
        query_pipeline.lane_chunks = drop_last_chunk(query_pipeline)
    for seed in args.seeds:
        sys.argv = ["run.py", "--workload", "fanout-fleet", "--seed",
                    str(seed), "--seconds", args.seconds, "--trace", "0"]
        bench_run.main()
