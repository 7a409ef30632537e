#!/usr/bin/env python3
"""The controls of dash-live.  Float32, the precision below the float64
that the deployment states, planted in the served program
(test_control_dash.planted_float32), must fail `panel_max_rel_gap`;
and a run whose open rows are dropped on their way to the program (the
panel then answers from the sealed blocks alone) must not be correct.

    python benchmark/tests/test_control_live.py --planted --seeds 1 2 3
    python benchmark/tests/test_control_live.py --dropped --seeds 4

on the chip, at the cell's own size, prints each run's lines.  The
pytest cases hold both at a size a test run can hold.
"""

from __future__ import annotations

import argparse
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent))

from test_control_dash import planted_float32  # noqa: E402


def drop_open_rows(engine_module):
    """-> a `_pack_array_rows` for m3_tpu.query.engine.Engine that hands
    the program no open rows."""
    def dropped(pk, parts, bucket):
        pk["open"] = None

    return staticmethod(dropped)


def test_served_program_in_float32_is_not_correct(run_cell, monkeypatch):
    from m3_tpu.models import query_pipeline

    monkeypatch.setattr(query_pipeline, "_grouped_reduce",
                        planted_float32(query_pipeline))
    query_pipeline.device_grouped_pipeline.clear_cache()
    try:
        line = run_cell("dash-live", 41)
        assert line["correct"] is False
        assert not line["checks"]["panel_max_rel_gap"]["ok"]
    finally:
        query_pipeline.device_grouped_pipeline.clear_cache()


def test_sound_then_open_rows_dropped(run_cell, monkeypatch):
    from m3_tpu.query import engine

    assert run_cell("dash-live", 42)["correct"] is True
    monkeypatch.setattr(engine.Engine, "_pack_array_rows",
                        drop_open_rows(engine))
    line = run_cell("dash-live", 42)
    assert line["correct"] is False
    assert not line["checks"]["panel_max_rel_gap"]["ok"]


def test_traced_run_reports_its_layers(run_cell):
    line = run_cell("dash-live", 43, trace=1)
    assert line["correct"] is True
    assert {"device_served_pct.live", "fetch_ms.live", "open_read_ms.live",
            "open_rows_pct.live", "pack_ms.live", "h2d_ms.live",
            "device_ms.live", "device_queue_depth.live",
            "write_ack_ms.live"} <= set(line["metrics"])
    assert line["metrics"]["device_served_pct.live"]["value"] == 100.0
    assert abs(line["metrics"]["open_rows_pct.live"]["value"]
               - 100.0 / 3) < 1e-9
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
    if args.planted:
        from m3_tpu.models import query_pipeline
        query_pipeline._grouped_reduce = planted_float32(query_pipeline)
    if args.dropped:
        from m3_tpu.query import engine
        engine.Engine._pack_array_rows = drop_open_rows(engine)
    for seed in args.seeds:
        sys.argv = ["run.py", "--workload", "dash-live", "--seed", str(seed),
                    "--seconds", args.seconds, "--trace", "0"]
        bench_run.main()
