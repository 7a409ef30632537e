#!/usr/bin/env python3
"""The controls of dash-2d.  Float32, the precision below the float64
that the deployment states, planted in the served program
(test_control_dash.planted_float32), must fail `panel_max_rel_gap` and
that check alone; a run whose program is handed every lane without its
oldest block (`drop_oldest_block`: the lane's first row arrives with no
bits, so the panel has no rate over the first two hours) must fail by
the gap too; and a run of the same cell at `hours` 4 (2 rows and 1,536
samples a lane: the selection, not the gathers) must fail
`records_not_on_the_gather_form` and that check alone.

    python benchmark/tests/test_control_2d.py --planted --seeds 1 2
    python benchmark/tests/test_control_2d.py --dropped --seeds 3
    python benchmark/tests/test_control_2d.py --seeds 4          # sound

on the chip, at the cell's own size, prints each run's lines; the
`check_done` line has the least gap of any job.  The pytest cases hold
the three at the rehearsal size (25 series of 22 blocks, 64 lanes of
15,872 samples: the gather form too); tests/test_benchmark_longrange_kind.py
runs them in tier-1.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
for path in (HERE, HERE.parent, HERE.parent.parent):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))

from test_control_dash import planted_float32  # noqa: E402

CELL = "dash-2d"
# what harness/service.py and run.py set in the process's environment
_ENV = ("M3_DEVICE_SERVING", "M3TPU_DATA", "M3TPU_COORDINATOR_PORT",
        "M3TPU_CARBON_PORT")


def drop_oldest_block(query_pipeline):
    """-> a `device_grouped_pipeline` for m3_tpu.models.query_pipeline
    that hands the real one every lane's first row (its oldest block)
    with a bit length of 0: the row decodes to no sample, as a padding
    row does, and the lane is merged from the other 21."""
    import jax.numpy as jnp

    real = query_pipeline.device_grouped_pipeline

    def dropped(words, nbits, slots, *args, **kwargs):
        lane = np.asarray(slots)
        first = np.concatenate([[True], lane[1:] != lane[:-1]])
        return real(words, jnp.where(jnp.asarray(first), 0, nbits), slots,
                    *args, **kwargs)

    return dropped


@pytest.fixture
def run_cell(capsys, monkeypatch):
    """benchmark/run.py's main() for the cell with --rehearse -> the
    result line.  As benchmark/tests/conftest.py's, and fit to run in
    another suite's process: run.py's reading of the process's start is
    taken anew (the kind's watchdog counts `open_within_s` from it) and
    what a run sets in the environment is put back."""
    import run as bench_run

    before = {key: os.environ.get(key) for key in _ENV}

    def go(seed: int, trace: int = 0, seconds: float = 15.0):
        monkeypatch.setattr(bench_run, "T_PROCESS", time.perf_counter())
        monkeypatch.setattr(sys, "argv", [
            "run.py", "--workload", CELL, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace), "--rehearse"])
        assert bench_run.main() == 0
        return json.loads(capsys.readouterr().out.strip().splitlines()[-1])

    yield go
    for key, value in before.items():
        if value is None:
            os.environ.pop(key, None)
        else:
            os.environ[key] = value


def _cell_metrics() -> set[str]:
    """The per-layer metrics the manifest lists for the cell."""
    manifest = json.loads((HERE.parent.parent / "BENCHMARK.json").read_text())
    return {m["name"] for m in manifest["per_layer"]
            if CELL in m.get("workloads", [CELL])}


def _failed(line) -> set[str]:
    return {name for name, c in line["checks"].items() if not c["ok"]}


def test_served_program_in_float32_fails_by_the_gap_alone(run_cell,
                                                          monkeypatch):
    from m3_tpu.models import query_pipeline

    monkeypatch.setattr(query_pipeline, "_grouped_reduce",
                        planted_float32(query_pipeline))
    query_pipeline.device_grouped_pipeline.clear_cache()
    try:
        line = run_cell(61)
    finally:
        query_pipeline.device_grouped_pipeline.clear_cache()
    assert line["correct"] is False
    assert _failed(line) == {"panel_max_rel_gap"}
    assert 1e-8 < line["checks"]["panel_max_rel_gap"]["value"] < 1e-4
    # an untraced run's line: what the cell is judged by
    assert set(line["metrics"]) == {"panel_ms_p50", "setup_s"}


def test_sound_and_traced_then_the_oldest_block_dropped(run_cell,
                                                        monkeypatch):
    from m3_tpu.models import query_pipeline

    line = run_cell(62, trace=1)
    assert line["correct"] is True, line["checks"]
    assert 0 < line["checks"]["panel_max_rel_gap"]["value"] < 1e-9
    # a traced run's line has the cell's layers: all but the roofline
    # share, which needs a chip's peaks, the CPU clock's mean, which one
    # query in sixteen carries, and the two that need a whole run of the
    # program inside the traced slice (XLA:CPU takes seconds over one)
    assert {"device_served_pct.2d", "fetch_ms.2d", "pack_ms.2d",
            "h2d_ms.2d", "device_ms.2d", "device_wait_ms.2d", "d2h_ms.2d",
            "device_queue_depth.2d", "reply_ms.2d", "panel_p95_ms.2d",
            "program_hbm_peak_mb.2d", "samples_per_lane.2d",
            "rows_per_lane.2d"} <= set(line["metrics"])
    assert set(line["metrics"]) <= _cell_metrics()
    value = {k: m["value"] for k, m in line["metrics"].items()}
    assert value["device_served_pct.2d"] == 100.0
    assert value["samples_per_lane.2d"] == 15872.0
    assert value["rows_per_lane.2d"] == 22.0
    assert value["program_hbm_peak_mb.2d"] > 0
    assert line["device"]["busy_s"] > 0

    monkeypatch.setattr(query_pipeline, "device_grouped_pipeline",
                        drop_oldest_block(query_pipeline))
    line = run_cell(62)
    assert line["correct"] is False
    # the first two hours' steps are missing from every row: no gap can
    # be taken, which reads as an infinite one
    assert _failed(line) == {"panel_max_rel_gap"}
    assert line["checks"]["panel_max_rel_gap"]["value"] == float("inf")


def test_a_four_hour_read_is_not_on_the_gather_form(run_cell, monkeypatch):
    import run as bench_run

    real = bench_run.Run.param

    def param(self, group, key):
        return 4 if key == "hours" else real(self, group, key)

    monkeypatch.setattr(bench_run.Run, "param", param)
    line = run_cell(63)
    assert line["correct"] is False
    assert _failed(line) == {"records_not_on_the_gather_form"}
    assert line["checks"]["records_not_on_the_gather_form"]["value"] >= 1


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--planted", action="store_true")
    ap.add_argument("--dropped", action="store_true")
    ap.add_argument("--seconds", default="8")
    args = ap.parse_args()
    import run as bench_run
    from m3_tpu.models import query_pipeline
    if args.planted:
        query_pipeline._grouped_reduce = planted_float32(query_pipeline)
    if args.dropped:
        query_pipeline.device_grouped_pipeline = drop_oldest_block(
            query_pipeline)
    for seed in args.seeds:
        sys.argv = ["run.py", "--workload", CELL, "--seed", str(seed),
                    "--seconds", args.seconds, "--trace", "0"]
        bench_run.T_PROCESS = time.perf_counter()
        bench_run.main()
