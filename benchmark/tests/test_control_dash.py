#!/usr/bin/env python3
"""The control of dash-sealed: float32, the precision below the float64
that the deployment states.  Its answers must fail `panel_max_rel_gap`.

Two forms.  Planted: the served device program itself, with each
lane's rate rounded to float32 and the grouped sum made in float32
(`planted_float32`), driven through a whole run:

    python benchmark/tests/test_control_dash.py --planted --seeds 1 2 3

on the chip, at the cell's own size, prints each run's lines; its
`check_done` line has the smallest gap of any job.  Host proxy: the
numpy reference computed in float32 against itself in float64
(`--seeds` alone); it runs anywhere and says nothing of the device.
The pytest cases hold both at a size a test run can hold.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from harness import reference  # noqa: E402
from harness.fleet import Fleet  # noqa: E402


def panel_gaps(seed: int, jobs: int, instances: int, dtype) -> list[float]:
    """Per job: the gap between the panel computed in `dtype` and the
    float64 reference, as query_closed_loop.check compares them."""
    cfg = json.loads((HERE.parent / "configs" / "m3query-fanout.json")
                     .read_text())
    mix = json.loads((HERE.parent / "traffic" / "panels-sealed-4c.json")
                     .read_text())
    fleet = Fleet(dict(cfg, jobs=jobs, instances_per_job=instances), seed,
                  1_790_000_000, cfg["hours"] * 3600 // cfg["block_s"])
    steps = np.arange(fleet.t0 + mix["start_offset_s"],
                      fleet.seal_end - mix["step_s"] + 1, mix["step_s"])
    zones = np.arange(instances) % fleet.zones
    gaps = []
    for j in range(jobs):
        ts, vs = fleet.job_arrays(j)
        panels = [reference.drop_nan(steps, reference.sum_by(
            zones, reference.rate(ts, vs, steps, mix["range_s"], dtype=d)))
            for d in (dtype, np.float64)]
        gaps.append(reference.max_rel_gap(*panels))
    return gaps


def planted_float32(query_pipeline):
    """-> a `_grouped_reduce` for m3_tpu.models.query_pipeline that makes
    the served program round each lane's rate to float32 and reduce the
    groups in float32."""
    import jax.numpy as jnp

    real = query_pipeline._grouped_reduce

    def low(out, *args, **kwargs):
        return real(out.astype(jnp.float32), *args, **kwargs).astype(
            jnp.float64)

    return low


def limit() -> float:
    return json.loads((HERE.parent / "traffic" / "panels-sealed-4c.json")
                      .read_text())["limits"]["panel_max_rel_gap"]


def test_float32_panel_fails_the_limit_and_float64_passes():
    low = panel_gaps(5, 2, 50, np.float32)
    assert min(low) > 3 * limit(), low
    assert max(panel_gaps(5, 2, 50, np.float64)) == 0.0


def test_served_program_in_float32_is_not_correct(run_cell, monkeypatch):
    from m3_tpu.models import query_pipeline

    monkeypatch.setattr(query_pipeline, "_grouped_reduce",
                        planted_float32(query_pipeline))
    query_pipeline.device_grouped_pipeline.clear_cache()
    try:
        assert run_cell("dash-sealed", 31)["correct"] is False
    finally:
        query_pipeline.device_grouped_pipeline.clear_cache()


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--planted", action="store_true")
    ap.add_argument("--seconds", default="8")
    args = ap.parse_args()
    if args.planted:
        sys.path.insert(0, str(HERE.parent.parent))
        import run as bench_run
        from m3_tpu.models import query_pipeline
        query_pipeline._grouped_reduce = planted_float32(query_pipeline)
        for seed in args.seeds:
            sys.argv = ["run.py", "--workload", "dash-sealed", "--seed",
                        str(seed), "--seconds", args.seconds, "--trace", "0"]
            bench_run.main()
        sys.exit(0)
    cfg = json.loads((HERE.parent / "configs" / "m3query-fanout.json")
                     .read_text())
    for seed in args.seeds:
        gaps = panel_gaps(seed, cfg["jobs"], cfg["instances_per_job"],
                          np.float32)
        print(json.dumps({"control": "dash-sealed float32", "seed": seed,
                          "jobs": len(gaps), "min_gap": min(gaps),
                          "max_gap": max(gaps), "limit": limit(),
                          "fails": min(gaps) > limit()}))
