#!/usr/bin/env python3
"""The controls of dash-p99, each of which must fail `correct`.

Float32, the precision below the float64 that the deployment states,
planted in the `hq` node (`planted_float32`: the bucket rows' rates
rounded to float32 on their way in, as a `_rate_device` in float32
would hand them over, and `bucket_quantile` computed in float32) must
fail `panel_max_rel_gap` and that check alone.  The `hq` lowering
declined (`declined`: the fused planner's extraction refuses
histogram_quantile, so the engine interpolates on the host over the
per-node tier's rates) must fail
`records_not_served_whole_by_the_fused_program`: the values are still
the reference's, the cell's mechanism is not in the cell.

    python benchmark/tests/test_control_hq.py --planted --seeds 1 2
    python benchmark/tests/test_control_hq.py --declined --seeds 3
    python benchmark/tests/test_control_hq.py --mean --seeds 4     # sound

on the chip, at the cell's own size, prints each run's lines.  `--mean`
adds to a sound run, after its checks, the mean-latency panel of every
job (`sum(rate(.._sum{job=J}[5m])) / sum(rate(.._count{job=J}[5m]))`,
the only reader of the float `_sum` series) against
harness/reference_hq.mean_latency: a `mean_latency` line with the
largest gap, judged by nothing.  The pytest cases hold the controls and
the traced run at the rehearsal size (2 jobs x 10 instances x 14
series); tests/test_benchmark_hq_kind.py runs them in tier-1.
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

from test_control_2d import _ENV, _failed  # noqa: E402

CELL = "dash-p99"
MEAN = ('sum(rate(<M>_sum{job="<J>"}[5m])) / '
        'sum(rate(<M>_count{job="<J>"}[5m]))')


def planted_float32(query_pipeline):
    """-> a `bucket_quantile` for m3_tpu.models.query_pipeline that is
    handed its rates in float32 and interpolates in float32."""
    import jax.numpy as jnp

    real = query_pipeline.bucket_quantile

    def low(counts, ubs, caps, phi):
        return real(*(jnp.asarray(x).astype(jnp.float32)
                      for x in (counts, ubs, caps, phi))).astype(jnp.float64)

    return low


def declined(plan):
    """-> an `_extract` for m3_tpu.query.plan that has no fused form for
    histogram_quantile."""
    real = plan._extract

    def extract(node, counts, root=False):
        if getattr(node, "fn", None) == "histogram_quantile":
            raise plan.Unsupported("planted: no fused form",
                                   reason="unsupported_fn")
        return real(node, counts, root)

    return extract


def with_mean_latency(kind):
    """-> a `check` for traffic_kinds.query_hq_loop that, after the
    cell's own, asks the mean-latency panel of every job over HTTP and
    prints its largest gap to the reference."""
    from harness import loadgen, loadgen_fleet, reference_hq
    from m3_tpu.query import slowlog

    real = kind.check

    def check(run, state, result):
        real(run, state, result)
        fleet, mix = state["fleet"], run.mix
        steps = np.arange(fleet.t0 + mix["start_offset_s"],
                          fleet.seal_end - mix["step_s"] + 1, mix["step_s"])
        client = loadgen_fleet.client_with_timeout(
            run.svc.http_port, mix["request_timeout_s"])
        gaps, seconds, whole = [], [], []
        for j in range(fleet.jobs):
            query = MEAN.replace("<M>", fleet.metric).replace(
                "<J>", fleet.job_name(j))
            took, _, rows = loadgen.panel(
                client, query, start=int(steps[0]), end=int(steps[-1]),
                step=mix["step_s"])
            rec = slowlog.log().records(limit=1)[0]
            whole.append(rec["expr"] == query and rec["device_serving"]
                         and rec.get("device_tier", {}).get("host_nodes")
                         == 0)
            ts, _, sums, counts = fleet.job_histograms(j)
            want = reference_hq.mean_latency(ts, sums, counts, steps,
                                             mix["range_s"])
            (t, v), = rows.values()
            assert np.array_equal(t, steps) and not np.isnan(want).any()
            gaps.append(float(np.max(np.abs(v - want) / np.abs(want))))
            seconds.append(took)
        client.close()
        run.emit("mean_latency", jobs=fleet.jobs, max_rel_gap=max(gaps),
                 least_job_gap=min(gaps), served_whole_fused=all(whole),
                 first_s=round(seconds[0], 3),
                 median_s=round(float(np.median(seconds[1:])), 4))

    return check


@pytest.fixture
def run_cell(capsys, monkeypatch):
    """benchmark/run.py's main() for the cell with --rehearse -> the
    result line.  As test_control_2d's: fit to run in another suite's
    process (run.py's reading of the process's start taken anew, what a
    run sets in the environment put back)."""
    import run as bench_run

    before = {key: os.environ.get(key) for key in _ENV}

    def go(seed: int, trace: int = 0, seconds: float = 2.0):
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


def test_quantile_in_float32_fails_by_the_gap_alone(run_cell, monkeypatch):
    from m3_tpu.models import query_pipeline

    monkeypatch.setattr(query_pipeline, "bucket_quantile",
                        planted_float32(query_pipeline))
    query_pipeline.device_expr_pipeline.clear_cache()
    try:
        line = run_cell(71)
    finally:
        query_pipeline.device_expr_pipeline.clear_cache()
    assert line["correct"] is False
    assert _failed(line) == {"panel_max_rel_gap"}
    assert 1e-8 < line["checks"]["panel_max_rel_gap"]["value"] < 1e-2
    # an untraced run's line: what the cell is judged by
    assert {"panel_ms_p95", "setup_s"} <= set(line["metrics"])


def test_sound_and_traced_then_the_hq_lowering_declined(run_cell,
                                                        monkeypatch):
    from m3_tpu.query import plan

    line = run_cell(72, trace=1)
    assert line["correct"] is True, line["checks"]
    assert 0 < line["checks"]["panel_max_rel_gap"]["value"] < 1e-9
    # a traced run's line has the cell's layers: all but the roofline
    # share, which needs a chip's peaks, and the CPU clock's means,
    # which one query in sixteen carries
    assert _cell_metrics() - {"program_roofline_pct.hq", "engine_cpu_ms.hq",
                              "interp_wait_ms.hq"} <= set(line["metrics"])
    assert set(line["metrics"]) <= _cell_metrics()
    value = {k: m["value"] for k, m in line["metrics"].items()}
    assert value["fused_served_pct.hq"] == 100.0
    assert value["hq_groups.hq"] == 10.0 and value["hq_buckets.hq"] == 12.0
    assert value["rows_per_reply.hq"] == 10.0
    # (XLA:CPU names no operation by its scope: the share reads 0 here)
    assert 0 <= value["hq_share_pct.hq"] < 100
    assert value["plan_ms.hq"] > 0 and value["program_hbm_peak_mb.hq"] > 0
    assert line["device"]["busy_s"] > 0

    monkeypatch.setattr(plan, "_extract", declined(plan))
    line = run_cell(72)
    assert line["correct"] is False
    assert "records_not_served_whole_by_the_fused_program" in _failed(line)
    # the host's quantile over the per-node tier's rates is the
    # reference's too
    assert line["checks"]["panel_max_rel_gap"]["ok"]
    assert line["checks"]["failed_requests"]["ok"]


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--planted", action="store_true")
    ap.add_argument("--declined", action="store_true")
    ap.add_argument("--mean", action="store_true")
    ap.add_argument("--seconds", default="8")
    ap.add_argument("--rehearse", action="store_true",
                    help="on the CPU, at the rehearsal size: never a number")
    args = ap.parse_args()
    import run as bench_run
    from m3_tpu.models import query_pipeline
    from m3_tpu.query import plan
    from traffic_kinds import query_hq_loop
    if args.planted:
        query_pipeline.bucket_quantile = planted_float32(query_pipeline)
    if args.declined:
        plan._extract = declined(plan)
    if args.mean:
        query_hq_loop.check = with_mean_latency(query_hq_loop)
    for seed in args.seeds:
        sys.argv = ["run.py", "--workload", CELL, "--seed", str(seed),
                    "--seconds", args.seconds, "--trace", "0"] + (
                        ["--rehearse"] if args.rehearse else [])
        bench_run.T_PROCESS = time.perf_counter()
        bench_run.main()
