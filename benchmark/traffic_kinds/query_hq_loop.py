"""Closed-loop latency panels over sealed blocks:
`histogram_quantile(q, rate(<family>_bucket{job=J}[5m]))` of one job, a
row an instance, served whole by the fused device program.

Set-up is query_topk_loop's over the fleet of harness/fleet_histogram.py
(every instance a Prometheus histogram: 12 `le` series, `_sum`,
`_count`): the fleet written block by block at the config's backfill
pace and sealed by the service's own tick + flush; then one panel of
EVERY job, so that whatever program a job's streams need is loaded; the
run reports how many programs that minted.  The window is
query_topk_loop's own (`window`: harness/loadgen.py as a child, the
ramp, the `RecordTap` that reads the slow-query ring out while the
window runs, the traced slice, both watchdogs: never a wait without a
limit), after which a traced run's slice is reduced once more with its
operations named down to the program's sub-scopes and every scope's
seconds summed (harness/trace_subscopes.py: `scope_s`, which
`hq_share_pct.hq` reads).

The check, after the window: the first reply of each job against
harness/reference_hq.py on the generator's arrays (`reference.rate` a
bucket series, the quantile a (instance, step); see its `compare`);
every later reply of a job equal to its first bit for bit; every panel
of the window with a record, every record served whole by one fused
program (`device_serving`, `device_tier.host_nodes` 0, the host-split
and decline counters unmoved) and holding the cell's mechanism
(`hq_groups`, `hq_buckets` as the mix states them; a record from before
the fields, a parent's, is held to `device_serving` alone); the fleet
read back (count_over_time per job and series name, host tier) equals
the samples acknowledged.
"""

from __future__ import annotations

import time

import numpy as np

from harness import fleet_histogram, loadgen, loadgen_fleet, reference_hq
from harness import service, trace_reduce, trace_subscopes
from traffic_kinds import query_closed_loop as sealed_loop
from traffic_kinds import query_topk_loop as topk_loop
from traffic_kinds.query_fleet_loop import Watchdog, _process_started


def setup(run):
    watchdog = Watchdog("the window's opening", _process_started(run),
                        run.mix["open_within_s"])
    try:
        return dict(_load_and_warm(run), watchdog=watchdog)
    except BaseException:
        watchdog.done()
        raise


def _load_and_warm(run) -> dict:
    cfg, mix = run.config, run.mix
    n_blocks = run.param(cfg, "hours") * 3600 // cfg["block_s"]
    fleet = fleet_histogram.for_run(run, n_blocks)
    if run.param(cfg, "query_fanout_series") != (
            fleet.instances * len(fleet_histogram.LE)):
        raise ValueError("a panel reads one job's buckets: "
                         "query_fanout_series must equal "
                         "instances_per_job x the le values")
    client = loadgen_fleet.client_with_timeout(run.svc.http_port,
                                               mix["request_timeout_s"])
    t0 = time.perf_counter()
    acked = sealed_loop._ingest(fleet, client, cfg["backfill_samples_per_s"])
    run.emit("ingest", series=fleet.n_series, blocks=n_blocks,
             samples_acked=acked,
             seconds=round(time.perf_counter() - t0, 2))
    sealed = service.seal(run.svc)
    run.emit("seal", **{k: (round(v, 3) if isinstance(v, float) else v)
                        for k, v in sealed.items()})
    want = [fleet.t0 + k * cfg["block_s"] for k in range(n_blocks)]
    if sealed["block_starts"] != want:
        raise RuntimeError(f"sealed {sealed['block_starts']}, want {want}")
    from m3_tpu.ops import kernel_telemetry
    before = kernel_telemetry.snapshot()
    t0 = time.perf_counter()
    warm_s = [loadgen.panel(client, sealed_loop._query(mix, fleet, j),
                            **sealed_loop._range(mix, fleet))[0]
              for j in range(fleet.jobs)]
    kernels = topk_loop._kernel_delta(kernel_telemetry.snapshot(), before)
    run.emit("warm", jobs=fleet.jobs,
             seconds=round(time.perf_counter() - t0, 3),
             first_s=round(warm_s[0], 3), slowest_s=round(max(warm_s), 3),
             programs_minted=sum(k["compiles"] for k in kernels.values()),
             kernels={name: {f: round(k[f], 3) for f in (
                 "invocations", "compiles", "compile_s", "execute_s")}
                 for name, k in kernels.items() if k["invocations"]})
    client.close()
    return {"fleet": fleet, "acked": acked}


def window(run, state):
    result = topk_loop.window(run, state)
    if run.trace_summary is not None:
        run.trace_summary = trace_subscopes.reduce(
            trace_reduce.find_xplane(str(run.out_dir / "trace")))
    # the quantile's shape as the records have it, where they do
    result["summary"]["hq_groups_buckets"] = sorted(
        {(r.get("hq_groups"), r.get("hq_buckets"))
         for r in run.slow_records}, key=repr)
    return result


def without_hq(records, groups: int, buckets: int) -> int:
    """Records that did not interpolate `groups` label combinations of
    `buckets` buckets.  A record from before the fields (a parent's
    program) is not held to them."""
    return sum((r.get("hq_groups", groups), r.get("hq_buckets", buckets))
               != (groups, buckets) for r in records)


def compare_job(fleet, mix, job: int, rows: dict, steps) -> dict:
    """The first reply of one job against the reference."""
    ts, buckets, _, _ = fleet.job_histograms(job)
    rates = reference_hq.bucket_rates(ts, buckets, steps, mix["range_s"])
    ubs = [float(le) for le in fleet_histogram.LE]
    limit = mix["limits"]["panel_max_rel_gap"]
    keys = [tuple(sorted({"job": fleet.job_name(job),
                          "zone": f"zone-{i % fleet.zones}",
                          "instance": fleet.instance_name(i)}.items()))
            for i in range(fleet.instances)]
    return reference_hq.compare(
        rows, keys, steps, reference_hq.quantile(mix["q"], ubs, rates),
        reference_hq.tied(mix["q"], rates, limit), limit)


def check(run, state, result):
    fleet, mix = state["fleet"], run.mix
    steps = np.arange(fleet.t0 + mix["start_offset_s"],
                      fleet.seal_end - mix["step_s"] + 1, mix["step_s"],
                      dtype=np.int64)
    t0 = time.perf_counter()
    found = [compare_job(fleet, mix, job, rows, steps)
             for job, rows in sorted(state["first_reply"].items())]

    def worst(key):
        return max((f[key] for f in found), default=0)

    run.check("panel_max_rel_gap", float(worst("max_rel_gap")),
              mix["limits"]["panel_max_rel_gap"])
    run.check("hq_points_nan_mismatch", worst("points_nan_mismatch"), 0)
    run.check("hq_rows_unknown", worst("rows_unknown"), 0)
    run.check("hq_rows_missing", worst("rows_missing"), 0)
    run.check("jobs_without_a_reply",
              fleet.jobs - len(state["first_reply"]), 0)
    run.check("replies_differing_from_first_of_job",
              len(state["mismatched"]), 0)
    run.check("failed_requests", result["failed"], 0)
    run.check("loadgen_clock_gap_s", state["clock_gap"],
              mix["limits"]["loadgen_clock_gap_s"])
    run.check("compiles_in_window",
              result["summary"]["compiles_in_window"], 0)
    # the cell's mechanism: every panel one fused program, no node of
    # its tree left to the host, nothing declined, and the quantile of
    # every instance over every le
    run.check("records_not_served_whole_by_the_fused_program", sum(
        not r.get("device_serving")
        or r.get("device_tier", {}).get("host_nodes") != 0
        for r in run.slow_records), 0)
    run.check("records_without_hq", without_hq(
        run.slow_records, run.param(mix, "hq_groups"), mix["hq_buckets"]), 0)
    run.check("panels_without_a_record",
              max(result["summary"]["requests"]
                  - result["summary"]["records"], 0), 0)
    run.check("record_tap_overruns",
              result["summary"]["record_tap_overruns"], 0)
    for name, moved in state["counters_moved"].items():
        run.check(f"{name}_moved", moved, 0)

    # read-back by the host tier of the same engine: every acknowledged
    # sample of every job is in the sealed blocks
    from m3_tpu.query.engine import Engine
    host = Engine(run.svc.db, run.svc.cfg.unagg_namespace,
                  device_serving=False)
    span = fleet.seal_end - fleet.t0
    at = (fleet.seal_end - fleet.cadence_s) * 10**9
    counted = series = 0
    for j in range(fleet.jobs):
        for suffix in ("_bucket", "_sum", "_count"):
            _, mat = host.query_range(
                f'count_over_time({fleet.metric}{suffix}'
                f'{{job="{fleet.job_name(j)}"}}[{span}s])', at, at, 10**9)
            col = np.asarray(mat.values)[:, -1]
            series += int((~np.isnan(col)).sum())
            counted += int(np.nansum(col))
    run.check("samples_acked_minus_read_back", state["acked"] - counted, 0,
              ok=counted == state["acked"])
    run.check("series_missing", fleet.n_series - series, 0,
              ok=series == fleet.n_series)
    run.emit("check_done", jobs_compared=len(found),
             least_job_gap=min((f["max_rel_gap"] for f in found),
                               default=0.0),
             points_tied=sum(f["points_tied"] for f in found),
             seconds=round(time.perf_counter() - t0, 2))
