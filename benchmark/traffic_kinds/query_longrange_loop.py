"""Closed-loop panels over every sealed block the retention holds: a
long read, served by the per-node device program on the gather's side
of its windowed stage.

Set-up is query_closed_loop's: the config's fleet written block by
block at the config's backfill pace and sealed by the service's own
tick + flush (all `hours` of it by hand: the mediator is off); then
one panel of EVERY job, so that the program is loaded whichever job a
client asks first; the run reports how many programs that minted.  The
clients are query_closed_loop's (harness/loadgen.py as a child:
`clients` threads, each on its own keep-alive connection, each sending
its next `query_range` when the last reply is parsed, going round the
jobs in an order from the seed).  The range is the mix's own: from
`start_offset_s` after the oldest sealed block's start to
`end_offset_s` before the newest's end.  The loop runs `ramp_s`
seconds before the window opens (set-up).  With --trace 1 a slice of
`trace_slice_s` seconds a third into the window is traced, and its
operations are named down to the program's sub-scopes
(harness/trace_subscopes.py).

A watchdog (query_fleet_loop's) ends the process, exit 1 and no result
line, if the window has not opened `open_within_s` after the process
started or not closed `seconds + request_timeout_s + 10` after it
opened, with `trace_stop_within_s` more in a traced run; the warm
panels give up after `request_timeout_s`.

The check, after the window: the first reply of each job against
harness/reference.py on the generator's arrays over all blocks; every
later reply of a job equal to its first bit for bit; every record of
the window served by the device tier, none declined, the host-split
and decline counters unmoved; every record on the gather form at no
fewer than `gather_min_n_cap` samples a lane (the cell's mechanism: a
program from before the record's `n_cap` is held to `window_form`
alone); the fleet read back (count_over_time per job, host tier)
equals the samples acknowledged.
"""

from __future__ import annotations

import gc
import os
import time

import numpy as np

from harness import fleet as fleets
from harness import loadgen, loadgen_fleet, reference, service
from harness import trace_reduce, trace_subscopes
from traffic_kinds import query_closed_loop as sealed_loop
from traffic_kinds.query_fleet_loop import Watchdog, _process_started
from traffic_kinds.query_topk_loop import (UNMOVED, _counter_totals,
                                           _kernel_delta)


def _range(mix: dict, fleet) -> dict:
    return {"start": fleet.t0 + mix["start_offset_s"],
            "end": fleet.seal_end - mix["end_offset_s"],
            "step": mix["step_s"]}


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
    fleet = fleets.for_run(run, n_blocks)
    if run.param(cfg, "query_fanout_series") != fleet.instances:
        raise ValueError("a panel reads one job: query_fanout_series "
                         "must equal instances_per_job")
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
                            **_range(mix, fleet))[0]
              for j in range(fleet.jobs)]
    kernels = _kernel_delta(kernel_telemetry.snapshot(), before)
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
    import jax

    from m3_tpu.ops import kernel_telemetry
    from m3_tpu.query import slowlog

    fleet, mix = state["fleet"], run.mix
    order = sealed_loop._job_order(run.seed, fleet.jobs)
    queries = [sealed_loop._query(mix, fleet, j) for j in range(fleet.jobs)]
    gc_pauses = []          # (offset in the window, seconds) of full GCs
    t_start = float("inf")  # set when the window opens, after the ramp

    def on_gc(phase, info, _t=[0.0]):
        if info["generation"] == 2:
            if phase == "start":
                _t[0] = time.perf_counter()
            else:
                gc_pauses.append((round(_t[0] - t_start, 3),
                                  round(time.perf_counter() - _t[0], 4)))

    watchdog, child = state["watchdog"], None
    try:
        child = loadgen.Child()
        watchdog.children = (child,)
        run.emit("loadgen", server_pid=os.getpid(), loadgen_pid=child.pid)
        clock_gap = child.handshake(dict(
            _range(mix, fleet), port=run.svc.http_port, queries=queries,
            clients=run.param(mix, "clients"),
            order=[int(j) for j in order],
            seconds=run.seconds))
        time.sleep(run.param(mix, "ramp_s"))
        gc.callbacks.append(on_gc)
        k_before = kernel_telemetry.snapshot()
        c_before = _counter_totals()
        t_wall = time.time()
        t_start = run.window_opens()
        child.window_opens(t_start)
        watchdog.done()
        watchdog = Watchdog(
            "the window's end", t_start, run.seconds
            + mix["request_timeout_s"] + 10
            + (mix["trace_stop_within_s"] if run.trace else 0), (child,))
        if run.trace:
            # a steady slice a third into the window; the Python tracer
            # is off, the decode scan alone is thousands of events
            time.sleep(run.seconds / 3)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            trace_dir = run.trace_dir()
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation("bench:window"):
                time.sleep(min(mix["trace_slice_s"], run.seconds / 3))
            jax.profiler.stop_trace()
        done = child.result()       # blocks until the loop has ended
    finally:
        watchdog.done()
        if child is not None:
            child.stop()
    elapsed = time.perf_counter() - t_start
    gc.callbacks.remove(on_gc)
    c_after = _counter_totals()
    sent_at, ms, jobs = ([p[k] for p in done["panels"]] for k in range(3))
    errors = done["errors"]
    # for reading a far-off run without a second one: every panel of
    # the window, and the interpreter's full collections
    run.emit("panels", log_only=True, gc_full=gc_pauses,
             sent_at_s=[round(t, 3) for t in sent_at],
             ms=[round(x, 2) for x in ms], job=jobs)
    if run.trace:
        path = trace_reduce.find_xplane(trace_dir)
        run.trace_summary = trace_subscopes.reduce(path) if path else None

    # a window holds some 300 panels: the log's ring keeps them all
    asked = frozenset(queries)
    run.slow_records = [r for r in slowlog.log().records()
                        if r.get("ts", 0) >= t_wall and r["expr"] in asked]
    # where a stalled panel spent its time: the four slowest records
    run.emit("slowest", log_only=True, records=[
        {"at_s": round(r.get("ts", t_wall) - t_wall, 3), "phases": r["phases"]}
        for r in sorted(run.slow_records,
                        key=lambda r: -r["phases"]["total_s"])[:4]])
    run.kernels = _kernel_delta(kernel_telemetry.snapshot(), k_before)
    run.timers["request_s"] = [x / 1000.0 for x in ms]
    lat = np.asarray(ms, dtype=np.float64)
    n = len(ms)
    end_to_end, beyond_p95 = {}, 0
    if n:
        p50, p95 = (float(np.percentile(lat, q)) for q in (50, 95))
        end_to_end = {name: value for name, value in
                      (("panel_ms_p50", p50), ("panel_ms_p95", p95))
                      if name in mix["end_to_end"]}
        beyond_p95 = int((lat > p95).sum())
        run.timers["request_p95_s"] = [p95 / 1000.0]    # panel_p95_ms.2d
    first_reply = {job: loadgen.rows_of(doc)
                   for job, doc in done["first_reply"].items()}
    state.update(first_reply=first_reply, mismatched=done["differing"],
                 clock_gap=clock_gap, counters_moved={
                     name: c_after[name] - c_before[name]
                     for name in UNMOVED})
    return {"attempted": n + len(errors), "failed": len(errors),
            "end_to_end": end_to_end,
            "summary": {"requests": n, "errors": errors[:3],
                        "elapsed_s": round(elapsed, 3),
                        "panels_per_s": round(n / elapsed, 3),
                        "distinct_jobs": len(first_reply),
                        "max_ms": round(float(lat.max(initial=0)), 1),
                        "beyond_p95": beyond_p95,
                        "records": len(run.slow_records),
                        # the program's shape as its records have it,
                        # where they do: the form of the windowed
                        # stage's reads, samples and rows a lane, steps
                        "form_ncap_rows_steps": sorted({
                            (r.get("window_form"), r.get("n_cap"),
                             r.get("rows_per_lane"), r.get("steps_pad"))
                            for r in run.slow_records}, key=repr),
                        "gc_full_s": round(sum(s for _, s in gc_pauses), 3),
                        "compiles_in_window": sum(
                            k.get("compiles", 0)
                            for k in run.kernels.values())}}


def off_the_gather_form(records, min_n_cap: int) -> int:
    """Records whose windowed stage did not read its windows' ends by
    gathers at `min_n_cap` samples a lane or more.  A record from
    before `n_cap` (a parent's program) is held to its form alone."""
    return sum(r.get("window_form") != "gather"
               or r.get("n_cap", min_n_cap) < min_n_cap for r in records)


def check(run, state, result):
    fleet, mix = state["fleet"], run.mix
    rng = _range(mix, fleet)
    steps = np.arange(rng["start"], rng["end"] + 1, rng["step"],
                      dtype=np.int64)
    zones = np.arange(fleet.instances) % fleet.zones
    t0 = time.perf_counter()
    gaps = []
    for job, rows in sorted(state["first_reply"].items()):
        ts, vs = fleet.job_arrays(job)
        by_zone = reference.sum_by(
            zones, reference.rate(ts, vs, steps, mix["range_s"]))
        want = reference.drop_nan(steps, {
            (("zone", f"zone-{z}"),): row for z, row in by_zone.items()})
        gaps.append(reference.max_rel_gap(rows, want))
    run.check("panel_max_rel_gap", max(gaps, default=0.0),
              mix["limits"]["panel_max_rel_gap"])
    run.check("jobs_without_a_reply",
              fleet.jobs - len(state["first_reply"]), 0)
    run.check("replies_differing_from_first_of_job",
              len(state["mismatched"]), 0)
    run.check("failed_requests", result["failed"], 0)
    run.check("loadgen_clock_gap_s", state["clock_gap"],
              mix["limits"]["loadgen_clock_gap_s"])
    run.check("compiles_in_window",
              result["summary"]["compiles_in_window"], 0)
    run.check("records_not_served_by_the_device_tier",
              sum(not r.get("device_serving") or bool(r.get("device_declines"))
                  for r in run.slow_records), 0)
    run.check("panels_without_a_record",
              max(result["summary"]["requests"]
                  - result["summary"]["records"], 0), 0)
    # the cell's mechanism: the long lane, read by gathers
    run.check("records_not_on_the_gather_form",
              off_the_gather_form(run.slow_records, mix["gather_min_n_cap"]),
              0)
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
        _, mat = host.query_range(
            f'count_over_time({fleet.metric}{{job="{fleet.job_name(j)}"}}'
            f'[{span}s])', at, at, 10**9)
        col = np.asarray(mat.values)[:, -1]
        series += int((~np.isnan(col)).sum())
        counted += int(np.nansum(col))
    run.check("samples_acked_minus_read_back", state["acked"] - counted, 0,
              ok=counted == state["acked"])
    run.check("series_missing", fleet.n_series - series, 0,
              ok=series == fleet.n_series)
    run.emit("check_done", jobs_compared=len(gaps),
             least_job_gap=min(gaps, default=0.0),
             seconds=round(time.perf_counter() - t0, 2))
