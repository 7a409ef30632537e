"""Closed-loop dashboard panels over sealed blocks.

Set-up writes the config's fleet block by block at the config's
backfill pace, seals it with the service's own tick + flush, and sends
one panel so that the device program is loaded.  The clients run in a
process of their own, as a Grafana or an agent does: harness/loadgen.py,
started as a child, runs `clients` threads, each on its own keep-alive
connection, each sending its next `query_range` when the last reply is
parsed; the panels go round the jobs in an order drawn from the seed.
This process, the server's, keeps the slow-query records, the kernel
telemetry, the trace and every check, and no thread of the harness but
the main one, which sleeps through the window.  The loop runs for
`ramp_s` seconds before the window opens, so that the window sees the
loop's steady state and not four clients starting at once; the ramp is
set-up.  The window's panels are those sent in it; one in flight when
it ends is completed and counted.  With --trace 1 a slice of
`trace_slice_s` seconds in the middle of the window is traced.

The check, after the window: every reply of the window equals the
first reply for its job, and that one is compared with the numpy
reference computed from the generator's arrays; the fleet is read
back (count_over_time per job) and equals the samples acknowledged.
"""

from __future__ import annotations

import gc
import os
import time

import numpy as np

from harness import fleet as fleets
from harness import loadgen, reference, service, trace_reduce
from harness.client import Client
from harness.fleet import Fleet


def _ingest(fleet: Fleet, client: Client, samples_per_s: float) -> int:
    """Write the fleet block by block, oldest first, no faster than
    `samples_per_s`: the service takes such a backfill at one of two
    speeds from run to run (PERF.md), and the pace, just under the
    slower, makes set-up take the same time in both."""
    acked, t0 = 0, time.perf_counter()
    for k in range(fleet.n_blocks):
        for lo, hi in fleet.block_requests(k):
            body, n = fleet.body(lo, hi, k)
            time.sleep(max(0.0, t0 + acked / samples_per_s
                           - time.perf_counter()))
            client.remote_write(body)
            acked += n
    return acked


def _query(mix: dict, fleet: Fleet, job: int) -> str:
    return mix["query"].replace("<METRIC>", fleet.metric).replace(
        "<J>", fleet.job_name(job))


def _range(mix: dict, fleet: Fleet) -> dict:
    return {"start": fleet.t0 + mix["start_offset_s"],
            "end": fleet.seal_end - mix["step_s"], "step": mix["step_s"]}


def _job_order(seed: int, n_jobs: int) -> np.ndarray:
    """The order in which the window's panels go round the jobs: every
    seed asks the same jobs equally often, in another order."""
    return np.random.default_rng([seed, 0x5a1f]).permutation(n_jobs)


def setup(run):
    cfg, mix = run.config, run.mix
    n_blocks = run.param(cfg, "hours") * 3600 // cfg["block_s"]
    fleet = fleets.for_run(run, n_blocks)
    if run.param(cfg, "query_fanout_series") != fleet.instances:
        raise ValueError("a panel reads one job: query_fanout_series "
                         "must equal instances_per_job")
    client = Client(run.svc.http_port)
    t0 = time.perf_counter()
    acked = _ingest(fleet, client, cfg["backfill_samples_per_s"])
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
    seconds, _, _ = loadgen.panel(client, _query(mix, fleet, 0),
                                  **_range(mix, fleet))
    after = kernel_telemetry.snapshot()
    run.emit("warm", seconds=round(seconds, 3), kernels={
        k: {f: round(st[f] - before.get(k, {}).get(f, 0), 3)
            for f in ("invocations", "compiles", "compile_s", "execute_s")}
        for k, st in after.items()
        if st["invocations"] - before.get(k, {}).get("invocations", 0)})
    client.close()
    return {"fleet": fleet, "acked": acked}


def window(run, state):
    import jax

    from m3_tpu.ops import kernel_telemetry
    from m3_tpu.query import slowlog

    fleet, mix = state["fleet"], run.mix
    order = _job_order(run.seed, fleet.jobs)
    gc_pauses = []          # (offset in the window, seconds) of full GCs
    t_start = float("inf")  # set when the window opens, after the ramp

    def on_gc(phase, info, _t=[0.0]):
        if info["generation"] == 2:
            if phase == "start":
                _t[0] = time.perf_counter()
            else:
                gc_pauses.append((round(_t[0] - t_start, 3),
                                  round(time.perf_counter() - _t[0], 4)))

    child = loadgen.Child()
    try:
        run.emit("loadgen", server_pid=os.getpid(), loadgen_pid=child.pid)
        clock_gap = child.handshake(dict(
            _range(mix, fleet), port=run.svc.http_port,
            queries=[_query(mix, fleet, j) for j in range(fleet.jobs)],
            clients=mix["clients"], order=[int(j) for j in order],
            seconds=run.seconds))
        time.sleep(mix["ramp_s"])
        gc.callbacks.append(on_gc)
        k_before = kernel_telemetry.snapshot()
        t_wall = time.time()
        t_start = run.window_opens()
        child.window_opens(t_start)
        if run.trace:
            # a steady slice in the middle of the window; the Python
            # tracer is off, the decode scan alone is thousands of events
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
        child.stop()
    elapsed = time.perf_counter() - t_start
    gc.callbacks.remove(on_gc)
    sent_at, ms, jobs = ([p[k] for p in done["panels"]] for k in range(3))
    errors = done["errors"]
    # for reading a far-off run without a second one: every panel of
    # the window, and the interpreter's full collections
    run.emit("panels", log_only=True, gc_full=gc_pauses,
             sent_at_s=[round(t, 3) for t in sent_at],
             ms=[round(x, 2) for x in ms], job=jobs)
    if run.trace:
        path = trace_reduce.find_xplane(trace_dir)
        run.trace_summary = trace_reduce.reduce(path) if path else None

    expr_head = mix["query"].split("<J>")[0].replace("<METRIC>",
                                                     fleet.metric)
    run.slow_records = [r for r in slowlog.log().records()
                        if r.get("ts", 0) >= t_wall
                        and r["expr"].startswith(expr_head)]
    # where a stalled panel spent its time: the four slowest records
    run.emit("slowest", log_only=True, records=[
        {"at_s": round(r.get("ts", t_wall) - t_wall, 3), "phases": r["phases"]}
        for r in sorted(run.slow_records,
                        key=lambda r: -r["phases"]["total_s"])[:4]])
    k_after = kernel_telemetry.snapshot()
    run.kernels = {
        name: {f: st[f] - k_before.get(name, {}).get(f, 0) for f in st}
        for name, st in k_after.items()}
    run.timers["request_s"] = [x / 1000.0 for x in ms]
    lat = np.asarray(ms, dtype=np.float64)
    n = len(ms)
    end_to_end, beyond_p95 = {}, 0
    if n:
        end_to_end = {"panel_ms_p50": float(np.percentile(lat, 50)),
                      "panel_ms_p95": float(np.percentile(lat, 95))}
        beyond_p95 = int((lat > end_to_end["panel_ms_p95"]).sum())
    first_reply = {job: loadgen.rows_of(doc)
                   for job, doc in done["first_reply"].items()}
    state.update(first_reply=first_reply, mismatched=done["differing"],
                 clock_gap=clock_gap)
    return {"attempted": n + len(errors), "failed": len(errors),
            "end_to_end": end_to_end,
            "summary": {"requests": n, "errors": errors[:3],
                        "elapsed_s": round(elapsed, 3),
                        "panels_per_s": round(n / elapsed, 3),
                        "distinct_jobs": len(first_reply),
                        "max_ms": round(float(lat.max(initial=0)), 1),
                        "beyond_p95": beyond_p95,
                        "gc_full_s": round(sum(s for _, s in gc_pauses), 3),
                        "compiles_in_window": sum(
                            k.get("compiles", 0)
                            for k in run.kernels.values())}}


def check(run, state, result):
    fleet, mix = state["fleet"], run.mix
    steps = np.arange(fleet.t0 + mix["start_offset_s"],
                      fleet.seal_end - mix["step_s"] + 1, mix["step_s"],
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
    run.check("replies_differing_from_first_of_job",
              len(state["mismatched"]), 0)
    run.check("failed_requests", result["failed"], 0)
    run.check("loadgen_clock_gap_s", state["clock_gap"],
              mix["limits"]["loadgen_clock_gap_s"])
    run.check("compiles_in_window",
              result["summary"]["compiles_in_window"], 0)
    run.check("no_request_completed", 0 if state["first_reply"] else 1, 0)

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
