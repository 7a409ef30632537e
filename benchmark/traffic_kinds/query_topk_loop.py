"""Closed-loop top-k panels over sealed blocks: `topk(k, sum by
(<label>)(rate(..)))` of one job, served whole by the fused device
program.

Set-up is query_closed_loop's, over the skewed fleet of
harness/fleet_skewed.py: the fleet written block by block at the
config's backfill pace and sealed by the service's own tick + flush;
then one panel of EVERY job, so that whatever program a job's streams
need (their pow2 word bucket) is loaded; the run reports how many
programs that minted.  The clients are query_closed_loop's
(harness/loadgen.py as a child: `clients` threads, each on its own
keep-alive connection, each sending its next `query_range` when the
last reply is parsed, going round the jobs in an order from the seed).
The loop runs `ramp_s` seconds before the window opens (set-up).  With
--trace 1 a slice of `trace_slice_s` seconds a third into the window
is traced.

A watchdog (query_fleet_loop's) ends the process, exit 1 and no result
line, if the window has not opened `open_within_s` after the process
started or not closed `seconds + request_timeout_s + 10` after it
opened, with `trace_stop_within_s` more in a traced run (the profiler
takes 40-60 s to write a 3 s slice of these programs, inside the
window's wait); the warm panels give up after `request_timeout_s`.

The window's slow-query records are read out of the log's ring every
two seconds while the window runs (`RecordTap`): the ring keeps 2,048
and a window holds more panels, so a reading after the window would
miss the first of them.  Every panel has its record, or the run fails.

The check, after the window: the first reply of each job against
harness/reference_topk.py on the generator's arrays (values, count a
step, rank, rows: see its `compare`); every later reply of a job equal
to its first bit for bit; every panel of the window with a record, and
every record served whole by one fused program (`device_serving`,
`device_tier.host_nodes` 0, the host-split and decline counters
unmoved); the fleet read back (count_over_time per job, host tier)
equals the samples acknowledged.
"""

from __future__ import annotations

import gc
import os
import threading
import time

import numpy as np

from harness import fleet_skewed, loadgen, loadgen_fleet, reference_topk
from harness import service, trace_reduce
from traffic_kinds import query_closed_loop as sealed_loop
from traffic_kinds.query_fleet_loop import Watchdog, _process_started

FUSED = "device_expr_pipeline"
# counters a panel served whole by one fused program never moves
UNMOVED = ("m3_query_host_split_total", "m3_query_device_decline_total")


def _counter_totals() -> dict[str, float]:
    from m3_tpu.utils import instrument
    out = dict.fromkeys(UNMOVED, 0.0)
    for sample in instrument.registry().collect():
        if sample.name in out:
            out[sample.name] += sample.value
    return out


def _kernel_delta(after: dict, before: dict) -> dict:
    """Counts and seconds as the difference; a peak (hbm_peak_bytes)
    as it stands after."""
    return {name: {f: st[f] if f.endswith("_peak_bytes")
                   else st[f] - before.get(name, {}).get(f, 0) for f in st}
            for name, st in after.items()}


class RecordTap(threading.Thread):
    """The slow-query records cut from `since` (wall clock) on for the
    expressions `asked`, every one of them: the log's ring is read out
    every `every_s` seconds, newest first down to the newest record
    already taken.  `overruns` counts the readings that found no record
    they knew, so that some may have left the ring unread."""

    def __init__(self, log, since: float, asked, every_s: float = 2.0):
        super().__init__(name="record-tap", daemon=True)
        self._log, self.since, self._asked = log, since, frozenset(asked)
        self._every_s, self._halt = every_s, threading.Event()
        self._newest = None     # the newest record of the last reading
        self.records: list[dict] = []
        self.overruns = 0

    def take(self) -> None:
        ring = self._log.records()      # newest first
        fresh = []
        for rec in ring:
            if rec is self._newest:
                break
            fresh.append(rec)
        else:
            self.overruns += self._newest is not None
        if ring:
            self._newest = ring[0]
        self.records += [r for r in reversed(fresh)
                         if r.get("ts", 0) >= self.since
                         and r["expr"] in self._asked]

    def run(self) -> None:
        while not self._halt.wait(self._every_s):
            self.take()

    def halt(self) -> None:
        self._halt.set()

    def finish(self) -> list[dict]:
        """Stop, read the ring once more -> the records, oldest first."""
        self.halt()
        if self.is_alive():
            self.join()
        self.take()
        return self.records


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
    fleet = fleet_skewed.for_run(run, n_blocks)
    if run.param(cfg, "query_fanout_series") != fleet.per_job:
        raise ValueError("a panel reads one job: query_fanout_series must "
                         "equal instances_per_job x handlers")
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

    watchdog, child, tap = state["watchdog"], None, None
    try:
        child = loadgen.Child()
        watchdog.children = (child,)
        run.emit("loadgen", server_pid=os.getpid(), loadgen_pid=child.pid)
        clock_gap = child.handshake(dict(
            sealed_loop._range(mix, fleet), port=run.svc.http_port,
            queries=queries, clients=mix["clients"],
            order=[int(j) for j in order], seconds=run.seconds))
        time.sleep(mix["ramp_s"])
        gc.callbacks.append(on_gc)
        k_before = kernel_telemetry.snapshot()
        c_before = _counter_totals()
        tap = RecordTap(slowlog.log(), time.time(), queries)
        tap.take()      # the ring as the ramp left it: nothing older counts
        tap.start()
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
        if tap is not None:
            tap.halt()
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
        run.trace_summary = trace_reduce.reduce(path) if path else None

    run.slow_records = tap.finish()
    # where a stalled panel spent its time: the four slowest records
    run.emit("slowest", log_only=True, records=[
        {"at_s": round(r["ts"] - tap.since, 3), "phases": r["phases"]}
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
        # what a panel metric left out of the list above is read from
        run.timers["request_p50_s"] = [p50 / 1000.0]
        run.timers["request_p95_s"] = [p95 / 1000.0]
    first_reply = {job: loadgen.rows_of(doc)
                   for job, doc in done["first_reply"].items()}
    state.update(first_reply=first_reply, mismatched=done["differing"],
                 clock_gap=clock_gap, counters_moved={
                     name: c_after[name] - c_before[name]
                     for name in UNMOVED})
    fused = run.kernels.get(FUSED, {})
    return {"attempted": n + len(errors), "failed": len(errors),
            "end_to_end": end_to_end,
            "summary": {"requests": n, "errors": errors[:3],
                        "elapsed_s": round(elapsed, 3),
                        "panels_per_s": round(n / elapsed, 3),
                        "distinct_jobs": len(first_reply),
                        "max_ms": round(float(lat.max(initial=0)), 1),
                        "beyond_p95": beyond_p95,
                        "fused_calls": fused.get("invocations", 0),
                        "records": len(run.slow_records),
                        "record_tap_overruns": tap.overruns,
                        "rows_per_reply": sorted({
                            len(rows) for rows in first_reply.values()}),
                        # the program's shape as its records have it,
                        # where they do
                        "lanes_groups_k": sorted({
                            (r["lanes"], r["lanes_pad"], r["groups"],
                             r["topk_k"])
                            for r in run.slow_records if "groups" in r}),
                        "gc_full_s": round(sum(s for _, s in gc_pauses), 3),
                        "compiles_in_window": sum(
                            k.get("compiles", 0)
                            for k in run.kernels.values())}}


def compare_job(fleet, mix, job: int, rows: dict, steps) -> dict:
    """The first reply of one job against the reference."""
    ts, vs = fleet.job_arrays(job)
    ids, sums = reference_topk.group_sums(
        ts, vs, steps, mix["range_s"],
        fleet.instance_of(np.arange(fleet.per_job)))
    return reference_topk.compare(
        rows, mix["by"], [fleet.instance_name(i) for i in ids], steps, sums,
        mix["k"], mix["limits"]["panel_max_rel_gap"])


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
    run.check("topk_steps_miscounted", worst("steps_miscounted"), 0)
    run.check("topk_points_misranked", worst("points_misranked"), 0)
    run.check("topk_rows_unknown", worst("rows_unknown"), 0)
    run.check("topk_rows_without_a_point", worst("rows_without_a_point"), 0)
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
    # its tree left to the host, nothing declined
    run.check("records_not_served_whole_by_the_fused_program", sum(
        not r.get("device_serving")
        or r.get("device_tier", {}).get("host_nodes") != 0
        for r in run.slow_records), 0)
    # over every panel: each has its record (those in flight when the
    # window opened add up to `clients` records more), none left the
    # ring unread
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
    run.emit("check_done", jobs_compared=len(found),
             least_job_gap=min((f["max_rel_gap"] for f in found),
                               default=0.0),
             seconds=round(time.perf_counter() - t0, 2))
