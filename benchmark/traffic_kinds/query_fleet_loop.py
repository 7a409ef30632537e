"""Closed-loop fleet-wide panels over sealed blocks: every panel reads
every series the node holds.

Set-up is query_closed_loop's: the config's fleet written block by
block at the config's backfill pace and sealed by the service's own
tick + flush; then one panel of each of the mix's `queries`, so that
each query's device program is loaded.  The clients run in a process of
their own (harness/loadgen_fleet.py, a child): `clients` threads, each
on its own keep-alive connection, each sending its next `query_range`
when the last reply is parsed, each going round the queries; the client
that starts on the second query is drawn from the seed.  The loop runs
`ramp_s` seconds before the window opens (set-up).  With --trace 1 a
slice of `trace_slice_s` seconds a third into the window is traced.

Nothing here waits without a limit.  The warm panels and every request
of the clients give up after `request_timeout_s`; a watchdog ends the
process (exit 1, no result line) if the window has not opened
`open_within_s` after the process started, or has not closed
`seconds + request_timeout_s + 10` after it opened, whatever the main
thread is stuck in; a request that timed out raises.  The watchdog is a
thread that sleeps: it holds no lock of the interpreter while the run
is sound.

The check, after the window: every reply equals the first reply of its
query, and that one is compared with the numpy reference computed from
the generator's arrays of all series, summed by job and by zone; every
record of the window was served by the device tier; the fleet is read
back (count_over_time per job, host tier) and equals the samples
acknowledged.
"""

from __future__ import annotations

import gc
import os
import sys
import threading
import time

import numpy as np

from harness import fleet as fleets
from harness import loadgen, loadgen_fleet, loadgen_live, reference, service
from harness import trace_reduce
from traffic_kinds import query_closed_loop as sealed_loop


class Watchdog:
    """Ends the process with exit code 1 unless `done()` is called
    within `seconds` of `since` (a `time.perf_counter()` reading)."""

    def __init__(self, what: str, since: float, seconds: float,
                 children=()):
        self.what, self.seconds, self.children = what, seconds, children
        self._done = threading.Event()
        self._wait_s = since + seconds - time.perf_counter()
        threading.Thread(target=self._watch, name="bench-watchdog",
                         daemon=True).start()

    def _watch(self):
        if self._done.wait(max(self._wait_s, 0.0)):
            return
        print(f"benchmark: {self.what} not reached within "
              f"{self.seconds:g} s: the run is ended", file=sys.stderr,
              flush=True)
        for child in self.children:
            child.proc.kill()
        sys.stdout.flush()
        os._exit(1)

    def done(self):
        self._done.set()


def _process_started(run) -> float:
    """run.py's reading of the clock when the process started (what
    `setup_s` counts from)."""
    return sys.modules[type(run).__module__].T_PROCESS


def _queries(mix: dict, fleet) -> list[str]:
    return [q["query"].replace("<METRIC>", fleet.metric)
            for q in mix["queries"]]


def _range(mix: dict, fleet) -> dict:
    return {"start": fleet.t0 + mix["start_offset_s"],
            "end": fleet.seal_end - mix["step_s"], "step": mix["step_s"]}


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
    if run.param(cfg, "query_fanout_series") != fleet.n_series:
        raise ValueError("a panel reads the whole fleet: query_fanout_series "
                         "must equal jobs x instances_per_job")
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
    for query in _queries(mix, fleet):
        before = kernel_telemetry.snapshot()
        seconds, _, _ = loadgen.panel(client, query, **_range(mix, fleet))
        after = kernel_telemetry.snapshot()
        run.emit("warm", query=query, seconds=round(seconds, 3), kernels={
            k: {f: round(st[f] - before.get(k, {}).get(f, 0), 3)
                for f in ("invocations", "compiles", "compile_s",
                          "execute_s")}
            for k, st in after.items()
            if st["invocations"] - before.get(k, {}).get("invocations", 0)})
    client.close()
    return {"fleet": fleet, "acked": acked}


def window(run, state):
    import jax

    from m3_tpu.ops import kernel_telemetry
    from m3_tpu.query import slowlog

    fleet, mix = state["fleet"], run.mix
    queries = _queries(mix, fleet)
    # client i starts on query i; the one that starts on the second
    # query is the seed's
    on_second = int(np.random.default_rng([run.seed, 0xf1ee]).integers(
        mix["clients"]))
    first = [(i - on_second + 1) % len(queries)
             for i in range(mix["clients"])]
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
        child = loadgen_live.Child(loadgen_fleet.__file__)
        watchdog.children = (child,)
        run.emit("loadgen", server_pid=os.getpid(), loadgen_pid=child.pid,
                 first_query_of_client=first)
        clock_gap = child.handshake(dict(
            _range(mix, fleet), port=run.svc.http_port, queries=queries,
            first=first, timeout_s=mix["request_timeout_s"],
            seconds=run.seconds))
        time.sleep(mix["ramp_s"])
        gc.callbacks.append(on_gc)
        k_before = kernel_telemetry.snapshot()
        t_wall = time.time()
        t_start = run.window_opens()
        child.window_opens(t_start)
        watchdog.done()
        watchdog = Watchdog("the window's end", t_start, run.seconds
                            + mix["request_timeout_s"] + 10, (child,))
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
    if done["timed_out"]:
        raise RuntimeError(f"a request gave up after "
                           f"{mix['request_timeout_s']:g} s: "
                           f"{done['timed_out']}")
    sent_at, ms, asked = ([p[k] for p in done["panels"]] for k in range(3))
    errors = done["errors"]
    # for reading a far-off run without a second one: every panel of
    # the window, and the interpreter's full collections
    run.emit("panels", log_only=True, gc_full=gc_pauses,
             sent_at_s=[round(t, 3) for t in sent_at],
             ms=[round(x, 2) for x in ms], query=asked)
    if run.trace:
        path = trace_reduce.find_xplane(trace_dir)
        run.trace_summary = trace_reduce.reduce(path) if path else None

    run.slow_records = [r for r in slowlog.log().records()
                        if r.get("ts", 0) >= t_wall and r["expr"] in queries]
    run.emit("slowest", log_only=True, records=[
        {"at_s": round(r.get("ts", t_wall) - t_wall, 3), "phases": r["phases"]}
        for r in sorted(run.slow_records,
                        key=lambda r: -r["phases"]["total_s"])[:4]])
    k_after = kernel_telemetry.snapshot()
    # counts and seconds as the window's delta; a peak (hbm_peak_bytes,
    # where the program records one) as it stands after it
    run.kernels = {
        name: {f: st[f] if f.endswith("_peak_bytes")
               else st[f] - k_before.get(name, {}).get(f, 0) for f in st}
        for name, st in k_after.items()}
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
        run.timers["request_p95_s"] = [p95 / 1000.0]   # panel_p95_ms.fan
    state.update(
        first_reply={q: loadgen.rows_of(doc)
                     for q, doc in done["first_reply"].items()},
        mismatched=done["differing"], clock_gap=clock_gap)
    return {"attempted": n + len(errors), "failed": len(errors),
            "end_to_end": end_to_end,
            "summary": {"requests": n, "errors": errors[:3],
                        "elapsed_s": round(elapsed, 3),
                        "panels_per_s": round(n / elapsed, 3),
                        "per_query": [asked.count(q)
                                      for q in range(len(queries))],
                        "max_ms": round(float(lat.max(initial=0)), 1),
                        "beyond_p95": beyond_p95,
                        # the fan-out as the program's records have
                        # it, where they do
                        "lanes_pad_chunks": sorted({
                            (r["lanes"], r["lanes_pad"], r["lane_chunks"])
                            for r in run.slow_records if "lanes" in r}),
                        "gc_full_s": round(sum(s for _, s in gc_pauses), 3),
                        "compiles_in_window": sum(
                            k.get("compiles", 0)
                            for k in run.kernels.values())}}


def check(run, state, result):
    fleet, mix = state["fleet"], run.mix
    steps = np.arange(fleet.t0 + mix["start_offset_s"],
                      fleet.seal_end - mix["step_s"] + 1, mix["step_s"],
                      dtype=np.int64)
    t0 = time.perf_counter()
    series = np.arange(fleet.n_series)
    # the label each query groups by: its name, the group of every
    # series, the group's value as the program spells it
    by = {"job": (series // fleet.instances, fleet.job_name),
          "zone": (series % fleet.instances % fleet.zones,
                   lambda z: f"zone-{z}")}
    rates = np.concatenate([
        reference.rate(*fleet.job_arrays(j), steps, mix["range_s"])
        for j in range(fleet.jobs)])
    gaps = {}
    for q, rows in sorted(state["first_reply"].items()):
        label = mix["queries"][q]["by"]
        groups, name = by[label]
        want = reference.drop_nan(steps, {
            ((label, name(g)),): row
            for g, row in reference.sum_by(groups, rates).items()})
        gaps[label] = reference.max_rel_gap(rows, want)
    run.check("panel_max_rel_gap", max(gaps.values(), default=0.0),
              mix["limits"]["panel_max_rel_gap"])
    run.check("queries_without_a_reply",
              len(mix["queries"]) - len(state["first_reply"]), 0)
    run.check("replies_differing_from_first_of_query",
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
              max(result["summary"]["requests"] - len(run.slow_records), 0),
              0)

    # read-back by the host tier of the same engine: every acknowledged
    # sample of every job is in the sealed blocks
    from m3_tpu.query.engine import Engine
    host = Engine(run.svc.db, run.svc.cfg.unagg_namespace,
                  device_serving=False)
    span = fleet.seal_end - fleet.t0
    at = (fleet.seal_end - fleet.cadence_s) * 10**9
    counted = found = 0
    for j in range(fleet.jobs):
        _, mat = host.query_range(
            f'count_over_time({fleet.metric}{{job="{fleet.job_name(j)}"}}'
            f'[{span}s])', at, at, 10**9)
        col = np.asarray(mat.values)[:, -1]
        found += int((~np.isnan(col)).sum())
        counted += int(np.nansum(col))
    run.check("samples_acked_minus_read_back", state["acked"] - counted, 0,
              ok=counted == state["acked"])
    run.check("series_missing", fleet.n_series - found, 0,
              ok=found == fleet.n_series)
    run.emit("check_done", gap_by=gaps,
             seconds=round(time.perf_counter() - t0, 2))
