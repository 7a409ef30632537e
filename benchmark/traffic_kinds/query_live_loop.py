"""Closed-loop dashboard panels whose range ends at now, under ingest,
beside the node's own mediator, at a fixed phase of the block cycle.

Set-up, in this order.  The node's clock (m3_tpu/utils/clock.py; a
program without it cannot run this kind, and fails here, at import) is
set so that it reads `block_phase_s` past a block's start, before any
write; it runs at the wall clock's rate from there, and every time in
this file's traffic is a reading of it.  The config's fleet is written
block by block at its backfill pace and sealed with the service's own
tick + flush; then the live tail (`live_tail_s` of samples after the
last sealed block) is written at the same pace and stays in the open
buffers.  From there on the fleet is scraped: harness/scrapegen.py, a
child process, sends the scrape as remote-write requests, open loop on
the node's clock, first the ticks that fell due during the backfill,
at once (the end of the catch-up), then each when it is due; it logs
every request's due, wake, send and acknowledgement time, whether it
started late and whether it found every connection taken, and beside
them every stall of its own process (its witness thread).  One
snapshot is taken, so that what the mediator's snapshot runs is loaded,
and the node's own `Mediator` is started with the config's periods.
One panel is sent, so that the device program is loaded.
The second child is harness/loadgen_live.py: `clients` closed-loop
readers; it logs every panel's send and completion time, its range's
end, the first and last reply of each job and, for every other reply,
the earliest step at which it differs from the first of its job.  The
readers run for `ramp_s` seconds before the window opens,
`mediator_lead_s` (plus whole snapshot periods, where warm-up took
longer) after the mediator's start, so that every window holds the
same ticks and one snapshot at the same offsets; the writer has been
on its schedule for most of a minute by then.  This process, the
server's, keeps the slow-query
records, the kernel telemetry, the trace and every check, and no
thread of the harness but the main one, which sleeps through the
window.  With --trace 1 a slice of `trace_slice_s` seconds from
`trace_start_s` into the window is traced: the one that holds the
mediator's snapshot pass.

End to end the cell reports `panel_ms_p95` and `setup_s`.  The median
of the same panels is in the `window_done` line (`panel_ms_p50`) and,
traced, in `panel_median_ms.live`, and is not judged: four closed-loop
readers under one interpreter lock follow the speed of the host they
share with other machines, sets of six runs spread by 1.7 to 5.4% in
it (PR 29), and a new cell is admitted at half the metric's 4%.

What the readers under readers/ find on `run` afterwards: `timers`
(`request_s`: every panel's seconds, send to parsed reply;
`write_ack_s`: every scrape request's seconds, send to
acknowledgement), `slow_records` (the window's slow-query records of
the panel's query), `kernels` (the kernel telemetry's delta over the
window) and, with --trace 1, `trace_summary`.

The check, after the window: the first and the last reply of each job
against the numpy reference on the generator's arrays, cut to the
scrape ticks the writer's log shows acknowledged before the reader's
log shows the panel sent (a step whose range may hold a sample in
flight is compared with both answers); every other reply equal to the
first of its job, bit for bit, on the steps they share up to that
first reply's last acknowledged tick; no failed panel or write, no
compile in the window, every record served by the device tier, both
children on the parent's clock, the mediator without an error; and the
fleet read back by the host tier (count_over_time per job over sealed
blocks and open buffers) equal to the samples acknowledged.

That the traffic offered was the mix's is three checks, each on what
the node or the generator did and none on the host's scheduler
(harness/scrapegen.account): `scrapes_held_share`, the requests that
found all `write_connections` taken and then waited more than
`late_after_s` for one (the node held the loop closed);
`scrapes_missing`, (job, tick) pairs the schedule has due in the
window that no request was sent for; `scrapes_a_tick_behind`, requests
sent a whole cadence or more after they were due.  How late the
generator's own thread woke, and every stall of its process, is the
host's: reported in every run (`scrapes_late`, `scrapes_woke_late`,
`host_stall_max_ms`, `host_stalled_ms`; the log's `scrapes` and
`host_stalls` lines) and judged in none.
"""

from __future__ import annotations

# first: the parent of the PR that brought this kind has no such
# clock, and has to fail at once rather than serve this traffic
from m3_tpu.utils import clock  # isort: skip

import gc
import math
import os
import pathlib
import time

import numpy as np

from harness import (loadgen, loadgen_live, reference, scrapegen, service,
                     trace_reduce, wire)
from harness.client import Client
from harness.fleet import Fleet
from traffic_kinds.query_closed_loop import _job_order, _query

HARNESS = pathlib.Path(loadgen_live.__file__).resolve().parent


def _write_block(fleet: Fleet, client: Client, samples_per_s: float,
                 pace: dict, block: int, cols: int) -> None:
    """The first `cols` samples of every series of one block, in the
    backfill's requests (25 series each), no faster than
    `samples_per_s` over the whole backfill (`pace`: its start and the
    samples acknowledged so far)."""
    ts_ms = fleet.block_ts(block)[:cols] * 1000
    for lo, hi in fleet.block_requests(block):
        vals = fleet.block_values(lo, hi, block)[:, :cols]
        body = wire.write_request(
            [wire.label_bytes(fleet.labels(i)) for i in range(lo, hi)],
            ts_ms, vals)
        time.sleep(max(0.0, pace["t0"] + pace["acked"] / samples_per_s
                       - time.perf_counter()))
        client.remote_write(body)
        pace["acked"] += vals.size


def setup(run):
    cfg, mix = run.config, run.mix
    block_s = cfg["block_s"]
    # the node's clock, before any write: block_phase_s past the start
    # of the block the wall clock is in
    wall = time.time()
    anchor = math.floor(wall / block_s) * block_s + cfg["block_phase_s"]
    clock.set_offset_nanos(round((anchor - wall) * 1e9))
    offset_s = clock.offset_nanos() / 1e9
    n_blocks = run.param(cfg, "hours") * 3600 // block_s
    fleet = Fleet(dict(cfg, jobs=run.param(cfg, "jobs"),
                       instances_per_job=run.param(cfg, "instances_per_job")),
                  run.seed, anchor, n_blocks)
    if run.param(cfg, "query_fanout_series") != fleet.instances:
        raise ValueError("a panel reads one job: query_fanout_series "
                         "must equal instances_per_job")
    if fleet.seal_end + cfg["live_tail_s"] != anchor:
        raise ValueError("the live tail must end at the clock's anchor")
    tail_cols = cfg["live_tail_s"] // fleet.cadence_s
    run.emit("clock", offset_s=offset_s, anchor_s=anchor,
             open_block_s=fleet.seal_end, tail_samples=tail_cols)

    client = Client(run.svc.http_port)
    pace = {"t0": time.perf_counter(), "acked": 0}
    for k in range(n_blocks):
        _write_block(fleet, client, cfg["backfill_samples_per_s"], pace, k,
                     fleet.per_block)
    run.emit("ingest", series=fleet.n_series, blocks=n_blocks,
             samples_acked=pace["acked"],
             seconds=round(time.perf_counter() - pace["t0"], 2))
    sealed = service.seal(run.svc)
    run.emit("seal", **{k: (round(v, 3) if isinstance(v, float) else v)
                        for k, v in sealed.items()})
    want = [fleet.t0 + k * block_s for k in range(n_blocks)]
    if sealed["block_starts"] != want:
        raise RuntimeError(f"sealed {sealed['block_starts']}, want {want}")
    t0 = time.perf_counter()
    sealed_acked = pace["acked"]
    pace = {"t0": t0, "acked": 0}
    _write_block(fleet, client, cfg["backfill_samples_per_s"], pace,
                 n_blocks, tail_cols)
    t1 = time.perf_counter()
    client.close()
    run.emit("tail", samples_acked=pace["acked"], seconds=round(t1 - t0, 2))
    state = {"fleet": fleet, "offset_s": offset_s, "anchor": anchor,
             "tail_cols": tail_cols,
             "acked": sealed_acked + pace["acked"]}
    # from here on the fleet is scraped, tick by tick
    state["writer"] = writer = loadgen_live.Child(HARNESS / "scrapegen.py")
    try:
        _warm(run, state)
    except BaseException:
        writer.stop()
        raise
    return state


def _warm(run, state) -> None:
    """The rest of set-up, with the scrape running beside it."""
    cfg, mix, fleet = run.config, run.mix, state["fleet"]
    state["clock_gap_writer"] = state["writer"].handshake(dict(
        port=run.svc.http_port, seconds=run.seconds,
        fleet={"cfg": fleet.cfg, "seed": fleet.seed,
               "now_s": state["anchor"], "n_blocks": fleet.n_blocks},
        first_tick=state["tail_cols"], clock_offset_s=state["offset_s"],
        late_after_s=mix["late_after_s"],
        connections=run.param(mix, "write_connections")))
    # what the mediator's snapshot runs is loaded before it starts
    t0 = time.perf_counter()
    run.svc.db.snapshot()
    run.emit("snapshot", seconds=round(time.perf_counter() - t0, 2),
             writer_pid=state["writer"].pid)

    from m3_tpu.storage.database import Mediator
    med = cfg["mediator"]
    run.svc.mediator = Mediator(run.svc.db, tick_every=med["tick_every_s"],
                                snapshot_every=med["snapshot_every_s"])
    run.svc.mediator.start()           # the service's stop() stops it
    state["t_mediator"] = time.perf_counter()

    from m3_tpu.ops import kernel_telemetry
    client = Client(run.svc.http_port)
    before = kernel_telemetry.snapshot()
    seconds, _, _ = loadgen.panel(
        client, _query(mix, fleet, 0), **loadgen_live.live_range(
            state["offset_s"], mix["span_s"], mix["step_s"]))
    after = kernel_telemetry.snapshot()
    run.emit("warm", seconds=round(seconds, 3), kernels={
        k: {f: round(st[f] - before.get(k, {}).get(f, 0), 3)
            for f in ("invocations", "compiles", "compile_s", "execute_s")}
        for k, st in after.items()
        if st["invocations"] - before.get(k, {}).get("invocations", 0)})
    client.close()


def window(run, state):
    import jax

    from m3_tpu.ops import kernel_telemetry
    from m3_tpu.query import slowlog

    fleet, mix, cfg = state["fleet"], run.mix, run.config
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

    writer = state["writer"]
    readers = None
    try:
        readers = loadgen_live.Child()
        run.emit("loadgen", server_pid=os.getpid(), readers_pid=readers.pid,
                 writer_pid=writer.pid)
        # the window opens mediator_lead_s after the mediator's start,
        # or whole snapshot periods later where warm-up took longer
        opens = state["t_mediator"] + run.param(mix, "mediator_lead_s")
        while opens - mix["ramp_s"] < time.perf_counter() + 0.5:
            opens += cfg["mediator"]["snapshot_every_s"]
        time.sleep(opens - mix["ramp_s"] - time.perf_counter())
        gap_r = readers.handshake(dict(
            port=run.svc.http_port, seconds=run.seconds,
            queries=[_query(mix, fleet, j) for j in range(fleet.jobs)],
            span=mix["span_s"], step=mix["step_s"],
            clock_offset_s=state["offset_s"], clients=mix["clients"],
            order=[int(j) for j in order]))
        time.sleep(max(0.0, opens - time.perf_counter()))
        gc.callbacks.append(on_gc)
        k_before = kernel_telemetry.snapshot()
        t_wall = time.time()
        t_start = run.window_opens()
        readers.window_opens(t_start)
        writer.window_opens(t_start)
        if run.trace:
            # a slice that holds the mediator's snapshot pass from its
            # start to its end; the Python tracer is off, the decode
            # scan alone is thousands of events
            time.sleep(min(mix["trace_start_s"], run.seconds / 3))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            trace_dir = run.trace_dir()
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation("bench:window"):
                time.sleep(min(mix["trace_slice_s"], run.seconds / 3))
            jax.profiler.stop_trace()
        done = readers.result()     # blocks until the loop has ended
        wrote = writer.result()
    finally:
        if readers is not None:
            readers.stop()
        writer.stop()
    elapsed = time.perf_counter() - t_start
    gc.callbacks.remove(on_gc)
    panels = done["panels"]
    ms = [p[1] for p in panels]
    errors = done["errors"]
    requests = wrote["requests"]
    in_window = [r for r in requests if r[3] >= t_start]
    scrapes = scrapegen.account(
        requests, _due_in_window(fleet, state["tail_cols"],
                                 wrote["to_perf"], t_start, run.seconds),
        wrote["host_stalls"], t_start, run.seconds, mix["late_after_s"],
        fleet.cadence_s, run.param(mix, "write_connections"))
    # for reading a far-off run without a second one: every panel and
    # every scrape request of the window, the interpreter's full
    # collections, the stalls of the generator's process
    run.emit("panels", log_only=True, gc_full=gc_pauses,
             sent_at_s=[round(p[0], 3) for p in panels],
             ms=[round(x, 2) for x in ms], job=[p[2] for p in panels],
             end_s=[p[3] for p in panels])
    run.emit("scrapes", log_only=True,
             sent_at_s=[round(r[3] - t_start, 3) for r in requests],
             ack_ms=[round((r[4] - r[3]) * 1000, 2) for r in requests],
             lag_ms=[round((r[3] - r[2]) * 1000, 2) for r in requests],
             wake_lag_ms=[round((r[7] - r[2]) * 1000, 2) for r in requests],
             conn_wait_ms=[round((r[3] - r[7]) * 1000, 2) for r in requests],
             held=[int(r[8]) for r in requests],
             busy=[r[9] for r in requests],
             job=[r[0] for r in requests], tick=[r[1] for r in requests])
    run.emit("host_stalls", log_only=True, stalls=[
        [round(at - t_start, 3), round(s * 1000, 1)]
        for at, s in wrote["host_stalls"]])
    if run.trace:
        path = trace_reduce.find_xplane(trace_dir)
        run.trace_summary = trace_reduce.reduce(path) if path else None

    expr_head = mix["query"].split("<J>")[0].replace("<METRIC>",
                                                     fleet.metric)
    run.slow_records = [r for r in slowlog.log().records()
                        if r.get("ts", 0) >= t_wall
                        and r["expr"].startswith(expr_head)]
    # every record's phases, so that a drift of the level inside the
    # window can be laid to a phase
    keys = ("fetch_s", "open_read_s", "pack_s", "device_s", "frontend_s",
            "total_s")
    run.emit("records", log_only=True,
             at_s=[round(r.get("ts", t_wall) - t_wall, 3)
                   for r in run.slow_records],
             **{k: [round(r["phases"].get(k, 0.0) * 1000, 2)
                    for r in run.slow_records] for k in keys})
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
    run.timers["write_ack_s"] = [r[4] - r[3] for r in in_window]
    lat = np.asarray(ms, dtype=np.float64)
    n = len(ms)
    end_to_end, beyond_p95 = {}, 0
    if n:
        # the median is not this cell's to be judged by (see the
        # docstring): it goes to the summary and, traced, to
        # panel_median_ms.live
        end_to_end = {"panel_ms_p95": float(np.percentile(lat, 95))}
        beyond_p95 = int((lat > end_to_end["panel_ms_p95"]).sum())
    state.update(done=done, wrote=wrote, t_start=t_start, scrapes=scrapes,
                 clock_gaps=(gap_r, state["clock_gap_writer"]))
    acks = np.asarray(run.timers["write_ack_s"]) * 1000
    return {"attempted": n + len(errors) + len(in_window)
            + len(wrote["errors"]),
            "failed": len(errors) + len(wrote["errors"]),
            "end_to_end": end_to_end,
            "summary": {"requests": n, "errors": errors[:3],
                        "write_errors": wrote["errors"][:3],
                        "elapsed_s": round(elapsed, 3),
                        "panels_per_s": round(n / elapsed, 3),
                        "distinct_jobs": len(done["first_reply"]),
                        "panel_ms_p50": float(np.median(lat)) if n else None,
                        "max_ms": round(float(lat.max(initial=0)), 1),
                        "beyond_p95": beyond_p95,
                        "scrapes_caught_up": sum(r[6] for r in requests),
                        **{k: (round(v, 1) if k.endswith("_ms") else v)
                           for k, v in scrapes.items()
                           if k not in _SCRAPE_CHECKS},
                        "write_ack_ms_p50": round(float(
                            np.median(acks)) if len(acks) else 0.0, 2),
                        "write_ack_ms_max": round(float(
                            acks.max(initial=0)), 1),
                        "gc_full_s": round(sum(s for _, s in gc_pauses), 3),
                        "compiles_in_window": sum(
                            k.get("compiles", 0)
                            for k in run.kernels.values())}}


# of scrapegen.account's numbers, the ones check() judges
_SCRAPE_CHECKS = ("scrapes_held_share", "scrapes_missing",
                  "scrapes_a_tick_behind")


def _due_in_window(fleet, first_tick: int, to_perf: float, t_start: float,
                   seconds: float):
    """(job, tick) of the scrape requests the schedule has due in the
    window, by the law the generator sends by."""
    out = []
    for job, tick, due in scrapegen.schedule_of(
            fleet.block_ts(fleet.n_blocks), first_tick, to_perf,
            fleet.cadence_s, fleet.jobs):
        if due > t_start + seconds:
            break
        if due >= t_start:
            out.append((job, tick))
    return out


def _acked_ticks(requests, job: int, before: float) -> tuple[int, int]:
    """(ticks of `job` acknowledged before `before`, ticks sent before
    it), as counts past the tail: the scrape is in tick order."""
    mine = [r for r in requests if r[0] == job]
    return (sum(r[4] < before for r in mine),
            sum(r[3] < before for r in mine))


def _panel_gap(fleet, mix, state, job: int, reply: dict, requests):
    """-> (largest relative gap of any step to the reference, steps
    that were compared with two answers because a sample of their
    range was in flight)."""
    rows = loadgen.rows_of(reply["doc"])
    steps = np.arange(reply["end"] - mix["span_s"], reply["end"] + 1,
                      mix["step_s"], dtype=np.int64)
    zones = np.arange(fleet.instances) % fleet.zones
    ts, vs = fleet.job_arrays(job, blocks=range(fleet.n_blocks + 1))
    # acknowledged before the panel was sent: in the answer; sent
    # before the reply was complete: may be
    sure, _ = _acked_ticks(requests, job, reply["sent"])
    _, maybe = _acked_ticks(requests, job, reply["done"])
    sealed_cols = fleet.n_blocks * fleet.per_block + state["tail_cols"]
    keys = [(("zone", f"zone-{z}"),) for z in range(fleet.zones)]
    if set(rows) != set(keys) or not all(
            np.array_equal(rows[k][0], steps.astype(np.float64))
            for k in keys):
        return float("inf"), 0
    served = np.stack([rows[k][1] for k in keys])
    sealed_cols = fleet.n_blocks * fleet.per_block + state["tail_cols"]
    wants = []
    for ticks in sorted({sure, maybe}):
        cols = sealed_cols + ticks
        by_zone = reference.sum_by(zones, reference.rate(
            ts[:cols], vs[:, :cols], steps, mix["range_s"]))
        wants.append(np.stack([by_zone[z] for z in range(fleet.zones)]))
    gaps = [np.where(served == w, 0.0, np.nan_to_num(
        np.abs(served - w) / np.maximum(np.abs(w), 1e-300), nan=np.inf))
        for w in wants]
    return (float(np.minimum.reduce(gaps).max()),
            int((wants[0] != wants[-1]).any(axis=0).sum()))


def check(run, state, result):
    fleet, mix = state["fleet"], run.mix
    done, wrote = state["done"], state["wrote"]
    requests = wrote["requests"]
    t0 = time.perf_counter()
    gaps, in_flight = [], []
    for which in ("first_reply", "last_reply"):
        for job, reply in sorted(done[which].items()):
            gap, both = _panel_gap(fleet, mix, state, job, reply, requests)
            gaps.append(gap)
            in_flight.append(both)
    run.check("panel_max_rel_gap", max(gaps, default=0.0),
              mix["limits"]["panel_max_rel_gap"])
    run.check("steps_in_flight_per_panel", max(in_flight, default=0),
              mix["limits"]["steps_in_flight_per_panel"])
    # every other reply: equal to the first of its job on the steps
    # they share, up to the last tick acknowledged before that first
    # reply was sent
    tick0 = fleet.seal_end + state["tail_cols"] * fleet.cadence_s
    horizon = {}
    for job, reply in done["first_reply"].items():
        sure, _ = _acked_ticks(requests, job, reply["sent"])
        horizon[job] = tick0 + (sure - 1) * fleet.cadence_s
    differing = [p[2] for p in done["panels"]
                 if p[4] is not None and p[4] <= horizon[p[2]]]
    run.check("replies_differing_from_first_of_job", len(differing), 0)
    run.check("failed_requests", len(done["errors"]), 0)
    run.check("failed_writes", len(wrote["errors"]), 0)
    # the traffic offered was the mix's: what the node and the
    # generator did to it; what the host did is in the summary
    for name in _SCRAPE_CHECKS:
        run.check(name, state["scrapes"][name], mix["limits"][name])
    run.check("no_scrape_in_window",
              0 if state["scrapes"]["scrapes"] else 1, 0)
    for name, gap in zip(("readers", "writer"), state["clock_gaps"]):
        run.check(f"loadgen_clock_gap_s.{name}", gap,
                  mix["limits"]["loadgen_clock_gap_s"])
    run.check("compiles_in_window",
              result["summary"]["compiles_in_window"], 0)
    run.check("records_not_device_served",
              sum(not r.get("device_serving") for r in run.slow_records), 0)
    run.check("no_request_completed", 0 if done["first_reply"] else 1, 0)
    run.check("mediator_errors",
              0 if run.svc.mediator.last_error is None else 1, 0)

    # read-back by the host tier of the same engine: every acknowledged
    # sample of every job is in the sealed blocks or the open buffers
    from m3_tpu.query.engine import Engine
    host = Engine(run.svc.db, run.svc.cfg.unagg_namespace,
                  device_serving=False)
    at = int(clock.now_s()) * 10**9
    span = at // 10**9 - fleet.t0 + fleet.cadence_s
    acked = state["acked"] + wrote["samples_acked"]
    counted = series = 0
    for j in range(fleet.jobs):
        _, mat = host.query_range(
            f'count_over_time({fleet.metric}{{job="{fleet.job_name(j)}"}}'
            f'[{span}s])', at, at, 10**9)
        col = np.asarray(mat.values)[:, -1]
        series += int((~np.isnan(col)).sum())
        counted += int(np.nansum(col))
    run.check("samples_acked_minus_read_back", acked - counted, 0,
              ok=counted == acked)
    run.check("series_missing", fleet.n_series - series, 0,
              ok=series == fleet.n_series)
    run.emit("check_done", panels_compared=len(gaps),
             least_panel_gap=min(gaps, default=0.0),
             samples_acked=acked,
             seconds=round(time.perf_counter() - t0, 2))
