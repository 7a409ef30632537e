#!/usr/bin/env python3
"""The fleet's scrape, remote-written tick by tick, in an interpreter of
its own: an open loop on the node's clock.

Every `cadence_s` seconds each job sends one remote-write request of its
series x 1 sample (the sample of the fleet's tick, harness/fleet.py),
the jobs spread evenly over the cadence: job j's request for the tick
at data time T is due at node time T + j * cadence / jobs.  A request
is sent when it is due, whatever became of the ones before it, as an
agent's remote-write shards do: `connections` keep-alive connections,
each with one request in flight at most.  A request that finds none of
them free waits for one.  The ticks between `first_tick` and the moment
this process starts are due already: they are sent at once, in order,
as the end of the catch-up replay (`catch_up`, never counted late or
held), and the schedule runs from there.  It imports numpy,
harness/fleet.py, wire.py and client.py; nothing of the program.

A request that starts more than `late_after_s` behind its due time is
`late`, and the generator records what it can tell apart about why:

    woke   the schedule thread's clock straight after its sleep, before
           the request is handed to a connection.  `woke - due` is how
           late this process ran: the host's doing.
    held   whether every one of the `connections` had a request in
           flight when this one was handed over, by a count kept under
           the generator's lock and by no timing (a slow hand-off on a
           machine that stood still must not read as the node's doing).
           `sent - woke` of a held request is its wait for a
           connection: the node's doing.
    busy   that count: the requests in flight ahead of this one.

A witness of the host that does not depend on when a request happens
to be due: one more thread (`HostWitness`) sleeps `NAP_S` at a time and
keeps every sleep that overran by more than `STALL_S` as `[at,
seconds]`.  This process is idle but for a few sends a second, so an
overrun is the machine's.  `account` turns the rows into the window's
numbers, the ones the kind judges and the ones it only reports; it is
here so that one arithmetic serves the run and its tests.

The conversation is loadgen.py's (so loadgen_live.Child drives it):

    child   {"ready": <pid>}
    parent  {"port", "fleet": {"cfg", "seed", "now_s", "n_blocks"},
             "first_tick", "clock_offset_s", "late_after_s",
             "connections", "seconds"}
    child   {"clock": <its time.perf_counter()>} and the loop starts
    parent  {"window_opens_at": <the parent's perf_counter reading>}
    child   {"requests": [[job, tick, due, sent, acked, late,
             catch_up, woke, held, busy], ...], "samples_acked",
             "errors": [...], "to_perf", "host_stalls": [[at,
             seconds], ...]}

`due`, `woke`, `sent`, `acked` and a stall's `at` (the moment its sleep
should have ended) are `time.perf_counter()` readings; `to_perf` is
the reading at which the node's clock reads 0, which the schedule is
laid by; the requests come in the order of their acknowledgement.  The
loop ends with the first request due after the window's end, which it
learns when the parent opens the window, some time after the loop's
start.
"""

from __future__ import annotations

import concurrent.futures
import json
import os
import pathlib
import sys
import threading
import time

if __name__ == "__main__":       # started as a file: harness/ -> benchmark/
    sys.path[0] = str(pathlib.Path(__file__).resolve().parents[1])

from harness.client import Client  # noqa: E402
from harness.fleet import Fleet  # noqa: E402
from harness import wire  # noqa: E402


class Scrape:
    """Job j's request for tick c of the block after the sealed ones."""

    def __init__(self, fleet: Fleet):
        self.fleet, self.block = fleet, fleet.n_blocks
        self.ts_s = fleet.block_ts(self.block)
        self._values = {}       # job -> float64 [instances, per_block]
        self._labels = {}

    def body(self, job: int, tick: int) -> bytes:
        f = self.fleet
        lo = job * f.instances
        if job not in self._values:
            self._values[job] = f.block_values(lo, lo + f.instances,
                                               self.block)
            self._labels[job] = [wire.label_bytes(f.labels(i))
                                 for i in range(lo, lo + f.instances)]
        return wire.write_request(
            self._labels[job], self.ts_s[tick:tick + 1] * 1000,
            self._values[job][:, tick:tick + 1])


# a request's row, by index
JOB, TICK, DUE, SENT, ACKED, LATE, CATCH_UP, WOKE, HELD, BUSY = range(10)

NAP_S = 0.01        # the witness's sleep
STALL_S = 0.05      # an overrun it keeps


def schedule_of(ts_s, first_tick: int, to_perf: float, cadence_s: float,
                jobs: int):
    """The schedule's law -> (job, tick, due) in the order they fall
    due: job j's request for the tick at data time T is due when the
    node's clock reads T + j * cadence / jobs.  The generator sends by
    it and the kind counts by it what was due in a window."""
    for tick in range(first_tick, len(ts_s)):
        for job in range(jobs):
            yield job, tick, (float(ts_s[tick]) + to_perf
                              + job * cadence_s / jobs)


class HostWitness(threading.Thread):
    """Sleeps NAP_S at a time until `end()`; `stalls` holds `[at,
    seconds]` of every sleep that overran by more than STALL_S, `at`
    the moment it should have ended."""

    def __init__(self):
        super().__init__(name="host-witness", daemon=True)
        self.stalls: list[list[float]] = []
        self._ended = False

    def run(self) -> None:
        while not self._ended:
            wake_at = time.perf_counter() + NAP_S
            time.sleep(NAP_S)
            over = time.perf_counter() - wake_at
            if over > STALL_S:
                self.stalls.append([wake_at, over])

    def end(self) -> list[list[float]]:
        self._ended = True
        self.join()
        return self.stalls


def account(requests: list, due_pairs: list, host_stalls: list,
            t_start: float, seconds: float, late_after_s: float,
            cadence_s: float, connections: int) -> dict:
    """The scrape's numbers of one window, from the generator's rows,
    its witness's stalls and `due_pairs`, the (job, tick) the schedule
    has due in the window.  A request is the window's when it was sent
    after the window opened; the catch-up's are neither late nor held.

    Judged by the kind: `scrapes_held_share` (requests that found every
    connection taken and then waited more than `late_after_s` for one,
    over the window's requests: the node held the loop closed),
    `scrapes_missing` (pairs due in the window that no request was sent
    for), `scrapes_a_tick_behind` (requests sent a cadence or more
    after they were due: over some period the rate offered was not the
    mix's, whoever's doing).  Reported only: the rest.
    """
    rows = [r for r in requests if r[SENT] >= t_start]
    live = [r for r in rows if not r[CATCH_UP]]
    late = [r for r in live if r[LATE]]
    held = [r for r in live if r[HELD]]
    sent_for = {(r[JOB], r[TICK]) for r in requests}
    inside = [s for at, s in host_stalls
              if t_start <= at + s and at <= t_start + seconds]
    return {
        "scrapes": len(rows),
        "scrapes_late": len(late),
        "scrapes_woke_late": sum(not r[HELD] for r in late),
        "scrapes_held": len(held),
        "write_connections_busy_max": max(
            (min(r[BUSY] + 1, connections) for r in rows), default=0),
        "host_stall_max_ms": max(inside, default=0.0) * 1000,
        "host_stalled_ms": sum(inside, 0.0) * 1000,
        "scrapes_held_share": sum(
            r[SENT] - r[WOKE] > late_after_s for r in held)
        / max(len(rows), 1),
        "scrapes_missing": sum(p not in sent_for for p in due_pairs),
        "scrapes_a_tick_behind": sum(
            r[SENT] - r[DUE] >= cadence_s for r in live),
    }


def open_loop(spec: dict, window_end: list) -> dict:
    f = spec["fleet"]
    fleet = Fleet(f["cfg"], f["seed"], f["now_s"], f["n_blocks"])
    scrape = Scrape(fleet)
    for job in range(fleet.jobs):        # the fleet's arrays, before
        scrape.body(job, spec["first_tick"])    # the schedule starts
    # perf_counter reading at which the node's clock reads t
    to_perf = (time.perf_counter() - time.time()
               - spec["clock_offset_s"])
    local = threading.local()
    lock = threading.Lock()
    requests, errors = [], []
    in_flight = [0]         # handed to the pool and not yet ended
    started = time.perf_counter()

    def send(job: int, tick: int, due: float, woke: float, busy: int,
             body: bytes) -> None:
        try:
            if not hasattr(local, "client"):
                local.client = Client(spec["port"])
            sent = time.perf_counter()
            try:
                local.client.remote_write(body)
            except Exception as e:  # noqa: BLE001 - counted; the
                # schedule goes on
                with lock:
                    errors.append(f"{type(e).__name__}: {e}"[:300])
                local.client.close()
                local.client = Client(spec["port"])
                return
            acked = time.perf_counter()
            catch_up = due < started
            late = not catch_up and sent - due > spec["late_after_s"]
            held = not catch_up and busy >= spec["connections"]
            with lock:
                requests.append([job, tick, due, sent, acked, late,
                                 catch_up, woke, held, busy])
        finally:
            with lock:
                in_flight[0] -= 1

    def schedule(pool) -> None:
        for job, tick, due in schedule_of(
                scrape.ts_s, spec["first_tick"], to_perf, fleet.cadence_s,
                fleet.jobs):
            if due > window_end[0]:
                return
            body = scrape.body(job, tick)
            time.sleep(max(0.0, due - time.perf_counter()))
            woke = time.perf_counter()
            with lock:
                busy = in_flight[0]
                in_flight[0] += 1
            pool.submit(send, job, tick, due, woke, busy, body)
        errors.append("the open block ran out of ticks")

    witness = HostWitness()
    witness.start()
    try:
        with concurrent.futures.ThreadPoolExecutor(
                spec["connections"]) as pool:
            schedule(pool)
    finally:
        host_stalls = witness.end()
    return {"requests": requests, "errors": errors,
            "samples_acked": len(requests) * fleet.instances,
            "to_perf": to_perf, "host_stalls": host_stalls}


def main() -> int:
    def say(doc):
        sys.stdout.write(json.dumps(doc) + "\n")
        sys.stdout.flush()

    say({"ready": os.getpid()})
    spec = json.loads(sys.stdin.readline())
    say({"clock": time.perf_counter()})
    window_end = [float("inf")]

    def hear_window():
        opened = json.loads(sys.stdin.readline())["window_opens_at"]
        window_end[0] = opened + spec["seconds"]

    threading.Thread(target=hear_window, daemon=True).start()
    say(open_loop(spec, window_end))
    return 0


if __name__ == "__main__":
    sys.exit(main())
