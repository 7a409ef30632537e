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
them free waits for one, and one that starts more than `late_after_s`
behind its due time is counted late.  The ticks between `first_tick`
and the moment this process starts are due already: they are sent at
once, in order, as the end of the catch-up replay (`catch_up`, not
counted late), and the schedule runs from there.  It imports numpy,
harness/fleet.py, wire.py and client.py; nothing of the program.

The conversation is loadgen.py's (so loadgen_live.Child drives it):

    child   {"ready": <pid>}
    parent  {"port", "fleet": {"cfg", "seed", "now_s", "n_blocks"},
             "first_tick", "clock_offset_s", "late_after_s",
             "connections", "seconds"}
    child   {"clock": <its time.perf_counter()>} and the loop starts
    parent  {"window_opens_at": <the parent's perf_counter reading>}
    child   {"requests": [[job, tick, due, sent, acked, late,
             catch_up], ...], "samples_acked", "errors": [...]}

`due`, `sent` and `acked` are `time.perf_counter()` readings; the
requests come in the order of their acknowledgement.  The loop ends
with the first request due after the window's end, which it learns
when the parent opens the window, some time after the loop's start.
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
    started = time.perf_counter()

    def send(job: int, tick: int, due: float, body: bytes) -> None:
        if not hasattr(local, "client"):
            local.client = Client(spec["port"])
        sent = time.perf_counter()
        try:
            local.client.remote_write(body)
        except Exception as e:  # noqa: BLE001 - counted; the schedule
            # goes on
            with lock:
                errors.append(f"{type(e).__name__}: {e}"[:300])
            local.client.close()
            local.client = Client(spec["port"])
            return
        acked = time.perf_counter()
        catch_up = due < started
        late = not catch_up and sent - due > spec["late_after_s"]
        with lock:
            requests.append([job, tick, due, sent, acked, late, catch_up])

    def schedule(pool) -> None:
        for tick in range(spec["first_tick"], fleet.per_block):
            for job in range(fleet.jobs):
                due = (float(scrape.ts_s[tick]) + to_perf
                       + job * fleet.cadence_s / fleet.jobs)
                if due > window_end[0]:
                    return
                body = scrape.body(job, tick)
                time.sleep(max(0.0, due - time.perf_counter()))
                pool.submit(send, job, tick, due, body)
        errors.append("the open block ran out of ticks")

    with concurrent.futures.ThreadPoolExecutor(spec["connections"]) as pool:
        schedule(pool)
    return {"requests": requests, "errors": errors,
            "samples_acked": len(requests) * fleet.instances}


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
