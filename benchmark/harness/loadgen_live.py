#!/usr/bin/env python3
"""The closed-loop clients of a dashboard whose range ends at now, in an
interpreter of their own.

A sibling of loadgen.py, with the same conversation, ramp, deadline and
counting; it differs in what a panel asks for.  loadgen.py sends one
fixed `start` / `end`; here every panel's `end` is the node clock's now
rounded down to the step and its `start` lies `span` seconds before it,
as Grafana's relative range does.  The node's clock is the wall clock
plus `clock_offset_s`, handed over as a number; this file imports numpy,
harness/client.py and loadgen.py's reply helpers, nothing of the program.

Over the child's stdin and stdout, one JSON document a line:

    child   {"ready": <pid>}
    parent  {"port", "queries": [one per job], "span", "step",
             "clock_offset_s", "clients", "order": [job, ...], "seconds"}
    child   {"clock": <its time.perf_counter()>} and the loop starts
    parent  {"window_opens_at": <the parent's perf_counter reading>}
    child   {"panels": [[sent_at_s, ms, job, end_s, first_diff_step_s],
             ...], "first_reply": {job: {"sent", "done", "end", "doc"}},
             "last_reply": {job: the same}, "errors": [...]}

`sent` and `done` are `time.perf_counter()` readings (one monotonic
clock for every process of the run), so that the parent can tell which
scrapes the writer's log shows acknowledged before a panel was sent.
Every reply after the first of its job is compared with that first one
on the steps both hold: `first_diff_step_s` is the earliest step at
which they differ, or null.  The ramp's panels are left out; one in
flight at the deadline is completed and counted; a failed one is
counted, the ramp's too, and its connection reopened.
"""

from __future__ import annotations

import itertools
import json
import math
import os
import pathlib
import subprocess
import sys
import threading
import time

import numpy as np

if __name__ == "__main__":       # started as a file: harness/ -> benchmark/
    sys.path[0] = str(pathlib.Path(__file__).resolve().parents[1])

from harness import loadgen  # noqa: E402
from harness.client import Client  # noqa: E402


def live_range(clock_offset_s: float, span: int, step: int) -> dict:
    """The panel's range at this moment of the node's clock."""
    end = math.floor((time.time() + clock_offset_s) / step) * step
    return {"start": end - span, "end": end, "step": step}


def first_diff_step(rows: dict, first: dict) -> float | None:
    """The earliest step that both answers hold and at which they
    differ (a row that only one of them has differs from its start)."""
    worst = None
    for key in set(rows) | set(first):
        if key not in rows or key not in first:
            t = min(r[0][0] for r in (rows.get(key), first.get(key)) if r)
        else:
            (ta, va), (tb, vb) = rows[key], first[key]
            lo, hi = max(ta[0], tb[0]), min(ta[-1], tb[-1])
            a = (ta >= lo) & (ta <= hi)
            b = (tb >= lo) & (tb <= hi)
            if a.sum() != b.sum() or not np.array_equal(ta[a], tb[b]):
                t = lo                  # a step missing in between
            else:
                bad = np.flatnonzero(va[a] != vb[b])
                if not len(bad):
                    continue
                t = ta[a][bad[0]]
        worst = t if worst is None else min(worst, t)
    return None if worst is None else float(worst)


def closed_loop(spec: dict, window_opens_at) -> dict:
    order = spec["order"]
    next_draw = itertools.count()
    lock = threading.Lock()
    panels, errors = [], []
    first_reply, first_rows, last_reply = {}, {}, {}
    # set when the window opens, after the ramp
    t_start = deadline = float("inf")

    def client_loop():
        client = Client(spec["port"])
        try:
            while time.perf_counter() < deadline:
                with lock:
                    job = int(order[next(next_draw) % len(order)])
                rng = live_range(spec["clock_offset_s"], spec["span"],
                                 spec["step"])
                try:
                    seconds, doc, rows = loadgen.panel(
                        client, spec["queries"][job], **rng)
                except Exception as e:  # noqa: BLE001 - a failed panel
                    # is counted, the ramp's too, and the loop goes on
                    with lock:
                        errors.append(f"{type(e).__name__}: {e}"[:300])
                    client.close()
                    client = Client(spec["port"])
                    continue
                done = time.perf_counter()
                sent = done - seconds
                if sent < t_start:
                    continue                    # the ramp's
                reply = {"sent": sent, "done": done, "end": rng["end"],
                         "doc": doc}
                with lock:
                    diff = None
                    if job not in first_rows:
                        first_reply[job], first_rows[job] = reply, rows
                    else:
                        diff = first_diff_step(rows, first_rows[job])
                    last_reply[job] = reply
                    panels.append([sent - t_start, seconds * 1000.0, job,
                                   rng["end"], diff])
        finally:
            client.close()

    threads = [threading.Thread(target=client_loop, name=f"client-{i}")
               for i in range(spec["clients"])]
    for t in threads:
        t.start()
    opened = float(window_opens_at())
    deadline = opened + spec["seconds"]
    t_start = opened
    for t in threads:
        t.join()
    return {"panels": panels, "first_reply": first_reply,
            "last_reply": last_reply, "errors": errors}


def main() -> int:
    def say(doc):
        sys.stdout.write(json.dumps(doc) + "\n")
        sys.stdout.flush()

    say({"ready": os.getpid()})
    spec = json.loads(sys.stdin.readline())
    say({"clock": time.perf_counter()})
    say(closed_loop(spec, lambda: json.loads(
        sys.stdin.readline())["window_opens_at"]))
    return 0


class Child(loadgen.Child):
    """The parent's side: loadgen.Child's conversation with this file
    (or another of the same handshake) as the child."""

    def __init__(self, script: pathlib.Path | None = None):
        self.proc = subprocess.Popen(
            [sys.executable,
             str(script or pathlib.Path(__file__).resolve())],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        self.pid = self._hear()["ready"]

    def result(self) -> dict:
        """Blocks until the child has ended.  Job keys are ints again."""
        out = self._hear()
        for key in ("first_reply", "last_reply"):
            if key in out:
                out[key] = {int(j): r for j, r in out[key].items()}
        self.proc.wait()
        return out


if __name__ == "__main__":
    sys.exit(main())
