#!/usr/bin/env python3
"""The closed-loop clients, in an interpreter of their own.

A traffic kind starts this file as a child process, so that what the
clients do (sending, parsing 2,400 `[t, "v"]` pairs a reply to numpy,
comparing) never holds the interpreter lock of the server they time.
It imports numpy and harness/client.py, and nothing of the program.

Over the child's stdin and stdout, one JSON document a line:

    child   {"ready": <pid>}                     once it has imported
    parent  {"port", "queries": [one per job], "start", "end", "step",
             "clients", "order": [job, ...], "seconds"}
    child   {"clock": <its time.perf_counter()>} and the loop starts
    parent  {"window_opens_at": <the parent's perf_counter reading>}
    child   {"panels": [[sent_at_s, ms, job], ...], "first_reply":
             {job: reply}, "differing": [job, ...], "errors": [...]}

Both processes read `time.perf_counter()`, one monotonic clock on
Linux; `handshake` lets the parent see that the child's reading lies
between two of its own.  `clients` threads, each on its own keep-alive
connection, send their next `query_range` when the last reply is
parsed, going round `order`.  A panel is timed from send to parsed
reply.  One sent before the window opened is the ramp's and left out;
one in flight at the deadline is completed and counted; a failed one
is counted, the ramp's too, and its connection reopened.
"""

from __future__ import annotations

import itertools
import json
import os
import pathlib
import subprocess
import sys
import threading
import time

import numpy as np

if __name__ == "__main__":       # started as a file: harness/ -> benchmark/
    sys.path[0] = str(pathlib.Path(__file__).resolve().parents[1])

from harness.client import Client  # noqa: E402


def rows_of(doc: dict) -> dict:
    """A query_range reply -> {labels: (steps_s, values)}."""
    return {tuple(sorted(s["metric"].items())): (
        np.array([t for t, _ in s["values"]], dtype=np.float64),
        np.array([float(v) for _, v in s["values"]]))
        for s in doc["data"]["result"]}


def panel(client: Client, query: str, start, end, step):
    """One panel over HTTP -> (seconds send to parsed reply, the reply,
    its rows)."""
    t0 = time.perf_counter()
    doc = client.get_json("/api/v1/query_range", query=query, start=start,
                          end=end, step=step)
    rows = rows_of(doc)
    seconds = time.perf_counter() - t0
    if doc["status"] != "success":
        raise RuntimeError(f"{query}: {doc}")
    return seconds, doc, rows


def same_rows(a: dict, b: dict) -> bool:
    return set(a) == set(b) and all(
        np.array_equal(a[k][0], b[k][0]) and np.array_equal(a[k][1], b[k][1])
        for k in a)


def closed_loop(spec: dict, window_opens_at) -> dict:
    """Run the loop until `seconds` past the window's opening, which
    `window_opens_at()` blocks for and returns."""
    order = spec["order"]
    next_draw = itertools.count()
    lock = threading.Lock()
    panels, errors, differing = [], [], []
    first_reply, first_rows = {}, {}
    # set when the window opens, after the ramp
    t_start = deadline = float("inf")

    def client_loop():
        client = Client(spec["port"])
        try:
            while time.perf_counter() < deadline:
                with lock:
                    job = int(order[next(next_draw) % len(order)])
                try:
                    seconds, doc, rows = panel(
                        client, spec["queries"][job], spec["start"],
                        spec["end"], spec["step"])
                except Exception as e:  # noqa: BLE001 - a failed panel
                    # is counted, the ramp's too, and the loop goes on
                    with lock:
                        errors.append(f"{type(e).__name__}: {e}"[:300])
                    client.close()
                    client = Client(spec["port"])
                    continue
                sent = time.perf_counter() - seconds
                if sent < t_start:
                    continue                    # the ramp's
                with lock:
                    panels.append([sent - t_start, seconds * 1000.0, job])
                    if job not in first_rows:
                        first_reply[job], first_rows[job] = doc, rows
                    elif not same_rows(rows, first_rows[job]):
                        differing.append(job)
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
            "differing": differing, "errors": errors}


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


class Child:
    """The parent's side of the conversation above."""

    def __init__(self):
        self.proc = subprocess.Popen(
            [sys.executable, str(pathlib.Path(__file__).resolve())],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        self.pid = self._hear()["ready"]

    def _say(self, doc: dict) -> None:
        self.proc.stdin.write(json.dumps(doc) + "\n")
        self.proc.stdin.flush()

    def _hear(self) -> dict:
        line = self.proc.stdout.readline()
        if not line:
            raise RuntimeError(
                f"the load generator ended early, code {self.proc.wait()}")
        return json.loads(line)

    def handshake(self, spec: dict) -> float:
        """Hand over the work; the loop starts.  -> seconds by which the
        child's clock lies outside the parent's two readings around it
        (0.0 on one clock)."""
        t0 = time.perf_counter()
        self._say(spec)
        theirs = self._hear()["clock"]
        t1 = time.perf_counter()
        return max(t0 - theirs, theirs - t1, 0.0)

    def window_opens(self, at: float) -> None:
        self._say({"window_opens_at": at})

    def result(self) -> dict:
        """Blocks until the loop has ended.  Job keys are ints again."""
        out = self._hear()
        out["first_reply"] = {int(j): doc
                              for j, doc in out["first_reply"].items()}
        self.proc.wait()
        return out

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
        self.proc.stdin.close()
        self.proc.stdout.close()


if __name__ == "__main__":
    sys.exit(main())
