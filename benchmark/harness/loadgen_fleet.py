#!/usr/bin/env python3
"""The closed-loop clients of a row of fleet-wide panels, in an
interpreter of their own.

A sibling of loadgen.py, with the same conversation, ramp, deadline and
counting (loadgen_live.Child is the parent's side).  It differs in two
things that loadgen.py cannot be told.  Each client goes round the
queries on its own, client `i` starting on query `first[i]`, where
loadgen.py hands every draw of one shared order to whichever client
asks next.  And every request gives up after `timeout_s` seconds, where
harness/client.py waits 300: a panel that reads the whole fleet is the
one that can get stuck (a compile, a queue, the server's own deadline),
and a run must never wait for one without a limit.  A request that
timed out ends the loop at once and is reported apart from the failed
ones, under `timed_out`: the traffic kind ends the run on it.  This
file imports numpy, harness/client.py and loadgen.py's reply helpers,
nothing of the program.

Over the child's stdin and stdout, one JSON document a line:

    child   {"ready": <pid>}
    parent  {"port", "queries": [...], "first": [query of client i's
             first panel, ...], "start", "end", "step", "timeout_s",
             "seconds"}
    child   {"clock": <its time.perf_counter()>} and the loop starts
    parent  {"window_opens_at": <the parent's perf_counter reading>}
    child   {"panels": [[sent_at_s, ms, query], ...], "first_reply":
             {query: reply}, "differing": [query, ...], "errors": [...],
             "timed_out": [...]}
"""

from __future__ import annotations

import json
import os
import pathlib
import sys
import threading
import time

if __name__ == "__main__":       # started as a file: harness/ -> benchmark/
    sys.path[0] = str(pathlib.Path(__file__).resolve().parents[1])

from harness import loadgen  # noqa: E402
from harness.client import Client  # noqa: E402


def client_with_timeout(port: int, timeout_s: float) -> Client:
    """harness/client.py's keep-alive client, giving up after
    `timeout_s` a request (the connection opens on its first one)."""
    client = Client(port)
    client.conn.timeout = timeout_s
    return client


def closed_loop(spec: dict, window_opens_at) -> dict:
    """Run the loop until `seconds` past the window's opening, which
    `window_opens_at()` blocks for and returns."""
    queries = spec["queries"]
    lock = threading.Lock()
    panels, errors, timed_out, differing = [], [], [], []
    first_reply, first_rows = {}, {}
    # set when the window opens, after the ramp; -inf once a request
    # has timed out
    t_start = deadline = float("inf")

    def client_loop(q: int):
        nonlocal deadline
        client = client_with_timeout(spec["port"], spec["timeout_s"])
        try:
            while time.perf_counter() < deadline:
                asked, q = q, (q + 1) % len(queries)
                try:
                    seconds, doc, rows = loadgen.panel(
                        client, queries[asked], spec["start"], spec["end"],
                        spec["step"])
                except TimeoutError as e:
                    with lock:
                        timed_out.append(f"{queries[asked]}: {e}"[:300])
                        deadline = float("-inf")
                    return
                except Exception as e:  # noqa: BLE001 - a failed panel
                    # is counted, the ramp's too, and the loop goes on
                    with lock:
                        errors.append(f"{type(e).__name__}: {e}"[:300])
                    client.close()
                    client = client_with_timeout(spec["port"],
                                                 spec["timeout_s"])
                    continue
                sent = time.perf_counter() - seconds
                if sent < t_start:
                    continue                    # the ramp's
                with lock:
                    panels.append([sent - t_start, seconds * 1000.0, asked])
                    if asked not in first_rows:
                        first_reply[asked], first_rows[asked] = doc, rows
                    elif not loadgen.same_rows(rows, first_rows[asked]):
                        differing.append(asked)
        finally:
            client.close()

    threads = [threading.Thread(target=client_loop, args=(q,),
                                name=f"client-{i}")
               for i, q in enumerate(spec["first"])]
    for t in threads:
        t.start()
    opened = float(window_opens_at())
    with lock:
        deadline = min(deadline, opened + spec["seconds"])
    t_start = opened
    for t in threads:
        t.join()
    return {"panels": panels, "first_reply": first_reply,
            "differing": differing, "errors": errors,
            "timed_out": timed_out}


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


if __name__ == "__main__":
    sys.exit(main())
