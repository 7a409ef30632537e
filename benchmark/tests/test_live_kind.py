"""The live kind's scrape generator (harness/scrapegen.py) against stub
servers: what it records of every request (when its own thread woke,
whether every connection was taken), its witness of the host, and the
arithmetic that turns the rows into the window's numbers
(scrapegen.account), the three the kind judges among them.  No jax, no
program: stub HTTP servers on port 0, a few seconds in all.
"""

import http.server
import pathlib
import signal
import sys
import threading
import time

import pytest

BENCHMARK = pathlib.Path(__file__).resolve().parents[1]
if str(BENCHMARK) not in sys.path:
    sys.path.insert(0, str(BENCHMARK))

from harness import loadgen_live, scrapegen  # noqa: E402
from harness.scrapegen import (ACKED, DUE, HELD, LATE, SENT,  # noqa: E402
                               WOKE)

CADENCE_S, JOBS = 1, 5          # a request every 0.2 s


class _Stub(http.server.ThreadingHTTPServer):
    """Acknowledges every remote write after `hold_s` seconds."""
    daemon_threads = True

    def __init__(self, hold_s=0.0):
        super().__init__(("127.0.0.1", 0), _Handler)
        self.hold_s, self.lock, self.writes = hold_s, threading.Lock(), 0
        threading.Thread(target=self.serve_forever, args=(0.02,),
                         daemon=True).start()

    def end(self):
        self.shutdown()
        self.server_close()


class _Handler(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def log_message(self, *args):
        pass

    def do_POST(self):
        self.rfile.read(int(self.headers["Content-Length"]))
        with self.server.lock:
            self.server.writes += 1
        time.sleep(self.server.hold_s)
        self.wfile.write(b"HTTP/1.1 200 X\r\nContent-Length: 0\r\n\r\n")


def _spec(stub, seconds, connections, late_after_s):
    """A fleet of JOBS jobs x 25 series scraped every CADENCE_S, its
    open block the one the wall clock is in, the next tick the first."""
    now = int(time.time())
    cfg = {"metric": "m", "jobs": JOBS, "instances_per_job": 25, "zones": 5,
           "cadence_s": CADENCE_S, "block_s": 7200, "buffer_past_s": 0}
    return {"port": stub.server_address[1], "seconds": seconds,
            "fleet": {"cfg": cfg, "seed": 7, "now_s": now, "n_blocks": 0},
            "first_tick": (now % 7200) // CADENCE_S + 1,
            "clock_offset_s": 0.0, "late_after_s": late_after_s,
            "connections": connections}


def _account(spec, out, t_start):
    fleet = scrapegen.Fleet(**{k: spec["fleet"][k] for k in
                               ("cfg", "seed", "now_s", "n_blocks")})
    due = [(job, tick) for job, tick, at in scrapegen.schedule_of(
        fleet.block_ts(0)[:spec["first_tick"] + 60], spec["first_tick"],
        out["to_perf"], CADENCE_S, JOBS)
        if t_start <= at <= t_start + spec["seconds"]]
    return scrapegen.account(
        out["requests"], due, out["host_stalls"], t_start, spec["seconds"],
        spec["late_after_s"], CADENCE_S, spec["connections"]), due


def _in_process(hold_s, seconds, connections, late_after_s):
    stub = _Stub(hold_s)
    try:
        spec = _spec(stub, seconds, connections, late_after_s)
        t_start = time.perf_counter()
        out = scrapegen.open_loop(spec, [t_start + seconds])
    finally:
        stub.end()
    assert out["errors"] == []
    assert out["samples_acked"] == 25 * len(out["requests"]) == 25 * stub.writes
    return spec, out, t_start


def test_prompt_server_nothing_held_late_or_missing():
    # late_after_s wide: the machine that runs the tests is a busy one
    spec, out, t_start = _in_process(0.0, 1.5, 4, late_after_s=0.5)
    numbers, due = _account(spec, out, t_start)
    assert 3 <= len(due) <= 8 and numbers["scrapes"] >= len(due)
    for r in out["requests"]:
        assert r[DUE] <= r[WOKE] <= r[SENT] <= r[ACKED]
        assert not r[HELD] and not r[LATE]
    assert numbers["scrapes_held"] == numbers["scrapes_late"] == 0
    assert numbers["scrapes_held_share"] == 0.0
    assert numbers["scrapes_missing"] == 0
    assert numbers["scrapes_a_tick_behind"] == 0
    assert 1 <= numbers["write_connections_busy_max"] <= 4


def test_server_that_holds_every_write_fills_the_connections():
    spec, out, t_start = _in_process(0.7, 2.0, 2, late_after_s=0.1)
    numbers, due = _account(spec, out, t_start)
    held = [r for r in out["requests"] if r[HELD]]
    # under three a second can end, five a second fall due: from the
    # third on a request finds both connections taken and waits for one
    assert len(held) >= 3 and numbers["scrapes_held"] == len(held)
    assert all(r[SENT] - r[WOKE] > 0.1 for r in held)
    assert all(r[WOKE] - r[DUE] < 0.1 for r in held)
    assert numbers["write_connections_busy_max"] == 2
    assert numbers["scrapes_held_share"] > 0.01
    assert numbers["scrapes_late"] >= len(held)
    assert numbers["scrapes_missing"] == 0      # held, and sent all the same


def test_stopped_process_woke_late_and_its_witness_says_so():
    stub = _Stub()
    child = loadgen_live.Child(BENCHMARK / "harness" / "scrapegen.py")
    try:
        spec = _spec(stub, 2.0, 4, late_after_s=0.1)
        assert child.handshake(spec) <= 0.001
        t_start = time.perf_counter()
        child.window_opens(t_start)
        time.sleep(0.6)
        child.proc.send_signal(signal.SIGSTOP)
        time.sleep(0.5)
        child.proc.send_signal(signal.SIGCONT)
        out = child.result()
    finally:
        child.stop()
        stub.end()
    assert out["errors"] == []
    numbers, due = _account(spec, out, t_start)
    # 0.5 s of a request every 0.2 s: two or three woke late, and no
    # connection kept any of them waiting
    assert numbers["scrapes_late"] >= 2
    assert numbers["scrapes_woke_late"] == numbers["scrapes_late"]
    assert numbers["scrapes_held"] == 0
    assert numbers["scrapes_held_share"] == 0.0
    assert numbers["scrapes_missing"] == 0
    assert numbers["scrapes_a_tick_behind"] == 0
    long = [s for at, s in out["host_stalls"] if s > 0.3]
    assert len(long) == 1 and 0.4 <= long[0] <= 0.9
    assert 400 <= numbers["host_stall_max_ms"] <= 900
    assert numbers["host_stalled_ms"] >= numbers["host_stall_max_ms"]


def _row(job, tick, due, woke=None, sent=None, acked=None, held=False,
         busy=0, catch_up=False):
    woke = due if woke is None else woke
    sent = woke if sent is None else sent
    acked = sent + 0.03 if acked is None else acked
    late = not catch_up and sent - due > 0.1
    return [job, tick, due, sent, acked, late, catch_up, woke, held, busy]


# the window is [100, 150]; a request every 0.4 s would make 125
_SOUND = [_row(j % 25, j // 25, 100.0 + 0.4 * j) for j in range(125)]
_PAIRS = [(r[0], r[1]) for r in _SOUND]


@pytest.mark.parametrize("name, rows, pairs, stalls, want", [
    ("sound", _SOUND, _PAIRS, [], {"scrapes": 125}),
    # it found every connection taken, and one came free inside
    # late_after_s: held, counted, and no part of the share
    ("held under late_after_s",
     _SOUND[:-1] + [_row(24, 4, 149.6, sent=149.68, held=True, busy=16)],
     _PAIRS, [],
     {"scrapes_held": 1, "write_connections_busy_max": 16}),
    ("held and kept waiting: two of 125 are over the share",
     _SOUND[:-2] + [_row(23, 4, 149.2, sent=149.5, held=True, busy=17),
                    _row(24, 4, 149.6, sent=149.9, held=True, busy=17)],
     _PAIRS, [],
     {"scrapes_held": 2, "scrapes_late": 2, "scrapes_held_share": 2 / 125,
      "write_connections_busy_max": 16}),
    # the machine stood still: four requests woke 1.2 to 0.1 s late and
    # found connections free; the witness saw it; nothing is judged
    ("woke late",
     _SOUND[:50] + [_row(r[0], r[1], r[2], woke=121.3) for r in _SOUND[50:54]]
     + _SOUND[54:], _PAIRS, [[119.9, 1.4], [90.0, 2.0]],
     {"scrapes_late": 3, "scrapes_woke_late": 3,
      "host_stall_max_ms": 1400.0, "host_stalled_ms": 1400.0}),
    ("a pair due in the window and never sent",
     _SOUND[:60] + _SOUND[61:], _PAIRS, [],
     {"scrapes": 124, "scrapes_missing": 1}),
    ("a request exactly a cadence behind",
     _SOUND[:-1] + [_row(24, 4, 139.6, woke=149.6)], _PAIRS, [],
     {"scrapes_late": 1, "scrapes_woke_late": 1,
      "scrapes_a_tick_behind": 1}),
    ("just under a cadence behind",
     _SOUND[:-1] + [_row(24, 4, 139.6, woke=149.59)], _PAIRS, [],
     {"scrapes_late": 1, "scrapes_woke_late": 1}),
    # the end of the catch-up, sent inside the window: the window's
    # request, and neither late, held nor behind whatever its times say
    ("a catch-up row",
     [_row(0, -1, 60.0, woke=100.5, sent=101.0, busy=20, catch_up=True)]
     + _SOUND, _PAIRS, [],
     {"scrapes": 126, "write_connections_busy_max": 16}),
    ("sent before the window opened: not the window's",
     [_row(24, -1, 99.6, sent=99.9, held=True, busy=16)] + _SOUND, _PAIRS, [],
     {"scrapes": 125}),
    ("an empty window", [], [], [], {"scrapes": 0}),
    ("an empty window that had requests due", [], _PAIRS[:3], [],
     {"scrapes": 0, "scrapes_missing": 3}),
])
def test_account(name, rows, pairs, stalls, want):
    zero = {"scrapes": 125, "scrapes_late": 0, "scrapes_woke_late": 0, "scrapes_held": 0,
            "write_connections_busy_max": 1 if rows else 0,
            "host_stall_max_ms": 0.0, "host_stalled_ms": 0.0,
            "scrapes_held_share": 0.0, "scrapes_missing": 0,
            "scrapes_a_tick_behind": 0}
    got = scrapegen.account(rows, pairs, stalls, 100.0, 50.0, 0.1, 10, 16)
    assert got == pytest.approx({**zero, **want}), name
