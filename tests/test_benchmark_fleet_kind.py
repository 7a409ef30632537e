"""The limits of the benchmark's fleet-wide traffic kind
(benchmark/traffic_kinds/query_fleet_loop.py and its clients,
benchmark/harness/loadgen_fleet.py), which must never wait without
one: a request gives up after `timeout_s` and ends the loop, a window
whose request timed out raises, and a set-up that does not open the
window in time ends the process with a non-zero exit and no result
line.  Stub servers, no jax device work, every server on port 0."""

import http.server
import json
import pathlib
import subprocess
import sys
import threading
import time
import types

import pytest

BENCHMARK = pathlib.Path(__file__).resolve().parents[1] / "benchmark"
if str(BENCHMARK) not in sys.path:
    sys.path.insert(0, str(BENCHMARK))

from harness import loadgen_fleet  # noqa: E402
from traffic_kinds import query_fleet_loop  # noqa: E402


class _Stub(http.server.ThreadingHTTPServer):
    """Answers query_range with one row named after the query, after
    `delay_s(n)` seconds for the n-th request."""
    daemon_threads = True

    def __init__(self, delay_s=lambda n: 0.01):
        super().__init__(("127.0.0.1", 0), _Handler)
        self.delay_s, self.lock, self.seen = delay_s, threading.Lock(), []
        threading.Thread(target=self.serve_forever, args=(0.02,),
                         daemon=True).start()

    @property
    def port(self):
        return self.server_address[1]

    def end(self):
        self.shutdown()
        self.server_close()


class _Handler(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def log_message(self, *args):
        pass

    def do_GET(self):
        srv = self.server
        query = self.path.split("query=")[1].split("&")[0]
        with srv.lock:
            srv.seen.append(query)
            n = len(srv.seen)
        time.sleep(srv.delay_s(n))
        body = json.dumps({"status": "success", "data": {"result": [
            {"metric": {"q": query}, "values": [[10, "1.5"]]}]}}).encode()
        try:
            self.wfile.write(b"HTTP/1.1 200 X\r\nContent-Length: %d\r\n\r\n%s"
                             % (len(body), body))
        except OSError:
            pass                        # the client gave up


def _spec(stub, **over):
    return dict({"port": stub.port, "queries": ["qa", "qb"], "first": [0, 1],
                 "start": 0, "end": 20, "step": 10, "timeout_s": 5.0,
                 "seconds": 0.3}, **over)


def _loop(stub, ramp_s=0.05, **over):
    def window_opens_at():
        time.sleep(ramp_s)
        return time.perf_counter()

    try:
        return loadgen_fleet.closed_loop(_spec(stub, **over),
                                         window_opens_at)
    finally:
        stub.end()


def test_each_client_goes_round_the_queries_from_its_own_first():
    stub = _Stub()
    out = _loop(stub)
    assert out["errors"] == [] and out["timed_out"] == []
    assert set(out["first_reply"]) == {0, 1} and out["differing"] == []
    per_query = [sum(p[2] == q for p in out["panels"]) for q in (0, 1)]
    assert min(per_query) >= 3 and abs(per_query[0] - per_query[1]) <= 2
    # the two clients' first panels ask for different queries
    assert set(stub.seen[:2]) == {"qa", "qb"}


def test_a_request_that_times_out_ends_the_loop_and_is_told_apart():
    # the fifth request hangs for longer than the clients wait
    stub = _Stub(delay_s=lambda n: 3.0 if n == 5 else 0.01)
    t0 = time.perf_counter()
    out = _loop(stub, timeout_s=0.2, seconds=30.0)
    assert time.perf_counter() - t0 < 2.0       # not the window's 30 s
    assert len(out["timed_out"]) == 1 and out["errors"] == []
    assert out["timed_out"][0].startswith(("qa:", "qb:"))


class _Run:
    """What query_fleet_loop.window needs of benchmark/run.py's Run."""
    trace, seed = False, 5

    def __init__(self, port, seconds, mix):
        self.svc = types.SimpleNamespace(http_port=port)
        self.seconds, self.mix = seconds, mix
        self.timers, self.phases = {}, []

    def emit(self, phase, **fields):
        self.phases.append(phase)

    def window_opens(self):
        return time.perf_counter()


def _mix(**over):
    mix = json.loads((BENCHMARK / "traffic" / "panels-fleet-2c.json")
                     .read_text())
    return dict(mix, ramp_s=0.05, **over)


def _state(watchdog):
    fleet = types.SimpleNamespace(metric="m", t0=0, seal_end=14400)
    return {"fleet": fleet, "acked": 0, "watchdog": watchdog}


def test_window_raises_when_a_clients_request_timed_out():
    stub = _Stub(delay_s=lambda n: 3.0 if n == 4 else 0.01)
    run = _Run(stub.port, 30.0, _mix(request_timeout_s=0.3))
    watchdog = query_fleet_loop.Watchdog("the window's opening",
                                         time.perf_counter(), 60.0)
    t0 = time.perf_counter()
    try:
        with pytest.raises(RuntimeError, match="gave up after 0.3 s"):
            query_fleet_loop.window(run, _state(watchdog))
    finally:
        stub.end()
    assert time.perf_counter() - t0 < 10.0
    assert watchdog._done.is_set() and "window_done" not in run.phases


_STUCK_SETUP = """
import sys, time
sys.path.insert(0, {benchmark!r})
T_PROCESS = time.perf_counter()
from traffic_kinds import query_fleet_loop

class Run:
    mix = {{"open_within_s": 0.5}}

query_fleet_loop._load_and_warm = lambda run: time.sleep(60)
print("phase line", flush=True)
query_fleet_loop.setup(Run())
print('{{"correct": true}}', flush=True)
"""


def test_a_setup_that_does_not_open_the_window_in_time_ends_the_process():
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, "-c", _STUCK_SETUP.format(benchmark=str(BENCHMARK))],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    assert time.perf_counter() - t0 < 50.0
    assert proc.stdout.strip().splitlines() == ["phase line"]
    assert "the window's opening not reached within 0.5 s" in proc.stderr


def test_a_setup_that_fails_lets_its_watchdog_go(monkeypatch):
    monkeypatch.setattr(sys.modules[__name__], "T_PROCESS",
                        time.perf_counter(), raising=False)
    seen = []
    real = query_fleet_loop.Watchdog

    def watchdog(*args, **kwargs):
        seen.append(real(*args, **kwargs))
        return seen[-1]

    def broken(run):
        raise ValueError("no such fleet")

    monkeypatch.setattr(query_fleet_loop, "Watchdog", watchdog)
    monkeypatch.setattr(query_fleet_loop, "_load_and_warm", broken)
    run = _Run(0, 1.0, {"open_within_s": 600.0})
    with pytest.raises(ValueError, match="no such fleet"):
        query_fleet_loop.setup(run)
    assert seen and seen[0]._done.is_set()
