"""The ruler's own parts: the spread function and the rule that sets a
bound from a recorded study (harness/noise.py), the lint that holds
the manifest to it, and the load generator against a stub server (no
jax, no service; every server on port 0).
"""

import http.server
import json
import threading
import time

import pytest

import lint_manifest
from harness import loadgen, noise


# ---- the spread ---------------------------------------------------------

@pytest.mark.parametrize("values, want", [
    ([7.0] * 6, 0.0),                           # six equal runs
    # quartiles of 1..6 (exclusive method) are 1.75 and 5.25 about 3.5;
    # without a farthest run (1, which ties with 6) 2.5 and 5.5 about 4
    ([1, 2, 3, 4, 5, 6], 3.0 / 4.0),
    # one far run: left out, the rest spread by (103.5 - 100.5) / 102
    ([100, 101, 102, 103, 104, 200], 3.0 / 102),
    # leaving out the farthest (90) would widen it: 10 / 100 against
    # (110 - 97.5) / 100 of the whole set
    ([90, 100, 100, 100, 110, 110], 0.10),
    ([10.0, 11.0, 12.0], 2.0 / 11.0),           # too few to leave one out
])
def test_spread_on_hand_made_sets(values, want):
    assert noise.spread(values) == pytest.approx(want)


def test_spread_leaves_the_farthest_out_only_where_that_narrows_it():
    far = [100, 101, 102, 103, 104, 200]
    assert noise.spread(far) < noise.iqr_share(far)
    near = [90, 100, 100, 100, 110, 110]
    assert noise.spread(near) == pytest.approx(
        min(noise.iqr_share(near), noise.iqr_share(near[1:])))


def _study(a, b, metric="panel_ms_p50", cold_first=False):
    runs = []
    for name, values in (("A", a), ("B", b)):
        for i, v in enumerate(values):
            runs.append({"set": name, "call": 1, "seed": i, metric: v,
                         "setup_s": 60.0 + i, "correct": True,
                         "cold": cold_first and i == 0})
    return {"cell": "c", "runs": runs}


def test_rule_twice_the_widest_spread_rounded_up_and_the_floor():
    quiet = _study([100] * 6, [100, 100, 100, 100, 100, 100.5])
    assert noise.rule_bound(quiet, "panel_ms_p50") == 0.02
    # set B spreads by 3 / 102 = 2.94%: twice that is 5.9%, so 0.06
    wide = _study([100] * 6, [100, 101, 102, 103, 104, 200])
    assert noise.widest_spread(wide, "panel_ms_p50") == pytest.approx(3 / 102)
    assert noise.rule_bound(wide, "panel_ms_p50") == 0.06


def test_rule_is_raised_to_cover_two_sets_of_one_call_whose_medians_differ():
    apart = _study([100] * 6, [107] * 6)
    assert noise.widest_pair_gap(apart, "panel_ms_p50") == pytest.approx(0.07)
    assert noise.rule_bound(apart, "panel_ms_p50") == 0.07
    # on two machines the levels differ and nothing compares them
    for run in apart["runs"]:
        run["call"] = 1 if run["set"] == "A" else 2
    assert noise.widest_pair_gap(apart, "panel_ms_p50") == 0.0
    assert noise.widest_pair_gap(apart, "panel_ms_p50",
                                 same_call=False) == pytest.approx(0.07)
    assert noise.rule_bound(apart, "panel_ms_p50") == 0.02


def test_a_cold_run_is_left_out_of_setup_s_only():
    study = _study([100] * 6, [100] * 6, cold_first=True)
    assert [len(v) for v in noise.sets(study, "setup_s").values()] == [5, 5]
    assert [len(v) for v in noise.sets(study, "panel_ms_p50").values()] == [
        6, 6]


def test_record_reads_a_runs_captured_stdout(tmp_path):
    captured = tmp_path / "run.log"
    captured.write_text("\n".join(json.dumps(x) for x in [
        {"phase": "start", "compile_cache_entries": 0},
        "a line of the service's log",
        {"phase": "window_done", "requests": 600, "beyond_p95": 30,
         "gc_full_s": 0.7, "compiles_in_window": 0},
        {"phase": "check", "check": "panel_max_rel_gap", "value": 3e-12},
        {"correct": True, "attempted": 600, "failed": 0, "metrics": {
            "panel_ms_p50": {"value": 316.0, "unit": "ms"},
            "setup_s": {"value": 90.0, "unit": "s"}}, "device": {}}]))
    noise.record("c", "A", 2, [(7, captured)], tmp_path)
    study = noise.record("c", "A", 2, [(8, captured)], tmp_path)
    assert study == noise.load("c", tmp_path)
    assert [r["seed"] for r in study["runs"]] == [7, 8]
    assert study["runs"][0] == {
        "set": "A", "call": 2, "seed": 7, "panel_ms_p50": 316.0,
        "setup_s": 90.0, "panels": 600, "beyond_p95": 30, "gc_full_s": 0.7,
        "failed": 0, "compiles_in_window": 0, "correct": True,
        "panel_max_rel_gap": 3e-12, "cold": True}


# ---- the lint -----------------------------------------------------------

def test_lint_is_clean_on_the_tree():
    assert lint_manifest.lint() == []


def _lint_with(tmp_path, study, p50_bound):
    man = json.loads((lint_manifest.ROOT / "BENCHMARK.json").read_text())
    for m in man["end_to_end"]:
        if m["name"] == "panel_ms_p50":
            m["bound"] = p50_bound
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(man))
    if study is not None:
        study = dict(study, runs=[dict(r, panel_ms_p95=400.0)
                                  for r in study["runs"]])
        (tmp_path / "dash-sealed.json").write_text(json.dumps(study))
    return lint_manifest.lint(path, tmp_path)


def test_lint_refuses_a_bound_under_twice_a_recorded_spread(tmp_path):
    study = _study([100] * 6, [100, 101, 102, 103, 104, 200])
    errs = _lint_with(tmp_path, study, 0.05)
    assert len(errs) == 1 and "panel_ms_p50" in errs[0] and "twice" in errs[0]
    assert _lint_with(tmp_path, study, 0.06) == []


def test_lint_refuses_a_cell_without_a_noise_file(tmp_path):
    errs = _lint_with(tmp_path, None, 0.05)
    assert errs == ["cell dash-sealed: no noise/dash-sealed.json"]


def test_lint_refuses_a_study_of_too_few_runs(tmp_path):
    errs = _lint_with(tmp_path, _study([100] * 5, [100] * 5), 0.05)
    assert len(errs) == 1 and "10 runs in 2 sets" in errs[0]


# ---- the load generator against a stub ----------------------------------

class _Stub(http.server.ThreadingHTTPServer):
    """Answers query_range with one row whose value the test sets for
    the n-th request of a job; `fail_on` requests get a 500."""
    daemon_threads = True

    def __init__(self, delay_s=0.02, fail_on=(), value_of=lambda job, n: 1.0):
        super().__init__(("127.0.0.1", 0), _Handler)
        self.delay_s, self.fail_on = delay_s, set(fail_on)
        self.value_of = value_of
        self.lock, self.seen, self.per_job = threading.Lock(), 0, {}
        threading.Thread(target=self.serve_forever, args=(0.02,),
                         daemon=True).start()


class _Handler(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def log_message(self, *args):
        pass

    def do_GET(self):
        srv = self.server
        job = self.path.split("job%3D")[1].split("&")[0]
        with srv.lock:
            srv.seen += 1
            n_all = srv.seen
            n = srv.per_job[job] = srv.per_job.get(job, 0) + 1
        time.sleep(srv.delay_s)
        if n_all in srv.fail_on:
            body, status = b"boom", 500
        else:
            body, status = json.dumps({"status": "success", "data": {
                "result": [{"metric": {"zone": "z"}, "values": [
                    [10, str(srv.value_of(job, n))], [20, "2.5"]]}]}}
            ).encode(), 200
        # one write: a reply in two segments waits 40 ms for an ACK
        self.wfile.write(b"HTTP/1.1 %d X\r\nContent-Length: %d\r\n\r\n%s"
                         % (status, len(body), body))


def _loop(stub, ramp_s=0.1, seconds=0.3, clients=2, jobs=2):
    spec = {"port": stub.server_address[1],
            "queries": [f"job={j}" for j in range(jobs)],
            "start": 0, "end": 20, "step": 10, "clients": clients,
            "order": list(range(jobs)), "seconds": seconds}
    opened = []

    def window_opens_at():
        time.sleep(ramp_s)
        opened.append(time.perf_counter())
        return opened[0]

    try:
        out = loadgen.closed_loop(spec, window_opens_at)
    finally:
        stub.shutdown()
        stub.server_close()
    return out, opened[0], stub


def test_loadgen_leaves_the_ramps_panels_out_and_counts_one_in_flight():
    out, opened, stub = _loop(_Stub(delay_s=0.04), ramp_s=0.1, seconds=0.3)
    sent = [p[0] for p in out["panels"]]
    assert out["errors"] == [] and out["differing"] == []
    # every panel of the window was sent in it, and the ramp's two or
    # three rounds are not among them
    assert all(0 <= t < 0.3 for t in sent)
    assert len(sent) < stub.seen
    # each client's last panel was sent before the deadline and ended
    # after it: in flight at the deadline, completed and counted
    assert max(t + p[1] / 1000 for t, p in zip(sent, out["panels"])) > 0.3
    assert set(out["first_reply"]) == {0, 1}
    assert all(p[1] >= 40 for p in out["panels"])


def test_loadgen_counts_a_500_as_failed_and_goes_on():
    out, _, stub = _loop(_Stub(fail_on={8, 9}), ramp_s=0.1, seconds=0.3)
    assert len(out["errors"]) == 2 and "HTTP 500" in out["errors"][0]
    assert len(out["panels"]) > 4 and stub.seen > 12


def test_loadgen_counts_a_reply_that_differs_from_the_first_of_its_job():
    # job 1's twelfth reply, well inside the window, has another value
    stub = _Stub(value_of=lambda job, n: 7.0 if (job, n) == ("1", 12)
                 else 1.0)
    out, _, _ = _loop(stub, ramp_s=0.1, seconds=0.5)
    assert out["differing"] == [1]
    assert out["errors"] == []


def test_child_process_speaks_the_protocol_on_one_clock():
    stub = _Stub()
    child = loadgen.Child()
    try:
        gap = child.handshake({
            "port": stub.server_address[1], "queries": ["job=0"],
            "start": 0, "end": 20, "step": 10, "clients": 2, "order": [0],
            "seconds": 0.2})
        time.sleep(0.1)
        child.window_opens(time.perf_counter())
        out = child.result()
    finally:
        child.stop()
        stub.shutdown()
        stub.server_close()
    assert gap == 0.0
    assert child.pid != 0 and child.proc.returncode == 0
    assert out["errors"] == [] and len(out["panels"]) >= 4
    rows = loadgen.rows_of(out["first_reply"][0])
    assert rows[(("zone", "z"),)][1][1] == 2.5
