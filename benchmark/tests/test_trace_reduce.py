"""trace_reduce on a small trace recorded on a TPU v5e (PR 24, by
record_small_trace.py): one jitted program run five times, 10 ms of
sleep after each, inside one `bench:window` span.

Read by hand from the trace's dump: the program's five runs on
`/device:TPU:0` last 11,863 / 11,863 / 11,864 / 11,862 / 11,862 ns.
The device's timeline runs 1.03 ms ahead of the host's, so the first
run ends before the host's `bench:window` span opens (at 47,771,544 ns,
58,506,134 ns long) and four runs fall inside it.
"""

import pathlib

from harness import trace_reduce

TRACE = pathlib.Path(__file__).parent / "small_trace" / "small.xplane.pb"


def test_busy_share_and_program_time_of_the_recorded_trace():
    out = trace_reduce.reduce(str(TRACE))
    assert out["n_devices"] == 1
    assert out["window_s"] == 58506134 / 1e9
    prog = out["programs"]["jit_small_program"]
    assert prog["calls"] == 4
    assert abs(prog["device_s"] - 4 * 11.863e-6) < 1e-8
    assert prog["min_s"] <= prog["median_s"] <= prog["max_s"]
    assert abs(prog["median_s"] - 11.863e-6) < 2e-9
    # the operations fill all but some tens of ns of each program run
    assert 0.99 * prog["device_s"] < out["busy_s"] <= prog["device_s"]
    assert abs(out["busy_s"] / out["window_s"] - 8.1e-4) < 0.1e-4
    gaps = dict(out["idle_gaps"])
    assert abs(sum(gaps.values()) - (out["window_s"] - out["busy_s"])) < 1e-9
    # five `bench:awaiting_reply` spans of about 0.9 ms each, idle but
    # for the program's 12 us; the sleeps are between requests
    assert 0.004 < gaps["awaiting_reply"] < 0.005
    assert gaps["between_requests"] > 0.05
    assert out["device_ops"][0][0] == "fusion f32[]"
    assert all(len(name) <= 80 for name, _ in out["device_ops"])


def test_a_trace_directory_without_a_trace():
    assert trace_reduce.find_xplane(str(TRACE.parent / "nothing")) is None
