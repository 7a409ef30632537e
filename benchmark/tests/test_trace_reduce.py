"""trace_reduce on a small trace recorded on a TPU v5e (PR 28, by
record_small_trace.py): one jitted program run five times inside one
`bench:window` span.  The program has a loop under the named scope
`m3.decode` and a matrix product under `m3.temporal`; each run comes
after 2 ms under an `m3:fetch` annotation and inside `m3:device`, which
holds `m3:kernel`, and 10 ms of sleep follow it.

Read by hand from the trace's dump: the program's five runs on
`/device:TPU:0` last 30,295 / 30,120 / 30,483 / 30,211 / 30,486 ns, all
inside the host's `bench:window` span (at 42,936,106 ns, 69,539,004 ns
long).  A run is copy-start, copy-done (6.2 us), `%while` (12.3 us,
with six `%add_tanh_fusion.2` of 1.6 us nested in it, whose `tf_op`
names `m3.decode`; the loop's own event has none) and
`%convolution_reduce_fusion` (11.8 us, `m3.temporal`).  The device's
timeline runs 1 ms ahead of the host's, so a run falls under the
`m3:fetch` span that precedes its call.
"""

import pathlib

from harness import trace_reduce

TRACE = pathlib.Path(__file__).parent / "small_trace" / "small.xplane.pb"
RUNS_NS = (30295, 30120, 30483, 30211, 30486)


def test_busy_share_and_program_time_of_the_recorded_trace():
    out = trace_reduce.reduce(str(TRACE))
    assert out["n_devices"] == 1
    assert out["window_s"] == 69539004 / 1e9
    prog = out["programs"]["jit_small_program"]
    assert prog["calls"] == 5
    assert abs(prog["device_s"] - sum(RUNS_NS) / 1e9) < 1e-12
    assert (prog["min_s"], prog["median_s"], prog["max_s"]) == (
        30120 / 1e9, 30295 / 1e9, 30486 / 1e9)
    # the operations fill all but some tens of ns of each program run
    assert 0.99 * prog["device_s"] < out["busy_s"] <= prog["device_s"]
    assert abs(out["busy_s"] / out["window_s"] - 2.18e-3) < 0.01e-3


def test_device_operations_carry_the_programs_scope():
    ops = trace_reduce.reduce(str(TRACE))["device_ops"]
    names = [name for name, _ in ops]
    # the loop has no tf_op of its own: it takes its body's scope, and
    # its body's operations are not listed beside it
    assert names[0].startswith("m3.decode/while (s32[], f32[1024,1024]")
    assert names[1] == "m3.temporal/convolution_reduce_fusion f32[]"
    assert names[2] == "copy-done f32[1024,1024]"      # under no scope
    assert not any("add_tanh_fusion" in name for name in names)
    assert abs(ops[0][1] - 61931e-9) < 1e-12
    assert abs(ops[1][1] - 59128e-9) < 1e-12
    # top-level operations only, so they sum to the device's busy time
    assert len(ops) == 10 and all(len(name) <= 80 for name in names)
    assert sum(s for _, s in ops) <= sum(RUNS_NS) / 1e9


def test_idle_gaps_are_labelled_by_the_programs_own_annotations():
    out = trace_reduce.reduce(str(TRACE))
    gaps = dict(out["idle_gaps"])
    assert set(gaps) == {"m3:fetch", "m3:device", "m3:kernel",
                         "between_requests"}
    idle = out["window_s"] - out["busy_s"]
    # nested labels are reported each for itself: m3:kernel lies inside
    # m3:device; m3:fetch and m3:device never overlap on one thread, and
    # what no label covers is between requests
    assert 0.0039 < gaps["m3:kernel"] < gaps["m3:device"] < 0.0041
    assert 0.011 < gaps["m3:fetch"] < 0.012
    assert abs(gaps["between_requests"] + gaps["m3:fetch"]
               + gaps["m3:device"] - idle) < 1e-9
    assert out["idle_gaps"][0][0] == "between_requests"


def test_op_scopes_reads_tf_op_from_the_files_metadata():
    scopes = trace_reduce._op_scopes(str(TRACE))["/device:TPU:0"]
    assert sorted(set(scopes.values())) == ["m3.decode", "m3.temporal"]
    assert all(name.startswith("%") for name in scopes)


def test_a_trace_directory_without_a_trace():
    assert trace_reduce.find_xplane(str(TRACE.parent / "nothing")) is None
