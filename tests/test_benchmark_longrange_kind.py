"""The two-day panel (configuration m3query-longrange, cell dash-2d) on
the CPU, held by the suite the driver runs.

The cell's controls and its traced run at rehearsal size, through the
whole served path (benchmark/run.py --rehearse in this process: the
coordinator, HTTP, the engine's device tier): the cases of
benchmark/tests/test_control_2d.py.  The panel through the engine
against the plain reference (benchmark/harness/reference.py) and the
host evaluator, with what its record says of the program it ran; the
check that holds the cell to its mechanism; the trace's sub-scopes; the
manifest's new entries."""

import json
import pathlib
import sys
import time

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCHMARK = ROOT / "benchmark"
for path in (BENCHMARK, BENCHMARK / "tests"):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))

from harness import reference, trace_subscopes  # noqa: E402
from harness.fleet import Fleet  # noqa: E402
from test_control_2d import *  # noqa: E402,F401,F403 - its cases are run here
from test_metric_merge_share_2d import *  # noqa: E402,F401,F403 - and its
from traffic_kinds import query_longrange_loop  # noqa: E402

SEC = 10**9
CONFIG = json.loads((BENCHMARK / "configs" / "m3query-longrange.json")
                    .read_text())
MIX = json.loads((BENCHMARK / "traffic" / "panels-2d-2c.json").read_text())


def _sealed_fleet(path, hours: int):
    """The configuration's fleet at its rehearsal size over `hours`,
    written block by block and sealed by the database's own tick and
    flush -> (database, fleet)."""
    from m3_tpu.storage.database import Database, DatabaseOptions
    from m3_tpu.storage.namespace import NamespaceOptions, RetentionOptions

    fleet = Fleet(dict(CONFIG, **CONFIG["rehearse"]), 4501,
                  int(time.time()), hours * 3600 // CONFIG["block_s"])
    db = Database(DatabaseOptions(path=str(path), num_shards=4,
                                  commit_log_enabled=False))
    db.create_namespace(NamespaceOptions(
        name="default", retention=RetentionOptions(
            retention_period=48 * 3600 * SEC,
            block_size=CONFIG["block_s"] * SEC)))
    ids = [b"s%04d" % i for i in range(fleet.n_series)]
    tags = [fleet.labels(i) for i in range(fleet.n_series)]
    for k in range(fleet.n_blocks):
        ts = (fleet.block_ts(k) * SEC).tolist()
        vals = fleet.block_values(0, fleet.n_series, k)
        for i in range(fleet.n_series):
            db.write_batch("default", [ids[i]] * len(ts),
                           [tags[i]] * len(ts), ts, vals[i].tolist())
    db.tick()
    db.flush()
    return db, fleet


@pytest.mark.parametrize("hours,form,n_cap,rows,merge", [
    pytest.param(44, "gather", 15872, 22, "window", id="two_days"),
    pytest.param(4, "select", 1536, 2, "rotate", id="four_hours"),
])
def test_panel_equals_the_reference_and_the_host_and_records_its_shape(
        tmp_path, hours, form, n_cap, rows, merge):
    """The cell's query over every sealed block, served whole by the
    per-node device program: equal to the plain reference on the
    generator's arrays and to the host evaluator to 1e-9; the record
    says which form read the windows' ends and which merged the rows,
    at how many samples and rows a lane and how many steps."""
    from m3_tpu.query import slowlog
    from m3_tpu.query.engine import Engine

    db, fleet = _sealed_fleet(tmp_path, hours)
    try:
        query = MIX["query"].replace("<METRIC>", fleet.metric).replace(
            "<J>", fleet.job_name(0))
        start, end = fleet.t0 * SEC, fleet.seal_end * SEC
        step = MIX["step_s"] * SEC
        served = Engine(db, "default", lookback_nanos=300 * SEC,
                        device_serving=True)
        _, got = served.query_range(query, start, end, step)
        rec = slowlog.log().records(limit=1)[0]
        _, host = Engine(db, "default", lookback_nanos=300 * SEC,
                         device_serving=False).query_range(
                             query, start, end, step)
    finally:
        db.close()
    assert rec["expr"] == query and rec["device_serving"] is True
    assert not rec.get("device_declines")
    steps = np.arange(fleet.t0, fleet.seal_end + 1, MIX["step_s"])
    assert (rec["window_form"], rec["n_cap"], rec["rows_per_lane"]) == (
        form, n_cap, rows)
    assert rec["merge_form"] == merge
    assert rec["steps_pad"] == -(-len(steps) // 64) * 64
    assert rec["lanes"] == fleet.instances and rec["lane_chunks"] == 1
    assert query_longrange_loop.off_the_gather_form(
        [rec], MIX["gather_min_n_cap"]) == (form != "gather")

    by_zone = reference.sum_by(
        np.arange(fleet.instances) % fleet.zones,
        reference.rate(*fleet.job_arrays(0), steps, MIX["range_s"]))
    want = np.stack([by_zone[z] for z in sorted(by_zone)])
    zone_of = [int(ls[b"zone"].split(b"-")[1]) for ls in got.labels]
    assert sorted(zone_of) == list(range(fleet.zones))
    for name, matrix in (("device", got), ("host", host)):
        vals = np.asarray(matrix.values)[np.argsort(
            [int(ls[b"zone"].split(b"-")[1]) for ls in matrix.labels])]
        assert vals.shape == want.shape, name
        assert np.array_equal(np.isnan(vals), np.isnan(want)), name
        # the first step's window holds one sample: no rate
        assert np.isnan(vals[:, 0]).all() and not np.isnan(vals[:, 1:]).any()
        gap = np.nanmax(np.abs(vals - want) / np.abs(want))
        assert gap < 1e-9, (name, gap)


def test_off_the_gather_form_counts_form_and_bucket():
    off = query_longrange_loop.off_the_gather_form
    sound = {"window_form": "gather", "n_cap": 15872}
    assert off([sound] * 3, 12289) == 0
    assert off([sound, {"window_form": "select", "n_cap": 1536}], 12289) == 1
    # gathers at a lane the selection should have served: the constant
    # moved under the cell
    assert off([{"window_form": "gather", "n_cap": 12288}], 12289) == 1
    assert off([{"window_form": None, "n_cap": 15872}], 12289) == 1
    # a program from before the record's n_cap is held to the form alone
    assert off([{"window_form": "gather"}], 12289) == 0
    assert off([{"window_form": "select"}], 12289) == 1


@pytest.mark.parametrize("tf_op,want", [
    ("jit(device_grouped_pipeline)/m3.temporal/bounds/vmap(jit(searchsorted))"
     "/reduce_sum", "m3.temporal/bounds"),
    ("jit(device_grouped_pipeline)/m3.temporal/take/jit(take_along_axis)"
     "/gather", "m3.temporal/take"),
    ("jit(device_grouped_pipeline)/m3.temporal/while/body/bounds/le_to",
     "m3.temporal/bounds"),
    ("jit(device_grouped_pipeline)/m3.temporal/reduce_window_sum",
     "m3.temporal"),
    ("jit(f)/m3.decode/while/body/refill/reduce", "m3.decode/refill"),
    ("jit(f)/m3.merge/take_me/not_a_scope", "m3.merge"),
    ("jit(f)/m3.group/scatter-add", "m3.group"),
])
def test_trace_names_an_operation_by_scope_and_sub_scope(tf_op, want):
    assert trace_subscopes.ScopeAndSub.search(tf_op).group(0) == want


def test_trace_sub_scopes_leave_the_reduction_as_it_was():
    from harness import trace_reduce

    assert trace_subscopes.ScopeAndSub.search("jit(f)/add") is None
    pattern, name_of = trace_reduce._SCOPE, trace_reduce._op_name
    small = BENCHMARK / "tests" / "small_trace" / "small.xplane.pb"
    plain = trace_reduce.reduce(str(small))
    deeper = trace_subscopes.reduce(str(small))
    assert trace_reduce._SCOPE is pattern
    assert trace_reduce._op_name is name_of
    # the recorded trace is of a program without sub-scopes: the same
    # summary either way, and beside it every scope's seconds over all
    # operations (here three: they are the trace's whole busy time)
    by_scope = deeper.pop("scope_s")
    assert deeper == plain and plain["device_ops"]
    assert set(by_scope) == {"m3.decode", "m3.temporal", ""}
    assert sum(by_scope.values()) == pytest.approx(plain["busy_s"])
    assert by_scope["m3.temporal"] == pytest.approx(sum(
        s for name, s in plain["device_ops"]
        if name.startswith("m3.temporal/")))


def test_scope_total_reads_every_operation_of_a_scope():
    import types

    from readers import trace_scope_share, trace_scope_total

    gathers = [[f"m3.temporal/take/fusion.{i} u32[8]", 0.2] for i in range(12)]
    ops = sorted([["m3.merge/while.89", 2.4],
                  ["m3.temporal/bounds/convert_reduce_fusion", 0.7]] + gathers,
                 key=lambda kv: -kv[1])
    run = types.SimpleNamespace(trace_summary={
        "programs": {"jit_p": {"calls": 21.0, "device_s": 5.8}},
        "device_ops": ops[:10],
        "scope_s": {"m3.merge": 2.4, "m3.temporal/bounds": 0.7,
                    "m3.temporal/take": 2.4, "m3.temporal": 0.3, "": 0.0}})
    args = {"program": "jit_p", "scope": "m3.temporal"}
    # ten operations hold eight of the twelve gathers; the totals all
    assert trace_scope_share.read(run, args) == pytest.approx(
        100 * (0.7 + 8 * 0.2) / 5.8)
    assert trace_scope_total.read(run, args) == pytest.approx(
        100 * (0.7 + 2.4 + 0.3) / 5.8)
    assert trace_scope_total.read(run, dict(args, scope="m3.temp")) == 0.0
    assert trace_scope_total.read(run, dict(args, program="jit_q")) is None
    del run.trace_summary["scope_s"]      # a kind that reduces plainly
    assert trace_scope_total.read(run, args) is None
    run.trace_summary = None              # an untraced run
    assert trace_scope_total.read(run, args) is None


def test_lint_passes_over_the_metric_and_its_entry():
    """benchmark/tests/test_metric_merge_share_2d.py's case of this name
    (imported above, replaced here) looks for `merge_share_pct.2d` at
    the end of `per_layer`, where it stood until PR 47 appended
    `dash-p99`'s entries; that file may not be edited by the PR that
    appends.  The same assertions, the entry found by its name."""
    import lint_manifest

    name, twin = "merge_share_pct.2d", "temporal_share_pct.2d"
    assert lint_manifest.lint() == []
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    by_name = {m["name"]: m for m in man["per_layer"]}
    spec = json.loads((BENCHMARK / "metrics" / f"{name}.json").read_text())
    assert by_name[name] == dict(by_name[twin], name=name)
    assert spec["cells"] == by_name[name]["workloads"] == ["dash-2d"]
    assert (spec["reader"], spec["args"]) == ("trace_scope_total", {
        "program": "jit_device_grouped_pipeline", "scope": "m3.merge"})


def test_manifest_has_the_cell_and_its_metrics():
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in man["workloads"] if w["name"] == "dash-2d")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "m3query-longrange", "panels-2d-2c", 1)
    cfg = next(c for c in man["configs"] if c["name"] == "m3query-longrange")
    assert cfg["source"] == CONFIG["source"] and len(cfg["source"]) <= 200
    assert cfg["reduced"] == sorted(CONFIG["reduced"]) == [
        "hours", "jobs", "query_fanout_series"]
    assert (CONFIG["hours"], CONFIG["jobs"], CONFIG["rehearse"]["hours"]) == (
        44, 4, 44)
    p50 = next(m for m in man["end_to_end"] if m["name"] == "panel_ms_p50")
    assert "dash-2d" in p50["workloads"] and p50["bound"] == 0.08
    mine = {m["name"] for m in man["per_layer"]
            if m.get("workloads") == ["dash-2d"]}
    assert mine == {f"{name}.2d" for name in (
        "device_served_pct", "fetch_ms", "pack_ms", "h2d_ms", "device_ms",
        "device_wait_ms", "d2h_ms", "device_queue_depth", "reply_ms",
        "engine_cpu_ms", "panel_p95_ms", "program_ms", "program_hbm_peak_mb",
        "program_roofline_pct", "temporal_share_pct", "samples_per_lane",
        "rows_per_lane", "merge_share_pct", "band_served_pct")}
    assert MIX["kind"] == "query_longrange_loop" and MIX["clients"] == 2
    assert MIX["gather_min_n_cap"] == 12289
