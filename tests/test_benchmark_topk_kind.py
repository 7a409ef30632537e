"""The benchmark's top-k cell on the CPU: the traffic kind end to end
(benchmark/traffic_kinds/query_topk_loop.py through benchmark/run.py
--rehearse, a process of its own), the tie rule of its reference
(benchmark/harness/reference_topk.py) on hand-made sums, the skewed
generator (benchmark/harness/fleet_skewed.py) and the manifest's new
entries."""

import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCHMARK = ROOT / "benchmark"
if str(BENCHMARK) not in sys.path:
    sys.path.insert(0, str(BENCHMARK))

import lint_manifest  # noqa: E402
from harness import fleet_skewed, reference_topk  # noqa: E402

LIMIT = 1e-9


def test_kind_end_to_end_over_http_against_the_reference():
    """The rehearsal fleet: 2 jobs x 10 instances x 5 handlers, six
    instances contesting the fifth place at every step."""
    proc = subprocess.run(
        [sys.executable, str(BENCHMARK / "run.py"), "--workload", "dash-topk",
         "--seed", "2147483999", "--seconds", "2", "--trace", "0",
         "--rehearse"],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(x) for x in proc.stdout.splitlines()
             if x.startswith("{")]
    line = lines[-1]
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 2
    assert set(line["metrics"]) == {"panel_ms_p95", "setup_s"}
    checks = line["checks"]
    assert 0 < checks["panel_max_rel_gap"]["value"] < LIMIT
    for name in ("topk_steps_miscounted", "topk_points_misranked",
                 "topk_rows_unknown", "topk_rows_without_a_point",
                 "jobs_without_a_reply", "compiles_in_window",
                 "records_not_served_whole_by_the_fused_program",
                 "panels_without_a_record", "record_tap_overruns",
                 "m3_query_host_split_total_moved",
                 "m3_query_device_decline_total_moved",
                 "samples_acked_minus_read_back", "series_missing"):
        assert checks[name]["value"] == 0 and checks[name]["ok"], name
    phases = {x["phase"]: x for x in lines if "phase" in x}
    # every job warmed, one program for both; the reply is a union over
    # steps, wider than k
    assert phases["warm"]["jobs"] == 2
    assert phases["warm"]["programs_minted"] == 1
    done = phases["window_done"]
    assert done["distinct_jobs"] == 2 and max(done["rows_per_reply"]) > 5
    # a record a panel, and up to one a client more (in flight at the
    # window's opening)
    assert 0 <= done["records"] - done["requests"] <= 4
    assert done["lanes_groups_k"] == [[50, 64, 10, 5]]


STEPS = np.arange(0, 300, 60)
NAMES = [f"g{i}" for i in range(6)]


def _reply(served):
    """{group: {step index: value}} -> loadgen.rows_of's form."""
    return {(("instance", NAMES[g]),): (
        np.array([STEPS[s] for s in sorted(cells)], dtype=np.float64),
        np.array([cells[s] for s in sorted(cells)]))
        for g, cells in served.items()}


def _sums():
    """Six groups over five steps, k = 3: g0 > g1 lead; at step 1, g2 and
    g3 tie exactly at the third place; at step 2, g3 lies 1e-12
    (relative) under g2; at step 3, g4 has no value; g5 is far below."""
    sums = np.tile(np.array([[60.0], [50.0], [40.0], [30.0], [20.0],
                             [1.0]]), (1, 5))
    sums[3, 1] = 40.0
    sums[3, 2] = 40.0 * (1 - 1e-12)
    sums[4, 3] = np.nan
    return sums


def _own_answer(sums, k=3):
    sel = reference_topk.topk(sums, k)
    return {g: {s: sums[g, s] for s in range(sums.shape[1]) if sel[g, s]}
            for g in range(len(sums)) if sel[g].any()}


def test_the_references_own_answer_compares_clean():
    sums = _sums()
    found = reference_topk.compare(_reply(_own_answer(sums)), "instance",
                                   NAMES, STEPS, sums, 3, LIMIT)
    assert found == {"max_rel_gap": 0.0, "steps_miscounted": 0,
                     "points_misranked": 0, "rows_unknown": 0,
                     "rows_without_a_point": 0}
    # the stated tie rule: the group that comes first wins
    sel = reference_topk.topk(sums, 3)
    assert sel[2, 1] and not sel[3, 1]


@pytest.mark.parametrize("step", [1, 2])
def test_a_tie_and_a_near_tie_may_fall_either_way(step):
    sums = _sums()
    served = _own_answer(sums)
    del served[2][step]                 # g3 for g2, at the tie
    served.setdefault(3, {})[step] = sums[3, step]
    found = reference_topk.compare(_reply(served), "instance", NAMES,
                                   STEPS, sums, 3, LIMIT)
    assert found["points_misranked"] == found["steps_miscounted"] == 0
    assert found["max_rel_gap"] == 0.0


def test_what_is_not_a_tie_is_held():
    sums = _sums()
    # the fourth group served for the third where they lie 25% apart
    served = _own_answer(sums)
    del served[2][0]
    served.setdefault(3, {})[0] = sums[3, 0]
    found = reference_topk.compare(_reply(served), "instance", NAMES,
                                   STEPS, sums, 3, LIMIT)
    # (the third itself is within the limit of the third: the count of
    # a step's points and the fourth's distance hold it)
    assert found["points_misranked"] == 1 and found["steps_miscounted"] == 0
    # both sides of a tie served: one point too many at that step
    served = _own_answer(sums)
    served.setdefault(3, {})[1] = sums[3, 1]
    found = reference_topk.compare(_reply(served), "instance", NAMES,
                                   STEPS, sums, 3, LIMIT)
    assert found["steps_miscounted"] == 1 and found["points_misranked"] == 0
    # a value in float32; a point where the reference has none
    served = _own_answer(sums)
    served[0][4] = float(np.float32(sums[0, 4] * (1 + 3e-7)))
    served.setdefault(4, {})[3] = 20.0
    found = reference_topk.compare(_reply(served), "instance", NAMES,
                                   STEPS, sums, 3, LIMIT)
    assert found["max_rel_gap"] == np.inf and found["points_misranked"] == 1
    del served[4]
    found = reference_topk.compare(_reply(served), "instance", NAMES,
                                   STEPS, sums, 3, LIMIT)
    assert 1e-7 < found["max_rel_gap"] < 1e-6
    # a row of another label, and a row without a point
    rows = _reply(_own_answer(sums))
    rows[(("zone", "z1"),)] = (STEPS[:1].astype(np.float64), np.array([1.0]))
    rows[(("instance", "g5"),)] = (np.array([]), np.array([]))
    found = reference_topk.compare(rows, "instance", NAMES, STEPS, sums, 3,
                                   LIMIT)
    assert (found["rows_unknown"], found["rows_without_a_point"]) == (1, 1)


def test_record_tap_takes_every_record_a_small_ring_would_lose():
    """The kind reads the slow-query ring out while the window runs: a
    ring of 8 under 50 records in bursts of 5 loses none, in order; a
    burst longer than the ring is counted as an overrun; records from
    before the tap's start or of other expressions are left out."""
    from m3_tpu.query import slowlog
    from traffic_kinds.query_topk_loop import RecordTap

    log = slowlog.SlowQueryLog(capacity=8)
    log.record({"expr": "q", "n": -1, "ts": 50.0})
    tap = RecordTap(log, since=100.0, asked=["q"])
    tap.take()
    n = 0
    for _burst in range(10):
        for _ in range(5):
            log.record({"expr": "q" if n % 10 else "other", "n": n,
                        "ts": 100.0 + n})
            n += 1
        tap.take()
    got = tap.finish()
    assert [r["n"] for r in got] == [i for i in range(50) if i % 10]
    assert tap.overruns == 0
    for i in range(9):      # one more than the ring holds, unread
        log.record({"expr": "q", "n": 100 + i, "ts": 300.0 + i})
    tap.take()
    assert tap.overruns == 1 and len(tap.records) == 45 + 8


def test_fewer_groups_with_a_value_than_k():
    sums = np.full((4, 2), np.nan)
    sums[1] = [5.0, np.nan]
    sums[2] = [7.0, np.nan]
    n, kth = reference_topk.kth_largest(sums, 3)
    assert list(n) == [2, 0] and kth[0] == 5.0 and np.isnan(kth[1])
    found = reference_topk.compare(
        {(("instance", "g1"),): (np.array([0.0]), np.array([5.0])),
         (("instance", "g2"),): (np.array([0.0]), np.array([7.0]))},
        "instance", NAMES[:4], STEPS[:2], sums, 3, LIMIT)
    assert not any(found.values())


CFG = dict(json.loads((BENCHMARK / "configs" / "m3query-topk.json")
                      .read_text()), jobs=3)


def test_skewed_fleet_regenerates_a_block_and_keeps_its_rank_law():
    fleet = fleet_skewed.SkewedFleet(CFG, 2147484000, 1_800_000_000, 2)
    again = fleet_skewed.SkewedFleet(CFG, 2147484000, 1_800_000_000, 2)
    assert (fleet.per_job, fleet.n_series) == (500, 1500)
    for lo, hi, k in ((0, 25, 0), (475, 525, 1), (1475, 1500, 1)):
        assert np.array_equal(fleet.block_values(lo, hi, k),
                              again.block_values(lo, hi, k))
    other = fleet_skewed.SkewedFleet(CFG, 2147484001, 1_800_000_000, 2)
    assert not np.array_equal(fleet.rank, other.rank)
    # Zipf with exponent 1: every job's instances hold each rank once,
    # M = max(2, round(2000 / (1 + rank))), shared by the five handlers
    for j in range(3):
        assert sorted(fleet.rank[j]) == list(range(100))
    want = np.maximum(2, np.rint(2000 / (1.0 + fleet.rank)))
    assert np.array_equal(fleet.m, want)
    assert fleet.m.max() == 2000 and fleet.m.min() == 20
    m = fleet.series_m(500, 1000)
    assert np.array_equal(m.reshape(100, 5), np.repeat(
        fleet.m[1][:, None], 5, axis=1))
    # increments within 0..M-1, counters that never reset across blocks
    ts, vs = fleet.job_arrays(1)
    assert len(ts) == 1440 and vs.shape == (500, 1440)
    inc = np.diff(vs[:, :720], axis=1)
    assert inc.min() >= 0 and (inc.max(axis=1) < m).all()
    assert (inc.max(axis=1) >= 0.9 * (m - 1)).all()
    assert (np.diff(vs, axis=1) >= 0).all()
    # the fleet's mean increment is the uniform fleets' (49.5), near 52
    assert 50.0 < (fleet.m - 1).mean() / 2 < 53.0
    labels = fleet.labels(500 + 7 * 5 + 3)
    assert labels == {b"__name__": b"http_requests_total",
                      b"job": b"job-001", b"zone": b"zone-7",
                      b"instance": b"inst-0007", b"handler": b"/api/h3"}


def test_manifest_holds_the_cell_and_lints():
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = next(c for c in man["configs"] if c["name"] == "m3query-topk")
    assert cfg["reduced"] == ["hours", "jobs", "query_fanout_series"]
    assert len(cfg["source"]) <= 200
    cell = next(w for w in man["workloads"] if w["name"] == "dash-topk")
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        "dash-topk", "m3query-topk", "panels-topk-4c", 1)
    judged = [m["name"] for m in man["end_to_end"]
              if "dash-topk" in m.get("workloads", ["dash-topk"])]
    assert "setup_s" in judged and len(judged) >= 2
    mix = json.loads((BENCHMARK / "traffic" / "panels-topk-4c.json")
                     .read_text())
    assert sorted(mix["end_to_end"] + ["setup_s"]) == sorted(judged)
    layered = {m["name"]: m for m in man["per_layer"]
               if m.get("workloads") == ["dash-topk"]}
    assert {"fused_served_pct.topk", "plan_ms.topk", "program_ms.topk",
            "program_roofline_pct.topk", "topk_share_pct.topk",
            "program_hbm_peak_mb.topk", "rows_per_reply.topk",
            "panel_median_ms.topk"} <= set(layered)
    assert all(m["moves"] in judged for m in layered.values())
    assert lint_manifest.lint() == []
