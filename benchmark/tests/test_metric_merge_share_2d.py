"""`merge_share_pct.2d`: the merge's share of dash-2d's program, the
twin of `temporal_share_pct.2d` (PR 46).  A data file and its manifest
entry over a reader that was there (readers/trace_scope_total.py): the
lint passes over both, the reader returns the share of `m3.merge` from
a summary's `scope_s`, and a dash-2d line carries the two shares side
by side.  tests/test_benchmark_longrange_kind.py runs these in tier-1.
"""

from __future__ import annotations

import json
import pathlib
import sys
import types

import pytest

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent.parent
for path in (HERE, HERE.parent):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))

__all__ = ["test_lint_passes_over_the_metric_and_its_entry",
           "test_reader_returns_the_merges_share_of_the_program",
           "test_a_dash_2d_line_carries_both_shares"]
NAME, TWIN = "merge_share_pct.2d", "temporal_share_pct.2d"
# ledger PR 45, dash-2d, the change's traced run: seconds of the 6 s
# slice by scope (the merge's `m3.merge/while.89`, the windowed stage's
# 3.031 s over its sub-scopes) and the program's whole device time
PR45 = {
    "programs": {"jit_device_grouped_pipeline": {"calls": 21.0,
                                                 "device_s": 5.696}},
    "device_ops": [["m3.merge/while.89", 2.4217],
                   ["m3.temporal/bounds/convert_reduce_fusion", 0.7349]],
    "scope_s": {"m3.merge": 2.4217, "m3.temporal/bounds": 0.7349,
                "m3.temporal/take": 2.2961, "m3.temporal": 0.0,
                "m3.decode": 0.2037, "m3.group": 0.04, "": 0.0},
}


def _manifest() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_lint_passes_over_the_metric_and_its_entry():
    import lint_manifest

    assert lint_manifest.lint() == []
    man = _manifest()
    entry = man["per_layer"][-1]
    spec = json.loads((HERE.parent / "metrics" / f"{NAME}.json").read_text())
    twin = next(m for m in man["per_layer"] if m["name"] == TWIN)
    assert entry == dict(twin, name=NAME)
    assert spec["cells"] == entry["workloads"] == ["dash-2d"]
    assert (spec["reader"], spec["args"]) == ("trace_scope_total", {
        "program": "jit_device_grouped_pipeline", "scope": "m3.merge"})


def test_reader_returns_the_merges_share_of_the_program():
    from harness import trace_subscopes
    from readers import trace_scope_total

    spec = json.loads((HERE.parent / "metrics" / f"{NAME}.json").read_text())
    run = types.SimpleNamespace(trace_summary=PR45)
    assert trace_scope_total.read(run, spec["args"]) == pytest.approx(
        100 * 2.4217 / 5.696)                      # 42.5
    # the open rows' laying stands under the merge's scope and counts
    wider = dict(PR45, scope_s=dict(PR45["scope_s"],
                                    **{"m3.merge/m3.open": 0.1}))
    run.trace_summary = wider
    assert trace_scope_total.read(run, spec["args"]) == pytest.approx(
        100 * 2.5217 / 5.696)
    # a recorded trace of a program that names no merge: 0, not nothing
    small = trace_subscopes.reduce(
        str(HERE / "small_trace" / "small.xplane.pb"))
    assert "m3.merge" not in small["scope_s"] and small["programs"]
    run.trace_summary = small
    for program in small["programs"]:
        assert trace_scope_total.read(
            run, dict(spec["args"], program=program)) == 0.0
    run.trace_summary = None                       # an untraced run
    assert trace_scope_total.read(run, spec["args"]) is None


def test_a_dash_2d_line_carries_both_shares():
    import run as bench_run

    man = _manifest()
    both = [m for m in man["per_layer"] if m["name"] in (NAME, TWIN)]
    assert [m["name"] for m in both] == [TWIN, NAME]
    run = types.SimpleNamespace(cell={"name": "dash-2d"}, trace_summary=PR45)
    got = bench_run.read_per_layer(run, {"per_layer": both})
    assert got[NAME] == {"value": pytest.approx(42.516, abs=1e-3),
                         "unit": "%"}
    assert got[TWIN]["value"] == pytest.approx(100 * 3.031 / 5.696)
    # and no other cell's line does
    run.cell = {"name": "dash-sealed"}
    assert bench_run.read_per_layer(run, {"per_layer": both}) == {}
