"""The per-layer metrics that read a phase's waits and its CPU clock
(PR 42): `slowlog_cpu` on hand-made records, each new metric's file
against the manifest's entry, each metric's number from a fabricated
run, and nothing, not an error, from the records of a program from
before the clocks (the parent's traced runs read these files too);
`device_wait_ms.*` reads a counter that program has.
"""

import json
import pathlib
import types

import pytest

import lint_manifest
import run as bench_run
from readers import slowlog_cpu

METRICS = pathlib.Path(lint_manifest.HERE) / "metrics"
MANIFEST = json.loads((lint_manifest.ROOT / "BENCHMARK.json").read_text())
CELLS = {"dash": "dash-sealed", "live": "dash-live", "fan": "fanout-fleet",
         "topk": "dash-topk"}
# metric -> the value the fabricated run below holds for it
WANT = {
    **{f"db_lock_wait_ms.{c}": 10.0 for c in ("live", "fan", "dash")},
    **{f"device_wait_ms.{c}": 8.0 for c in CELLS},
    **{f"engine_cpu_ms.{c}": 17.0 for c in CELLS},
    **{f"interp_wait_ms.{c}": 33.0 for c in CELLS},
    "fetch_cpu_ms.live": 4.0, "fetch_cpu_ms.fan": 4.0,
    "open_read_cpu_ms.live": 5.0,
    "pack_cpu_ms.fan": 6.0, "pack_cpu_ms.dash": 6.0,
}
OLD_TOO = {f"device_wait_ms.{c}" for c in CELLS}


def _fabricated(name: str, clocks: bool):
    """Sixteen records, one of them clocked; with `clocks` false the
    records and counters of the parent's program."""
    phases = {"parse_s": 0.001, "fetch_s": 0.06, "open_read_s": 0.08,
              "pack_s": 0.009, "device_s": 0.014, "total_s": 0.2}
    records = [{"phases": dict(phases), "device_serving": True}
               for _ in range(16)]
    if clocks:
        for i, r in enumerate(records):
            # the mean, not the median, sees the one panel that stood
            # behind a snapshot
            r["phases"].update(db_lock_wait_s=0.16 if i == 3 else 0.0,
                               device_wait_s=0.008, gc_pause_s=0.0)
        records[7].update(
            cpu={"fetch_s": 0.004, "open_read_s": 0.005, "pack_s": 0.006,
                 "total_s": 0.017},
            interp_wait_s=0.033)
    kernel = {"invocations": 100, "compiles": 0, "execute_s": 1.4,
              "dispatch_s": 0.6, "wait_s": 0.8, "queued_ahead": 50}
    spec = json.loads((METRICS / f"{name}.json").read_text())
    return types.SimpleNamespace(
        cell={"name": spec["cells"][0]}, slow_records=records,
        kernels={"device_grouped_pipeline": kernel,
                 "device_expr_pipeline": kernel},
        timers={}, trace_summary=None, peaks=None), spec


def test_manifest_and_metric_files_agree():
    assert lint_manifest.lint() == []


@pytest.mark.parametrize("name", sorted(WANT))
def test_metric_file_is_the_manifests_entry(name):
    spec = json.loads((METRICS / f"{name}.json").read_text())
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert entry["workloads"] == spec["cells"] == [
        CELLS[name.rsplit(".", 1)[1]]]
    assert entry["unit"] == spec["unit"] == "ms"
    assert entry["better"] == "lower"
    assert entry["moves"] == ("panel_ms_p50" if name.endswith(
        (".dash", ".fan")) else "panel_ms_p95")
    assert entry["source"] == ("program_counter" if name in OLD_TOO
                               else "program_span")
    assert (METRICS.parent / "readers" / f"{spec['reader']}.py").is_file()


@pytest.mark.parametrize("name", sorted(WANT))
def test_metric_reads_its_number(name):
    run, spec = _fabricated(name, True)
    reader = bench_run.load_module("readers", spec["reader"])
    assert reader.read(run, spec["args"]) == pytest.approx(WANT[name])
    old, _ = _fabricated(name, False)
    value = reader.read(old, spec["args"])
    if name in OLD_TOO:
        assert value == pytest.approx(WANT[name])
    else:
        assert value is None


@pytest.mark.parametrize("cell", sorted(CELLS.values()))
def test_result_line_of_the_parents_program_leaves_them_out(cell):
    mine = {n for n in WANT if CELLS[n.rsplit(".", 1)[1]] == cell}
    name = sorted(mine)[0]
    old = bench_run.read_per_layer(_fabricated(name, False)[0], MANIFEST)
    new = bench_run.read_per_layer(_fabricated(name, True)[0], MANIFEST)
    assert mine & set(old) == mine & OLD_TOO
    assert mine <= set(new)


@pytest.mark.parametrize("records, key, want", [
    ([{"cpu": {"pack_s": 0.01}, "interp_wait_s": 0.02}, {},
      {"cpu": {"pack_s": 0.03}, "interp_wait_s": 0.04}], "pack_s", 20.0),
    ([{"cpu": {"pack_s": 0.01}, "interp_wait_s": 0.02}, {"phases": {}},
      {"cpu": {"pack_s": 0.03}, "interp_wait_s": 0.04}],
     "interp_wait_s", 30.0),
    # a clocked record's zero is a reading; an unclocked record is none
    ([{"cpu": {"pack_s": 0.0}, "interp_wait_s": 0.0}, {}], "pack_s", 0.0),
    ([{}, {"phases": {"pack_s": 1.0}}], "pack_s", None),
    ([], "interp_wait_s", None),
    (None, "interp_wait_s", None),
])
def test_slowlog_cpu_reads_the_clocked_records_alone(records, key, want):
    run = types.SimpleNamespace(slow_records=records)
    got = slowlog_cpu.read(run, {"key": key, "scale": 1000.0})
    assert got == (want if want is None else pytest.approx(want))
