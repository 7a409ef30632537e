"""The per-layer metrics that read the query's cost record and the
device queue (PR 25): the manifest and the metric files agree, each
metric reads its number from a fabricated run, and reads nothing, not
an error, from the records of a program that has no such phase or stat.
"""

import json
import pathlib
import types

import pytest

import lint_manifest
import run as bench_run

METRICS = pathlib.Path(lint_manifest.HERE) / "metrics"
# metric -> the value the fabricated run below holds for it
WANT = {"pack_ms.dash": 6.0, "h2d_ms.dash": 1.5, "d2h_ms.dash": 0.25,
        "engine_self_ms.dash": 2.0, "frontend_ms.dash": 4.0,
        "device_queue_depth.dash": 2.9}


def _fabricated(with_new_keys: bool):
    phases = {"parse_s": 0.001, "fetch_s": 0.061, "decode_s": 0.0,
              "device_s": 0.7526, "total_s": 0.8403}
    kernel = {"invocations": 100, "compiles": 0, "compile_s": 0.0,
              "execute_s": 75.26, "elements": 1, "bytes": 1,
              "result_bytes": 1}
    if with_new_keys:
        phases.update(pack_s=0.006, h2d_s=0.0015, d2h_s=0.00025,
                      self_s=0.002, frontend_s=0.004, merge_s=0.0)
        kernel.update(queued_ahead=290, dispatch_s=0.1, wait_s=75.16)
    records = [{"phases": dict(phases), "device_serving": True}
               for _ in range(5)]
    return types.SimpleNamespace(
        cell={"name": "dash-sealed"}, slow_records=records,
        kernels={"device_grouped_pipeline": kernel}, timers={},
        trace_summary=None, peaks=None)


def test_manifest_and_metric_files_agree():
    assert lint_manifest.lint() == []


@pytest.mark.parametrize("name", sorted(WANT))
def test_metric_reads_its_number(name):
    spec = json.loads((METRICS / f"{name}.json").read_text())
    reader = bench_run.load_module("readers", spec["reader"])
    assert reader.read(_fabricated(True), spec["args"]) == pytest.approx(
        WANT[name])
    assert reader.read(_fabricated(False), spec["args"]) is None


def test_result_line_of_an_older_program_leaves_them_out():
    manifest = json.loads((lint_manifest.ROOT / "BENCHMARK.json")
                          .read_text())
    old = bench_run.read_per_layer(_fabricated(False), manifest)
    new = bench_run.read_per_layer(_fabricated(True), manifest)
    assert not set(WANT) & set(old)
    assert set(WANT) <= set(new)
    assert old["fetch_ms.dash"] == new["fetch_ms.dash"]
