"""The per-layer metrics that read a phase's waits and its CPU clock
(PR 42), held by the suite the driver runs: the cases of
benchmark/tests/test_wait_metrics.py (the reader `slowlog_cpu` on
hand-made records, each metric's file against the manifest's entry and
against a fabricated run, with and without the clocks; no service)."""

import pathlib
import sys

BENCHMARK = pathlib.Path(__file__).resolve().parents[1] / "benchmark"
for path in (BENCHMARK, BENCHMARK / "tests"):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))

from test_wait_metrics import *  # noqa: E402,F401,F403 - its cases are run here
