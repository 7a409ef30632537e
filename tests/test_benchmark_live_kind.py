"""The checks that judge `dash-live`'s scrapes (PR 41), held by the
suite the driver runs: the cases of benchmark/tests/test_live_kind.py
(the scrape generator against stub servers on port 0, its witness of
the host, the arithmetic of `scrapegen.account`; no jax, no service,
a few seconds in all)."""

import pathlib
import sys

BENCHMARK = pathlib.Path(__file__).resolve().parents[1] / "benchmark"
for path in (BENCHMARK, BENCHMARK / "tests"):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))

import test_live_kind  # noqa: E402
from test_live_kind import *  # noqa: E402,F401,F403 - its cases are run here


def test_stopped_process_woke_late_and_its_witness_says_so():
    """The one case that times a process: it stops the generator for
    0.5 s against a lateness of 0.1 s, and whether one request or two
    wake late turns on where the stop falls in the 0.2 s schedule (a
    run in three reads one, alone on this host; more beside five other
    workers).  Here it may take five tries; by hand, under
    benchmark/tests, it stays as strict as it was written."""
    for tries_left in range(4, -1, -1):
        try:
            return (test_live_kind.
                    test_stopped_process_woke_late_and_its_witness_says_so())
        except AssertionError:
            if not tries_left:
                raise
