"""The benchmark's own rules, held by the suite the driver runs: the
cases of benchmark/tests/test_yardstick.py (the spread function, the
rule that sets a bound from a recorded study, the lint's noise rule,
the load generator against a stub server; no jax, no service) and
benchmark/lint_manifest.py on the tree as it stands, so that a cell
whose recorded noise does not fit its bounds fails here."""

import pathlib
import shutil
import sys

import pytest

BENCHMARK = pathlib.Path(__file__).resolve().parents[1] / "benchmark"
for path in (BENCHMARK, BENCHMARK / "tests"):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))

import lint_manifest  # noqa: E402
from harness import noise  # noqa: E402
from test_yardstick import *  # noqa: E402,F401,F403 - its cases are run here


@pytest.fixture(autouse=True)
def other_cells_keep_their_studies(monkeypatch):
    """test_yardstick's hand-made cases lint the tree's manifest against
    a directory that holds a study for dash-sealed alone (or none): the
    recorded studies of the manifest's other cells are put beside it."""
    real = lint_manifest.lint

    def lint(path=lint_manifest.ROOT / "BENCHMARK.json",
             noise_dir=noise.NOISE):
        if noise_dir != noise.NOISE:
            for study in noise.NOISE.glob("*.json"):
                if study.name != "dash-sealed.json":
                    shutil.copy(study, noise_dir)
        return real(path, noise_dir)

    monkeypatch.setattr(lint_manifest, "lint", lint)


def test_manifest_metrics_and_recorded_noise_agree():
    assert lint_manifest.lint() == []
