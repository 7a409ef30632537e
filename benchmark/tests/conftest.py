"""benchmark/tests are run by hand, on the CPU, from the repo's root:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

They are not part of the tier-1 suite under tests/.
"""

import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parent.parent))


@pytest.fixture
def run_cell(capsys):
    """Drive benchmark/run.py's main() for one cell with --rehearse
    (which skips the look for a chip) -> the result line."""
    import run as bench_run

    def go(workload: str, seed: int, trace: int = 0, seconds: float = 2.0):
        argv = sys.argv
        sys.argv = ["run.py", "--workload", workload, "--seed", str(seed),
                    "--seconds", str(seconds), "--trace", str(trace),
                    "--rehearse"]
        try:
            rc = bench_run.main()
        finally:
            sys.argv = argv
        assert rc == 0
        return json.loads(capsys.readouterr().out.strip().splitlines()[-1])

    return go
