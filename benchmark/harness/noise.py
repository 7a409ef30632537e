"""The recorded A/A studies under benchmark/noise/ and the rule that
sets an end-to-end bound from them.

`noise/<cell>.json` holds every run of one tree in that cell, none
left out: {"cell", "runs": [{"set", "call", "seed", <metric>: value,
..., "correct", optionally "cold": true}, ...]}.  A set is one pass
over the study's seeds on one machine; a `cold` run is the first of
its checkout, which compiles or builds the native libraries, and its
`setup_s` is left out as the driver leaves it out.

The rule: a bound is twice the widest spread that any set shows for
the metric, rounded up to the next 0.005 and no lower than 0.02; then
the medians of any two sets of one call (one machine: what the driver
compares) must agree within it, and it is raised to cover a pair that
does not.  Sets of different calls are on different machines, whose
levels differ by more than any bound; no check compares across them.
"""

from __future__ import annotations

import itertools
import json
import math
import pathlib
import statistics

NOISE = pathlib.Path(__file__).resolve().parents[1] / "noise"
MIN_RUNS, MIN_SETS = 12, 2


def iqr_share(values) -> float:
    """(Q3 - Q1) / median, quartiles as `statistics.quantiles(n=4)`."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def spread(values) -> float:
    """A set's spread as the ledger has it: the distance between the
    quartiles over the median, leaving out the run farthest from the
    median where that narrows it."""
    values = list(values)
    whole = iqr_share(values)
    if len(values) < 4:
        return whole
    mid = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - mid))
    return min(whole, iqr_share(values[:far] + values[far + 1:]))


def load(cell: str, root: pathlib.Path = NOISE) -> dict | None:
    path = root / f"{cell}.json"
    return json.loads(path.read_text()) if path.is_file() else None


def sets(study: dict, metric: str) -> dict[str, list[float]]:
    """{set: the metric's values in the set's runs}."""
    out: dict[str, list[float]] = {}
    for run in study["runs"]:
        if metric in run and not (metric == "setup_s" and run.get("cold")):
            out.setdefault(run["set"], []).append(run[metric])
    return out


def widest_spread(study: dict, metric: str) -> float | None:
    """The widest spread of any set with at least three runs of it."""
    found = [spread(v) for v in sets(study, metric).values() if len(v) >= 3]
    return max(found, default=None)


def widest_pair_gap(study: dict, metric: str,
                    same_call: bool = True) -> float:
    """The most by which the medians of two sets differ, as a share of
    the lower one: of two sets of one call, or of any two."""
    call_of = {run["set"]: run["call"] for run in study["runs"]}
    mids = {name: statistics.median(v)
            for name, v in sets(study, metric).items()}
    return max((abs(mids[a] - mids[b]) / min(mids[a], mids[b])
                for a, b in itertools.combinations(mids, 2)
                if not same_call or call_of[a] == call_of[b]), default=0.0)


def rule_bound(study: dict, metric: str) -> float:
    """The bound the rule gives the metric from this study."""
    def up(x):          # to the next 0.005; 1e-9 forgives 0.03 = 0.03000..04
        return round(math.ceil(x / 0.005 - 1e-9) * 0.005, 3)

    return max(0.02, up(2 * widest_spread(study, metric)),
               up(widest_pair_gap(study, metric)))


def problems(cell: str, bounds: dict[str, float],
             root: pathlib.Path = NOISE) -> list[str]:
    """What the lint says of one cell's study against the manifest's
    bounds ({metric the cell reports: bound})."""
    study = load(cell, root)
    if study is None:
        return [f"cell {cell}: no noise/{cell}.json"]
    runs = study["runs"]
    n_sets = len({r["set"] for r in runs})
    if len(runs) < MIN_RUNS or n_sets < MIN_SETS:
        return [f"cell {cell}: noise/{cell}.json holds {len(runs)} runs in "
                f"{n_sets} sets, under {MIN_RUNS} in {MIN_SETS}"]
    out = []
    if not all(r["correct"] for r in runs):
        out.append(f"cell {cell}: noise/{cell}.json has a run that is not "
                   f"correct")
    for metric, bound in bounds.items():
        wide = widest_spread(study, metric)
        if wide is None:
            out.append(f"cell {cell}: noise/{cell}.json has no set of "
                       f"{metric}")
        elif bound < 2 * wide:
            out.append(f"metric {metric}: bound {bound} is under twice the "
                       f"spread {wide:.4f} recorded for cell {cell}")
    return out


def run_of(stdout_path: pathlib.Path) -> dict:
    """One run's entry from the captured stdout of benchmark/run.py:
    the phase lines and, last, the result."""
    lines = [json.loads(x) for x in stdout_path.read_text().splitlines()
             if x.startswith("{")]
    phases = {x["phase"]: x for x in lines if "phase" in x}
    result, done = lines[-1], phases.get("window_done", {})
    run = {name: m["value"] for name, m in result["metrics"].items()}
    run.update(
        panels=done.get("requests"), beyond_p95=done.get("beyond_p95"),
        gc_full_s=done.get("gc_full_s"), failed=result["failed"],
        compiles_in_window=done.get("compiles_in_window"),
        correct=result["correct"], panel_max_rel_gap=next(
            (x["value"] for x in lines
             if x.get("check") == "panel_max_rel_gap"), None))
    # a checkout's first run compiles, or builds the native libraries:
    # its set-up is not held to the bound
    start = phases["start"]
    if (start.get("compile_cache_entries") == 0
            or start.get("native_build_s", 0) >= 1.0):
        run["cold"] = True
    return run


def record(cell: str, set_name: str, call: int, captured,
           root: pathlib.Path = NOISE) -> dict:
    """Append one set's runs, `captured` = [(seed, stdout path), ...] in
    the order they were made, to noise/<cell>.json.  -> the study."""
    study = load(cell, root) or {"cell": cell, "runs": []}
    study["runs"] += [{"set": set_name, "call": call, "seed": seed,
                       **run_of(pathlib.Path(path))}
                      for seed, path in captured]
    root.mkdir(exist_ok=True)
    (root / f"{cell}.json").write_text(json.dumps(study, indent=1) + "\n")
    return study


if __name__ == "__main__":
    # python3 benchmark/harness/noise.py <cell> <set> <call> <seed>=<stdout>...
    import sys

    pairs = [arg.split("=", 1) for arg in sys.argv[4:]]
    done = record(sys.argv[1], sys.argv[2], int(sys.argv[3]),
                  [(int(seed), path) for seed, path in pairs])
    print(f"noise/{sys.argv[1]}.json: {len(done['runs'])} runs")
