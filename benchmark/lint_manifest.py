#!/usr/bin/env python3
"""Check BENCHMARK.json, benchmark/metrics/*.json and the recorded noise
of each cell (benchmark/noise/<cell>.json) against each other and
against the rules a manifest is refused for.

    python benchmark/lint_manifest.py        # exit 0 = clean
"""

from __future__ import annotations

import json
import pathlib
import re
import sys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

from harness import noise  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
DATA_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")


def lint(path: pathlib.Path = ROOT / "BENCHMARK.json",
         noise_dir: pathlib.Path = noise.NOISE) -> list[str]:
    """Problems of the manifest."""
    errs = []
    man = json.loads(path.read_text())

    def name(kind, value):
        if not NAME.match(str(value)):
            errs.append(f"{kind} {value!r}: not a name")

    def line(kind, value):
        if not (isinstance(value, str) and 1 <= len(value) <= 200
                and "\n" not in value and "\t" not in value):
            errs.append(f"{kind}: not one line of 1 to 200 characters")

    configs = {c["name"]: c for c in man["configs"]}
    for c in man["configs"]:
        name("config", c["name"])
        line(f"config {c['name']} source", c["source"])
        line(f"config {c['name']} why", c["why"])
        path = ROOT / c["file"]
        if not path.is_file():
            errs.append(f"config {c['name']}: no file {c['file']}")
            continue
        doc = json.loads(path.read_text())
        for key in c["reduced"]:
            name("reduced key", key)
            if key not in doc.get("reduced", {}):
                errs.append(f"config {c['name']}: reduced key {key!r} is "
                            f"not explained in {c['file']}")
        if set(doc.get("reduced", {})) - set(c["reduced"]):
            errs.append(f"config {c['name']}: {c['file']} reduces keys "
                        f"the manifest does not list")
    cells = {}
    for w in man["workloads"]:
        name("cell", w["name"])
        name("traffic", w["traffic"])
        line(f"cell {w['name']} why", w["why"])
        cells[w["name"]] = w
        if w["config"] not in configs:
            errs.append(f"cell {w['name']}: no config {w['config']!r}")
        if w["chips"] not in (1, 4):
            errs.append(f"cell {w['name']}: chips {w['chips']}")
        mixes = [p for p in (HERE / "traffic").glob(f"{w['traffic']}.*")
                 if p.suffix in DATA_SUFFIXES]
        if len(mixes) != 1:
            errs.append(f"cell {w['name']}: {len(mixes)} data files for "
                        f"mix {w['traffic']!r}")
        elif mixes[0].suffix == ".json":
            kind = json.loads(mixes[0].read_text()).get("kind")
            if not (HERE / "traffic_kinds" / f"{kind}.py").is_file():
                errs.append(f"mix {w['traffic']}: no traffic kind {kind!r}")
    used = {w["config"] for w in man["workloads"]}
    errs += [f"config {c}: used by no cell" for c in configs if c not in used]

    e2e = {}
    for m in man["end_to_end"]:
        name("metric", m["name"])
        e2e[m["name"]] = set(m.get("workloads", cells))
        if not UNIT.match(m["unit"]):
            errs.append(f"metric {m['name']}: unit {m['unit']!r}")
        if m["source"] not in ("host_clock", "device_trace"):
            errs.append(f"metric {m['name']}: source {m['source']!r}")
        if not 0.01 <= m["bound"] <= 0.25:
            errs.append(f"metric {m['name']}: bound {m['bound']}")
        errs += [f"metric {m['name']}: no cell {c!r}"
                 for c in e2e[m["name"]] if c not in cells]
    if "setup_s" not in e2e or e2e["setup_s"] != set(cells):
        errs.append("setup_s must be reported by every cell")
    # a cell brings its recorded runs, and no bound is under twice the
    # spread they show (harness/noise.py)
    for c in cells:
        errs += noise.problems(
            c, {m["name"]: m["bound"] for m in man["end_to_end"]
                if c in e2e[m["name"]]}, noise_dir)

    seen = set(e2e)
    layered = set()
    for m in man["per_layer"]:
        name("metric", m["name"])
        if m["name"] in seen:
            errs.append(f"metric {m['name']}: name used twice")
        seen.add(m["name"])
        if not UNIT.match(m["unit"]):
            errs.append(f"metric {m['name']}: unit {m['unit']!r}")
        if m["source"] not in SOURCES:
            errs.append(f"metric {m['name']}: source {m['source']!r}")
        line(f"metric {m['name']} layer", m["layer"])
        if m["moves"] not in e2e:
            errs.append(f"metric {m['name']}: moves {m['moves']!r}, which "
                        f"is no end-to-end metric")
            continue
        where = set(m.get("workloads", e2e[m["moves"]]))
        layered |= where
        errs += [f"metric {m['name']}: cell {c} does not report "
                 f"{m['moves']}" for c in where - e2e[m["moves"]]]
        path = HERE / "metrics" / f"{m['name']}.json"
        if not path.is_file():
            errs.append(f"metric {m['name']}: no file {path.name}")
            continue
        spec = json.loads(path.read_text())
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            if spec.get(key) != m[key]:
                errs.append(f"metric {m['name']}: {key} differs between "
                            f"BENCHMARK.json and {path.name}")
        if set(spec.get("cells", [])) != where:
            errs.append(f"metric {m['name']}: cells differ between "
                        f"BENCHMARK.json and {path.name}")
        if not (HERE / "readers" / f"{spec.get('reader')}.py").is_file():
            errs.append(f"metric {m['name']}: no reader "
                        f"{spec.get('reader')!r}")
    listed = {m["name"] for m in man["per_layer"]}
    errs += [f"metrics/{p.name}: not in the manifest"
             for p in (HERE / "metrics").glob("*.json")
             if p.stem not in listed]
    for c in cells:
        if not any(c in ws for n, ws in e2e.items() if n != "setup_s"):
            errs.append(f"cell {c}: no end-to-end metric besides setup_s")
        if c not in layered:
            errs.append(f"cell {c}: no per-layer metric")
    if sum(w["chips"] == 4 for w in cells.values()) > max(1, len(cells) // 2):
        errs.append("too many four-chip cells")
    if not (isinstance(man["run_seconds"], int)
            and 1 <= man["run_seconds"] <= 51):
        errs.append(f"run_seconds {man['run_seconds']!r}")
    return errs


if __name__ == "__main__":
    problems = lint()
    for p in problems:
        print(p)
    print(f"lint_manifest: {len(problems)} problem(s)")
    sys.exit(1 if problems else 0)
