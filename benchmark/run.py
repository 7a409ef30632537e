#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json on the machine this is started on.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process starts the coordinator service, makes the cell's data
from --seed, sets up and warms, measures for --seconds, checks what the
window produced against the numpy reference, and prints the result as
the last line of stdout.  The cell's traffic kind may start a load
generator as a child (harness/loadgen.py); it stops it before it
returns.  Everything that belongs to one cell is found
by name: the configuration and the traffic mix named in BENCHMARK.json,
the mix's `kind` under traffic_kinds/, each per-layer metric under
metrics/ and its reader under readers/.  See README.md.

--rehearse (never what the driver runs) lets it run on another
platform than the TPU, at the sizes under the mix's and config's
`rehearse` keys, and says so in its result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT))


def load_module(kind: str, name: str):
    """benchmark/<kind>/<name>.py, found by name."""
    return importlib.import_module(f"{kind}.{name}")


class Run:
    """What a traffic kind and the readers see of one run."""

    def __init__(self, args, cell, config, mix):
        self.args, self.cell, self.config, self.mix = args, cell, config, mix
        self.seed, self.seconds = args.seed, args.seconds
        self.trace, self.rehearse = bool(args.trace), args.rehearse
        self.svc = self.device = self.peaks = None
        self.timers: dict[str, list[float]] = {}    # harness_timer
        self.slow_records: list[dict] = []          # slowlog_phase
        self.kernels: dict[str, dict] = {}          # kernel_telemetry
        self.trace_summary: dict | None = None      # trace_program
        self.checks: list[dict] = []
        self.t_window: float | None = None
        self.out_dir = ROOT / ".bench_out" / cell["name"]
        log_dir = ROOT / "chiprun_out" / "benchmark"
        log_dir.mkdir(parents=True, exist_ok=True)
        self._log = open(log_dir / (f"{cell['name']}.seed{args.seed}."
                                    f"trace{args.trace}.jsonl"), "w")

    def emit(self, phase: str, log_only: bool = False, **fields) -> None:
        line = json.dumps({"phase": phase,
                           "t": round(time.perf_counter() - T_PROCESS, 3),
                           **fields})
        if not log_only:
            print(line, flush=True)
        self._log.write(line + "\n")
        self._log.flush()

    def window_opens(self) -> float:
        """The kind calls this where its measured window starts; all
        before it, the kind's own ramp included, is set-up.  -> the
        clock's reading at that moment."""
        self.t_window = time.perf_counter()
        self.emit("window_opens",
                  setup_s=round(self.t_window - T_PROCESS, 3))
        return self.t_window

    def param(self, group: dict, key: str):
        """A parameter of the config or the mix; --rehearse takes the
        value under the file's `rehearse` key where there is one."""
        if self.rehearse and key in group.get("rehearse", {}):
            return group["rehearse"][key]
        return group[key]

    def check(self, name: str, value: float, limit: float,
              ok: bool | None = None) -> None:
        """One number compared, beside its limit; printed in every run."""
        ok = bool(value <= limit) if ok is None else bool(ok)
        self.checks.append({"check": name, "value": value, "limit": limit,
                            "ok": ok})
        self.emit("check", check=name, value=value, limit=limit, ok=ok)

    def trace_dir(self) -> str:
        path = self.out_dir / "trace"
        shutil.rmtree(path, ignore_errors=True)
        return str(path)


def read_per_layer(run: Run, manifest: dict) -> dict:
    out = {}
    for entry in manifest["per_layer"]:
        if run.cell["name"] not in entry.get("workloads", [run.cell["name"]]):
            continue
        spec = json.loads((HERE / "metrics" / f"{entry['name']}.json")
                          .read_text())
        value = load_module("readers", spec["reader"]).read(
            run, spec.get("args", {}))
        if value is not None:
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next((w for w in manifest["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        print(f"no cell {args.workload!r} in the manifest", file=sys.stderr)
        return 2
    cfg_entry = next(c for c in manifest["configs"]
                     if c["name"] == cell["config"])
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    mix = json.loads((HERE / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    kind = load_module("traffic_kinds", mix["kind"])
    run = Run(args, cell, config, mix)

    if args.rehearse:
        # XLA:CPU only serves from the device tier when told to
        os.environ.setdefault("M3_DEVICE_SERVING", "1")
    from harness import service
    from m3_tpu.utils import compile_cache
    cache_dir = compile_cache.configure()
    # 0 in a checkout's first run, which compiles
    cached = len(os.listdir(cache_dir)) if cache_dir and os.path.isdir(
        cache_dir) else 0
    try:
        run.device, run.peaks = service.device_info(args.rehearse,
                                                    cell["chips"])
    except service.NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    run.out_dir.mkdir(parents=True, exist_ok=True)
    run.svc, native_s = service.start(run.out_dir, config["service_config"],
                                      config["service_overlay"])
    run.emit("start", pid=os.getpid(), **run.device, rehearse=args.rehearse,
             compile_cache_dir=cache_dir, compile_cache_entries=cached,
             native_build_s=round(native_s, 2),
             http_port=run.svc.http_port)
    try:
        state = kind.setup(run)
        result = kind.window(run, state)
        setup_s = run.t_window - T_PROCESS
        run.emit("window_done", **result["summary"])
        kind.check(run, state, result)
    finally:
        run.svc.stop()
        shutil.rmtree(run.out_dir / "data", ignore_errors=True)
        shutil.rmtree(run.out_dir / "trace", ignore_errors=True)

    failed = result["failed"]
    correct = bool(run.checks) and all(c["ok"] for c in run.checks)
    if args.trace:
        metrics = read_per_layer(run, manifest)
    else:
        metrics = {m["name"]: {"value": result["end_to_end"][m["name"]],
                               "unit": m["unit"]}
                   for m in manifest["end_to_end"]
                   if m["name"] in result["end_to_end"]}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    device = dict(run.device,
                  memory_peak_bytes=service.memory_peak_bytes())
    line = {"correct": correct, "attempted": result["attempted"],
            "failed": failed, "metrics": metrics, "device": device}
    if args.trace and run.trace_summary:
        ts = run.trace_summary
        run.emit("trace", busy_s=ts["busy_s"], window_s=ts["window_s"],
                 programs=ts["programs"])
        device["busy_s"], device["window_s"] = ts["busy_s"], ts["window_s"]
        line["breakdown"] = {"device_ops": ts["device_ops"],
                             "idle_gaps": ts["idle_gaps"]}
    if args.rehearse:
        line["rehearse"] = True
    run.emit("done", correct=correct, seconds=round(
        time.perf_counter() - T_PROCESS, 2))
    # each number compared beside its limit: the result's last key, and
    # the last lines of stderr
    line["checks"] = {c["check"]: {"value": c["value"], "limit": c["limit"],
                                   "ok": c["ok"]} for c in run.checks}
    for c in run.checks:
        print(f"check {c['check']}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'NOT OK'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
