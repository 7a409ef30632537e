"""From a profiler trace (.xplane.pb) to device busy time, program
time, top operations and labelled idle gaps.  Reads the trace with
`jax.profiler.ProfileData` and nothing else.

Device planes are `/device:TPU:<n>`; on them the line `XLA Ops` holds
one event per executed operation and `XLA Modules` one per program run.
A trace with no device plane (XLA:CPU, rehearsal only) has its
operations on host threads, marked by an `hlo_module` stat; they are
then read as one pseudo-device so that the same code runs end to end.

The harness marks what it is doing with `jax.profiler.TraceAnnotation`
spans named `bench:<label>`; `bench:window` bounds the traced slice.
"""

from __future__ import annotations

import glob
import os
import re

_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
_OPS_LINE, _MODULES_LINE = "XLA Ops", "XLA Modules"


def find_xplane(log_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def _union(intervals):
    """Merged, sorted [start, end) list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _intersect(a, b):
    """Intersection of two merged, sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _events(line):
    return [(ev.name, float(ev.start_ns), float(ev.duration_ns))
            for ev in line.events]


def _module_name(name: str) -> str:
    """`jit_f(1234567)` -> `jit_f`."""
    return name.split("(", 1)[0]


_LAYOUT = re.compile(r"\{[^{}]*\}")


def _op_name(name: str) -> str:
    """The TPU's operation events carry the whole HLO line:
    `%fusion.24 = (f32[786432]{0:T(1024)S(1)}, ...) fusion(...)` ->
    `fusion.24 (f32[786432], ...)`, at most 80 characters."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:80]
    depth, end = 0, len(rest)
    for i, ch in enumerate(rest):      # the result type ends at the
        depth += ch in "([{"           # first space outside brackets
        depth -= ch in ")]}"
        if ch == " " and depth == 0:
            end = i
            break
    return f"{head.lstrip('%')} {_LAYOUT.sub('', rest[:end])}"[:80]


def reduce(xplane_path: str) -> dict:
    """-> {window_s, busy_s, n_devices, programs {name: {calls,
    device_s, min_s, median_s, max_s}}, device_ops [[name, s]], idle_gaps [[label, s]]}.
    Seconds are averaged over the device planes found."""
    import jax

    data = jax.profiler.ProfileData.from_file(xplane_path)
    devices, spans = [], []     # per device: (ops, modules); host spans
    pseudo_ops, pseudo_modules = [], []
    for plane in data.planes:
        is_dev = _DEVICE_PLANE.match(plane.name)
        ops = modules = None
        for line in plane.lines:
            if is_dev:
                if line.name == _OPS_LINE:
                    ops = _events(line)
                elif line.name == _MODULES_LINE:
                    modules = _events(line)
                continue
            for ev in line.events:
                if ev.name.startswith("bench:"):
                    spans.append((ev.name[6:], float(ev.start_ns),
                                  float(ev.start_ns + ev.duration_ns)))
                elif ev.duration_ns > 0:
                    module = dict(ev.stats).get("hlo_module")
                    if module:
                        pseudo_ops.append((ev.name, float(ev.start_ns),
                                           float(ev.duration_ns)))
                        pseudo_modules.append(
                            (module, float(ev.start_ns),
                             float(ev.duration_ns)))
        if is_dev and (ops or modules):
            devices.append((ops or modules, modules or []))
    if not devices and pseudo_ops:
        devices.append((pseudo_ops, pseudo_modules))
    if not devices:
        return {"window_s": 0.0, "busy_s": 0.0, "n_devices": 0,
                "programs": {}, "device_ops": [], "idle_gaps": []}

    windows = [(s, e) for name, s, e in spans if name == "window"]
    if windows:
        lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    else:
        lo = min(s for ops, _ in devices for _, s, _ in ops)
        hi = max(s + d for ops, _ in devices for _, s, d in ops)
    n = len(devices)
    busy_ns = 0.0
    programs: dict[str, dict] = {}
    op_ns: dict[str, float] = {}
    gap_ns: dict[str, float] = {}
    by_label: dict[str, list] = {}
    for name, s, e in spans:
        if name != "window":
            by_label.setdefault(name, []).append((s, e))
    by_label = {name: _union(iv) for name, iv in by_label.items()}
    for ops, modules in devices:
        busy = _union(_clip([(s, s + d) for _, s, d in ops], lo, hi))
        busy_ns += sum(e - s for s, e in busy)
        for name, s, d in ops:
            if s + d > lo and s < hi:
                short = _op_name(name)
                op_ns[short] = op_ns.get(short, 0.0) + d
        for name, s, d in modules:
            # whole runs only: one cut by the window's edge would count
            # as a call with part of its time
            if s >= lo and s + d <= hi:
                p = programs.setdefault(
                    _module_name(name),
                    {"calls": 0, "device_s": 0.0, "run_s": []})
                p["calls"] += 1 / n
                p["device_s"] += d / 1e9 / n
                p["run_s"].append(d / 1e9)
        # idle = the window minus busy; each harness label gets the
        # idle time its spans cover, and what none covers is
        # `between_requests`
        idle = []
        edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                idle.append((g0, g1))
        covered = []
        for name, merged in by_label.items():
            part = _intersect(idle, merged)
            covered += part
            if part:
                gap_ns[name] = gap_ns.get(name, 0.0) + sum(
                    e - s for s, e in part)
        rest = (sum(e - s for s, e in idle)
                - sum(e - s for s, e in _union(covered)))
        if rest > 0:
            gap_ns["between_requests"] = (
                gap_ns.get("between_requests", 0.0) + rest)

    for p in programs.values():         # the runs' spread, then drop them
        runs = sorted(p.pop("run_s"))
        p.update(min_s=runs[0], median_s=runs[len(runs) // 2],
                 max_s=runs[-1])

    def top(d):
        return [[k, v / 1e9 / n] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {"window_s": (hi - lo) / 1e9, "busy_s": busy_ns / 1e9 / n,
            "n_devices": n, "programs": programs,
            "device_ops": top(op_ns), "idle_gaps": top(gap_ns)}
