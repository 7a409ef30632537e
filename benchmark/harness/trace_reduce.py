"""From a profiler trace (.xplane.pb) to device busy time, program
time, top operations by the program's own scopes and labelled idle
gaps.  Reads the events with `jax.profiler.ProfileData`; the one thing
that hides, the operations' metadata (`tf_op`, where the named scope
is), is read from the file's protobuf wire format by `_op_scopes`.

Device planes are `/device:TPU:<n>`; on them the line `XLA Ops` holds
one event per executed operation and `XLA Modules` one per program run.
A trace with no device plane (XLA:CPU, rehearsal only) has its
operations on host threads, marked by an `hlo_module` stat; they are
then read as one pseudo-device so that the same code runs end to end.

Host spans are `jax.profiler.TraceAnnotation`s: the harness's
`bench:<label>` (`bench:window` bounds the traced slice) and the
program's own `m3:<phase>` (utils/tracing.phase), which keep their
prefix as labels.  A device operation is named by the `m3.*` scope in
its `tf_op` (`m3.temporal/fusion.81 f32[131072]`); a loop's event has
no `tf_op` and takes the scope in which its body's operations spend
most time.  Operations and programs are counted over whole program
runs only: the profiler cuts the runs in flight at the trace's edges.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
_OPS_LINE, _MODULES_LINE = "XLA Ops", "XLA Modules"
_SCOPE = re.compile(r"\bm3\.[A-Za-z0-9_]+")
_LABELS = ("bench:", "m3:")


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


def _inside(merged, t: float) -> bool:
    """Whether `t` lies in one of a merged, sorted interval list."""
    i = bisect.bisect_right(merged, [t, float("inf")]) - 1
    return i >= 0 and merged[i][0] <= t < merged[i][1]


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


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a
    varint, the bytes for a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            val, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            val, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {kind}")
        yield key >> 3, val


def _op_scopes(xplane_path: str) -> dict[str, dict[str, str]]:
    """{device plane: {operation's event name: its `m3.*` scope}}, from
    the `tf_op` stat of the plane's event metadata (XSpace.planes = 1;
    XPlane.name = 2, event_metadata = 4, stat_metadata = 5, each a map
    entry with the message under 2; XEventMetadata.name = 2, stats = 5;
    XStatMetadata.id = 1, name = 2; XStat.metadata_id = 1, str_value =
    5, ref_value = 7, which names a stat metadata whose name is the
    value)."""
    out = {}
    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    for num, plane in _fields(space):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for num, val in _fields(plane):
            if num == 2:
                name = bytes(val).decode()
            elif num in (4, 5):
                entry = dict(_fields(val))
                if 2 not in entry:
                    continue
                if num == 4:
                    events.append(entry[2])
                else:
                    meta = dict(_fields(entry[2]))
                    stat_names[meta.get(1, entry.get(1))] = bytes(
                        meta.get(2, b"")).decode(errors="replace")
        if not _DEVICE_PLANE.match(name):
            continue
        tf_op = next((k for k, v in stat_names.items() if v == "tf_op"), None)
        scopes = out.setdefault(name, {})
        for meta in events:
            ev_name = found = None
            for num, val in _fields(meta):
                if num == 2:
                    ev_name = bytes(val).decode(errors="replace")
                elif num == 5 and found is None:
                    stat = dict(_fields(val))
                    if stat.get(1) == tf_op:
                        found = (bytes(stat[5]).decode(errors="replace")
                                 if 5 in stat else stat_names.get(stat.get(7)))
            scope = _SCOPE.search(found or "")
            if ev_name and scope:
                scopes[ev_name] = scope.group(0)
    return out


def _top_level(ops):
    """[(name, start, duration, [nested events])] of a line's events:
    an event that starts inside an earlier one is nested in it."""
    out = []
    for name, s, d in sorted(ops, key=lambda ev: (ev[1], -ev[2])):
        if out and s < out[-1][1] + out[-1][2]:
            out[-1][3].append((name, s, d))
        else:
            out.append((name, s, d, []))
    return out


def _scoped_name(name: str, nested, scopes: dict[str, str]) -> str:
    scope = scopes.get(name)
    if scope is None:
        inside: dict[str, float] = {}
        for child, _, d in nested:
            if child in scopes:
                inside[scopes[child]] = inside.get(scopes[child], 0.0) + d
        scope = max(inside, key=inside.get) if inside else None
    short = _op_name(name)
    return f"{scope}/{short}"[:80] if scope else short


def reduce(xplane_path: str) -> dict:
    """-> {window_s, busy_s, n_devices, programs {name: {calls,
    device_s, min_s, median_s, max_s}}, device_ops [[name, s]],
    idle_gaps [[label, s]]}.
    Seconds are averaged over the device planes found."""
    import jax

    data = jax.profiler.ProfileData.from_file(xplane_path)
    scopes = _op_scopes(xplane_path)
    # per device: (ops, modules, scopes); host spans
    devices, spans = [], []
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
                if ev.name.startswith(_LABELS):
                    spans.append((ev.name.removeprefix("bench:"),
                                  float(ev.start_ns),
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
            devices.append((ops or modules, modules or [],
                            scopes.get(plane.name, {})))
    if not devices and pseudo_ops:
        devices.append((pseudo_ops, pseudo_modules, {}))
    if not devices:
        return {"window_s": 0.0, "busy_s": 0.0, "n_devices": 0,
                "programs": {}, "device_ops": [], "idle_gaps": []}

    windows = [(s, e) for name, s, e in spans if name == "window"]
    if windows:
        lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    else:
        lo = min(s for ops, _, _ in devices for _, s, _ in ops)
        hi = max(s + d for ops, _, _ in devices for _, s, d in ops)
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
    for ops, modules, op_scopes in devices:
        busy = _union(_clip([(s, s + d) for _, s, d in ops], lo, hi))
        busy_ns += sum(e - s for s, e in busy)
        # whole runs only: one cut by the window's edge would count as
        # a call with part of its time
        runs = _union([(s, s + d) for _, s, d in modules
                       if s >= lo and s + d <= hi])
        for name, s, d, nested in _top_level(ops):
            if _inside(runs, s + d / 2):
                short = _scoped_name(name, nested, op_scopes)
                op_ns[short] = op_ns.get(short, 0.0) + d
        for name, s, d in modules:
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
