"""From the window's slow-query records that read the CPU clock
(run.slow_records; one query in sixteen carries `cpu`, the thread's CPU
seconds by phase, and `interp_wait_s`).  The clock steps by 10 ms on the
chip's machine: a mean over the clocked records, never a record's value.

args: key (`interp_wait_s`, or a key of the record's `cpu`: fetch_s,
pack_s, total_s, ...), stat (mean|median|sum), scale.
Nothing where no record carries `cpu` (a program from before the clock).
"""

from __future__ import annotations

from readers import _stats


def read(run, args: dict) -> float | None:
    clocked = [r for r in run.slow_records or () if "cpu" in r]
    if not clocked:
        return None
    key = args["key"]
    values = [r[key] if key == "interp_wait_s" else r["cpu"][key]
              for r in clocked]
    return args.get("scale", 1.0) * _stats.stat(values,
                                                args.get("stat", "mean"))
