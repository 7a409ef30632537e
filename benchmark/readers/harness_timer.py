"""A time the harness took around its own calls into the system.

args: timer (a list in run.timers), stat (median|mean|sum), scale;
`minus` names another reader with its args, whose value (already
scaled) is subtracted.
"""

from __future__ import annotations

import importlib

from readers import _stats


def read(run, args: dict) -> float | None:
    values = run.timers.get(args["timer"], [])
    if not values:
        return None
    value = _stats.stat(values, args.get("stat", "median")) * args.get(
        "scale", 1.0)
    if "minus" in args:
        other = importlib.import_module(
            f"readers.{args['minus']['reader']}").read(
                run, args["minus"].get("args", {}))
        if other is None:
            return None
        value -= other
    return value
