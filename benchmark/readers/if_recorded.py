"""Another reader's value, and nothing where the program does not
record what that reader is asked for: a phase or a stat that a later PR
added reads as a missing key in the records of a program from before
it, and the metric is then left out of the line.

args: reader (its name under readers/), args (passed to it).
"""

from __future__ import annotations

import importlib


def read(run, args: dict) -> float | None:
    inner = importlib.import_module(f"readers.{args['reader']}")
    try:
        return inner.read(run, args.get("args", {}))
    except KeyError:
        return None
