"""A number that the window's slow-query records carry as a field of
their own (run.slow_records), e.g. `lanes`.

args: field (a key of a record), stat (median|mean|sum), scale.
Nothing where a record lacks the field (a program from before it).
"""

from __future__ import annotations

from readers import _stats


def read(run, args: dict) -> float | None:
    recs = run.slow_records
    if not recs or not all(args["field"] in r for r in recs):
        return None
    return args.get("scale", 1.0) * _stats.stat(
        [r[args["field"]] for r in recs], args.get("stat", "mean"))
