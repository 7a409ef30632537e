"""A ratio of two counts that the window's slow-query records carry
(run.slow_records), each summed over the records.

args: `of` and `over` (keys of a record), scale.  Nothing where the
program's records lack a key (a program from before it), or where the
denominator is 0.
"""

from __future__ import annotations


def read(run, args: dict) -> float | None:
    recs = run.slow_records
    if not recs or not all(args["of"] in r and args["over"] in r
                           for r in recs):
        return None
    over = sum(r[args["over"]] for r in recs)
    if not over:
        return None
    return args.get("scale", 1.0) * sum(r[args["of"]] for r in recs) / over
