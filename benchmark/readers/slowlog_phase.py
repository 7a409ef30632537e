"""From the slow-query records of the window's queries (run.slow_records,
the program's own per-query cost records).

args: either `phase` (a key of the record's `phases`: parse_s, fetch_s,
decode_s, device_s, total_s) with stat and scale, or `flag` (a boolean
key of the record, e.g. device_serving), whose share of the records is
returned times scale.
"""

from __future__ import annotations

from readers import _stats


def read(run, args: dict) -> float | None:
    recs = run.slow_records
    if not recs:
        return None
    scale = args.get("scale", 1.0)
    if "flag" in args:
        return scale * sum(bool(r.get(args["flag"])) for r in recs) / len(recs)
    return scale * _stats.stat([r["phases"][args["phase"]] for r in recs],
                               args.get("stat", "median"))
