"""The share of the window's slow-query records (run.slow_records) that
one fused device program served whole: `device_serving` set and a
`device_tier` block whose `host_nodes` is 0 (no node of the query's
tree was left to the host).

args: scale.
"""

from __future__ import annotations


def read(run, args: dict) -> float | None:
    recs = run.slow_records
    if not recs:
        return None
    whole = sum(bool(r.get("device_serving"))
                and r.get("device_tier", {}).get("host_nodes") == 0
                for r in recs)
    return args.get("scale", 1.0) * whole / len(recs)
