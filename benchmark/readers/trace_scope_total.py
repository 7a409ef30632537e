"""From the reduced profiler trace of a kind that names its operations
by sub-scope (run.trace_summary, see harness/trace_subscopes.py): the
share of one program's device time that ALL its operations under one
`m3.*` scope take, the scope's sub-scopes with it.

`scope_s` holds every scope's seconds over the whole runs in the slice,
not the ten largest operations that readers/trace_scope_share.py reads:
a stage of many operations of one size reads whole.  0 where the
program ran and nothing carries the scope (a program from before it, or
an executable that the compile cache kept from such a tree).  Nothing
without a trace, without `scope_s`, or where the program is not in it.

args: program (e.g. jit_device_grouped_pipeline), scope (e.g.
m3.temporal).
"""

from __future__ import annotations


def read(run, args: dict) -> float | None:
    ts = run.trace_summary
    if not ts or "scope_s" not in ts:
        return None
    prog = ts["programs"].get(args["program"])
    if not prog or not prog["calls"] or prog["device_s"] <= 0:
        return None
    scope = args["scope"]
    under = sum(s for name, s in ts["scope_s"].items()
                if name == scope or name.startswith(scope + "/"))
    return 100.0 * under / prog["device_s"]
