"""From the reduced profiler trace (run.trace_summary, see
harness/trace_reduce.py): the share of one program's device time that
its operations under one `m3.*` scope take.

`device_ops` holds the trace's ten largest operations, each named by
the first `m3.*` scope of its `tf_op`, summed over the whole runs in
the slice; the share is of those that carry the scope, so an operation
smaller than the tenth is not in it.  0 where the program ran and none
of the ten carries the scope: a program from before the scope, or an
executable that the compile cache kept from such a tree (the cache's
key leaves an operation's name out).  Nothing without a trace or where
the program is not in it.

args: program (e.g. jit_device_expr_pipeline), scope (e.g. m3.topk).
"""

from __future__ import annotations


def read(run, args: dict) -> float | None:
    ts = run.trace_summary
    if not ts:
        return None
    prog = ts["programs"].get(args["program"])
    if not prog or not prog["calls"] or prog["device_s"] <= 0:
        return None
    under = sum(s for name, s in ts["device_ops"]
                if name.startswith(args["scope"] + "/"))
    return 100.0 * under / prog["device_s"]
