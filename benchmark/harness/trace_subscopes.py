"""harness/trace_reduce.py's reduction with an operation named one
level deeper where its `tf_op` has one of the program's sub-scopes:
`m3.temporal/bounds/convert_reduce_fusion` and `m3.temporal/take/fusion.89`
where trace_reduce reads `m3.temporal/...` for both, so that a cell's
`breakdown.device_ops` tells the windowed stage's compare-and-sum from
its reads of the windows' ends (and `m3.decode/refill` from the scan's
steps).  A name still starts with its `m3.*` scope, so
readers/trace_scope_share.py reads such a summary as it reads any.

The summary also gains `scope_s`, every scope's device seconds over
ALL operations of the whole runs in the slice, where `device_ops` holds
the ten largest alone: this cell's windowed stage is a dozen gathers of
one size, and a share taken from ten operations leaves some of them out
(readers/trace_scope_total.py).

trace_reduce finds a scope by its module-level pattern `_SCOPE`
(`.search(tf_op).group(0)`) and shortens an operation's name by
`_op_name`; `reduce` here stands `ScopeAndSub` in for the first while it
runs, and for `scope_s` makes a second pass with every operation named
alike, so that the names are the scopes themselves and the ten largest
hold them all; both are put back.  A program from before the sub-scopes
reads as trace_reduce reads it.
"""

from __future__ import annotations

import re
import types

from harness import trace_reduce

SUB_SCOPES = ("bounds", "take", "refill")
_SUB = re.compile(r"/(%s)(?=/|$)" % "|".join(SUB_SCOPES))
_TOP = trace_reduce._SCOPE


def _scope_and_sub(tf_op: str):
    """What trace_reduce asks of its pattern's `search(tf_op)`: None or
    something whose `group(0)` is the scope.  The first `m3.*` scope of
    the `tf_op`, and after it the first sub-scope of SUB_SCOPES as a
    whole path component (inside a loop the path reads
    `m3.temporal/while/body/bounds/...`: the components between are
    left out of the name)."""
    top = _TOP.search(tf_op)
    if top is None:
        return None
    sub = _SUB.search(tf_op, top.end())
    name = top.group(0) + (f"/{sub.group(1)}" if sub else "")
    return types.SimpleNamespace(group=lambda _n=0: name)


ScopeAndSub = types.SimpleNamespace(search=_scope_and_sub)


def reduce(xplane_path: str) -> dict:
    """trace_reduce.reduce(xplane_path), operations named by scope and
    sub-scope, and `scope_s` {scope ("" for none): device seconds} over
    every operation (the ten largest scopes, should there be more)."""
    name_of = trace_reduce._op_name
    trace_reduce._SCOPE = ScopeAndSub
    try:
        summary = trace_reduce.reduce(xplane_path)
        trace_reduce._op_name = lambda name: ""
        by_scope = trace_reduce.reduce(xplane_path)["device_ops"]
    finally:
        trace_reduce._SCOPE, trace_reduce._op_name = _TOP, name_of
    summary["scope_s"] = {name.rstrip("/"): s for name, s in by_scope}
    return summary
