"""Graphite -> fused device plan lowering.

The Graphite function library (query/graphite.py) is host numpy; most
of its high-traffic builtins are the same consolidate / aggregate /
elementwise primitives the PromQL lowerer (query/plan.py) already
ships to the device under different names.  This module walks a parsed
Graphite Call-tree and emits the plan compiler's symbolic nodes:

  fetch          -> a PromQL "leaf" running last_over_time with the
                    step as the window — bit-identical to the host's
                    cons.step_consolidate (both pick the LAST sample
                    in the left-inclusive window [t-step, t]) — under
                    a "gsel" row gather applying the exact-path-depth
                    filter at build time
  series renames -> "gname" (plan passthrough, labels only)
  combiners      -> "gagg" grouped reduce with graphite (numpy nan-
                    reduction) semantics
  per-series fns -> "gcall" elementwise / windowed transforms
  name filters   -> "gsel" (sortByName / exclude / grep / limit)

Anything else raises _Unlowerable and the host evaluator serves that
node, retrying the device on each child subtree — the same
deepest-unsupported-node splitting PromQL does, counted in
m3_query_host_split_total{reason} and the slowlog device_tier record.

Series names ride INSIDE the label dicts (b"__name__") through the
plan build; try_device decodes them back into SeriesList names.
"""

from __future__ import annotations

import math
import re

import numpy as np

from m3_tpu.query import promql
from m3_tpu.query.graphite import (
    Call, Path, SeriesList, _AGG_DELEGATES, SECOND,
    pattern_matchers, split_components,
)

_REQ = object()


class _Unlowerable(Exception):
    """This node has no device form; reason is a bounded metric slug."""

    def __init__(self, reason: str):
        super().__init__(reason)
        self.reason = reason


def ast_size(node) -> int:
    """Graphite AST node count (Path / Call only — literals are
    arguments, not work)."""
    if isinstance(node, Path):
        return 1
    if isinstance(node, Call):
        return 1 + sum(ast_size(a) for a in node.args
                       if isinstance(a, (Call, Path))) \
            + sum(ast_size(v) for v in node.kwargs.values()
                  if isinstance(v, (Call, Path)))
    return 0


# -- name plumbing -----------------------------------------------------------


def _nm(ls: dict) -> str:
    return ls.get(b"__name__", b"").decode("latin-1")


def _set_names(labels, names):
    return [{**ls, b"__name__": n.encode("latin-1")}
            for ls, n in zip(labels, names)]


def _rename(fmt):
    """str->str name transform lifted to a label-list transform."""
    def name_fn(labels):
        return _set_names(labels, [fmt(_nm(ls)) for ls in labels])
    return name_fn


def _keep_names(labels):
    return labels


# -- argument helpers --------------------------------------------------------


def _arg(node: Call, i: int, name: str, default=_REQ):
    if len(node.args) > i:
        return node.args[i]
    if name in node.kwargs:
        return node.kwargs[name]
    if default is _REQ:
        raise _Unlowerable("graphite_bad_args")
    return default


def _series_child(node: Call):
    """The single SeriesList argument (args[0]) — combiners given
    extra series args (sumSeries(a, b)) merge lists, which needs the
    host's _merge_lists; those split."""
    series_args = [a for a in node.args if isinstance(a, (Call, Path))]
    series_args += [v for v in node.kwargs.values()
                    if isinstance(v, (Call, Path))]
    if len(series_args) != 1 or not node.args \
            or not isinstance(node.args[0], (Call, Path)):
        raise _Unlowerable("graphite_multi_series_args")
    return node.args[0]


def _num(x, reason="graphite_bad_args") -> float:
    if isinstance(x, bool) or not isinstance(x, (int, float)):
        raise _Unlowerable(reason)
    return float(x)


def _window_steps(window, step: int) -> int:
    if isinstance(window, str):
        from m3_tpu.metrics.policy import parse_duration
        return max(1, int(parse_duration(window) // step))
    return max(1, int(window))


# -- fetch -------------------------------------------------------------------


def _lower_path(pattern: str, step: int):
    sel = promql.Selector(matchers=pattern_matchers(pattern))
    # graphite fetch == last-value step consolidation with the step as
    # the lookback: exactly the device last_over_time window
    leaf = ("leaf", sel, "last_over_time", int(step), True,
            0.0, 0.5, 0.5, 0.5)
    n_comp = len(split_components(pattern))

    def select_fn(labels):
        keep, out = [], []
        for i, ls in enumerate(labels):
            depth = sum(1 for k in ls if k.startswith(b"__g"))
            if depth != n_comp:
                continue  # pattern matches exact path depth only
            keep.append(i)
            out.append({b"__name__": ls.get(b"__name__", b"")})
        return keep, out

    return ("gsel", select_fn, leaf)


# -- lowering handlers -------------------------------------------------------
# Each handler: (node, child_sym, step, real_S) -> sym.


def _gcall(fn, statics, fparams, name_fn, child):
    return ("gcall", fn, statics, tuple(fparams), name_fn, child)


def _h_scale(node, child, step, real_S):
    factor = _num(_arg(node, 1, "factor"))
    return _gcall("scale", (real_S,), (np.float64(factor),),
                  _rename(lambda n: f"scale({n},{factor:g})"), child)


def _h_scale_to_seconds(node, child, step, real_S):
    seconds = _num(_arg(node, 1, "seconds"))
    factor = seconds / (step / SECOND)
    return _gcall("scale", (real_S,), (np.float64(factor),),
                  _rename(lambda n: f"scaleToSeconds({n},{seconds:g})"),
                  child)


def _h_offset(node, child, step, real_S):
    amount = _num(_arg(node, 1, "amount"))
    return _gcall("offset", (real_S,), (np.float64(amount),),
                  _rename(lambda n: f"offset({n},{amount:g})"), child)


def _h_elemwise(fn, name):
    def h(node, child, step, real_S):
        return _gcall(fn, (real_S,), (),
                      _rename(lambda n: f"{name}({n})"), child)
    return h


def _h_log(node, child, step, real_S):
    base = _num(_arg(node, 1, "base", 10.0))
    return _gcall("logarithm", (real_S,),
                  (np.float64(math.log(base)),),
                  _rename(lambda n: f"logarithm({n})"), child)


def _h_pow(node, child, step, real_S):
    exp = _num(_arg(node, 1, "exp"))
    return _gcall("pow", (real_S,), (np.float64(exp),),
                  _rename(lambda n: f"pow({n},{exp:g})"), child)


def _h_per_second(node, child, step, real_S):
    return _gcall("perSecond", (real_S,),
                  (np.float64(step / SECOND),),
                  _rename(lambda n: f"perSecond({n})"), child)


def _h_keep_last(node, child, step, real_S):
    limit = _num(_arg(node, 1, "limit", np.inf))
    return _gcall("keepLastValue", (real_S,), (np.float64(limit),),
                  _rename(lambda n: f"keepLastValue({n})"), child)


def _h_transform_null(node, child, step, real_S):
    default = _num(_arg(node, 1, "default", 0.0))
    return _gcall("transformNull", (real_S,), (np.float64(default),),
                  _rename(lambda n: f"transformNull({n},{default:g})"),
                  child)


def _h_remove_value(fn):
    def h(node, child, step, real_S):
        n = _num(_arg(node, 1, "n"))
        return _gcall(fn, (real_S,), (np.float64(n),),
                      _rename(lambda nm: f"{fn}({nm},{n:g})"), child)
    return h


def _h_moving(fn):
    def h(node, child, step, real_S):
        window = _arg(node, 1, "window")
        w = _window_steps(window, step)
        return _gcall(fn, (real_S, w), (),
                      _rename(lambda n: f"{fn}({n},{window})"), child)
    return h


_SUMMARIZE_FUNCS = frozenset((
    "sum", "total", "", "avg", "average", "max", "min", "count",
    "range", "rangeOf", "multiply"))


def _h_summarize(name):
    def h(node, child, step, real_S):
        from m3_tpu.metrics.policy import parse_duration
        interval = _arg(node, 1, "interval")
        func = _arg(node, 2, "func", "sum")
        if not isinstance(interval, str) \
                or func not in _SUMMARIZE_FUNCS:
            raise _Unlowerable("graphite_agg_fn")
        k = max(1, int(parse_duration(interval) // step))
        return _gcall(
            "summarize", (real_S, k, func), (),
            _rename(lambda n: f'{name}({n},"{interval}","{func}")'),
            child)
    return h


def _h_hitcount(node, child, step, real_S):
    interval = _arg(node, 1, "interval", None)
    sec = step / 1e9
    if interval is None:
        return _gcall("scale", (real_S,), (np.float64(sec),),
                      _rename(lambda n: f"hitcount({n})"), child)
    if not isinstance(interval, str):
        raise _Unlowerable("graphite_bad_args")
    from m3_tpu.metrics.policy import parse_duration
    k = max(1, int(parse_duration(interval) // step))
    scaled = _gcall("scale", (real_S,), (np.float64(sec),),
                    _keep_names, child)
    return _gcall("summarize", (real_S, k, "sum"), (),
                  _rename(lambda n: f'hitcount({n},"{interval}")'),
                  scaled)


def _h_integral_by_interval(node, child, step, real_S):
    from m3_tpu.metrics.policy import parse_duration
    interval = _arg(node, 1, "interval")
    if not isinstance(interval, str):
        raise _Unlowerable("graphite_bad_args")
    k = max(1, int(parse_duration(interval) // step))
    return _gcall(
        "integralByInterval", (real_S, k), (),
        _rename(lambda n: f'integralByInterval({n},"{interval}")'),
        child)


def _h_delay(node, child, step, real_S):
    k = int(_num(_arg(node, 1, "steps")))
    return _gcall("delay", (real_S, k), (),
                  _rename(lambda n: f"delay({n},{k})"), child)


def _h_time_slice(node, child, step, real_S, step_times):
    from m3_tpu.metrics.policy import parse_duration
    now = int(step_times[-1])

    def bound(spec, default):
        if spec == "now":
            return now
        if isinstance(spec, str):
            return now - parse_duration(spec.lstrip("-"))
        if isinstance(spec, (int, float)):
            return now - int(abs(spec)) * SECOND
        return default

    lo = bound(_arg(node, 1, "start"), int(step_times[0]))
    hi = bound(_arg(node, 2, "end", "now"), now)
    return _gcall("timeSlice", (real_S,),
                  (np.int64(lo), np.int64(hi)),
                  _rename(lambda n: f"timeSlice({n})"), child)


def _h_n_percentile(node, child, step, real_S):
    n = _arg(node, 1, "n")
    q = _num(n)
    return _gcall("nPercentile", (real_S, q), (),
                  _rename(lambda nm: f"nPercentile({nm},{n})"), child)


def _h_remove_percentile(fn):
    def h(node, child, step, real_S):
        q = _num(_arg(node, 1, "n"))
        return _gcall(fn, (real_S, q), (), _keep_names, child)
    return h


# -- renames (gname) ---------------------------------------------------------


def _h_alias(node, child, step, real_S):
    name = _arg(node, 1, "name")
    if not isinstance(name, str):
        raise _Unlowerable("graphite_bad_args")
    return ("gname", _rename(lambda _n: name), child)


def _h_alias_by_node(node, child, step, real_S):
    nodes = [a for a in node.args[1:]]
    if not all(isinstance(a, (int, float)) for a in nodes):
        raise _Unlowerable("graphite_bad_args")

    def fmt(n):
        parts = n.split(".")
        return ".".join(parts[int(i)] for i in nodes
                        if -len(parts) <= int(i) < len(parts))

    return ("gname", _rename(fmt), child)


def _h_alias_by_metric(node, child, step, real_S):
    return ("gname", _rename(lambda n: n.split(".")[-1]), child)


def _h_alias_sub(node, child, step, real_S):
    search = _arg(node, 1, "search")
    replace = _arg(node, 2, "replace")
    if not isinstance(search, str) or not isinstance(replace, str):
        raise _Unlowerable("graphite_bad_args")
    rx = re.compile(search)
    return ("gname", _rename(lambda n: rx.sub(replace, n)), child)


def _h_consolidate_by(node, child, step, real_S):
    func = _arg(node, 1, "func")
    return ("gname",
            _rename(lambda n: f'consolidateBy({n},"{func}")'), child)


def _h_cumulative(node, child, step, real_S):
    return ("gname",
            _rename(lambda n: f'consolidateBy({n},"sum")'), child)


def _h_dashed(node, child, step, real_S):
    dash = _num(_arg(node, 1, "dash_length", 5.0))
    return ("gname",
            _rename(lambda n: f"dashed({n},{dash:g})"), child)


def _h_substr(node, child, step, real_S):
    start = int(_num(_arg(node, 1, "start", 0)))
    stop = int(_num(_arg(node, 2, "stop", 0)))

    def fmt(n):
        parts = n.split(".")
        return ".".join(parts[start:stop if stop else None])

    return ("gname", _rename(fmt), child)


# -- combiners + grouped reduces (gagg) --------------------------------------

# op of each single-group combiner, keyed by its REGISTERED name (also
# the name prefix graphite renders: sumSeries(a,b,c))
_COMBINE_OPS = {
    "sumSeries": "sum", "averageSeries": "avg", "minSeries": "min",
    "maxSeries": "max", "multiplySeries": "multiply",
    "diffSeries": "diff", "stddevSeries": "stddev",
    "rangeOfSeries": "range", "medianSeries": "median",
    "countSeries": "count_series",
}
_COMBINE_ALIASES = {"sum": "sumSeries", "avg": "averageSeries"}


def _combine_group_fn(prefix):
    def group_fn(labels):
        names = [_nm(ls) for ls in labels]
        name = f"{prefix}({','.join(names)})"
        tval = float(len(labels))  # countSeries' constant
        return ([0] * len(labels),
                [{b"__name__": name.encode("latin-1")}], tval)
    return group_fn


def _h_combine(form):
    op = _COMBINE_OPS[form]

    def h(node, child, step, real_S):
        return ("gagg", op, (), _combine_group_fn(form), child)
    return h


def _h_aggregate(node, child, step, real_S):
    func = _arg(node, 1, "func")
    if not isinstance(func, str):
        raise _Unlowerable("graphite_bad_args")
    target = _AGG_DELEGATES.get(func)
    if target is not None:
        return ("gagg", _COMBINE_OPS[target], (),
                _combine_group_fn(target), child)
    if func not in ("last", "current"):
        raise _Unlowerable("graphite_agg_fn")

    def group_fn(labels):
        names = [_nm(ls) for ls in labels]
        name = f'aggregate({",".join(names)},"{func}")'
        return ([0] * len(labels),
                [{b"__name__": name.encode("latin-1")}])

    return ("gagg", "last", (), group_fn, child)


def _h_percentile_of_series(node, child, step, real_S):
    n = _arg(node, 1, "n")
    q = _num(n)

    def group_fn(labels):
        first = _nm(labels[0]) if labels else ""
        name = f"percentileOfSeries({first},{n})"
        return ([0] * len(labels),
                [{b"__name__": name.encode("latin-1")}])

    return ("gagg", "percentile", (q,), group_fn, child)


_GROUP_OPS = {"sum": "sum", "avg": "avg", "average": "avg",
              "max": "max", "min": "min", "multiply": "multiply",
              "range": "range", "rangeOf": "range",
              "stddev": "stddev", "count": "count",
              "total": "sum", "": "sum"}


def _grouped(key_of, op):
    """gagg over host-computed name-key groups, sorted-key order."""
    def group_fn(labels):
        names = [_nm(ls) for ls in labels]
        groups: dict[str, list[int]] = {}
        for i, n in enumerate(names):
            groups.setdefault(key_of(n), []).append(i)
        uniq = sorted(groups)
        gid = {k: g for g, k in enumerate(uniq)}
        row_groups = [0] * len(names)
        for k, rows in groups.items():
            for i in rows:
                row_groups[i] = gid[k]
        return (row_groups,
                [{b"__name__": k.encode("latin-1")} for k in uniq])
    return ("gagg", op, (), group_fn)


def _h_group_by_node(node, child, step, real_S):
    pos = _arg(node, 1, "node")
    func = _arg(node, 2, "func", "sum")
    if not isinstance(pos, (int, float)) \
            or func not in ("sum", "avg", "average", "max", "min"):
        raise _Unlowerable("graphite_agg_fn")

    def key_of(n):
        parts = n.split(".")
        return (parts[int(pos)]
                if -len(parts) <= int(pos) < len(parts) else n)

    return _grouped(key_of, _GROUP_OPS[func]) + (child,)


def _h_group_by_nodes(node, child, step, real_S):
    func = _arg(node, 1, "func")
    nodes = node.args[2:]
    if func not in ("sum", "avg", "average", "max", "min") \
            or not all(isinstance(a, (int, float)) for a in nodes):
        # host groupByNodes also takes median — single-group-only on
        # device, and group count is data-dependent: host serves it
        raise _Unlowerable("graphite_agg_fn")

    def key_of(n):
        parts = n.split(".")
        return ".".join(parts[int(x)] for x in nodes
                        if -len(parts) <= int(x) < len(parts))

    return _grouped(key_of, _GROUP_OPS[func]) + (child,)


def _h_with_wildcards(op):
    def h(node, child, step, real_S):
        positions = node.args[1:]
        if not all(isinstance(a, (int, float)) for a in positions):
            raise _Unlowerable("graphite_bad_args")
        drop = {int(p) for p in positions}

        def key_of(n):
            parts = n.split(".")
            return ".".join(p for j, p in enumerate(parts)
                            if j not in drop)

        return _grouped(key_of, op) + (child,)
    return h


def _h_aggregate_with_wildcards(node, child, step, real_S):
    func = _arg(node, 1, "func")
    positions = node.args[2:]
    op = _GROUP_OPS.get(func)
    if op is None \
            or not all(isinstance(a, (int, float)) for a in positions):
        raise _Unlowerable("graphite_agg_fn")
    drop = {int(p) for p in positions}

    def key_of(n):
        parts = n.split(".")
        return ".".join(p for j, p in enumerate(parts)
                        if j not in drop and j - len(parts) not in drop)

    return _grouped(key_of, op) + (child,)


# -- name-based row selection (gsel) -----------------------------------------


def _select_sym(select_rows, child):
    def select_fn(labels):
        keep = select_rows([_nm(ls) for ls in labels])
        return keep, [labels[i] for i in keep]
    return ("gsel", select_fn, child)


def _h_sort_by_name(node, child, step, real_S):
    return _select_sym(
        lambda names: sorted(range(len(names)),
                             key=lambda i: names[i]), child)


def _h_exclude(node, child, step, real_S):
    rx = re.compile(_arg(node, 1, "pattern"))
    return _select_sym(
        lambda names: [i for i, n in enumerate(names)
                       if not rx.search(n)], child)


def _h_grep(node, child, step, real_S):
    rx = re.compile(_arg(node, 1, "pattern"))
    return _select_sym(
        lambda names: [i for i, n in enumerate(names)
                       if rx.search(n)], child)


def _h_limit(node, child, step, real_S):
    n = int(_num(_arg(node, 1, "n")))
    return _select_sym(lambda names: list(range(len(names)))[:n],
                       child)


# -- dispatch ----------------------------------------------------------------

_LOWER = {
    "scale": _h_scale,
    "scaleToSeconds": _h_scale_to_seconds,
    "offset": _h_offset,
    "absolute": _h_elemwise("absolute", "absolute"),
    "invert": _h_elemwise("invert", "invert"),
    "logarithm": _h_log, "log": _h_log,
    "pow": _h_pow,
    "squareRoot": _h_elemwise("squareRoot", "squareRoot"),
    "derivative": _h_elemwise("derivative", "derivative"),
    "nonNegativeDerivative": _h_elemwise("nonNegativeDerivative",
                                         "nonNegativeDerivative"),
    "perSecond": _h_per_second,
    "integral": _h_elemwise("integral", "integral"),
    "keepLastValue": _h_keep_last,
    "transformNull": _h_transform_null,
    "removeAboveValue": _h_remove_value("removeAboveValue"),
    "removeBelowValue": _h_remove_value("removeBelowValue"),
    "isNonNull": _h_elemwise("isNonNull", "isNonNull"),
    "changed": _h_elemwise("changed", "changed"),
    "delay": _h_delay,
    "offsetToZero": _h_elemwise("offsetToZero", "offsetToZero"),
    "minMax": _h_elemwise("minMax", "minMax"),
    "movingAverage": _h_moving("movingAverage"),
    "movingSum": _h_moving("movingSum"),
    "movingMax": _h_moving("movingMax"),
    "movingMin": _h_moving("movingMin"),
    "summarize": _h_summarize("summarize"),
    "smartSummarize": _h_summarize("smartSummarize"),
    "hitcount": _h_hitcount,
    "integralByInterval": _h_integral_by_interval,
    "nPercentile": _h_n_percentile,
    "removeAbovePercentile":
        _h_remove_percentile("removeAbovePercentile"),
    "removeBelowPercentile":
        _h_remove_percentile("removeBelowPercentile"),
    # renames
    "alias": _h_alias,
    "aliasByNode": _h_alias_by_node, "aliasByNodes": _h_alias_by_node,
    "aliasByMetric": _h_alias_by_metric,
    "aliasSub": _h_alias_sub,
    "consolidateBy": _h_consolidate_by,
    "cumulative": _h_cumulative,
    "dashed": _h_dashed,
    "substr": _h_substr,
    # combiners
    "sumSeries": _h_combine("sumSeries"),
    "sum": _h_combine("sumSeries"),
    "averageSeries": _h_combine("averageSeries"),
    "avg": _h_combine("averageSeries"),
    "minSeries": _h_combine("minSeries"),
    "maxSeries": _h_combine("maxSeries"),
    "multiplySeries": _h_combine("multiplySeries"),
    "diffSeries": _h_combine("diffSeries"),
    "stddevSeries": _h_combine("stddevSeries"),
    "rangeOfSeries": _h_combine("rangeOfSeries"),
    "medianSeries": _h_combine("medianSeries"),
    "countSeries": _h_combine("countSeries"),
    "aggregate": _h_aggregate,
    "percentileOfSeries": _h_percentile_of_series,
    # grouped
    "groupByNode": _h_group_by_node,
    "groupByNodes": _h_group_by_nodes,
    "sumSeriesWithWildcards": _h_with_wildcards("sum"),
    "averageSeriesWithWildcards": _h_with_wildcards("avg"),
    "multiplySeriesWithWildcards": _h_with_wildcards("multiply"),
    "aggregateWithWildcards": _h_aggregate_with_wildcards,
    # selection
    "sortByName": _h_sort_by_name,
    "exclude": _h_exclude,
    "grep": _h_grep,
    "limit": _h_limit,
}

_TIME_SLICE = {"timeSlice": _h_time_slice}


def _lower(node, step: int, step_times):
    """-> (sym, covered) where covered is this subtree's graphite AST
    node count.  Raises _Unlowerable at the shallowest node with no
    device form (the host then serves it and retries its children)."""
    if isinstance(node, Path):
        return _lower_path(node.pattern, step), 1
    if not isinstance(node, Call):
        raise _Unlowerable("graphite_literal")
    real_S = len(step_times)
    handler = _LOWER.get(node.fn)
    ts_handler = _TIME_SLICE.get(node.fn)
    if handler is None and ts_handler is None:
        from m3_tpu.query.graphite import FUNCTIONS
        raise _Unlowerable(
            "graphite_host_fn"
            if node.fn in FUNCTIONS or node.fn == "timeShift"
            else "graphite_unknown_fn")
    child_sym, covered = _lower(_series_child(node), step, step_times)
    if ts_handler is not None:
        sym = ts_handler(node, child_sym, step, real_S, step_times)
    else:
        sym = handler(node, child_sym, step, real_S)
    return sym, covered + 1


def _count_split(eng, reason: str) -> None:
    eng._cost().split(reason)


def try_device(geng, node, step_times, step):
    """Serve a graphite subtree with the fused device pipeline.
    Returns a SeriesList or None (host serves; splits counted in
    m3_query_host_split_total{reason} like the PromQL engine's
    _try_fused)."""
    eng = geng._engine
    if not eng._device_serving_active():
        return None
    cost = eng._cost()
    if cost.fused_poisoned:
        return None
    step_times = np.asarray(step_times, dtype=np.int64)
    if eng.planner is not None \
            and eng._ladder_lookbacks(step_times) is not None:
        # coarse retention rungs need the host path's per-band
        # lookback widening — same gate as Engine._try_fused
        _count_split(eng, "retention_coarse_lookback")
        return None
    try:
        sym, covered = _lower(node, int(step), step_times)
    except _Unlowerable as exc:
        if isinstance(node, Call):
            _count_split(eng, exc.reason)
        return None
    from m3_tpu.query import plan as qplan
    counts = {"ops": covered, "fns": [], "aggs": [], "new": True}
    try:
        mat = qplan.run_sym(eng, sym, step_times, counts, covered)
    except qplan.Unsupported as exc:
        _count_split(eng, getattr(exc, "reason", "unknown_node"))
        return None
    except Exception as exc:  # noqa: BLE001 — host must still serve
        cost.fused_error = f"{type(exc).__name__}: {exc}"[:200]
        return None
    if mat is None:
        return None
    names = [ls.get(b"__name__", b"").decode("latin-1")
             for ls in mat.labels]
    return SeriesList(names, np.asarray(mat.values, dtype=np.float64),
                      int(step), step_times)
