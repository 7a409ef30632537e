"""Graphite query engine: parser, function library, find.

(ref: src/query/graphite/ — lexer graphite/lexer/lexer.go, compiler
native/compiler.go, ~100 builtins native/builtin_functions.go, storage
adapter graphite/storage/m3_wrapper.go.)  Carbon ingest stores each
path component as a ``__gN__`` tag (m3_tpu/coordinator/carbon.py), so
a glob pattern compiles to per-component regex matchers against the
index — the same mapping the reference uses.

The evaluator is batched: a SeriesList is labels + one [L, S] numpy
grid on the query's step grid; every builtin is a vectorized
transform, mirroring how the PromQL engine executes (query/engine.py).
"""

from __future__ import annotations

import dataclasses
import fnmatch
import math
import os
import re
import time

import numpy as np

from m3_tpu.ops import consolidate as cons
from m3_tpu.query.engine import Engine

SECOND = 1_000_000_000


# --- parser (ref: graphite/lexer + native/compiler.go) ---------------------

_TOKEN_RE = re.compile(
    r"""\s*(?:
        (?P<number>-?\d+\.\d*|-?\.\d+|-?\d+)
      | (?P<string>"(?:[^"\\]|\\.)*"|'(?:[^'\\]|\\.)*')
      | (?P<name>[A-Za-z_][A-Za-z0-9_]*)
      | (?P<pathch>[A-Za-z0-9_*?{}\[\]\-.:,%$#@!~]+)
      | (?P<op>[(),=])
    )""",
    re.VERBOSE,
)

_PATH_CHARS = set("*?{}[].")


@dataclasses.dataclass
class Call:
    fn: str
    args: list
    kwargs: dict


@dataclasses.dataclass
class Path:
    pattern: str


def parse(expr: str):
    """One target expression -> AST (Call / Path / literal)."""
    node, pos = _parse_expr(expr, 0)
    if expr[pos:].strip():
        raise ValueError(f"graphite: trailing input {expr[pos:]!r}")
    return node


def _parse_expr(s: str, pos: int):
    m = _TOKEN_RE.match(s, pos)
    if not m:
        raise ValueError(f"graphite: parse error at {s[pos:pos+25]!r}")
    if m.lastgroup == "number":
        return float(m.group("number")), m.end()
    if m.lastgroup == "string":
        return m.group("string")[1:-1], m.end()
    # name: function call, bare path, or keyword literal
    start = m.start() + (len(m.group(0)) - len(m.group(0).lstrip()))
    if m.lastgroup in ("name", "pathch"):
        # greedily consume a dotted path; stop at '(' deciding call
        j = m.end()
        if m.lastgroup == "name" and j < len(s) and s[j] == "(":
            return _parse_call(s, m.group("name"), j + 1)
        while j < len(s) and (s[j] in "._-" or s[j].isalnum()
                              or s[j] in _PATH_CHARS):
            j += 1
        token = s[start:j].strip()
        if token in ("True", "true"):
            return True, j
        if token in ("False", "false"):
            return False, j
        if token in ("None", "none"):
            return None, j
        return Path(token), j
    raise ValueError(f"graphite: unexpected {m.group(0)!r}")


def _parse_call(s: str, fn: str, pos: int):
    args, kwargs = [], {}
    while True:
        m = _TOKEN_RE.match(s, pos)
        if m and m.group(0).strip() == ")":
            return Call(fn, args, kwargs), m.end()
        # kwarg?
        km = re.match(r"\s*([A-Za-z_][A-Za-z0-9_]*)\s*=", s[pos:])
        if km and not s[pos + km.end():].lstrip().startswith("="):
            val, pos = _parse_expr(s, pos + km.end())
            kwargs[km.group(1)] = val
        else:
            val, pos = _parse_expr(s, pos)
            args.append(val)
        m = _TOKEN_RE.match(s, pos)
        if not m:
            raise ValueError("graphite: unterminated call")
        tok = m.group(0).strip()
        pos = m.end()
        if tok == ")":
            return Call(fn, args, kwargs), pos
        if tok != ",":
            raise ValueError(f"graphite: expected ',' got {tok!r}")


# --- path pattern -> index matchers ----------------------------------------


def split_components(pattern: str) -> list[str]:
    """Split on '.' outside {...} alternation groups."""
    out, depth, cur = [], 0, []
    for ch in pattern:
        if ch == "{":
            depth += 1
        elif ch == "}":
            depth -= 1
        if ch == "." and depth == 0:
            out.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    out.append("".join(cur))
    return out


def component_regex(glob: str) -> bytes:
    """Graphite component glob -> regex (ref: graphite/glob.go)."""
    out, i = [], 0
    while i < len(glob):
        c = glob[i]
        if c == "*":
            out.append("[^.]*")
        elif c == "?":
            out.append("[^.]")
        elif c == "{":
            j = glob.index("}", i)
            alts = glob[i + 1:j].split(",")
            out.append("(?:" + "|".join(re.escape(a) for a in alts) + ")")
            i = j
        elif c == "[":
            j = glob.index("]", i)
            out.append(glob[i:j + 1])
            i = j
        else:
            out.append(re.escape(c))
        i += 1
    return "".join(out).encode()


def pattern_matchers(pattern: str) -> list:
    comps = split_components(pattern)
    out = []
    for i, comp in enumerate(comps):
        if comp == "*":
            continue  # existence is implied by the length filter
        out.append(("re", b"__g%d__" % i, component_regex(comp)))
    if not out:
        out.append(("re", b"__g0__", component_regex(comps[0])))
    return out


@dataclasses.dataclass
class SeriesList:
    names: list[str]
    values: np.ndarray  # [L, S]
    step_nanos: int
    step_times: np.ndarray  # [S] window-END timestamps (nanos)

    def clone(self, names=None, values=None):
        return SeriesList(
            names if names is not None else list(self.names),
            values if values is not None else self.values.copy(),
            self.step_nanos, self.step_times)


def _empty(step_times, step) -> SeriesList:
    return SeriesList([], np.zeros((0, len(step_times))), step,
                      step_times)


# --- engine -----------------------------------------------------------------


class GraphiteEngine:
    """(ref: graphite/native/engine.go:29)."""

    def __init__(self, db, namespace: str = "default",
                 lookback_nanos: int = cons.DEFAULT_LOOKBACK,
                 device: bool | None = None):
        self.db = db
        self.ns = namespace
        if device is None:
            env = os.environ.get("M3_GRAPHITE_DEVICE", "").lower()
            if env in ("1", "true", "yes"):
                device = True
            elif env in ("0", "false", "no"):
                device = False
        # device=None -> the inner engine's lazy auto-detection (any
        # non-cpu jax backend); the Call-tree lowerer rides the same
        # gate as PromQL's fused path (query/graphite_device.py)
        self._engine = Engine(db, namespace, lookback_nanos,
                              device_serving=device)
        # per-render device accounting, for tests and the bench leg:
        # {"ast_nodes", "device_nodes", "host_splits"}
        self.last_render_stats: dict | None = None

    # -- fetch ---------------------------------------------------------------

    def fetch(self, pattern: str, step_times, step) -> SeriesList:
        n_comp = len(split_components(pattern))
        matchers = pattern_matchers(pattern)
        start = int(step_times[0]) - step
        end = int(step_times[-1])
        labels, times, values = self._engine._fetch_raw(
            matchers, start, end)
        keep, names = [], []
        for i, ls in enumerate(labels):
            depth = sum(1 for k in ls if k.startswith(b"__g"))
            if depth != n_comp:
                continue  # pattern matches exact path depth only
            name = ls.get(b"__name__", b"").decode("latin-1")
            keep.append(i)
            names.append(name)
        if not keep:
            return _empty(step_times, step)
        times, values = times[keep], values[keep]
        # graphite semantics: per-step LAST value in (t-step, t]
        vals = cons.step_consolidate(times, values, step_times, step)
        return SeriesList(names, vals, step, step_times)

    # -- render --------------------------------------------------------------

    def render(self, target: str, start_nanos: int, end_nanos: int,
               step_nanos: int) -> SeriesList:
        steps = np.arange(
            start_nanos + step_nanos, end_nanos + 1, step_nanos,
            dtype=np.int64)
        if len(steps) == 0:
            raise ValueError("graphite: empty time range")
        from m3_tpu.query import graphite_device as gdev
        t0 = time.perf_counter_ns()
        eng = self._engine
        # the PromQL path's per-query scope: the fused lowerer's
        # accounting, the gather memo and the slow-query record
        with eng._query_scope(f"graphite://{target}", t0) as cost:
            with cost.phase("parse"):
                ast = parse(target)
            cost.ast_nodes = gdev.ast_size(ast)
            eng._qrange_local.value = (int(start_nanos), int(end_nanos))
            try:
                return self._eval(ast, steps, step_nanos)
            finally:
                self.last_render_stats = {
                    "ast_nodes": cost.ast_nodes,
                    "device_nodes": cost.fused_nodes,
                    "host_splits": dict(cost.host_split_reasons),
                }

    def _eval(self, node, step_times, step) -> SeriesList:
        if isinstance(node, (Path, Call)):
            # try lowering this subtree onto the fused device pipeline
            # first; on decline the host serves THIS node and the
            # recursion below retries each child — the same deepest-
            # unsupported-node splitting the PromQL engine does
            from m3_tpu.query import graphite_device as gdev
            dev = gdev.try_device(self, node, step_times, step)
            if dev is not None:
                return dev
        if isinstance(node, Path):
            return self.fetch(node.pattern, step_times, step)
        if isinstance(node, Call):
            if node.fn == "timeShift":
                # evaluate the wrapped expression at shifted times and
                # present it on the original grid (ref:
                # builtin_functions.go timeShift)
                from m3_tpu.metrics.policy import parse_duration
                spec = node.args[1] if len(node.args) > 1 else "1d"
                sign = -1
                if isinstance(spec, str):
                    if spec.startswith("+"):
                        sign, spec = 1, spec[1:]
                    elif spec.startswith("-"):
                        spec = spec[1:]
                    delta = sign * parse_duration(spec)
                else:
                    delta = int(spec) * SECOND * sign
                shifted = self._eval(node.args[0],
                                     step_times + delta, step)
                return SeriesList(
                    [f'timeShift({n},"{node.args[1] if len(node.args) > 1 else "1d"}")'
                     for n in shifted.names],
                    shifted.values, step, step_times)
            fn = FUNCTIONS.get(node.fn)
            if fn is None:
                raise ValueError(f"graphite: unknown function "
                                 f"{node.fn!r}")
            args = [self._eval(a, step_times, step)
                    if isinstance(a, (Path, Call)) else a
                    for a in node.args]
            kwargs = {k: (self._eval(v, step_times, step)
                          if isinstance(v, (Path, Call)) else v)
                      for k, v in node.kwargs.items()}
            return fn(self, step_times, step, *args, **kwargs)
        raise ValueError(f"graphite: cannot evaluate {node!r}")

    # -- find (ref: graphite find handler + storage FetchTaggedIDs) ---------

    def find(self, pattern: str) -> list[tuple[str, bool]]:
        """[(node_name, is_leaf)] for the pattern's last component."""
        comps = split_components(pattern)
        n = len(comps)
        matchers = []
        for i, comp in enumerate(comps):
            if comp != "*":
                matchers.append(("re", b"__g%d__" % i,
                                 component_regex(comp)))
        if not matchers:
            matchers.append(("re", b"__g0__", b".*"))
        idx = self.db._ns(self.ns).index
        nodes: dict[str, bool] = {}
        for sid in self.db.query_ids(self.ns, matchers):
            tags = idx.tags_of(idx.ordinal(sid))
            depth = sum(1 for k in tags if k.startswith(b"__g"))
            if depth < n:
                continue
            name = tags[b"__g%d__" % (n - 1)].decode("latin-1")
            is_leaf = depth == n
            # leaf wins if both a leaf and a branch exist at the name
            nodes[name] = nodes.get(name, False) or is_leaf
        return sorted(nodes.items())


# --- function library (ref: native/builtin_functions.go) -------------------

FUNCTIONS: dict = {}


def register(*names):
    def deco(fn):
        for n in names:
            FUNCTIONS[n] = fn
        return fn
    return deco


def _nansafe(reduction, x, axis=0):
    with np.errstate(all="ignore"):
        out = reduction(x, axis=axis)
    return out


def _combine(sl: SeriesList, name: str, reduction) -> SeriesList:
    if not sl.names:
        return sl
    vals = _nansafe(reduction, sl.values, axis=0)[None, :]
    return sl.clone([name], vals)


@register("sumSeries", "sum")
def _sum(eng, st, step, sl: SeriesList, *more):
    sl = _merge_lists(sl, more)
    return _combine(sl, f"sumSeries({','.join(sl.names)})", np.nansum)


@register("averageSeries", "avg")
def _avg(eng, st, step, sl, *more):
    sl = _merge_lists(sl, more)
    return _combine(sl, f"averageSeries({','.join(sl.names)})",
                    np.nanmean)


@register("minSeries")
def _min_series(eng, st, step, sl, *more):
    sl = _merge_lists(sl, more)
    return _combine(sl, f"minSeries({','.join(sl.names)})", np.nanmin)


@register("maxSeries")
def _max_series(eng, st, step, sl, *more):
    sl = _merge_lists(sl, more)
    return _combine(sl, f"maxSeries({','.join(sl.names)})", np.nanmax)


@register("countSeries")
def _count_series(eng, st, step, sl, *more):
    sl = _merge_lists(sl, more)
    vals = np.full((1, sl.values.shape[1]), float(len(sl.names)))
    return sl.clone([f"countSeries({','.join(sl.names)})"], vals)


@register("diffSeries")
def _diff_series(eng, st, step, sl, *more):
    sl = _merge_lists(sl, more)
    if not sl.names:
        return sl
    rest = np.nansum(sl.values[1:], axis=0)
    vals = (np.nan_to_num(sl.values[0]) - rest)[None, :]
    vals = np.where(np.isnan(sl.values).all(axis=0), np.nan, vals)
    return sl.clone([f"diffSeries({','.join(sl.names)})"], vals)


@register("multiplySeries")
def _multiply_series(eng, st, step, sl, *more):
    sl = _merge_lists(sl, more)
    return _combine(sl, f"multiplySeries({','.join(sl.names)})",
                    np.nanprod)


def _merge_lists(sl: SeriesList, more) -> SeriesList:
    for other in more:
        sl = sl.clone(sl.names + other.names,
                      np.concatenate([sl.values, other.values]))
    return sl


@register("scale")
def _scale(eng, st, step, sl, factor):
    return sl.clone([f"scale({n},{factor:g})" for n in sl.names],
                    sl.values * factor)


@register("scaleToSeconds")
def _scale_to_seconds(eng, st, step, sl, seconds):
    factor = seconds / (step / SECOND)
    return sl.clone([f"scaleToSeconds({n},{seconds:g})"
                     for n in sl.names], sl.values * factor)


@register("offset")
def _offset(eng, st, step, sl, amount):
    return sl.clone([f"offset({n},{amount:g})" for n in sl.names],
                    sl.values + amount)


@register("absolute")
def _absolute(eng, st, step, sl):
    return sl.clone([f"absolute({n})" for n in sl.names],
                    np.abs(sl.values))


@register("invert")
def _invert(eng, st, step, sl):
    with np.errstate(divide="ignore"):
        v = 1.0 / sl.values
    return sl.clone([f"invert({n})" for n in sl.names],
                    np.where(np.isinf(v), np.nan, v))


@register("logarithm", "log")
def _log(eng, st, step, sl, base=10.0):
    with np.errstate(all="ignore"):
        v = np.log(sl.values) / math.log(base)
    return sl.clone([f"logarithm({n})" for n in sl.names],
                    np.where(np.isfinite(v), v, np.nan))


@register("pow")
def _pow(eng, st, step, sl, exp):
    return sl.clone([f"pow({n},{exp:g})" for n in sl.names],
                    np.power(sl.values, exp))


@register("derivative")
def _derivative(eng, st, step, sl):
    d = np.diff(sl.values, axis=1)
    first = np.full((len(sl.names), 1), np.nan)
    return sl.clone([f"derivative({n})" for n in sl.names],
                    np.concatenate([first, d], axis=1))


@register("nonNegativeDerivative")
def _nn_derivative(eng, st, step, sl):
    d = np.diff(sl.values, axis=1)
    d = np.where(d < 0, np.nan, d)
    first = np.full((len(sl.names), 1), np.nan)
    return sl.clone([f"nonNegativeDerivative({n})" for n in sl.names],
                    np.concatenate([first, d], axis=1))


@register("perSecond")
def _per_second(eng, st, step, sl):
    d = np.diff(sl.values, axis=1) / (step / SECOND)
    d = np.where(d < 0, np.nan, d)
    first = np.full((len(sl.names), 1), np.nan)
    return sl.clone([f"perSecond({n})" for n in sl.names],
                    np.concatenate([first, d], axis=1))


@register("integral")
def _integral(eng, st, step, sl):
    return sl.clone([f"integral({n})" for n in sl.names],
                    np.nancumsum(sl.values, axis=1))


@register("keepLastValue")
def _keep_last(eng, st, step, sl, limit=np.inf):
    vals = sl.values.copy()
    for row in vals:
        last, gap = np.nan, 0
        for i in range(len(row)):
            if np.isnan(row[i]):
                gap += 1
                if not np.isnan(last) and gap <= limit:
                    row[i] = last
            else:
                last, gap = row[i], 0
    return sl.clone([f"keepLastValue({n})" for n in sl.names], vals)


@register("transformNull")
def _transform_null(eng, st, step, sl, default=0.0):
    return sl.clone([f"transformNull({n},{default:g})"
                     for n in sl.names],
                    np.where(np.isnan(sl.values), default, sl.values))


@register("removeAboveValue")
def _remove_above(eng, st, step, sl, n):
    return sl.clone([f"removeAboveValue({nm},{n:g})"
                     for nm in sl.names],
                    np.where(sl.values > n, np.nan, sl.values))


@register("removeBelowValue")
def _remove_below(eng, st, step, sl, n):
    return sl.clone([f"removeBelowValue({nm},{n:g})"
                     for nm in sl.names],
                    np.where(sl.values < n, np.nan, sl.values))


def _moving(name, window_fn):
    def fn(eng, st, step, sl, window):
        w = _window_steps(window, step)
        L, S = sl.values.shape
        out = np.full((L, S), np.nan)
        for i in range(S):
            lo = max(0, i - w + 1)
            seg = sl.values[:, lo:i + 1]
            with np.errstate(all="ignore"):
                out[:, i] = window_fn(seg, axis=1)
        return sl.clone([f"{name}({n},{window})" for n in sl.names],
                        out)
    return fn


FUNCTIONS["movingAverage"] = _moving("movingAverage", np.nanmean)
FUNCTIONS["movingSum"] = _moving("movingSum", np.nansum)
FUNCTIONS["movingMax"] = _moving("movingMax", np.nanmax)
FUNCTIONS["movingMin"] = _moving("movingMin", np.nanmin)


def _window_steps(window, step) -> int:
    if isinstance(window, str):
        from m3_tpu.metrics.policy import parse_duration
        return max(1, int(parse_duration(window) // step))
    return max(1, int(window))


@register("summarize")
def _summarize(eng, st, step, sl, interval, func="sum"):
    from m3_tpu.metrics.policy import parse_duration
    k = max(1, int(parse_duration(interval) // step))
    L, S = sl.values.shape
    n_out = (S + k - 1) // k
    pad = n_out * k - S
    v = np.concatenate(
        [sl.values, np.full((L, pad), np.nan)], axis=1)
    v = v.reshape(L, n_out, k)
    red = _AGG_REDUCTIONS.get(func)
    if red is None:
        raise ValueError(f"summarize: unknown function {func!r}")
    with np.errstate(all="ignore"):
        out = red(v, axis=2)
    out = np.repeat(out, k, axis=1)[:, :S]
    return sl.clone([f'summarize({n},"{interval}","{func}")'
                     for n in sl.names], out)


# -- alias + grouping --------------------------------------------------------


@register("alias")
def _alias(eng, st, step, sl, name):
    return sl.clone([name] * len(sl.names))


@register("aliasByNode", "aliasByNodes")
def _alias_by_node(eng, st, step, sl, *nodes):
    names = []
    for n in sl.names:
        parts = n.split(".")
        names.append(".".join(parts[int(i)] for i in nodes
                              if -len(parts) <= int(i) < len(parts)))
    return sl.clone(names)


@register("aliasByMetric")
def _alias_by_metric(eng, st, step, sl):
    return sl.clone([n.split(".")[-1] for n in sl.names])


@register("aliasSub")
def _alias_sub(eng, st, step, sl, search, replace):
    rx = re.compile(search)
    return sl.clone([rx.sub(replace, n) for n in sl.names])


@register("groupByNode")
def _group_by_node(eng, st, step, sl, node, func="sum"):
    groups: dict[str, list[int]] = {}
    for i, n in enumerate(sl.names):
        parts = n.split(".")
        key = parts[int(node)] if -len(parts) <= int(node) < len(parts) \
            else n
        groups.setdefault(key, []).append(i)
    red = {"sum": np.nansum, "avg": np.nanmean, "average": np.nanmean,
           "max": np.nanmax, "min": np.nanmin}[func]
    names, rows = [], []
    for key in sorted(groups):
        names.append(key)
        with np.errstate(all="ignore"):
            rows.append(red(sl.values[groups[key]], axis=0))
    return sl.clone(names, np.array(rows) if rows else
                    np.zeros((0, sl.values.shape[1])))


# -- filters + sorts ---------------------------------------------------------


def _series_stat(sl, kind):
    with np.errstate(all="ignore"):
        if kind == "current":
            v = sl.values
            # last non-NaN per row
            out = np.full(len(sl.names), np.nan)
            for i, row in enumerate(v):
                ok = ~np.isnan(row)
                if ok.any():
                    out[i] = row[np.nonzero(ok)[0][-1]]
            return out
        if kind == "average":
            return np.nanmean(sl.values, axis=1)
        if kind == "max":
            return np.nanmax(sl.values, axis=1)
        if kind == "total":
            return np.nansum(sl.values, axis=1)
        if kind == "min":
            return np.nanmin(sl.values, axis=1)
        if kind == "stddev":
            return np.nanstd(sl.values, axis=1)
    raise ValueError(kind)


def _select(sl, order, n=None):
    names = [sl.names[i] for i in order]
    vals = sl.values[order]
    if n is not None:
        names, vals = names[:int(n)], vals[:int(n)]
    return sl.clone(names, vals)


def _top(kind, reverse=True):
    def fn(eng, st, step, sl, n):
        # all-NaN series sort LAST in either direction
        fill = -np.inf if reverse else np.inf
        stat = np.nan_to_num(_series_stat(sl, kind), nan=fill)
        order = np.argsort(-stat if reverse else stat, kind="stable")
        return _select(sl, order.tolist(), n)
    return fn


FUNCTIONS["highestCurrent"] = _top("current")
FUNCTIONS["lowestCurrent"] = _top("current", reverse=False)
FUNCTIONS["highestAverage"] = _top("average")
FUNCTIONS["highestMax"] = _top("max")


def _threshold(kind, above):
    def fn(eng, st, step, sl, n):
        stat = _series_stat(sl, kind)
        keep = [i for i, s in enumerate(stat)
                if not np.isnan(s) and (s > n if above else s < n)]
        return _select(sl, keep)
    return fn


FUNCTIONS["currentAbove"] = _threshold("current", True)
FUNCTIONS["currentBelow"] = _threshold("current", False)
FUNCTIONS["averageAbove"] = _threshold("average", True)
FUNCTIONS["averageBelow"] = _threshold("average", False)
FUNCTIONS["maximumAbove"] = _threshold("max", True)
FUNCTIONS["maximumBelow"] = _threshold("max", False)


@register("sortByName")
def _sort_by_name(eng, st, step, sl):
    order = sorted(range(len(sl.names)), key=lambda i: sl.names[i])
    return _select(sl, order)


@register("sortByTotal")
def _sort_by_total(eng, st, step, sl):
    stat = np.nan_to_num(_series_stat(sl, "total"), nan=-np.inf)
    return _select(sl, np.argsort(-stat, kind="stable").tolist())


@register("sortByMaxima")
def _sort_by_maxima(eng, st, step, sl):
    stat = np.nan_to_num(_series_stat(sl, "max"), nan=-np.inf)
    return _select(sl, np.argsort(-stat, kind="stable").tolist())


@register("exclude")
def _exclude(eng, st, step, sl, pattern):
    rx = re.compile(pattern)
    keep = [i for i, n in enumerate(sl.names) if not rx.search(n)]
    return _select(sl, keep)


@register("grep")
def _grep(eng, st, step, sl, pattern):
    rx = re.compile(pattern)
    keep = [i for i, n in enumerate(sl.names) if rx.search(n)]
    return _select(sl, keep)


@register("limit")
def _limit(eng, st, step, sl, n):
    return _select(sl, list(range(len(sl.names))), n)


@register("asPercent")
def _as_percent(eng, st, step, sl, total=None):
    if total is None:
        denom = np.nansum(sl.values, axis=0)
    elif isinstance(total, SeriesList):
        denom = np.nansum(total.values, axis=0)
    else:
        denom = np.full(sl.values.shape[1], float(total))
    with np.errstate(all="ignore"):
        v = 100.0 * sl.values / denom
    return sl.clone([f"asPercent({n})" for n in sl.names],
                    np.where(np.isfinite(v), v, np.nan))


# -- breadth pass 2 (ref: native/builtin_functions.go — the remaining
#    high-traffic builtins) --------------------------------------------------


FUNCTIONS["minimumAbove"] = _threshold("min", True)
FUNCTIONS["minimumBelow"] = _threshold("min", False)
FUNCTIONS["lowestAverage"] = _top("average", reverse=False)
FUNCTIONS["lowestMax"] = _top("max", reverse=False)
FUNCTIONS["highestMin"] = _top("min")

_STAT_FUNCS = {"current": "current", "average": "average", "avg": "average",
               "max": "max", "min": "min", "sum": "total",
               "total": "total", "stddev": "stddev"}


@register("highest")
def _highest(eng, st, step, sl, n=1, func="average"):
    return _top(_STAT_FUNCS[func])(eng, st, step, sl, n)


@register("lowest")
def _lowest(eng, st, step, sl, n=1, func="average"):
    return _top(_STAT_FUNCS[func], reverse=False)(eng, st, step, sl, n)


@register("sortByMinima")
def _sort_by_minima(eng, st, step, sl):
    stat = np.nan_to_num(_series_stat(sl, "min"), nan=np.inf)
    return _select(sl, np.argsort(stat, kind="stable").tolist())


@register("mostDeviant")
def _most_deviant(eng, st, step, sl, n):
    stat = np.nan_to_num(_series_stat(sl, "stddev"), nan=-np.inf)
    return _select(sl, np.argsort(-stat, kind="stable").tolist(), n)


@register("stddevSeries")
def _stddev_series(eng, st, step, sl, *more):
    sl = _merge_lists(sl, more)
    return _combine(sl, f"stddevSeries({','.join(sl.names)})",
                    lambda x, axis: np.nanstd(x, axis=axis))


@register("rangeOfSeries")
def _range_of_series(eng, st, step, sl, *more):
    sl = _merge_lists(sl, more)
    return _combine(
        sl, f"rangeOfSeries({','.join(sl.names)})",
        lambda x, axis: np.nanmax(x, axis=axis) - np.nanmin(x, axis=axis))


@register("medianSeries")
def _median_series(eng, st, step, sl, *more):
    sl = _merge_lists(sl, more)
    return _combine(sl, f"medianSeries({','.join(sl.names)})",
                    lambda x, axis: np.nanmedian(x, axis=axis))


FUNCTIONS["movingMedian"] = _moving("movingMedian", np.nanmedian)


@register("exponentialMovingAverage")
def _ema(eng, st, step, sl, window):
    w = _window_steps(window, step)
    alpha = 2.0 / (w + 1.0)
    L, S = sl.values.shape
    out = np.full((L, S), np.nan)
    ema = np.full(L, np.nan)
    for i in range(S):
        x = sl.values[:, i]
        fresh = np.isnan(ema) & ~np.isnan(x)
        ema = np.where(fresh, x, ema)
        upd = ~np.isnan(ema) & ~np.isnan(x)
        ema = np.where(upd, alpha * x + (1 - alpha) * ema, ema)
        out[:, i] = ema
    return sl.clone(
        [f"exponentialMovingAverage({n},{window})" for n in sl.names], out)


@register("stdev")
def _stdev(eng, st, step, sl, points):
    return _moving("stdev", np.nanstd)(eng, st, step, sl, points)


@register("nPercentile")
def _n_percentile(eng, st, step, sl, n):
    with np.errstate(all="ignore"):
        p = np.nanpercentile(sl.values, float(n), axis=1)
    vals = np.repeat(p[:, None], sl.values.shape[1], axis=1)
    return sl.clone([f"nPercentile({name},{n})" for name in sl.names],
                    vals)


@register("percentileOfSeries")
def _percentile_of_series(eng, st, step, sl, n, interpolate=False):
    with np.errstate(all="ignore"):
        vals = np.nanpercentile(sl.values, float(n), axis=0)[None, :]
    return sl.clone([f"percentileOfSeries({sl.names[0] if sl.names else ''},{n})"],
                    vals)


def _remove_percentile(above):
    def fn(eng, st, step, sl, n):
        with np.errstate(all="ignore"):
            p = np.nanpercentile(sl.values, float(n), axis=1)
        v = sl.values.copy()
        mask = v > p[:, None] if above else v < p[:, None]
        v[mask] = np.nan
        return sl.clone(None, v)
    return fn


FUNCTIONS["removeAbovePercentile"] = _remove_percentile(True)
FUNCTIONS["removeBelowPercentile"] = _remove_percentile(False)


@register("squareRoot")
def _square_root(eng, st, step, sl):
    with np.errstate(all="ignore"):
        v = np.sqrt(sl.values)
    return sl.clone([f"squareRoot({n})" for n in sl.names],
                    np.where(np.isfinite(v), v, np.nan))


@register("offsetToZero")
def _offset_to_zero(eng, st, step, sl):
    with np.errstate(all="ignore"):
        mins = np.nanmin(sl.values, axis=1, keepdims=True)
    return sl.clone([f"offsetToZero({n})" for n in sl.names],
                    sl.values - mins)


@register("isNonNull")
def _is_non_null(eng, st, step, sl):
    return sl.clone([f"isNonNull({n})" for n in sl.names],
                    (~np.isnan(sl.values)).astype(float))


@register("changed")
def _changed(eng, st, step, sl):
    v = sl.values
    out = np.zeros_like(v)
    if v.shape[1] > 1:
        prev, curr = v[:, :-1], v[:, 1:]
        ch = (curr != prev) & ~np.isnan(curr) & ~np.isnan(prev)
        out[:, 1:] = ch.astype(float)
    return sl.clone([f"changed({n})" for n in sl.names], out)


@register("divideSeries")
def _divide_series(eng, st, step, sl, divisor):
    if not isinstance(divisor, SeriesList) or len(divisor.names) != 1:
        raise ValueError("divideSeries needs exactly one divisor series")
    with np.errstate(all="ignore"):
        v = sl.values / np.where(divisor.values[0] == 0, np.nan,
                                 divisor.values[0])
    return sl.clone(
        [f"divideSeries({n},{divisor.names[0]})" for n in sl.names],
        np.where(np.isfinite(v), v, np.nan))


@register("divideSeriesLists")
def _divide_series_lists(eng, st, step, sl, divisors):
    if len(sl.names) != len(divisors.names):
        raise ValueError("divideSeriesLists: length mismatch")
    with np.errstate(all="ignore"):
        v = sl.values / np.where(divisors.values == 0, np.nan,
                                 divisors.values)
    return sl.clone(
        [f"divideSeries({a},{b})" for a, b in zip(sl.names, divisors.names)],
        np.where(np.isfinite(v), v, np.nan))


@register("constantLine")
def _constant_line(eng, st, step, value):
    vals = np.full((1, len(st)), float(value))
    return SeriesList([str(value)], vals, step, st)


@register("threshold")
def _threshold_line(eng, st, step, value, label=None, color=None):
    out = _constant_line(eng, st, step, value)
    if label:
        out = out.clone([label])
    return out


@register("timeFunction", "time")
def _time_function(eng, st, step, name="Time", step_arg=None):
    vals = (np.asarray(st, dtype=np.float64) / 1e9)[None, :]
    return SeriesList([name if isinstance(name, str) else "Time"],
                      vals, step, st)


@register("group")
def _group(eng, st, step, sl, *more):
    return _merge_lists(sl, more)


@register("groupByNodes")
def _group_by_nodes(eng, st, step, sl, func, *nodes):
    groups: dict[str, list[int]] = {}
    for i, n in enumerate(sl.names):
        parts = n.split(".")
        key = ".".join(parts[int(x)] for x in nodes
                       if -len(parts) <= int(x) < len(parts))
        groups.setdefault(key, []).append(i)
    red = {"sum": np.nansum, "avg": np.nanmean, "average": np.nanmean,
           "max": np.nanmax, "min": np.nanmin,
           "median": np.nanmedian}[func]
    names, rows = [], []
    for key in sorted(groups):
        names.append(key)
        with np.errstate(all="ignore"):
            rows.append(red(sl.values[groups[key]], axis=0))
    return sl.clone(names, np.array(rows) if rows else
                    np.zeros((0, sl.values.shape[1])))


@register("substr")
def _substr(eng, st, step, sl, start=0, stop=0):
    names = []
    for n in sl.names:
        parts = n.split(".")
        sliced = parts[int(start):int(stop) if int(stop) else None]
        names.append(".".join(sliced))
    return sl.clone(names)


@register("weightedAverage")
def _weighted_average(eng, st, step, sl, weights, *nodes):
    """Pairs value/weight series BY NODE KEY (not positionally — the
    two wildcard fetches may enumerate in different orders); unmatched
    series drop, matching graphite semantics."""
    def key_of(name):
        parts = name.split(".")
        return tuple(parts[int(x)] for x in nodes
                     if -len(parts) <= int(x) < len(parts))

    w_by_key = {key_of(n): i for i, n in enumerate(weights.names)}
    pairs = [(i, w_by_key[key_of(n)]) for i, n in enumerate(sl.names)
             if key_of(n) in w_by_key]
    if not pairs:
        return _empty(st, step)
    vi = [a for a, _ in pairs]
    wi = [b for _, b in pairs]
    with np.errstate(all="ignore"):
        num = np.nansum(sl.values[vi] * weights.values[wi], axis=0)
        den = np.nansum(weights.values[wi], axis=0)
        v = num / np.where(den == 0, np.nan, den)
    return sl.clone(["weightedAverage"], v[None, :])


@register("interpolate")
def _interpolate(eng, st, step, sl, limit=np.inf):
    """Linear gap fill, but only for gaps of <= limit consecutive
    missing points (graphite semantics)."""
    v = sl.values.copy()
    for row in v:
        ok = np.nonzero(~np.isnan(row))[0]
        if len(ok) < 2:
            continue
        for a, b in zip(ok[:-1], ok[1:]):
            gap = b - a - 1
            if gap and gap <= limit:
                row[a + 1:b] = np.interp(
                    np.arange(a + 1, b), [a, b], [row[a], row[b]])
    return sl.clone([f"interpolate({n})" for n in sl.names], v)


@register("fallbackSeries")
def _fallback_series(eng, st, step, sl, fallback):
    return sl if sl.names else fallback


@register("delay")
def _delay(eng, st, step, sl, steps):
    k = int(steps)
    v = np.full_like(sl.values, np.nan)
    if k >= 0:
        if k < v.shape[1]:
            v[:, k:] = sl.values[:, :v.shape[1] - k]
    else:
        if -k < v.shape[1]:
            v[:, :k] = sl.values[:, -k:]
    return sl.clone([f"delay({n},{k})" for n in sl.names], v)


@register("timeSlice")
def _time_slice(eng, st, step, sl, start, end="now"):
    from m3_tpu.metrics.policy import parse_duration
    now = int(st[-1])

    def bound(spec, default):
        if spec == "now":
            return now
        if isinstance(spec, str):
            return now - parse_duration(spec.lstrip("-"))
        if isinstance(spec, (int, float)):
            # unquoted numbers parse as floats: relative seconds ago
            return now - int(abs(spec)) * SECOND
        return default

    lo = bound(start, int(st[0]))
    hi = bound(end, now)
    mask = (np.asarray(st) >= lo) & (np.asarray(st) <= hi)
    v = np.where(mask[None, :], sl.values, np.nan)
    return sl.clone([f'timeSlice({n})' for n in sl.names], v)


@register("hitcount")
def _hitcount(eng, st, step, sl, interval=None):
    # value-per-step -> hits per interval (rate x step seconds)
    sec = step / 1e9
    v = sl.values * sec
    if interval:
        out = _summarize(eng, st, step, sl.clone(None, v), interval, "sum")
        # user-visible names are hitcount(...), not the internal summarize
        return out.clone([f'hitcount({n},"{interval}")' for n in sl.names])
    return sl.clone([f"hitcount({n})" for n in sl.names], v)


@register("consolidateBy")
def _consolidate_by(eng, st, step, sl, func):
    # the render-time consolidation hint; values already consolidated
    return sl.clone([f'consolidateBy({n},"{func}")' for n in sl.names])


@register("averageSeriesWithWildcards")
def _avg_with_wildcards(eng, st, step, sl, *positions):
    return _with_wildcards(sl, positions, np.nanmean)


@register("sumSeriesWithWildcards")
def _sum_with_wildcards(eng, st, step, sl, *positions):
    return _with_wildcards(sl, positions, np.nansum)


@register("multiplySeriesWithWildcards")
def _mul_with_wildcards(eng, st, step, sl, *positions):
    return _with_wildcards(sl, positions, np.nanprod)


def _with_wildcards(sl, positions, red):
    drop = {int(p) for p in positions}
    groups: dict[str, list[int]] = {}
    for i, n in enumerate(sl.names):
        parts = n.split(".")
        key = ".".join(p for j, p in enumerate(parts) if j not in drop)
        groups.setdefault(key, []).append(i)
    names, rows = [], []
    for key in sorted(groups):
        names.append(key)
        with np.errstate(all="ignore"):
            rows.append(red(sl.values[groups[key]], axis=0))
    return sl.clone(names, np.array(rows) if rows else
                    np.zeros((0, sl.values.shape[1])))


@register("minMax")
def _min_max(eng, st, step, sl):
    with np.errstate(all="ignore"):
        mins = np.nanmin(sl.values, axis=1, keepdims=True)
        maxs = np.nanmax(sl.values, axis=1, keepdims=True)
        rng = np.where(maxs - mins == 0, np.nan, maxs - mins)
        v = (sl.values - mins) / rng
    return sl.clone([f"minMax({n})" for n in sl.names],
                    np.where(np.isfinite(v), v, 0.0))


# -- final builtin-parity block: the reference's remaining registered
#    functions (ref: graphite/native/builtin_functions.go,
#    aggregation_functions.go, summarize.go) --------------------------------

def _last_valid(x: np.ndarray, axis: int) -> np.ndarray:
    """Last non-NaN value along axis (graphite 'last'/'current'
    semantics — a trailing lookback gap must not poison the stat)."""
    x = np.moveaxis(np.asarray(x, dtype=np.float64), axis, -1)
    mask = ~np.isnan(x)
    any_valid = mask.any(axis=-1)
    idx = np.where(
        any_valid,
        x.shape[-1] - 1 - np.argmax(mask[..., ::-1], axis=-1),
        0,
    )
    out = np.take_along_axis(x, idx[..., None], axis=-1)[..., 0]
    return np.where(any_valid, out, np.nan)


def _diff_reduction(x, axis):
    """Matches diffSeries: NaN minuend counts as 0 unless every series
    is NaN at that step."""
    first = np.nan_to_num(np.take(x, 0, axis=axis))
    rest = np.nansum(
        np.take(x, range(1, x.shape[axis]), axis=axis), axis=axis)
    out = first - rest
    return np.where(np.isnan(x).all(axis=axis), np.nan, out)


_AGG_REDUCTIONS = {
    "sum": np.nansum, "total": np.nansum, "": np.nansum,
    "avg": np.nanmean, "average": np.nanmean,
    "max": np.nanmax, "min": np.nanmin, "median": np.nanmedian,
    "stddev": np.nanstd,
    "count": lambda x, axis: (~np.isnan(x)).sum(axis=axis).astype(float),
    "range": lambda x, axis: np.nanmax(x, axis=axis) - np.nanmin(x, axis=axis),
    "rangeOf": lambda x, axis: np.nanmax(x, axis=axis) - np.nanmin(x, axis=axis),
    "last": _last_valid,
    "current": _last_valid,
    "multiply": np.nanprod,
    "diff": _diff_reduction,
}

# aggregate() dispatches to the SAME registered series combiners the
# named forms use, so aggregate(x, "diff") == diffSeries(x) exactly
# (ref: aggregation_functions.go:279 — the reference delegates too)
_AGG_DELEGATES = {
    "sum": "sumSeries", "total": "sumSeries", "": "sumSeries",
    "min": "minSeries", "max": "maxSeries", "median": "medianSeries",
    "avg": "averageSeries", "average": "averageSeries",
    "multiply": "multiplySeries", "diff": "diffSeries",
    "count": "countSeries", "range": "rangeOfSeries",
    "rangeOf": "rangeOfSeries", "stddev": "stddevSeries",
}


@register("aggregate")
def _aggregate(eng, st, step, sl, func):
    """Generic form dispatching on the aggregation name
    (ref: aggregation_functions.go:279 aggregate)."""
    target = _AGG_DELEGATES.get(func)
    if target is not None:
        return FUNCTIONS[target](eng, st, step, sl)
    red = _AGG_REDUCTIONS.get(func)
    if red is None:
        raise ValueError(f"aggregate: unknown function {func!r}")
    return _combine(sl, f'aggregate({",".join(sl.names)},"{func}")', red)


@register("aggregateLine")
def _aggregate_line(eng, st, step, sl, func="average"):
    """Horizontal line at each series' aggregate value
    (ref: builtin_functions.go:1976)."""
    red = _AGG_REDUCTIONS.get(func)
    if red is None:
        raise ValueError(f"aggregateLine: unknown function {func!r}")
    with np.errstate(all="ignore"):
        stat = red(sl.values, axis=1)
    vals = np.repeat(np.asarray(stat, dtype=np.float64)[:, None],
                     sl.values.shape[1], axis=1)
    names = [f"aggregateLine({n},{s:g})" for n, s in zip(sl.names, stat)]
    return sl.clone(names, vals)


@register("aggregateWithWildcards")
def _aggregate_with_wildcards(eng, st, step, sl, func, *positions):
    """Group series by their name with the given node positions removed,
    aggregating each group (ref: aggregation_functions.go:335)."""
    red = _AGG_REDUCTIONS.get(func)
    if red is None:
        raise ValueError(f"aggregateWithWildcards: unknown {func!r}")
    drop = {int(p) for p in positions}
    groups: dict[str, list[int]] = {}
    for i, n in enumerate(sl.names):
        parts = n.split(".")
        key = ".".join(p for j, p in enumerate(parts)
                       if j not in drop and j - len(parts) not in drop)
        groups.setdefault(key, []).append(i)
    names, rows = [], []
    for key in sorted(groups):
        names.append(key)
        with np.errstate(all="ignore"):
            rows.append(red(sl.values[groups[key]], axis=0))
    return sl.clone(names, np.array(rows) if rows else
                    np.zeros((0, sl.values.shape[1])))


@register("applyByNode")
def _apply_by_node(eng, st, step, sl, node, template, new_name=None):
    """For each distinct prefix of the first node+1 name components,
    evaluate the template with '%' replaced by the prefix
    (ref: aggregation_functions.go:473)."""
    prefixes = sorted({
        ".".join(n.split(".")[: int(node) + 1])
        for n in sl.names
        if len(n.split(".")) > int(node)
    })
    names, rows = [], []
    for prefix in prefixes:
        out = eng._eval(parse(template.replace("%", prefix)), st, step)
        for n, row in zip(out.names, out.values):
            names.append(new_name.replace("%", prefix) if new_name else n)
            rows.append(row)
    return sl.clone(names, np.array(rows) if rows else
                    np.zeros((0, sl.values.shape[1])))


@register("cactiStyle")
def _cacti_style(eng, st, step, sl):
    """Append Current/Max/Min readouts to legends (display parity)."""
    cur = _series_stat(sl, "current")
    with np.errstate(all="ignore"):
        mx = np.nanmax(sl.values, axis=1)
        mn = np.nanmin(sl.values, axis=1)
    names = [
        f"{n} Current:{c:g} Max:{h:g} Min:{l:g}"
        for n, c, h, l in zip(sl.names, cur, mx, mn)
    ]
    return sl.clone(names)


@register("cumulative")
def _cumulative(eng, st, step, sl):
    """Alias for consolidateBy(series, 'sum') (ref:
    builtin_functions.go cumulative); values pass through because this
    engine consolidates on a fixed step grid at fetch time."""
    return sl.clone([f'consolidateBy({n},"sum")' for n in sl.names])


@register("dashed")
def _dashed(eng, st, step, sl, dash_length=5.0):
    """Display option only — values unchanged (parity with the
    reference, which just sets a render flag)."""
    return sl.clone([f"dashed({n},{float(dash_length):g})"
                     for n in sl.names])


def _holt_winters_fit(row: np.ndarray, step: int):
    """Graphite-style triple exponential smoothing (additive, season =
    1 day when the window allows, else the largest fitting cycle).
    Returns (forecast, deviation) arrays the length of the row."""
    s = len(row)
    season = max(2, min(int(86400 * 1e9 // step), s // 2)) if s >= 4 else 0
    alpha, beta, gamma = 0.1, 0.0035, 0.1
    forecast = np.full(s, np.nan)
    deviation = np.zeros(s)
    if s < 2:
        return forecast, deviation
    level = row[0] if not np.isnan(row[0]) else 0.0
    trend = 0.0
    dev = 0.0  # running EWMA — NaN gaps must carry it, not reset it
    seasonal = np.zeros(max(season, 1))
    for i in range(s):
        v = row[i]
        si = i % season if season else 0
        pred = level + trend + (seasonal[si] if season else 0.0)
        forecast[i] = pred
        if np.isnan(v):
            deviation[i] = dev
            continue
        err = v - pred
        last_level = level
        level = alpha * (v - (seasonal[si] if season else 0.0)) + (
            1 - alpha) * (level + trend)
        trend = beta * (level - last_level) + (1 - beta) * trend
        if season:
            seasonal[si] = gamma * (v - level) + (1 - gamma) * seasonal[si]
        dev = gamma * abs(err) + (1 - gamma) * dev
        deviation[i] = dev
    return forecast, deviation


@register("holtWintersForecast")
def _hw_forecast(eng, st, step, sl):
    out = np.full_like(sl.values, np.nan)
    for i, row in enumerate(sl.values):
        out[i], _ = _holt_winters_fit(row, step)
    return sl.clone([f"holtWintersForecast({n})" for n in sl.names], out)


@register("holtWintersConfidenceBands")
def _hw_bands(eng, st, step, sl, delta=3.0):
    names, rows = [], []
    for n, row in zip(sl.names, sl.values):
        f, d = _holt_winters_fit(row, step)
        names.append(f"holtWintersConfidenceUpper({n})")
        rows.append(f + float(delta) * d)
        names.append(f"holtWintersConfidenceLower({n})")
        rows.append(f - float(delta) * d)
    return sl.clone(names, np.array(rows) if rows else
                    np.zeros((0, sl.values.shape[1])))


@register("holtWintersAberration")
def _hw_aberration(eng, st, step, sl, delta=3.0):
    """Positive where the series exceeds the upper band, negative below
    the lower band, zero inside."""
    out = np.zeros_like(sl.values)
    for i, row in enumerate(sl.values):
        f, d = _holt_winters_fit(row, step)
        upper, lower = f + float(delta) * d, f - float(delta) * d
        with np.errstate(invalid="ignore"):
            out[i] = np.where(row > upper, row - upper,
                              np.where(row < lower, row - lower, 0.0))
        out[i] = np.where(np.isnan(row), 0.0, out[i])
    return sl.clone([f"holtWintersAberration({n})" for n in sl.names], out)


@register("identity")
def _identity(eng, st, step, sl_or_name="identity"):
    """Series whose value at each step is the step's unix timestamp
    (ref: builtin_functions.go identity)."""
    name = sl_or_name if isinstance(sl_or_name, str) else "identity"
    vals = (np.asarray(st, dtype=np.float64) / 1e9)[None, :]
    return SeriesList([f'identity("{name}")'], vals, step,
                      np.asarray(st, dtype=np.int64))


@register("integralByInterval")
def _integral_by_interval(eng, st, step, sl, interval):
    """Running sum that resets at each interval boundary
    (ref: builtin_functions.go:1301)."""
    from m3_tpu.metrics.policy import parse_duration

    k = max(1, int(parse_duration(interval) // step))
    v = np.nan_to_num(sl.values, nan=0.0)
    out = np.zeros_like(v)
    for start in range(0, v.shape[1], k):
        seg = v[:, start:start + k]
        out[:, start:start + k] = np.cumsum(seg, axis=1)
    return sl.clone(
        [f'integralByInterval({n},"{interval}")' for n in sl.names], out)


@register("legendValue")
def _legend_value(eng, st, step, sl, *value_types):
    """Append aggregate readouts to legends, e.g.
    legendValue(series, "last", "avg")."""
    names = list(sl.names)
    for vt in value_types:
        red = _AGG_REDUCTIONS.get(vt)
        if red is None:
            names = [f"{n} ({vt}: ?)" for n in names]
            continue
        with np.errstate(all="ignore"):
            stat = red(sl.values, axis=1)
        names = [f"{n} ({vt}: {s:g})" for n, s in zip(names, stat)]
    return sl.clone(names)


@register("randomWalkFunction", "randomWalk")
def _random_walk(eng, st, step, sl_or_name="randomWalk"):
    """Synthetic random-walk series (deterministic per name, so renders
    are reproducible)."""
    import zlib

    name = sl_or_name if isinstance(sl_or_name, str) else "randomWalk"
    # crc32, not hash(): str hashing is salted per process and would
    # break the documented per-name determinism
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    steps = rng.uniform(-0.5, 0.5, size=len(st))
    vals = np.cumsum(steps)[None, :]
    return SeriesList([f'randomWalk("{name}")'], vals, step,
                      np.asarray(st, dtype=np.int64))


@register("removeEmptySeries")
def _remove_empty_series(eng, st, step, sl, x_files_factor=0.0):
    """Drop series with no data (or below the xFilesFactor fraction of
    present points) — ref: builtin_functions.go:637."""
    frac = (~np.isnan(sl.values)).mean(axis=1) if len(sl.names) else []
    keep = [i for i, f in enumerate(frac)
            if f > 0 and f >= float(x_files_factor)]
    return _select(sl, keep)


@register("smartSummarize")
def _smart_summarize(eng, st, step, sl, interval, func="sum"):
    """summarize() with buckets aligned to the query start — which is
    exactly how this engine's fixed step grid buckets already align
    (ref: summarize.go:160); reuses the summarize kernel."""
    out = _summarize(eng, st, step, sl, interval, func)
    return out.clone([n.replace("summarize(", "smartSummarize(", 1)
                      for n in out.names])


def _sustained(above: bool):
    def fn(eng, st, step, sl, threshold, interval):
        """Values must hold the comparison for >= interval consecutive
        steps; shorter runs flatten to threshold -/+ |threshold|
        (ref: builtin_functions.go:567 sustainedCompare)."""
        from m3_tpu.metrics.policy import parse_duration

        thr = float(threshold)
        min_steps = max(1, int(parse_duration(interval) // step))
        zero = thr - abs(thr) if above else thr + abs(thr)
        out = np.full_like(sl.values, zero)
        for i, row in enumerate(sl.values):
            run = 0
            for j, v in enumerate(row):
                hit = (not np.isnan(v)) and (v >= thr if above else v <= thr)
                run = run + 1 if hit else 0
                if run >= min_steps:
                    out[i, j] = v
        name = "sustainedAbove" if above else "sustainedBelow"
        return sl.clone(
            [f'{name}({n},{thr:g},"{interval}")' for n in sl.names], out)
    return fn


FUNCTIONS["sustainedAbove"] = _sustained(True)
FUNCTIONS["sustainedBelow"] = _sustained(False)


@register("useSeriesAbove")
def _use_series_above(eng, st, step, sl, value, search, replace):
    """For each series whose max exceeds value, fetch the series named
    by search->replace substitution (ref: builtin_functions.go:108)."""
    with np.errstate(all="ignore"):
        mx = np.nanmax(sl.values, axis=1) if len(sl.names) else []
    names, rows = [], []
    for i, n in enumerate(sl.names):
        if np.isnan(mx[i]) or mx[i] <= float(value):
            continue
        fetched = eng.fetch(n.replace(search, replace), st, step)
        for fn_name, row in zip(fetched.names, fetched.values):
            names.append(fn_name)
            rows.append(row)
    return sl.clone(names, np.array(rows) if rows else
                    np.zeros((0, sl.values.shape[1])))
