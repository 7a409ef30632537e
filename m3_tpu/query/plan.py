"""Device query-plan compiler: lower a parsed PromQL AST into ONE
fused jitted program (models/query_pipeline.device_expr_pipeline).

The per-node device tier (engine._device_temporal / _device_grouped)
already fuses decode -> consolidate -> one temporal fn (-> one grouped
reduction), but a real dashboard query like

    sum by (job) (rate(http_requests[5m]))
      / on(job) sum by (job) (rate(http_limit[5m]))

still evaluates node-by-node in Python: every subtree result crosses
the device->host boundary and the binary op runs in numpy.  This
module walks the whole op-tree instead and emits a single compiled
program — packed compressed batches (or DecodedBlockCache-warm arrays
that skip on-device decode entirely) in, the root [rows, steps]
matrix out.  One host transfer per query.

Division of labor:

  host (this module, per query, microseconds):
    - symbolic extraction + support check (`_extract`)
    - the gather/pack front half (engine._device_gather_pack, with
      power-of-two shape bucketing so a varying-cardinality sweep
      lands in a handful of compiled programs)
    - ALL label-plane computation: group keys, vector-match row
      pairing, histogram `le` bucket layout, label_replace/label_join
      transforms, output label sets — labels never touch the device;
      vector matching compiles down to two row-gather index arrays
      and histogram grouping to one [groups, buckets] gather map
  device (device_expr_pipeline, one jit call):
    - decode, merge, multi-tier stitch cut, step consolidation,
      the full temporal/aggregation/binop/scalar-fn tree, plus the
      PR 11 lowerings: masked top/bottom-k lane selection, batched
      histogram-quantile interpolation, absent presence folds, and
      subqueries as a nested consolidation stage

Compile cache: the static `plan` tuple IS the canonical fingerprint —
op-tree shape, every shape bucket (lanes/steps/n_dp/n_cap/words), and
n_tiers are spelled into it, so jax's jit cache gives exact program
reuse and `_note_fingerprint` mirrors it for the
m3_query_compile_cache_{hits,misses}_total counters.  Under a serving
mesh the fingerprint (and the jit static set) additionally carries the
mesh, so single-chip and sharded programs never collide.  Recompile
wall time comes from the kernel-telemetry wrapper around the pipeline
(m3_kernel_compile_seconds{kernel="device_expr_pipeline[_sharded]"}).

Fallback matrix (docs/query_device.md): any unsupported construct
raises Unsupported during extraction — the engine then evaluates that
node on the host and retries fusion on each child subtree, so a query
splits at the deepest unsupported node and device-serves everything
underneath; every split increments
m3_query_host_split_total{reason} with the bounded reason slug
carried by the Unsupported instance.  Device-lowered here:
subqueries (nested consolidation), topk/bottomk (masked lane sort,
root position), histogram_quantile (batched bucket interpolation),
sort/sort_desc (host reorder of the device root), absent /
absent_over_time, quantile_over_time (HBM-gated),
label_replace/label_join (host label-plane transforms).  Still
declined: set ops (and/or/unless), calendar fns, count_values,
non-literal scalar arguments, nested topk/bottomk/sort,
subquery-argument quantile_over_time / absent_over_time, oversized
subquery grids, window grids over the QOT HBM budget, and selectors
with mutable or mixed payloads the packer can't take.  Host results
stay bit-for-bit identical to before: the fused path either serves
the whole subtree or leaves it untouched.
"""

from __future__ import annotations

import math
import re
import threading

import numpy as np

from m3_tpu.cache import stats as cache_stats
from m3_tpu.ops import consolidate as cons
from m3_tpu.query import cost as qcost
from m3_tpu.query import promql
from m3_tpu.query.matrix import (DEFAULT_SUBQUERY_STEP, Matrix, expand_go,
                                 signature)
from m3_tpu.utils import instrument


class Unsupported(Exception):
    """Subtree has no fused device form: the engine splits here and
    serves this node on the host tier (children retry fusion).
    `reason` is a bounded slug for the
    m3_query_host_split_total{reason} counter family."""

    def __init__(self, msg, reason: str = "unknown_node"):
        super().__init__(msg)
        self.reason = reason


# leaf temporal family with a device form (mirrors
# engine._DEVICE_TEMPORAL; quantile_over_time stays on its own
# HBM-gated path)
TEMPORAL_OK = frozenset(
    ("rate", "increase", "delta", "sum_over_time", "avg_over_time",
     "count_over_time", "present_over_time", "last_over_time",
     "irate", "idelta", "min_over_time", "max_over_time",
     "changes", "resets", "deriv", "predict_linear",
     "stddev_over_time", "stdvar_over_time", "holt_winters"))
AGG_OK = frozenset(("sum", "avg", "min", "max", "count", "group",
                    "stddev", "stdvar", "quantile"))
SCALARFN_OK = frozenset(("abs", "ceil", "floor", "exp", "sqrt", "sgn",
                         "ln", "log2", "log10", "round", "clamp",
                         "clamp_min", "clamp_max", "timestamp"))
ARITH_OPS = frozenset(("+", "-", "*", "/", "%", "^"))
CMP_OPS = frozenset(("==", "!=", ">", "<", ">=", "<="))

# device-served functions whose XLA lowering is ulp-level (not
# bit-level) equal to the host numpy forms on some backends — the
# differential suites key their tolerance on the stats fn/agg fields
LOOSE_FNS = ("deriv", "predict_linear", "stddev_over_time",
             "stdvar_over_time", "holt_winters", "quantile_over_time")
LOOSE_AGGS = ("stddev", "stdvar", "quantile")

# inner subquery grids above this bail to the host: the nested
# consolidation stage materializes [lanes, sub_steps] twice and a
# runaway 1ms-step subquery must not OOM the fused program
_SUBQ_MAX_STEPS = 4096

# fingerprint memo behind m3_query_compile_cache_{hits,misses}_total.
# Bounded: on overflow the epoch resets (counters stay monotonic, a
# handful of "misses" re-count — the jit cache itself is unaffected).
_FP_CAP = 4096
_FP_LOCK = threading.Lock()
_FP_SEEN: set = set()  # allow-unbounded-cache: epoch-reset at _FP_CAP


def _note_fingerprint(plan, bucket: str = "") -> bool:
    """Record a plan fingerprint; True = compile-cache hit (an equal
    plan already compiled this process)."""
    with _FP_LOCK:
        hit = plan in _FP_SEEN
        if hit:
            instrument.counter(
                "m3_query_compile_cache_hits_total").inc()
        else:
            if len(_FP_SEEN) >= _FP_CAP:
                _FP_SEEN.clear()
            _FP_SEEN.add(plan)
            instrument.counter(
                "m3_query_compile_cache_misses_total").inc()
    # device-ledger inventory: /debug/device lists plan fingerprints
    # (hashed — the raw plan tuple is unbounded text) with shape
    # bucket, hit counts, and last-use for manual eviction
    from m3_tpu import observe
    led = observe.device_ledger()
    led.compile_cache_register_evictor("query_plan", _evict_plan_cache)
    led.compile_cache_note(
        "query_plan", f"{hash(plan) & 0xFFFFFFFFFFFFFFFF:016x}",
        bucket=bucket, hit=hit)
    return hit


def _evict_plan_cache() -> int:
    """Registered /debug/device evictor: drops the fingerprint memo
    and the fused pipeline's jitted programs."""
    with _FP_LOCK:
        n = len(_FP_SEEN)
        _FP_SEEN.clear()
    try:
        from m3_tpu.models import query_pipeline as qp
        for fn_name in ("device_expr_pipeline",
                        "device_expr_pipeline_sharded",
                        "device_expr_pipeline_batched"):
            fn = getattr(qp, fn_name, None)
            if fn is not None and hasattr(fn, "clear_cache"):
                fn.clear_cache()
    except Exception:  # noqa: BLE001 — eviction is best-effort
        pass
    return n


def _DEVLED():
    from m3_tpu import observe
    return observe.device_ledger()


def _bucket_pow2(n: int, floor: int) -> int:
    """Power-of-two shape quantizer for the fused path: a 20-query
    cardinality sweep spans few pow2 buckets, so the whole sweep
    reuses a handful of compiled programs (the engine's linear
    _bucket would mint a program per 64-lane increment)."""
    n = max(int(n), int(floor))
    return 1 << (n - 1).bit_length()


def _scalar_lit(node):
    """Fold a literal scalar expression to a float (unary minus parses
    as 0-x, so constant arithmetic must fold too); None = not a
    literal."""
    if isinstance(node, promql.Scalar):
        return float(node.value)
    if isinstance(node, promql.BinOp) and node.op in ARITH_OPS:
        left = _scalar_lit(node.lhs)
        right = _scalar_lit(node.rhs)
        if left is not None and right is not None:
            # host scalar-scalar semantics (engine _ARITH)
            if node.op == "%":
                return math.fmod(left, right) if right else float("nan")
            with np.errstate(invalid="ignore", divide="ignore"):
                return float({"+": np.add, "-": np.subtract,
                              "*": np.multiply, "/": np.divide,
                              "^": np.power}[node.op](left, right))
    return None


def _lit(node) -> float:
    v = _scalar_lit(node)
    if v is None:
        raise Unsupported("non-literal scalar argument",
                          reason="non_literal_scalar")
    return v


def _extract(node, counts, root: bool = False):
    """Lower the AST into a light symbolic tree, raising Unsupported
    at the first node with no fused form.  counts tallies op nodes
    (agg/binop/scalar-fn — leaves don't count) plus the fn/agg names
    for the stats tolerance keying; counts["new"] marks node kinds
    with no per-node device tier at all (topk, histogram_quantile,
    absent, sort, label fns, subqueries), which bypass the >=2-ops
    engagement gate.  `root` is True only for the query root: topk /
    bottomk / sort are position-dependent (row ordering), so below
    the root they decline and the engine's natural splitting re-tries
    them as the root of their own fused subtree."""
    if isinstance(node, promql.Selector):
        if node.range_nanos:
            raise Unsupported("range selector outside a temporal fn",
                              reason="range_selector")
        # instant-vector consolidation = last_over_time over the
        # engine lookback, keeping __name__ (host _fetch_consolidated)
        return ("leaf", node, "last_over_time", None, True, 0.0,
                0.5, 0.5, 0.5)
    if isinstance(node, promql.Call):
        fn = node.fn
        if fn in TEMPORAL_OK:
            horizon, hw_sf, hw_tf = 0.0, 0.5, 0.5
            if fn == "predict_linear":
                horizon = _lit(node.args[1])
            elif fn == "holt_winters":
                hw_sf, hw_tf = _lit(node.args[1]), _lit(node.args[2])
                if not (0.0 < hw_sf < 1.0 and 0.0 < hw_tf < 1.0):
                    raise Unsupported("holt_winters factors out of "
                                      "range", reason="hw_factors")
            if node.args and isinstance(node.args[0], promql.Subquery):
                # nested consolidation: the inner expr evaluates on
                # the subquery grid, the outer fn windows over it
                counts["ops"] += 1
                counts["fns"].append(fn)
                counts["new"] = True
                child = _extract(node.args[0].expr, counts)
                return ("subq", node.args[0], fn, horizon, hw_sf,
                        hw_tf, child)
            if not (node.args
                    and isinstance(node.args[0], promql.Selector)
                    and node.args[0].range_nanos):
                raise Unsupported(f"{fn}() without a plain range "
                                  "selector", reason="range_selector")
            counts["fns"].append(fn)
            return ("leaf", node.args[0], fn, None, False, horizon,
                    hw_sf, hw_tf, 0.5)
        if fn == "quantile_over_time":
            phi = _lit(node.args[0])
            if not 0.0 <= phi <= 1.0:  # NaN fails too
                raise Unsupported("out-of-range quantile_over_time "
                                  "phi (host serves the +/-Inf form)",
                                  reason="quantile_phi")
            arg = node.args[1]
            if not (isinstance(arg, promql.Selector)
                    and arg.range_nanos):
                raise Unsupported("quantile_over_time needs a plain "
                                  "range selector",
                                  reason="temporal_arg")
            counts["fns"].append(fn)
            return ("leaf", arg, fn, None, False, 0.0, 0.5, 0.5, phi)
        if fn in SCALARFN_OK:
            extras = ()
            if fn == "round":
                to = _lit(node.args[1]) if len(node.args) > 1 else 1.0
                extras = (1.0 / to,)
            elif fn in ("clamp_min", "clamp_max"):
                extras = (_lit(node.args[1]),)
            elif fn == "clamp":
                extras = (_lit(node.args[1]), _lit(node.args[2]))
            counts["ops"] += 1
            child = _extract(node.args[0], counts)
            return ("call", fn, extras, child)
        if fn == "absent":
            counts["ops"] += 1
            counts["new"] = True
            child = _extract(node.args[0], counts)
            return ("absent", child)
        if fn == "absent_over_time":
            arg = node.args[0]
            if not (isinstance(arg, promql.Selector)
                    and arg.range_nanos):
                raise Unsupported("absent_over_time needs a plain "
                                  "range selector",
                                  reason="temporal_arg")
            # presence fold over a present_over_time leaf: 1.0 where
            # the window saw a sample, NaN otherwise, then the absent
            # node ORs lanes — the host's (right > left).any(0)
            counts["ops"] += 1
            counts["fns"].append("present_over_time")
            counts["new"] = True
            leaf = ("leaf", arg, "present_over_time", None, False,
                    0.0, 0.5, 0.5, 0.5)
            return ("absent", leaf)
        if fn in ("sort", "sort_desc"):
            if not root:
                raise Unsupported(f"{fn}() below the root reorders "
                                  "nothing", reason="sort_nested")
            counts["ops"] += 1
            counts["new"] = True
            child = _extract(node.args[0], counts)
            return ("sortv", fn == "sort_desc", child)
        if fn in ("label_replace", "label_join"):
            counts["ops"] += 1
            counts["new"] = True
            child = _extract(node.args[0], counts)
            return ("labelfn", node, child)
        if fn == "histogram_quantile":
            phi = _lit(node.args[0])  # kernel handles out-of-range
            counts["ops"] += 1
            counts["new"] = True
            child = _extract(node.args[1], counts)
            return ("hq", phi, child)
        raise Unsupported(f"no fused form for {fn}()",
                          reason="unsupported_fn")
    if isinstance(node, promql.Agg):
        if node.op in ("topk", "bottomk"):
            if not root:
                raise Unsupported(f"{node.op}() below the root (row "
                                  "ordering is root-positional)",
                                  reason="topk_nested")
            k = int(_lit(node.param))
            if k < 1:
                raise Unsupported(f"{node.op} k < 1 selects nothing",
                                  reason="topk_k")
            counts["ops"] += 1
            counts["aggs"].append(node.op)
            counts["new"] = True
            child = _extract(node.expr, counts)
            return ("topkk", node, k, child)
        if node.op not in AGG_OK:
            raise Unsupported(f"no fused form for {node.op}()",
                              reason="unsupported_agg")
        phi = 0.5
        if node.op == "quantile":
            phi = _lit(node.param)
            if not 0.0 <= phi <= 1.0:  # NaN fails too
                raise Unsupported("out-of-range quantile phi (host "
                                  "serves the +/-Inf form)",
                                  reason="quantile_phi")
        counts["ops"] += 1
        counts["aggs"].append(node.op)
        child = _extract(node.expr, counts)
        return ("agg", node, phi, child)
    if isinstance(node, promql.BinOp):
        if node.op in promql.SET_OPS:
            raise Unsupported("set operators are label-data-dependent",
                              reason="set_op")
        left_s, right_s = _scalar_lit(node.lhs), _scalar_lit(node.rhs)
        if left_s is not None and right_s is not None:
            raise Unsupported("scalar-scalar is host-trivial",
                              reason="scalar_scalar")
        counts["ops"] += 1
        if left_s is None and right_s is None:
            lhs = _extract(node.lhs, counts)
            rhs = _extract(node.rhs, counts)
            return ("vv", node, lhs, rhs)
        if right_s is not None:
            child = _extract(node.lhs, counts)
            return ("vs", node, True, right_s, child)
        child = _extract(node.rhs, counts)
        return ("vs", node, False, left_s, child)
    raise Unsupported(f"no fused form for {type(node).__name__}",
                      reason="unknown_node")


def _drop_name(labels):
    return [{k: v for k, v in ls.items() if k != b"__name__"}
            for ls in labels]


def _match_vv(node, lhs_labels, rhs_labels):
    """Host-side mirror of engine._vector_vector's matching: the same
    iteration order, js[0] pick, and output label rules, but emitting
    (out_labels, lhs_row, rhs_row) gather indices instead of values —
    the device applies the op to the gathered rows."""
    m = node.matching
    is_cmp = node.op in CMP_OPS
    group = m.group if m else ""
    swap = group == "right"
    many_labels, one_labels = ((rhs_labels, lhs_labels) if swap
                               else (lhs_labels, rhs_labels))
    one_by_sig: dict = {}
    for j, ls in enumerate(one_labels):
        one_by_sig.setdefault(signature(ls, m), []).append(j)
    include = {l.encode() for l in (m.include if m else ())}
    out_labels, lhs_rows, rhs_rows = [], [], []
    for i, ls in enumerate(many_labels):
        js = one_by_sig.get(signature(ls, m))
        if not js:
            continue
        j = js[0]
        if group:
            out_ls = dict(ls)
            if not (is_cmp and not node.bool_mod):
                out_ls.pop(b"__name__", None)
            for inc in include:
                if inc in one_labels[j]:
                    out_ls[inc] = one_labels[j][inc]
                else:
                    out_ls.pop(inc, None)
        elif is_cmp and not node.bool_mod:
            out_ls = dict(ls)
        else:
            out_ls = dict(signature(ls, m))
        out_labels.append(out_ls)
        li, ri = (j, i) if swap else (i, j)
        lhs_rows.append(li)
        rhs_rows.append(ri)
    return out_labels, lhs_rows, rhs_rows


def _apply_label_fn(node, labels):
    """Host-side mirror of engine._eval_label_fn on the label plane
    only: label_replace / label_join compile to a pure label
    transform over the child's output rows (values pass through the
    device program untouched — the fused form never moves labels)."""
    def s(i):
        a = node.args[i]
        if not isinstance(a, promql.StringLit):
            raise Unsupported(f"{node.fn}() argument {i} must be a "
                              "string literal", reason="label_fn_args")
        return a.value

    if node.fn == "label_replace":
        dst, repl, src, regex = s(1), s(2), s(3), s(4)
        rx = re.compile(regex)
        out = []
        for ls in labels:
            val = ls.get(src.encode(), b"").decode("utf-8", "replace")
            m = rx.fullmatch(val)
            new = dict(ls)
            if m is not None:
                expanded = expand_go(m, repl)
                if expanded:
                    new[dst.encode()] = expanded.encode()
                else:
                    new.pop(dst.encode(), None)
            out.append(new)
        return out
    # label_join(v, dst, sep, src...)
    dst, sep = s(1), s(2)
    srcs = [s(i) for i in range(3, len(node.args))]
    out = []
    for ls in labels:
        joined = sep.join(
            ls.get(n.encode(), b"").decode("utf-8", "replace")
            for n in srcs)
        new = dict(ls)
        if joined:
            new[dst.encode()] = joined.encode()
        else:
            new.pop(dst.encode(), None)
        out.append(new)
    return out


def _arrays_leaf(engine, sel, step_times, rng):
    """DecodedBlockCache -> device bridge: when every payload for a
    selector arrives as decoded (times, values) arrays — cache-warm
    blocks or open mutable buffers — feed padded device-ready grids to
    the fused pipeline, skipping on-device M3TSZ decode entirely
    (zero ops/decode_counter.py bumps: this path never touches a
    compressed stream).  Returns None when any payload is compressed
    (the words path handles the all-compressed case; mixed declines
    to the host tier)."""
    shifted = engine._eval_times(sel, step_times)
    lo, hi = int(shifted[0]) - rng, int(shifted[-1])
    labels, parts, compressed = engine._gather_cached(
        sel.matchers, lo, hi)
    if compressed or not parts or not labels:
        return None
    # stitch + merge + pad memoized on the gather entry: a batched
    # fleet adopting the cross-query fetch memo assembles the
    # device-ready grid once, not once per member
    grid = engine._arrays_grid_cached(sel.matchers, lo, hi, labels,
                                      parts)
    return {"labels": labels, "shifted": shifted, "rng": rng, **grid}


def _leaf_specs(sym, out):
    """Collect the distinct leaf symbols of a symbolic tree, keyed so
    identical selectors+ranges share one gather/pack/transfer."""
    tag = sym[0]
    if tag == "leaf":
        _, sel, fn, rng_override, _keep, _h, _sf, _tf, _phi = sym
        key = (tuple(sel.matchers), sel.range_nanos, sel.offset_nanos,
               repr(sel.at_nanos), rng_override)
        out.setdefault(key, sym)
    elif tag in ("call", "agg", "topkk"):
        _leaf_specs(sym[3], out)
    elif tag == "vs":
        _leaf_specs(sym[4], out)
    elif tag == "vv":
        _leaf_specs(sym[2], out)
        _leaf_specs(sym[3], out)
    elif tag in ("hq", "sortv", "labelfn"):
        _leaf_specs(sym[2], out)
    elif tag == "absent":
        _leaf_specs(sym[1], out)
    elif tag in ("subq", "gsel", "gname", "gagg", "gcall"):
        _leaf_specs(sym[-1], out)
    return out


def serve_fused(engine, node, step_times):
    """Try to serve `node` with the fused whole-query device pipeline.
    Returns a Matrix, or None to decline (the engine's per-node paths
    — device or host — then serve exactly as before)."""
    counts = {"ops": 0, "fns": [], "aggs": [], "new": False}
    sym = _extract(node, counts, root=True)  # Unsupported -> split

    # engagement gate: a single op node is what the per-node device
    # tier already serves transfer-optimally (and the tier-1 suite
    # pins its stats fields); fuse when the tree composes >= 2 ops,
    # when a node kind has no per-node form at all (counts["new"]), or
    # when a leaf can ride the DecodedBlockCache arrays bridge (warm
    # arrays have no per-node device form either)
    step_times = np.asarray(step_times, dtype=np.int64)
    if counts["ops"] < 2 and not counts["new"]:
        any_arrays = False
        for key, leaf_sym in _leaf_specs(sym, {}).items():
            _, sel, _fn, rng_override, _k, _h, _sf, _tf, _phi = \
                leaf_sym
            rng = (sel.range_nanos if rng_override is None
                   else rng_override) or engine.lookback
            shifted = engine._eval_times(sel, step_times)
            labels, parts, compressed = engine._gather_cached(
                sel.matchers, int(shifted[0]) - rng, int(shifted[-1]))
            if parts and not compressed and labels:
                any_arrays = True
                break
        if not any_arrays:
            return None

    return run_sym(engine, sym, step_times, counts,
                   promql.ast_size(node))


def run_sym(engine, sym, step_times, counts, ast_nodes):
    """Compile a symbolic tree into one fused device program and run
    it.  Shared backend of the PromQL extractor above and the Graphite
    lowerer (query/graphite_device.py): builds the leaf plan, traces
    params, dispatches the jitted pipeline, and fixes up the root on
    host.  Returns a Matrix; raises Unsupported to decline; returns
    None on a device runtime error (callers fall back to host)."""
    from m3_tpu.models import query_pipeline as qp
    from m3_tpu.ops import kernel_telemetry

    step_times = np.asarray(step_times, dtype=np.int64)
    n_shards = engine._serving_shards()
    leaves = []        # traced per-leaf pytrees, by leaf index
    leaf_plan = {}     # dedupe key -> (idx, kind, statics, pk)
    params = []        # traced per-node pytrees, by param index
    root_post = []     # host post-ops on the root matrix (sort/...)
    # for the query's record
    shape = {"groups": 0, "topk_k": 0, "hq_groups": 0, "hq_buckets": 0}
    rate_leaves = []   # the packed leaves a rate-family function reads
    cost = engine._cost()
    s_pad = _bucket_pow2(len(step_times), 64)
    # the plan phase: the build below (leaf plan, group keys, params,
    # the plan tuple) and the root's host reorder, without the gathers
    # and packs inside it, which stamp fetch and pack themselves
    planning = cost.phase("plan")

    def outside_plan(fn, *args, **kwargs):
        planning.stop()
        try:
            return fn(*args, **kwargs)
        finally:
            planning.start()

    def build_leaf(sym_leaf, grid):
        (_, sel, fn, rng_override, keep_name, horizon, hw_sf, hw_tf,
         phi) = sym_leaf
        rng = (sel.range_nanos if rng_override is None
               else rng_override)
        if fn == "last_over_time" and rng_override is None \
                and not sel.range_nanos:
            rng = engine.lookback
        key = (tuple(sel.matchers), sel.range_nanos, sel.offset_nanos,
               repr(sel.at_nanos), rng, grid.tobytes())
        cached = leaf_plan.get(key)
        if cached is None:
            sp = _bucket_pow2(len(grid), 64)
            pk, _why = outside_plan(
                engine._device_gather_pack, sel, grid, rng,
                bucket=_bucket_pow2)
            if pk is not None and pk["open"] is not None:
                if pk["n_streams"]:
                    # the fused program takes words or arrays, not
                    # both: the per-node tier serves sealed and open
                    # rows together
                    raise Unsupported("open rows beside sealed streams",
                                      reason="open_rows")
                pk = None   # arrays alone: the bridge below
            if pk is not None:
                kind = "words"
                # miss = packed compressed words shipped for on-device
                # decode; byte-weight the scoreboard for attribution
                cache_stats.note("device_bridge", False, nbytes=getattr(
                    pk.get("words"), "nbytes", 0))
                _DEVLED().track("decoded_block_bridge", [
                    v for v in pk.values() if hasattr(v, "nbytes")])
            else:
                pk = outside_plan(_arrays_leaf, engine, sel, grid, rng)
                if pk is None:
                    raise Unsupported("mixed or unknown payloads",
                                      reason="mixed_payloads")
                kind = "arrays"
                # hit = decoded-cache-warm arrays fed the fused program
                cache_stats.note("device_bridge", True, nbytes=sum(
                    getattr(v, "nbytes", 0) for v in pk.values()
                    if v is not None))
                _DEVLED().track("decoded_block_bridge", [
                    v for v in pk.values() if hasattr(v, "nbytes")])
            if n_shards > 1:
                if kind == "words":
                    # equal lanes + stream rows per shard, LOCAL slots
                    pk = engine._shard_repack(pk, n_shards)
                else:
                    local = engine._bucket(
                        -(-pk["lanes_pad"] // n_shards), 8)
                    new_pad = local * n_shards
                    if new_pad != pk["lanes_pad"]:
                        t_p, v_p = cons.pad_grid(
                            pk["times"], pk["values"], new_pad,
                            pk["n_cap"])
                        pk = {**pk, "times": t_p, "values": v_p,
                              "lanes_pad": new_pad}
            idx = len(leaves)
            lanes_pad, n_lanes = pk["lanes_pad"], pk["n_lanes"]
            valid = np.arange(lanes_pad) < n_lanes
            steps_p = np.full(sp, pk["shifted"][-1],
                              dtype=np.int64)
            steps_p[:len(pk["shifted"])] = pk["shifted"]
            if kind == "words":
                tiers = pk["tiers"]
                if tiers is None:
                    tiers = np.zeros(len(pk["nbits"]), dtype=np.int64)
                leaves.append({
                    "words": pk["words"], "nbits": pk["nbits"],
                    "slots": pk["slots"], "tiers": tiers,
                    "steps": steps_p, "rng": np.int64(pk["rng"]),
                    "valid": valid,
                })
                statics = (lanes_pad, pk["n_cap"], pk["n_dp"],
                           pk["n_tiers"], len(pk["nbits"]),
                           pk["words"].shape[1], sp)
            else:
                leaves.append({
                    "times": pk["times"], "values": pk["values"],
                    "steps": steps_p, "rng": np.int64(pk["rng"]),
                    "valid": valid,
                })
                statics = (lanes_pad, pk["n_cap"], 0, 1, 0, 0, sp)
            cached = leaf_plan[key] = (idx, kind, statics, pk)
        idx, kind, statics, pk = cached
        if fn == "quantile_over_time":
            # PER-DEVICE window-grid budget, same gate as the per-node
            # tier (engine._QOT_MAX_ELEMENTS commentary): lanes on
            # this shard x padded steps x samples per lane
            elements = (statics[0] // max(n_shards, 1)) \
                * statics[6] * statics[1]
            instrument.gauge("m3_device_hbm_gate_pressure").set(
                elements / engine._QOT_MAX_ELEMENTS)
            if elements > engine._QOT_MAX_ELEMENTS:
                instrument.counter(
                    "m3_device_hbm_gate_rejections_total").inc()
                raise Unsupported("quantile_over_time window grid "
                                  "over the HBM budget",
                                  reason="qot_hbm_gate")
        if fn in qcost.RATE_FAMILY:
            rate_leaves.append(pk)
        pidx = len(params)
        params.append((np.float64(horizon), np.float64(phi)))
        labels = ([dict(ls) for ls in pk["labels"]] if keep_name
                  else _drop_name(pk["labels"]))
        plan_node = ("leaf", idx, pidx, kind, fn) + statics \
            + (hw_sf, hw_tf)
        return plan_node, labels, pk["n_lanes"], pk["lanes_pad"]

    def build(sym_node, grid):
        """-> (plan_node, labels, n_real, rows_pad); `grid` is the
        step grid this subtree evaluates on (the subquery node swaps
        in its inner grid for the child walk)."""
        tag = sym_node[0]
        if tag == "leaf":
            return build_leaf(sym_node, grid)
        if tag == "call":
            _, fn, extras, child = sym_node
            plan_c, labels_c, n_real, rows_pad = build(child, grid)
            pidx = len(params)
            params.append(tuple(np.float64(e) for e in extras))
            # host _eval_scalar_fn always drop_name()s
            return (("call", fn, pidx, plan_c), _drop_name(labels_c),
                    n_real, rows_pad)
        if tag == "agg":
            _, agg_node, phi, child = sym_node
            plan_c, labels_c, n_real, rows_pad = build(child, grid)
            keys = engine._group_keys(Matrix(labels_c[:n_real], None),
                                      agg_node)
            uniq = sorted(set(keys))
            group_of = {k: i for i, k in enumerate(uniq)}
            g_pad = _bucket_pow2(max(len(uniq), 1), 8)
            # padding rows park on group 0: all-NaN rows are inert in
            # every reducer (the padded-lanes-are-NaN invariant, which
            # each fused node re-establishes by re-masking)
            groups_p = np.zeros(rows_pad, dtype=np.int64)
            groups_p[:n_real] = [group_of[k] for k in keys]
            gvalid = np.arange(g_pad) < len(uniq)
            pidx = len(params)
            params.append((groups_p, gvalid, np.float64(phi)))
            shape["groups"] += len(uniq)
            return (("agg", agg_node.op, g_pad, pidx, plan_c),
                    [dict(k) for k in uniq], len(uniq), g_pad)
        if tag == "vs":
            _, bin_node, mat_on_left, scalar, child = sym_node
            plan_c, labels_c, n_real, rows_pad = build(child, grid)
            is_cmp = bin_node.op in CMP_OPS
            if is_cmp and not bin_node.bool_mod:
                labels = labels_c  # filter keeps labels verbatim
            else:
                labels = _drop_name(labels_c)
            pidx = len(params)
            params.append((np.float64(scalar),))
            return (("vs", bin_node.op, bin_node.bool_mod,
                     mat_on_left, pidx, plan_c), labels, n_real,
                    rows_pad)
        if tag == "vv":
            _, bin_node, lhs_sym, rhs_sym = sym_node
            plan_l, labels_l, n_l, _rows_l = build(lhs_sym, grid)
            plan_r, labels_r, n_r, _rows_r = build(rhs_sym, grid)
            out_labels, lhs_rows, rhs_rows = _match_vv(
                bin_node, labels_l[:n_l], labels_r[:n_r])
            n_out = len(out_labels)
            out_pad = _bucket_pow2(max(n_out, 1), 8)
            lidx = np.zeros(out_pad, dtype=np.int64)
            ridx = np.zeros(out_pad, dtype=np.int64)
            lidx[:n_out] = lhs_rows
            ridx[:n_out] = rhs_rows
            valid = np.arange(out_pad) < n_out
            pidx = len(params)
            params.append((lidx, ridx, valid))
            return (("vv", bin_node.op, bin_node.bool_mod, out_pad,
                     pidx, plan_l, plan_r), out_labels, n_out, out_pad)
        if tag == "topkk":
            _, agg_node, k, child = sym_node
            plan_c, labels_c, n_real, rows_pad = build(child, grid)
            keys = engine._group_keys(Matrix(labels_c[:n_real], None),
                                      agg_node)
            uniq = sorted(set(keys))
            group_of = {kk: i for i, kk in enumerate(uniq)}
            # padding rows park on a DEDICATED trash group (last id):
            # unlike the inert-under-reduction padding above, a padded
            # -Inf-keyed lane inside a real group would win a top-k
            # slot whenever the group holds fewer than k real lanes
            g_pad = _bucket_pow2(len(uniq) + 1, 8)
            groups_p = np.full(rows_pad, g_pad - 1, dtype=np.int64)
            groups_p[:n_real] = [group_of[kk] for kk in keys]
            pidx = len(params)
            params.append((groups_p,))
            shape["topk_k"] = k
            # topk keeps child labels verbatim; row order is fixed up
            # on host from the (present, rank) aux after the transfer
            return (("topk", agg_node.op, k, g_pad, pidx, plan_c),
                    labels_c, n_real, rows_pad)
        if tag == "hq":
            _, phi, child = sym_node
            plan_c, labels_c, n_real, rows_pad = build(child, grid)
            # mirror engine._histogram_quantile's grouping exactly:
            # group on labels minus {le, __name__}, sort groups, sort
            # buckets by (ub, row), skip malformed groups
            groups: dict = {}
            for i, ls in enumerate(labels_c[:n_real]):
                le = ls.get(b"le")
                if le is None:
                    continue
                try:
                    ub = float(le)
                except ValueError:
                    continue
                gkey = tuple(sorted(
                    (k, v) for k, v in ls.items()
                    if k not in (b"le", b"__name__")))
                groups.setdefault(gkey, []).append((ub, i))
            out_labels, rows_g, ubs_g = [], [], []
            for gkey, buckets in sorted(groups.items()):
                buckets.sort()
                ubs = [b[0] for b in buckets]
                if len(ubs) < 2 or not math.isinf(ubs[-1]):
                    continue
                out_labels.append(dict(gkey))
                rows_g.append([b[1] for b in buckets])
                ubs_g.append(ubs)
            if not out_labels:
                raise Unsupported("no well-formed histogram groups "
                                  "(need >= 2 buckets and an +Inf "
                                  "top)", reason="hq_malformed")
            g_pad = _bucket_pow2(len(out_labels), 8)
            widest = max(len(r) for r in rows_g)
            b_pad = _bucket_pow2(widest, 8)
            rows_idx = np.zeros((g_pad, b_pad), dtype=np.int64)
            ubs_p = np.full((g_pad, b_pad), np.inf)
            caps = np.zeros(g_pad)
            for g, (rows, ubs) in enumerate(zip(rows_g, ubs_g)):
                # bucket-axis padding REPEATS the top bucket's row so
                # cumulative counts stay flat across padding and a
                # padded slot never becomes the interpolation target
                rows_idx[g, :len(rows)] = rows
                rows_idx[g, len(rows):] = rows[-1]
                ubs_p[g, :len(ubs)] = ubs
                caps[g] = ubs[-2]
            gvalid = np.arange(g_pad) < len(out_labels)
            pidx = len(params)
            params.append((rows_idx, ubs_p, caps, gvalid,
                           np.float64(phi)))
            shape["hq_groups"] += len(out_labels)
            shape["hq_buckets"] = max(shape["hq_buckets"], widest)
            return (("hq", g_pad, b_pad, pidx, plan_c), out_labels,
                    len(out_labels), g_pad)
        if tag == "absent":
            _, child = sym_node
            plan_c, _labels_c, _n_real, _rows_pad = build(child, grid)
            avalid = np.zeros(8, dtype=bool)
            avalid[0] = True
            pidx = len(params)
            params.append((avalid,))
            return ("absent", pidx, plan_c), [{}], 1, 8
        if tag == "sortv":
            _, desc, child = sym_node
            built = build(child, grid)
            root_post.append(("sort", desc))
            return built
        if tag == "labelfn":
            _, call_node, child = sym_node
            plan_c, labels_c, n_real, rows_pad = build(child, grid)
            return (plan_c, _apply_label_fn(call_node, labels_c),
                    n_real, rows_pad)
        if tag == "subq":
            _, sq, fn, horizon, hw_sf, hw_tf, child = sym_node
            shifted = engine._eval_times(sq, grid)
            rng = int(sq.range_nanos)
            sub_step = int(sq.step_nanos or DEFAULT_SUBQUERY_STEP)
            # inner grid aligned to absolute multiples of the step,
            # exactly engine._range_samples' subquery arm
            lo = int(shifted[0]) - rng
            hi = int(shifted[-1])
            first = lo - lo % sub_step \
                + (sub_step if lo % sub_step else 0)
            sub_times = np.arange(first, hi + 1, sub_step,
                                  dtype=np.int64)
            if len(sub_times) == 0:
                sub_times = np.asarray([hi], dtype=np.int64)
            if len(sub_times) > _SUBQ_MAX_STEPS:
                raise Unsupported("subquery inner grid too large for "
                                  "the fused program",
                                  reason="subquery_grid")
            plan_c, labels_c, n_real, rows_pad = build(child,
                                                      sub_times)
            s_in_pad = _bucket_pow2(len(sub_times), 64)
            sub_p = np.full(s_in_pad, sub_times[-1], dtype=np.int64)
            sub_p[:len(sub_times)] = sub_times
            sub_valid = np.arange(s_in_pad) < len(sub_times)
            steps_out = np.full(s_pad, shifted[-1], dtype=np.int64)
            steps_out[:len(shifted)] = shifted
            pidx = len(params)
            params.append((sub_p, sub_valid, steps_out,
                           np.int64(rng), np.float64(horizon)))
            return (("subq", fn, s_in_pad, hw_sf, hw_tf, pidx,
                     plan_c), _drop_name(labels_c), n_real, rows_pad)
        if tag == "gsel":
            # build-time row selection/reorder: select_fn sees the real
            # child labels and returns (kept row indices, new labels).
            # The device side is a pure gather, so any host-computable,
            # data-independent filter (graphite depth matching, sortBy
            # Name, limit, exclude/grep) lowers exactly.
            _, select_fn, child = sym_node
            plan_c, labels_c, n_real, rows_pad = build(child, grid)
            keep, new_labels = select_fn(labels_c[:n_real])
            n_out = len(keep)
            out_pad = _bucket_pow2(max(n_out, 1), 8)
            idx = np.zeros(out_pad, dtype=np.int64)
            idx[:n_out] = keep
            valid = np.arange(out_pad) < n_out
            pidx = len(params)
            params.append((idx, valid))
            return (("gsel", out_pad, pidx, plan_c),
                    list(new_labels), n_out, out_pad)
        if tag == "gname":
            # label/name plane only: the value plan passes through
            _, name_fn, child = sym_node
            plan_c, labels_c, n_real, rows_pad = build(child, grid)
            return plan_c, name_fn(labels_c), n_real, rows_pad
        if tag == "gagg":
            # grouped reduce with graphite NaN semantics.  group_fn
            # maps the child labels to (per-row group ids, one label
            # dict per group).  An empty series list stays host-side:
            # graphite's combiners pass empties through untouched,
            # which no all-NaN reduction can reproduce.
            _, op, extra, group_fn, child = sym_node
            plan_c, labels_c, n_real, rows_pad = build(child, grid)
            if n_real == 0:
                raise Unsupported("graphite aggregate over an empty "
                                  "series list", reason="graphite_empty")
            grouped = group_fn(labels_c)
            # optional third element: a build-time scalar traced to the
            # device (countSeries' series count)
            groups, out_labels = grouped[0], grouped[1]
            tval = grouped[2] if len(grouped) > 2 else 0.0
            n_groups = len(out_labels)
            g_pad = _bucket_pow2(max(n_groups, 1), 8)
            # padding rows park on group 0 — all-NaN rows are inert in
            # every graphite nan-reducer (padded-lanes-are-NaN)
            groups_p = np.zeros(rows_pad, dtype=np.int64)
            groups_p[:n_real] = groups
            gvalid = np.arange(g_pad) < n_groups
            pidx = len(params)
            params.append((groups_p, gvalid, np.float64(tval)))
            shape["groups"] += n_groups
            return (("gagg", op, extra, g_pad, pidx, plan_c),
                    out_labels, n_groups, g_pad)
        if tag == "gcall":
            # elementwise/windowed graphite transform: `statics` is a
            # hashable tuple baked into the plan key (window widths,
            # bucket sizes), `fparams` numpy scalars traced per call
            _, fn, statics, fparams, name_fn, child = sym_node
            plan_c, labels_c, n_real, rows_pad = build(child, grid)
            pidx = len(params)
            params.append(tuple(fparams))
            return (("gcall", fn, statics, pidx, plan_c),
                    name_fn(labels_c), n_real, rows_pad)
        raise Unsupported(f"unknown symbolic node {tag!r}",
                          reason="unknown_node")

    with planning:
        plan_t, root_labels, n_real, _rows_pad = build(sym, step_times)
    kernel_name = ("device_expr_pipeline_sharded" if n_shards > 1
                   else "device_expr_pipeline")
    plan_key = (plan_t if n_shards == 1
                else (plan_t, ("mesh", n_shards)))
    engine._check_deadline("device fused")

    steps_pad = np.full(s_pad, step_times[-1], dtype=np.int64)
    steps_pad[:len(step_times)] = step_times
    # megabatch upload estimate (every leaf + param + the step grid) —
    # the SAME pytree kernel telemetry's _arg_volume counts, so the
    # per-owner upload counter reconciles with the kernel counters
    from m3_tpu.observe.devmem import nbytes_of
    from m3_tpu import observe
    megabatch = (nbytes_of(leaves) + nbytes_of(params)
                 + steps_pad.nbytes)
    n_bufs = len(leaves) + len(params) + 1

    # cross-query megabatching seam (m3_tpu/serving/): inside a batch
    # scope with a scheduler installed, shape-identical concurrent
    # queries share ONE batched dispatch and each gets its demux slice
    # back; None = proceed on the solo path below.  Sharded meshes
    # stay solo — the batched kernel vmaps the single-chip program.
    from m3_tpu import serving
    batched = None
    if n_shards == 1:
        batched = serving.try_batched_dispatch(
            engine, plan_t, tuple(leaves), tuple(params), steps_pad,
            nbytes=megabatch, n_bufs=n_bufs)
    else:
        serving.count_solo("sharded_mesh")
    binfo = None
    windows = None      # a batched dispatch searches no band and says so
    if batched is not None:
        out_np, aux_np, errs_entry, binfo = batched
        errs_np = list(errs_entry)
        cache_hit = binfo["compile_cache_hit"]
        compiled = binfo["compiled"]
        compile_s = binfo["compile_s"]
        # the shared dispatch's own clock (serving/scheduler.py), which
        # this thread waited through
        for key in ("device_s", "device_wait_s"):
            cost.phases[key] = cost.phases.get(key, 0.0) + binfo[key]
    else:
        hit = _note_fingerprint(plan_key,
                                bucket=f"rows{_rows_pad}xsteps{s_pad}")
        ker = kernel_telemetry.kernels().get(kernel_name)
        before = ker.stats() if ker is not None else {}
        # device-ledger borrow: the megabatch is uploaded by jit for
        # the duration of the call (numpy leaves: the call stages them,
        # so h2d_s is 0 on this path)
        try:
            with cost.phase("device"):
                with observe.device_ledger().borrow(
                        "query_megabatch", megabatch, count=n_bufs):
                    if n_shards > 1:
                        out, aux, errs, windows = \
                            qp.device_expr_pipeline_sharded(
                                plan_t, engine.serving_mesh,
                                tuple(leaves), tuple(params), steps_pad)
                    else:
                        out, aux, errs, windows = qp.device_expr_pipeline(
                            plan_t, tuple(leaves), tuple(params),
                            steps_pad)
                with cost.phase("d2h"):
                    out_np = np.asarray(out)
                    aux_np = tuple(np.asarray(a) for a in aux)
                    errs_np = [np.asarray(e) for e in errs]
                    windows = np.asarray(windows)
        except Exception as exc:  # noqa: BLE001 — a device runtime
            # error must not fail a query the host tier can answer
            cost.stats = {
                "device_serving": False,
                "device_error": f"{type(exc).__name__}: {exc}"[:200],
            }
            cost.fused_error = f"{type(exc).__name__}: {exc}"[:200]
            return None
        after = ker.stats() if ker is not None else {}
        compiled = (after.get("compiles", 0) > before.get("compiles", 0))
        compile_s = (after.get("compile_s", 0.0)
                     - before.get("compile_s", 0.0))
        cache_hit = bool(hit and not compiled)

    # decode-error fallback: flags over the REAL stream rows of each
    # words leaf (ascending leaf index, the pipeline's error order;
    # shard-repacked leaves carry their row mask in real_rows)
    words_leaves = sorted(
        (ent[0], ent[3]) for ent in leaf_plan.values()
        if ent[1] == "words")
    for (idx, pk), err in zip(words_leaves, errs_np):
        real = pk.get("real_rows")
        bad = (err[real].any() if real is not None
               else err[:pk["n_streams"]].any())
        if bad:
            cost.fused_poisoned = True
            return None  # corrupt/unsorted stream: host re-decodes

    transfer_bytes = (out_np.nbytes + sum(a.nbytes for a in aux_np)
                      + sum(e.nbytes for e in errs_np))

    # per-query accounting for the slow-query log's device_tier phase.
    # The thread-local tally counts AST nodes COVERED (a fused temporal
    # leaf covers its Call and its Selector), so cost.record's
    # host_nodes = ast_nodes - fused_nodes is exact under splitting.
    fused_nodes = counts["ops"] + len(leaf_plan)
    cost.fused_nodes += ast_nodes
    cost.fused_compile_cache = "miss" if compiled else "hit"
    cost.fused_compile_s += compile_s
    cost.fused_transfer_bytes += transfer_bytes
    cost.fused_n_shards = max(cost.fused_n_shards, n_shards)
    if binfo is not None:
        cost.fused_batched = True
        cost.fused_batch_size = max(cost.fused_batch_size,
                                    binfo["batch_size"])
        cost.fused_batch_wait_s += binfo["waited_s"]
        task = cost.task
        if task is not None:
            # /debug/tasks shows which live queries rode a shared
            # dispatch and what the admission window cost them
            task.batch = {"size": binfo["batch_size"],
                          "wait_s": round(binfo["waited_s"], 6)}

    with planning:      # the root's host reorder
        values = out_np[:n_real, :len(step_times)]
        labels = root_labels[:n_real]
        if plan_t[0] == "topk":
            # eval_ordered semantics: rows ordered by final-step rank,
            # unselected-at-every-step rows dropped (host _eval_topk)
            present_np = aux_np[0][:n_real]
            rank_np = aux_np[1][:n_real]
            order = [i for i in np.argsort(rank_np, kind="stable")
                     if present_np[i]]
            labels = [labels[i] for i in order]
            values = values[order]
        for _tag, desc in root_post:
            # prometheus sorts instant vectors by value; for a range
            # result the last step's value is the sort key (host parity)
            last = np.where(np.isnan(values[:, -1]),
                            -np.inf if desc else np.inf,
                            values[:, -1])
            order = np.argsort(last, kind="stable")
            if desc:
                order = order[::-1]
            labels = [labels[i] for i in order]
            values = values[order]
    fn_stat = next((f for f in counts["fns"] if f in LOOSE_FNS),
                   counts["fns"][0] if counts["fns"] else None)
    agg_stat = next((a for a in counts["aggs"] if a in LOOSE_AGGS),
                    counts["aggs"][0] if counts["aggs"] else None)
    cost.publish(
        **qcost.program_shape([ent[3] for ent in leaf_plan.values()],
                              n_shards, len(steps_pad), rate_leaves),
        device_serving=True,
        device_fused=True,
        fused_nodes=fused_nodes,
        fn=fn_stat,
        agg=agg_stat,
        compile_cache="hit" if cache_hit else "miss",
        compiled=compiled,
        compile_s=compile_s,
        transfer_bytes=transfer_bytes,
        # the tree's real groups, a root top-k's k, a
        # histogram_quantile's label combinations and the buckets of
        # its widest one, and the rows of the answer
        groups=shape["groups"], topk_k=shape["topk_k"],
        hq_groups=shape["hq_groups"], hq_buckets=shape["hq_buckets"],
        rows_out=len(labels),
        band_served_pct=qcost.count_band_served(windows))
    if binfo is not None:
        cost.stats["batched"] = True
        cost.stats["batch_size"] = binfo["batch_size"]
    return Matrix(labels, values)
