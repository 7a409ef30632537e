"""What a query cost: the thread's cost object, the description of what
a device program ran at, and the one record a query leaves in the
slow-query ring (query/slowlog.py; docs/observability.md says what each
of its fields means).

Imports neither query/engine.py nor query/plan.py: both write into a
``QueryCost`` and neither knows the record's schema.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from m3_tpu import attribution
from m3_tpu.cache import stats as cache_stats
from m3_tpu.query import slowlog
from m3_tpu.utils import instrument, tracing

# the stamped phases that tile a query's time; h2d and d2h lie inside
# device and are recorded beside it, as the waits (tracing.WAIT_KEYS)
# are beside the phases they interrupted
TILING_PHASES = ("parse_s", "plan_s", "fetch_s", "open_read_s",
                 "pack_s", "decode_s", "merge_s", "device_s")
# the phases, of those that tile it, that block on nothing but locks:
# where wall - CPU - the database lock's wait is the wait for the
# interpreter lock
LOCK_ONLY_PHASES = TILING_PHASES[:-1] + ("self_s",)

# the record's fields that are a published stat under the same name, in
# the record's order, each with what the record says where no serving
# path published one
STATS_FIELDS = {
    "datapoints": 0, "rows": 0, "open_rows": 0, "lanes": 0, "lanes_pad": 0,
    "lane_chunks": 0, "n_cap": 0, "steps_pad": 0, "rows_per_lane": 0,
    "decode_refills": 0, "groups": 0, "topk_k": 0, "rows_out": 0,
    "hq_groups": 0, "hq_buckets": 0, "window_form": None,
    "merge_form": None, "device_serving": False, "fn": None, "n_shards": 1,
}
# the walk's two counts stand in the record before this one
_WALK_AT = list(STATS_FIELDS).index("device_serving")

# the temporal functions that read a window's two ends
RATE_FAMILY = ("rate", "increase", "delta")


class QueryCost:
    """What one query cost, written where it is paid: the phase stamps
    (``phase``, seconds by ``<phase>_s``), the serving path's stats
    (``Engine.last_fetch_stats``), the fused planner's tallies and the
    device tier's declines.  One per query and thread (``Costs``); it
    stays until the thread's next query begins, so a caller reads
    ``last_fetch_stats`` after the call, and never another thread's."""

    __slots__ = ("phases", "cpu", "cpu_t0_ns", "stats", "declines",
                 "task", "series", "gather_bytes", "walk_rows",
                 "fileset_scans", "ast_nodes", "fused_nodes",
                 "fused_compile_cache", "fused_compile_s",
                 "fused_transfer_bytes",
                 "fused_n_shards", "fused_batched", "fused_batch_size",
                 "fused_batch_wait_s", "fused_error", "fused_poisoned",
                 "host_split_reasons", "rung_selections")

    def __init__(self):
        # the waits' keys are there from the start: a collection's
        # callback may charge its pause at any point of the thread's
        # code, and must not change the size of a dict being copied
        self.phases: dict[str, float] = dict.fromkeys(
            tracing.WAIT_KEYS, 0.0)
        # a clocked query's (one in tracing.COST_CLOCK_1_IN): the
        # thread's CPU seconds by phase, and the CPU clock's reading
        # where the engine call began; None: no phase reads that clock
        self.cpu: dict[str, float] | None = None
        self.cpu_t0_ns = 0
        self.stats: dict | None = None
        self.declines: dict[str, int] = {}    # device tier, by reason
        # a PromQL query's entry in the task ledger while it runs
        self.task = None
        self.series = 0                       # rows of the answer
        self.gather_bytes = 0
        # directories the query's gathers had to list (a shard whose
        # fileset listing was not yet kept): 0 on a served node
        self.fileset_scans = 0
        # rows the query's walks were handed as columns, and rows they
        # had to classify one by one (a cold write beside a sealed
        # stream: a MIXED block)
        self.walk_rows = {"columns": 0, "by_row": 0}
        # whole-query fusion (query/plan.py): how much of the tree the
        # fused device program served, what it cost to (re)compile,
        # and how many bytes crossed back
        self.ast_nodes = 0
        self.fused_nodes = 0
        self.fused_compile_cache = None
        self.fused_compile_s = 0.0
        self.fused_transfer_bytes = 0
        self.fused_n_shards = 1
        self.fused_batched = False
        self.fused_batch_size = 0
        self.fused_batch_wait_s = 0.0
        self.fused_error = None
        self.fused_poisoned = False
        self.host_split_reasons: dict[str, int] = {}
        self.rung_selections: dict[str, int] = {}

    def phase(self, name: str):
        """``with cost.phase("pack"):`` — the one stamp of a phase
        (utils/tracing.phase): record, span and trace annotation."""
        return tracing.phase(name, self.phases, self.cpu)

    def publish(self, **fields) -> None:
        """A serving path's stats: the phases stamped so far in this
        query, unrounded, and the path's own fields."""
        self.stats = {**self.phases, **fields}

    def decline(self, reason: str) -> None:
        """The per-node device tier hands a selector to the host tier:
        counted by cause, and kept for the query's record."""
        instrument.bounded_counter(
            "m3_query_device_decline_total").labels(reason=reason).inc()
        self.declines[reason] = self.declines.get(reason, 0) + 1

    def split(self, reason: str) -> None:
        """A subtree the fused planner left to the host, by cause (the
        slugs of ``m3_query_host_split_total``)."""
        instrument.bounded_counter(
            "m3_query_host_split_total").labels(reason=reason).inc()
        self.host_split_reasons[reason] = (
            self.host_split_reasons.get(reason, 0) + 1)


class Costs:
    """An engine's cost objects, one a thread, and its count of the
    queries since the last one that read the CPU clock."""

    def __init__(self):
        self._local = threading.local()
        self._unclocked = 0
        self._unclocked_lock = threading.Lock()

    def current(self) -> QueryCost:
        """The calling thread's cost object: the running query's, or
        (a direct ``_fetch_raw`` caller, no query scope) one that the
        thread keeps until its next query."""
        cost = getattr(self._local, "cost", None)
        if cost is None:
            cost = self._local.cost = QueryCost()
        return cost

    def begin(self, live: bool = False) -> QueryCost:
        """Arm the calling thread's cost object for one query, and
        decide, once, whether the query reads the CPU clock: if its
        span is `live` (sampled by the tracer, or forced by the
        request's ``traceparent``) or it is this engine's
        ``tracing.COST_CLOCK_1_IN``-th query since the last that did."""
        cost = self._local.cost = QueryCost()
        with self._unclocked_lock:
            self._unclocked += 1
            clocked = live or self._unclocked >= tracing.COST_CLOCK_1_IN
            if clocked:
                self._unclocked = 0
        if clocked:
            cost.cpu = {}
            cost.cpu_t0_ns = time.thread_time_ns()
        return cost


def _count_forms(counter: str, forms) -> str | None:
    """Count each of a program's `forms` once -> the word for the
    query's record: the form, "mixed" where its leaves differ, None
    without one."""
    for form in sorted(forms):
        instrument.counter(counter, form=form).inc()
    return min(forms) if len(forms) == 1 else "mixed" if forms else None


def program_shape(leaves, n_shards: int, steps_pad: int,
                  rate_leaves) -> dict:
    """What a device program ran at, as both device tiers publish it:
    over the list of packed `leaves` it was handed (the per-node
    tier's one, a fused tree's all; as words or, the fused arrays
    bridge, as grids), the rows, series and lane buckets summed, the widest
    leaf's samples-a-lane bucket and rows a lane, the steps' bucket,
    the decode scans' refills, and the forms the merge and, over
    `rate_leaves` (those a function of ``RATE_FAMILY`` reads), the
    windows' ends took, each form counted once
    (``m3_device_merge_form_total``, ``m3_device_window_form_total``)."""
    # (imports jax: not on this module's import)
    from m3_tpu.models import query_pipeline as qp

    words = [pk for pk in leaves if "words" in pk]
    window_form = _count_forms("m3_device_window_form_total", {
        qp.window_form(pk["n_cap"]) for pk in rate_leaves})
    merge_form = _count_forms("m3_device_merge_form_total", {
        qp.merge_form(pk["n_cap"], pk["n_dp"]) for pk in words})
    return {
        "n_streams": sum(pk["n_streams"] for pk in leaves),
        "datapoints": sum(pk["datapoints"] for pk in leaves),
        "rows": sum(pk.get("n_rows", 0) for pk in leaves),
        "lanes": sum(pk["n_lanes"] for pk in leaves),
        "lanes_pad": sum(pk["lanes_pad"] for pk in leaves),
        "n_cap": max((pk["n_cap"] for pk in leaves), default=0),
        "steps_pad": steps_pad,
        "rows_per_lane": max((pk.get("rows_per_lane", 0)
                              for pk in leaves), default=0),
        "decode_refills": sum(
            qp.decode_refills(pk["n_dp"], pk["words"].shape[1])
            for pk in words),
        "window_form": window_form, "merge_form": merge_form,
        "n_shards": n_shards,
    }


def count_band_served(windows) -> float | None:
    """Count what a device program said of its windowed stages'
    lane chunks (`windows`: those served at the full width, all of
    them, on a mesh a row a shard; query_pipeline._temporal_eval) ->
    the share of 100 that searched a band of the lane, for the query's
    record; None where the program said nothing."""
    if windows is None:
        return None
    full, chunks = (int(n) for n in np.reshape(windows, (-1, 2)).sum(axis=0))
    instrument.counter("m3_device_window_band_total",
                       served="band").inc(chunks - full)
    instrument.counter("m3_device_window_band_total",
                       served="full").inc(full)
    return 100.0 * (chunks - full) / chunks if chunks else None


def record(cost: QueryCost, expr: str, namespace: str, t0_ns: int, meta,
           error: str | None) -> None:
    """One Monarch-style cost record per query into the slow-query
    ring; best-effort — accounting must never fail the query.

    ``phases`` carries every key in every record (0.0 where the
    path has no such step).  ``self_s`` is the engine's self time:
    ``total_s`` minus the phases that tile it, so those and
    ``self_s`` sum to ``total_s``.  ``frontend_s`` is the HTTP
    front end's, added by query/http.py once the reply is
    written; it lies outside ``total_s``, and ``render_s`` (a range
    query's matrix to its JSON bytes) inside ``frontend_s``.

    A clocked query's record (``QueryCost.cpu``) also carries
    ``cpu``, the thread's CPU seconds under the same keys
    (``total_s`` from two readings around the engine call, not a
    sum), and ``interp_wait_s``: over ``LOCK_ONLY_PHASES``, wall
    minus CPU, minus ``db_lock_wait_s``: the interpreter lock and
    whatever the host's scheduler took.  Any other record has
    neither key."""
    try:
        cpu_total_s = (None if cost.cpu is None else (
            time.thread_time_ns() - cost.cpu_t0_ns) / 1e9)
        total_s = (time.perf_counter_ns() - t0_ns) / 1e9
        published = cost.stats or {}

        def tiled(stamps: dict, total: float) -> dict:
            out = {k: stamps.get(k, 0.0)
                   for k in TILING_PHASES + ("h2d_s", "d2h_s")}
            out["self_s"] = total - sum(out[k] for k in TILING_PHASES)
            out["frontend_s"] = out["render_s"] = 0.0
            out["total_s"] = total
            return out

        phases = tiled(cost.phases, total_s)
        for k in tracing.WAIT_KEYS:
            phases[k] = cost.phases.get(k, 0.0)
        ctx = tracing.current_context()
        tenant = tracing.current_tenant() or namespace
        took = [(name, published.get(name, default))
                for name, default in STATS_FIELDS.items()]
        rec = {
            "expr": expr[:500],
            "tenant": tenant,
            "initiator": slowlog.current_initiator(),
            "total_s": total_s,
            "phases": phases,
            "series": cost.series,
            **dict(took[:_WALK_AT]),
            "fileset_scans": cost.fileset_scans,
            "walk_rows": dict(cost.walk_rows),
            **dict(took[_WALK_AT:]),
            "warnings": (meta.warning_strings()
                         if meta is not None else []),
            "exhaustive": (meta.exhaustive
                           if meta is not None else True),
            "error": error,
            "trace_id": (f"{ctx.trace_id:032x}"
                         if ctx is not None else None),
            "cache": cache_stats.snapshot(),
        }
        if cpu_total_s is not None:
            cpu = rec["cpu"] = tiled(cost.cpu, cpu_total_s)
            rec["interp_wait_s"] = sum(
                phases[k] - cpu[k] for k in LOCK_ONLY_PHASES
            ) - phases["db_lock_wait_s"]
        if cost.declines:
            rec["device_declines"] = dict(cost.declines)
        if published.get("band_served_pct") is not None:
            rec["band_served_pct"] = published["band_served_pct"]
        if cost.fused_nodes:
            rec["device_tier"] = {
                "compile_cache": cost.fused_compile_cache,
                "compile_s": cost.fused_compile_s,
                "device_nodes": cost.fused_nodes,
                "host_nodes": max(
                    (cost.ast_nodes or cost.fused_nodes)
                    - cost.fused_nodes, 0),
                "transfer_bytes": cost.fused_transfer_bytes,
                "n_shards": cost.fused_n_shards,
            }
            if cost.fused_batched:
                rec["device_tier"]["batched"] = True
                rec["device_tier"]["batch_size"] = (
                    cost.fused_batch_size)
                rec["device_tier"]["batch_wait_s"] = (
                    cost.fused_batch_wait_s)
            if cost.host_split_reasons:
                rec["device_tier"]["host_splits"] = dict(
                    cost.host_split_reasons)
        if cost.rung_selections:
            rec.setdefault("device_tier", {})["rungs"] = dict(
                cost.rung_selections)
            rec["device_tier"].setdefault(
                "read_bytes", published.get("read_bytes", 0))
        if cost.fused_error:
            rec["device_tier_error"] = cost.fused_error
        slowlog.log().record(rec)
        for name, counter in (("open_rows", "m3_query_open_rows_total"),
                              ("lanes", "m3_query_lanes_total"),
                              ("hq_groups", "m3_query_hq_groups_total")):
            if rec[name]:
                instrument.counter(counter).inc(rec[name])
        if attribution.enabled():
            # read-path attribution for this query (datapoints
            # scanned and device execute seconds are accounted at
            # their sources — fetch_tagged and InstrumentedKernel
            # — so only the engine-scoped costs land here)
            cache = rec["cache"] or {}
            attribution.account_read(
                tenant,
                transfer_bytes=cost.fused_transfer_bytes,
                cache_hit_bytes=int(sum(
                    v for k, v in cache.items()
                    if k.endswith("_hit_bytes"))),
                cache_miss_bytes=int(sum(
                    v for k, v in cache.items()
                    if k.endswith("_miss_bytes"))))
            attribution.account_query(
                tenant, expr, cost=float(rec["datapoints"] or 0) + 1.0)
    except Exception:  # noqa: BLE001 — accounting is best-effort
        pass
