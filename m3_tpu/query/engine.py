"""Query engine: AST -> batched execution against the storage node.

The reference's pull-less transform DAG (ref: src/query/executor/
engine.go:111 ExecuteExpr, functions/*) collapses here into direct
batched evaluation: every vector expression evaluates to a Matrix —
labels plus a [series, steps] value grid — and all per-series work
(decode, consolidation, temporal windows) runs batched across series.

Namespace fan-out (ref: src/query/storage/m3/cluster_resolver.go,
storage/m3/storage.go:93,234 fetchCompressed): a fetch consults the
unaggregated namespace plus every namespace declaring
``aggregated=True``, finest resolution first.  Results stitch per
series by data presence: a coarser namespace only contributes samples
OLDER than the earliest sample any finer namespace produced — the
downsampled tier serves reads beyond raw retention, raw data wins
wherever it exists (the reference's aggregated-namespace read path).
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import math
import operator
import re
import threading
import time
from collections import defaultdict, deque

import numpy as np

from m3_tpu import observe
from m3_tpu.cache import stats as cache_stats
from m3_tpu.metrics.policy import format_duration
from m3_tpu.ops import consolidate as cons
from m3_tpu.ops.m3tsz_decode import (decode_streams_adaptive,
                                     decode_streams_merged)
from m3_tpu.query import cost as qcost
from m3_tpu.query import plan as qplan
from m3_tpu.query import promql
from m3_tpu.query.matrix import (DEFAULT_SUBQUERY_STEP, Matrix, expand_go,
                                 signature)
from m3_tpu.storage.buffer import OpenRow, by_view
from m3_tpu.storage.database import Database
from m3_tpu.storage.limits import QueryDeadlineExceeded, ResultMeta
from m3_tpu.storage.shard import ARRAYS, MIXED, OPEN, STREAMS
from m3_tpu.utils import instrument, tracing

DEFAULT_LOOKBACK = cons.DEFAULT_LOOKBACK

# test seam: lets the differential suite force the per-fragment stitch
# path to cross-check the vectorized multi-tier branch
_VECTORIZED_STITCH = True


# what a row of the walk is, by the code the walk's `kind` column holds
_ROW_STREAM, _ROW_OPEN, _ROW_ARRAYS = 0, 1, 2
_ROW_OF_KIND = {STREAMS: _ROW_STREAM, OPEN: _ROW_OPEN, ARRAYS: _ROW_ARRAYS}


@dataclasses.dataclass(frozen=True, slots=True)
class StreamRows:
    """The gather's compressed rows, as columns: row i is
    ``streams[i]`` (the stream's bytes) of the series at ``slots[i]``,
    fetched from tier ``tiers[i]``, holding ``counts[i]`` datapoints
    (-1: no stored count).  Slot-grouped within a tier, block time
    ascending within a slot: the merge contract's order.  The arrays
    belong to the query's gather memo: read, never written."""

    streams: list
    slots: np.ndarray
    tiers: np.ndarray
    counts: np.ndarray

    def __len__(self) -> int:
        return len(self.streams)

    def known_counts(self) -> np.ndarray | None:
        """``counts``, or None where any row's count is unknown."""
        return None if (self.counts < 0).any() else self.counts

    def triples(self):
        """The rows one by one, (slot, tier, stream): for the one
        consumer that still goes round them."""
        return zip(self.slots.tolist(), self.tiers.tolist(), self.streams)


class Engine:
    def __init__(self, db: Database, namespace: str = "default",
                 lookback_nanos: int = DEFAULT_LOOKBACK,
                 device_serving: bool | None = None,
                 serving_mesh=None, planner=None):
        self.db = db
        self.ns = namespace
        self.lookback = lookback_nanos
        # retention.QueryPlanner: when set, fetches are clamped at each
        # tier's retention horizon and per-band rung selection is
        # recorded; None keeps the plain full-range namespace fan-out
        self.planner = planner
        self._qrange_local = threading.local()
        # _cost(): the calling thread's cost object; _begin_cost(live):
        # a new one, armed for one query (query/cost.py)
        costs = qcost.Costs()
        self._cost, self._begin_cost = costs.current, costs.begin
        # None = auto, resolved lazily per query from the backend JAX
        # reports (see _device_serving_active)
        self.device_serving = device_serving
        # multi-chip deployments: a jax.sharding.Mesh routes the device
        # tier through the shard_map'd pipelines (series-sharded lanes,
        # grouped reductions over ICI) instead of the single-chip jits
        self.serving_mesh = serving_mesh

    # --- namespace fan-out (ref: cluster_resolver.go) ---

    def _resolve_namespaces(self) -> list[str]:
        """Fetch plan: unaggregated first, then aggregated namespaces by
        increasing resolution (finest wins in the stitch)."""
        plan = [self.ns]
        aggs = []
        for name in self.db.namespaces():
            if name == self.ns:
                continue
            opts = self.db.namespace_options(name)
            if opts.aggregated and opts.aggregation_resolution:
                aggs.append((opts.aggregation_resolution, name))
        plan.extend(name for _, name in sorted(aggs))
        return plan

    # --- retention-ladder planning (m3_tpu/retention/planner.py) ---

    def _plan(self, start_nanos: int, end_nanos: int):
        """Memoized planner call + per-band rung-selection accounting
        (the counter and the slowlog dict are bumped once per computed
        plan, i.e. once per distinct fetch range per query)."""
        if self.planner is None:
            return None
        cache = getattr(self._qrange_local, "plan_cache", None)
        key = (start_nanos, end_nanos)
        if cache is not None and key in cache:
            return cache[key]
        plan = self.planner.plan(start_nanos, end_nanos)
        sel = self._cost().rung_selections
        fam = instrument.bounded_counter(
            "m3_query_resolution_selected_total", cap=32)
        for band in plan.bands:
            lab = band.resolution_label
            fam.labels(resolution=lab).inc()
            sel[lab] = sel.get(lab, 0) + 1
        if cache is not None:
            cache[key] = plan
        return plan

    def _fetch_plan(self, start_nanos: int, end_nanos: int
                    ) -> list[tuple[str, int, int]]:
        """Finest-first fetch specs [(namespace, lo, hi)], hi
        inclusive.  Without a planner: every fan-out namespace over the
        full range.  With one: each ladder tier clamped at its
        retention horizon; aggregated namespaces OUTSIDE the ladder
        keep the plain full-range fan-out, ranked by resolution."""
        plan = self._plan(start_nanos, end_nanos)
        if plan is None:
            return [(ns, start_nanos, end_nanos)
                    for ns in self._resolve_namespaces()]
        entries = [(f.resolution, f.namespace, f.lo, f.hi)
                   for f in plan.fetches]
        planned = self.planner.namespaces()
        for ns in self._resolve_namespaces():
            if ns in planned:
                continue
            res = (0 if ns == self.ns
                   else self.db.namespace_options(ns).aggregation_resolution)
            entries.append((res, ns, start_nanos, end_nanos))
        entries.sort(key=lambda e: e[0])
        return [(ns, lo, hi) for _, ns, lo, hi in entries]

    def _ladder_lookbacks(self, step_times) -> np.ndarray | None:
        """Per-step consolidation lookback under a retention ladder:
        a step inside a coarse band sees one sample per rung
        resolution, so its lookback widens to 2x that resolution or
        instant vectors go NaN right after every seam (the lookback
        re-anchoring half of seam handling; ordering is the stitch's).
        Returns None when every step keeps the base lookback — the
        bit-for-bit-preserving case."""
        if self.planner is None or len(step_times) == 0:
            return None
        ts = np.asarray(step_times, dtype=np.int64)
        plan = self._plan(int(ts[0]) - self.lookback, int(ts[-1]))
        res = np.zeros(len(ts), dtype=np.int64)
        for band in plan.bands:
            m = (ts >= band.lo) & (ts <= band.hi)
            if band.resolution:
                res[m] = band.resolution
        if not res.any():
            return None
        return np.maximum(self.lookback, 2 * res)

    # --- fetch + decode ---

    @property
    def last_fetch_stats(self) -> dict | None:
        """Stats of the calling thread's most recent serving path
        (phase seconds so far, stream and datapoint counts,
        ``device_serving``): the thread's own, whatever other server
        threads run through this engine meanwhile."""
        return self._cost().stats

    @last_fetch_stats.setter
    def last_fetch_stats(self, stats: dict | None) -> None:
        self._cost().stats = stats

    def _gather(self, matchers, start_nanos: int, end_nanos: int):
        """Collect the namespace fan-out's raw block payloads without
        decoding: -> (labels, parts, rows).

        parts[i] = (slot, tier, times, values, kind, after) rows that
        arrive as arrays: ``open`` a read of an open buffer, ``decoded``
        a block some cache or replica merge already decoded, ``cold`` a
        sealed stream merged on the host with a cold write beside it;
        ``after`` counts the compressed rows emitted before it;
        `rows` the compressed ones, as columns (``StreamRows``: stream,
        slot, tier, stored dp count).  Both arrive slot-grouped within
        a tier, block time ascending within a slot — the merge
        contract shared by the host and device serving tiers.

        The walk is the ``fetch`` phase: ``Database.fetch_tagged``
        passes each shard once, block by block, under the database
        lock, over tables that the writers of that state keep (the
        seal a block's sid -> row table, the flush the shard's fileset
        listing), and hands the rows over as columns, which the walk
        puts into this order with one sort a tier; nothing of it
        outlives the query.  Open buffers are only named during it
        (``OpenRow``: the buffer's view at that moment); reading them
        out, all lanes of a view in one call, is the ``open_read``
        phase that follows.
        """
        cost = self._cost()
        with cost.phase("fetch"):
            labels, parts, rows, named, ns_bytes = self._gather_walk(
                matchers, start_nanos, end_nanos)
        if named:
            with cost.phase("open_read"):
                self._read_open_rows(parts, named, ns_bytes)
        cost.gather_bytes = sum(ns_bytes.values())
        if self.planner is not None and ns_bytes:
            # per-rung read-bytes accounting (grafana panel 45): label
            # by declared resolution, "raw" for the unaggregated tier
            fam = instrument.bounded_counter(
                "m3_query_rung_read_bytes_total", cap=32)
            for ns, nb in ns_bytes.items():
                res = self.db.namespace_options(ns).aggregation_resolution
                lab = format_duration(res) if res else "raw"
                fam.labels(resolution=lab).inc(nb)
        return labels, parts, rows

    @staticmethod
    def _read_open_rows(parts: list, named: list, ns_bytes: dict) -> None:
        """Read the named open rows out of their buffers' views into
        ``parts``, whose ``None`` placeholders they fill; a lane with
        nothing in a buffer leaves no row."""
        for view, mine in by_view(named, lambda item: item[-1]):
            read = view.read_lanes([item[-1].lane for item in mine])
            for (at, ns, slot, tier, after, _row), (times, values) in zip(
                    mine, read):
                if len(times):
                    parts[at] = (slot, tier, times, values, "open", after)
                    ns_bytes[ns] = ns_bytes.get(ns, 0) + 16 * len(times)
        parts[:] = [p for p in parts if p is not None]

    def _gather_walk(self, matchers, start_nanos: int, end_nanos: int):
        """-> (labels, parts, rows, named, ns_bytes): the gather's walk
        over the fan-out; parts[at] is ``None`` for each (at, ns, slot,
        tier, after, OpenRow) of `named`.

        A tier's rows come as columns (``Gathered``) and are laid into
        a grid, a series' place in the tier by block start
        (``_lay_tier``); read row by row, the grid is the order a loop
        over the tier's series, sids ascending, and over each one's
        blocks would emit.  Slots are numbered by first sight across
        tiers.  Nothing runs once a series or once a row in the
        interpreter, but the rows of a ``MIXED`` block (a cold write
        beside a sealed stream), which are told apart one by one and
        counted as ``by_row``; and everything a row is done by
        builtins that keep the interpreter lock: an array call on a
        tier's rows would let go of it, and under other queries' load
        wait a switch interval to have it back, once a call."""
        labels: list[dict[bytes, bytes]] = []
        seen: list[bytes] = []      # the sid of each slot
        parts: list = []
        named: list[tuple] = []
        # the compressed rows' columns
        streams, slots, tiers, counts = [], [], [], []
        limits = getattr(self._qrange_local, "limits", None)
        meta = getattr(self._qrange_local, "meta", None)
        ns_bytes: dict[str, int] = {}
        cost = self._cost()
        for tier, (ns, lo, hi) in enumerate(
                self._fetch_plan(start_nanos, end_nanos)):
            if limits is not None:
                limits.check_deadline("gather")
            try:
                # +1: storage ranges are right-exclusive but a sample at
                # exactly end_nanos resolves at that instant (an eval at
                # the first block's very first timestamp must see it)
                gathered = self.db.fetch_tagged(
                    ns, matchers, lo, hi + 1, with_counts=True,
                    limits=limits, meta=meta, defer_open=True)
            except KeyError:
                continue
            cost.fileset_scans += gathered.fileset_scans
            # the slot of each of the tier's series: those an earlier
            # tier saw keep theirs, the others take the next ones
            slot_of = dict(zip(seen, itertools.count()))
            tier_slots = list(map(slot_of.get, gathered.sids))
            is_new = list(map(operator.is_, tier_slots,
                              itertools.repeat(None)))
            deque(map(tier_slots.__setitem__,
                      itertools.compress(itertools.count(), is_new),
                      range(len(seen), len(seen) + sum(is_new))), maxlen=0)
            seen.extend(itertools.compress(gathered.sids, is_new))
            # a copy each: the index's memo is shared
            labels.extend(map(dict, self.db._ns(ns).index.tags_of_many(
                list(itertools.compress(gathered.lanes, is_new)))))
            emitted = len(streams)
            nb = self._lay_tier(gathered.shards, tier_slots, tier, ns,
                                parts, named, streams, slots, counts)
            tiers.extend(itertools.repeat(tier, len(streams) - emitted))
            if nb:
                ns_bytes[ns] = ns_bytes.get(ns, 0) + nb
        try:
            counts = np.fromiter(counts, dtype=np.int64, count=len(counts))
        except TypeError:
            # a None among them (a stream without a stored count)
            # reads nan
            counts = np.asarray(counts, dtype=np.float64)
            counts = np.where(np.isnan(counts), -1, counts).astype(np.int64)
        return (labels, parts, StreamRows(
            streams, np.fromiter(slots, dtype=np.int64, count=len(slots)),
            np.fromiter(tiers, dtype=np.int64, count=len(tiers)), counts),
            named, ns_bytes)

    def _lay_tier(self, shards, tier_slots: list, tier: int, ns: str,
                  parts: list, named: list, streams: list, slots: list,
                  counts: list) -> int:
        """One tier's rows (``Gathered.shards``; `tier_slots` the slot
        of each of the tier's series) appended, in the gather's order,
        to `streams` / `slots` / `counts` (the compressed ones),
        `parts` (arrays) and `named` (open rows, ``None`` standing in
        `parts`).  -> the bytes the rows hold, the open rows' left out.

        The rows are laid into a grid, a line a series of the tier (its
        place among the tier's sids) and a column a block start: every
        block's rows end to end, one scatter a column of rows into the
        grid's cells; the grid read line by line, the cells that hold
        nothing dropped, is the gather's order.  The scatter is an
        object array's (it keeps the interpreter lock); the cells'
        numbers are made a shard at a time, on arrays too short for
        the array library to let go of the lock."""
        starts = sorted({block.block_start for _places, blocks in shards
                         for block in blocks})
        if not starts:
            return 0
        column_of = {bs: k for k, bs in enumerate(starts)}
        laid: list = []             # the blocks' rows end to end
        laid_counts: list = []
        kinds = None    # made by the first block that is not STREAMS
        cells = []
        by_row = 0
        for places, blocks in shards:
            line = np.asarray(places, dtype=np.int64) * len(starts)
            for bs, kind, rows, row_counts in blocks:
                cells.append(line + column_of[bs])
                if kind is not STREAMS:
                    if kinds is None:
                        kinds = [_ROW_STREAM] * len(laid)
                    if kind is MIXED:
                        # a cold write beside sealed streams: a row is
                        # whatever the shard made of the two
                        kinds.extend(
                            _ROW_STREAM if isinstance(p, (bytes, memoryview))
                            else _ROW_OPEN if isinstance(p, OpenRow)
                            else _ROW_ARRAYS for p in rows)
                        by_row += len(rows) - rows.count(None)
                    else:
                        kinds.extend(itertools.repeat(_ROW_OF_KIND[kind],
                                                      len(rows)))
                elif kinds is not None:
                    kinds.extend(itertools.repeat(_ROW_STREAM, len(rows)))
                laid.extend(rows)
                laid_counts.extend(itertools.repeat(None, len(rows))
                              if row_counts is None else row_counts)
        cell = np.concatenate(cells)

        def lines(column: list) -> list:
            """The grid's cells line by line, `column` scattered in."""
            grid = np.full(len(tier_slots) * len(starts), None, dtype=object)
            grid[cell] = np.fromiter(column, dtype=object, count=len(column))
            return grid.tolist()

        columns = [lines(laid),
                   list(itertools.chain.from_iterable(
                       zip(*[tier_slots] * len(starts)))),
                   lines(laid_counts)]
        if kinds is not None:
            columns.append(lines(kinds))
        if None in columns[0]:      # a series absent from a block
            held = list(map(operator.is_not, columns[0],
                            itertools.repeat(None)))
            columns = [list(itertools.compress(c, held)) for c in columns]
        payloads, slot, count = columns[:3]
        self._count_walk_rows(len(payloads) - by_row, by_row)
        if kinds is None:           # compressed rows alone
            streams.extend(payloads)
            slots.extend(slot)
            counts.extend(count)
            return sum(map(len, payloads))
        is_stream = list(map(operator.eq, columns[3],
                             itertools.repeat(_ROW_STREAM)))
        # a row that is no stream comes after the streams before it
        after = list(map(operator.add, itertools.accumulate(is_stream),
                         itertools.repeat(len(streams))))
        emitted = len(streams)
        streams.extend(itertools.compress(payloads, is_stream))
        slots.extend(itertools.compress(slot, is_stream))
        counts.extend(itertools.compress(count, is_stream))
        nb = sum(map(len, itertools.islice(streams, emitted, None)))
        # the rows that arrive as arrays or (open rows) will
        others = list(map(operator.not_, is_stream))
        payloads, slot, count, kind, after = (
            list(itertools.compress(c, others))
            for c in (payloads, slot, count, columns[3], after))
        is_open = list(map(operator.eq, kind, itertools.repeat(_ROW_OPEN)))
        named.extend(itertools.compress(
            zip(itertools.count(len(parts)), itertools.repeat(ns), slot,
                itertools.repeat(tier), after, payloads), is_open))
        rows: list = [None] * len(payloads)
        arrays = list(map(operator.not_, is_open))
        pairs = list(itertools.compress(payloads, arrays))
        times = list(map(operator.itemgetter(0), pairs))
        values = list(map(operator.itemgetter(1), pairs))
        # arrays without a count: the shard merged a sealed stream
        # with the cold write beside it
        names = map(("decoded", "cold").__getitem__,
                    map(operator.is_, itertools.compress(count, arrays),
                        itertools.repeat(None)))
        deque(map(rows.__setitem__,
                  itertools.compress(itertools.count(), arrays),
                  zip(itertools.compress(slot, arrays),
                      itertools.repeat(tier), times, values, names,
                      itertools.compress(after, arrays))), maxlen=0)
        parts.extend(rows)
        nbytes = operator.attrgetter("nbytes")
        return nb + sum(map(nbytes, times)) + sum(map(nbytes, values))

    def _count_walk_rows(self, columns: int, by_row: int) -> None:
        """Rows a walk was handed as columns, and rows it had to tell
        apart one by one: in the query's record and the registry."""
        walked = self._cost().walk_rows
        fam = instrument.bounded_counter("m3_query_walk_rows_total")
        for form, n in (("columns", columns), ("by_row", by_row)):
            walked[form] += n
            fam.labels(form=form).inc(n)

    def _gather_cached(self, matchers, start_nanos: int, end_nanos: int):
        """Per-query gather memo: when the device tier declines a query
        (mutable buffers, unknown counts, ...) the host fallback reuses
        the SAME gather instead of re-walking the index and filesets,
        and a query whose tree repeats a selector (the grouped-rate-
        ratio shape: `sum(rate(x[5m])) / sum(rate(x[5m]))`) gathers it
        once.  Keyed by matcher VALUE (matchers are hashable
        (kind, label, value) tuples), so two independently parsed but
        identical selectors share an entry.  The memo lives on the
        query-scoped thread-local and is released at query end
        (query_range_with_meta's finally), so it can never serve a
        stale storage snapshot to a later query — cross-query caching
        belongs to m3_tpu/cache, which sees invalidations.

        ``_gather`` stamps the walk (``fetch``, then ``open_read``) on
        a miss; a hit costs next to nothing and is stamped nowhere, so
        a query's ``fetch_s`` is the time it spent gathering, each walk
        counted once."""
        return self._gather_memoized(matchers, start_nanos, end_nanos)

    def _gather_memoized(self, matchers, start_nanos: int,
                         end_nanos: int):
        memo = getattr(self._qrange_local, "gather_cache", None)
        if memo is None:
            # no query scope on this thread (a direct _fetch_raw
            # caller, e.g. a live tailer): nothing would ever release
            # a memo, and repeated fetches must see fresh storage
            return self._gather(matchers, start_nanos, end_nanos)
        key = (tuple(matchers), start_nanos, end_nanos)
        ent = memo.get(key)
        if ent is None:
            # cross-query fetch memo (m3_tpu/serving/): two batched
            # queries over the same (ns, selector, window) share one
            # gather + pack instead of walking and packing the same
            # blocks twice.  The shared entry dict is adopted into the
            # query-local memo by reference, so a pack memoized by
            # either query serves both.
            from m3_tpu import serving
            ent = serving.shared_fetch_memo_get(self, key)
            if ent is not None:
                memo[key] = ent
        if ent is not None:
            self._cost().gather_bytes = ent["bytes"]
            return ent["g"]
        from m3_tpu import serving
        try:
            g = self._gather(matchers, start_nanos, end_nanos)
        except BaseException:
            # the miss above reserved the single-flight slot; release
            # it so fleet peers stop waiting on a gather that died
            serving.shared_fetch_memo_abort(self, key)
            raise
        memo[key] = {"g": g, "bytes": self._cost().gather_bytes}
        serving.shared_fetch_memo_put(self, key, memo[key])
        return g

    def _pack_streams_cached(self, matchers, start_nanos: int,
                             end_nanos: int, streams, bucket):
        """Memoize pack_streams output on the gather memo entry, so a
        query that repeats a selector (or a device path that declines
        after packing) skips the host-side re-pack, not just the
        re-gather.  `streams` must be derived deterministically from
        the memoized gather (same ordering), which every caller
        guarantees.  The words come laid out in their jit buckets
        (rows and width rounded up by `bucket`, zero past the
        streams), so the pack is keyed by the gather key and, under
        it, by `bucket`: the per-node tier's and the fused planner's
        differ, and a selector packed for one and asked for by the
        other is packed again."""
        memo = getattr(self._qrange_local, "gather_cache", None)
        key = (tuple(matchers), start_nanos, end_nanos)
        ent = memo.get(key) if memo is not None else None
        packs = {} if ent is None else ent.setdefault("pack", {})
        pack = packs.get(bucket)
        if pack is None:
            from m3_tpu.ops.bitstream import pack_streams
            pack = packs[bucket] = pack_streams(
                streams, pad=lambda n: bucket(n, 64))
        return pack

    def _arrays_grid_cached(self, matchers, start_nanos: int,
                            end_nanos: int, labels, parts):
        """Memoize the arrays-bridge grid (stitch + merge + pad) on the
        gather memo entry, the same way _pack_streams_cached memoizes
        the compressed-words pack.  The grid is derived from the
        memoized gather alone — step-grid-dependent fields (shifted,
        rng) stay OUT of the entry — so every query sharing the gather
        (a repeated selector in one tree, or a batched fleet adopting
        the cross-query fetch memo) shares ONE device-ready grid
        instead of re-stitching and re-padding per query."""
        memo = getattr(self._qrange_local, "gather_cache", None)
        key = (tuple(matchers), start_nanos, end_nanos)
        ent = memo.get(key) if memo is not None else None

        def _assemble():
            from m3_tpu.ops import consolidate as cons
            stitched = self._stitch(parts)
            times, values, counts = cons.merge_packed(stitched,
                                                      len(labels))
            n_lanes = len(labels)
            lanes_pad = qplan._bucket_pow2(n_lanes, 64)
            n_cap = qplan._bucket_pow2(times.shape[1], 128)
            times_p, values_p = cons.pad_grid(times, values, lanes_pad,
                                              n_cap)
            return {
                "times": times_p, "values": values_p,
                "n_lanes": n_lanes, "lanes_pad": lanes_pad,
                "n_cap": n_cap, "n_streams": len(stitched),
                "datapoints": int(counts.sum()),
            }

        with self._cost().phase("pack"):
            if ent is None:
                return _assemble()
            # entries adopted from the cross-query fetch memo are
            # shared by reference across a batched fleet: assemble
            # once, under a per-entry lock (setdefault is atomic),
            # never once per member
            with ent.setdefault("lock", threading.Lock()):
                grid = ent.get("arrays")
                if grid is None:
                    grid = ent["arrays"] = _assemble()
            return grid

    def _check_deadline(self, what: str) -> None:
        """Deadline hop for decode batching: device/host decode of a
        big fan-out starts only while the query still has budget.
        Doubles as the cooperative-cancel checkpoint: an operator
        cancel via /debug/tasks aborts the query here, and the task
        ledger's live phase tracks the checkpoint names."""
        cost = self._cost()
        task = cost.task
        if task is not None:
            task.set_phase(what)
            if (cost.stats or {}).get("device_serving"):
                task.device_tier = "device"
            task.check_cancelled()
        limits = getattr(self._qrange_local, "limits", None)
        if limits is not None:
            limits.check_deadline(what)

    def _fetch_raw(self, matchers, start_nanos: int, end_nanos: int):
        """-> (labels, times [L, N], values [L, N]) batched, decoded,
        stitched across the namespace fan-out."""
        labels, parts, rows = self._gather_cached(
            matchers, start_nanos, end_nanos)
        self._check_deadline("host decode")
        cost = self._cost()
        streams, slots, tiers = rows.streams, rows.slots, rows.tiers
        if rows and not parts and (tiers == tiers[0]).all():
            # hot path (warm node, single namespace, everything served
            # from compressed blocks): fused decode+merge writes every
            # block stream directly into the packed batch — no
            # per-stream grids, no stitch, no repack.  No range clamp:
            # block overfetch leaves a few edge samples outside
            # [start, end], and every consumer (step consolidation,
            # temporal windows) selects samples by time, so they are
            # simply never picked.
            with cost.phase("decode"):
                fused = decode_streams_merged(
                    streams, slots, len(labels),
                    counts=rows.known_counts())
                if fused is None:
                    # out-of-order data / no toolchain: general decode
                    # + merge
                    ts, vs, valid = decode_streams_adaptive(streams)
            if fused is not None:
                times2, values2, lane_counts = fused
                cost.publish(
                    n_streams=len(streams),
                    datapoints=int(lane_counts.sum()),
                    read_bytes=int(cost.gather_bytes))
                return labels, times2, values2
            with cost.phase("merge"):
                times2, values2, _ = cons.merge_grids(
                    slots, ts, vs, valid, len(labels),
                    t_min_excl=start_nanos - 1, t_max_incl=end_nanos)
            cost.publish(
                n_streams=len(streams),
                datapoints=int(np.asarray(valid).sum()),
                read_bytes=int(cost.gather_bytes))
            return labels, times2, values2
        if rows and not parts and _VECTORIZED_STITCH:
            # multi-tier, all-compressed (raw + aggregated namespaces
            # both serving from blocks): vectorized stitch over the
            # decoded grids — per-slot tier cuts computed with
            # minimum-scatters, then one merge — instead of the
            # per-(series, block) fragment slicing below
            with cost.phase("decode"):
                ts, vs, valid = decode_streams_adaptive(
                    streams, counts=rows.known_counts())
            with cost.phase("merge"):
                valid = np.array(valid)  # writable: cuts mask rows below
                n_lanes = len(labels)
                cut = np.full(n_lanes, cons._INF, dtype=np.int64)
                for tier in np.unique(tiers):  # ascending = finest first
                    at = np.nonzero(tiers == tier)[0]
                    keep = valid[at] & (
                        ts[at] < cut[slots[at]][:, None])
                    valid[at] = keep
                    row_min = np.where(keep, ts[at],
                                       cons._INF).min(axis=1)
                    np.minimum.at(cut, slots[at], row_min)
                times2, values2, _ = cons.merge_grids(
                    slots, ts, vs, valid, n_lanes,
                    t_min_excl=start_nanos - 1, t_max_incl=end_nanos)
            cost.publish(
                n_streams=len(streams),
                datapoints=int(valid.sum()),
                read_bytes=int(cost.gather_bytes),
                tiers=int(len(np.unique(tiers))))
            return labels, times2, values2
        # mutable buffers in the mix: decode, stitch, merge and clamp
        # as one step (the fragments interleave), filed under decode
        with cost.phase("decode"):
            if rows:
                ts, vs, valid = decode_streams_adaptive(streams)
                # copy: `parts` may be the list held by the gather
                # cache — appending in place would poison a later cache
                # hit with doubled (raw + decoded) fragments
                parts = list(parts)
                for i, (slot, tier, _) in enumerate(rows.triples()):
                    sel = valid[i]
                    parts.append((slot, tier, ts[i][sel], vs[i][sel],
                                  "decoded", i))
            raw_parts = self._stitch(parts)
            times, values, _counts = cons.merge_packed(raw_parts,
                                                       len(labels))
            # clamp to the query range (blocks overfetch)
            inside = ((times > start_nanos - 1) & (times <= end_nanos)
                      | (times == cons._INF))
            values = np.where(inside, values, np.nan)
            tmask = inside & (times != cons._INF)
            times2, values2, _ = cons.pack_valid(times, values, tmask)
        cost.publish(
            n_streams=len(parts),  # raw + decoded-compressed fragments
            datapoints=int(tmask.sum()),
            read_bytes=int(cost.gather_bytes))
        return labels, times2, values2

    @staticmethod
    def _stitch(parts):
        """Per-series cross-namespace stitch: a coarser tier contributes
        only samples strictly OLDER than the earliest sample of any
        finer tier (raw data wins wherever present)."""
        # single-tier fast path (no aggregated namespaces matched): no
        # cut computation needed, merge_packed handles fragment order
        if parts and all(p[1] == parts[0][1] for p in parts):
            return [(slot, t, v) for slot, _tier, t, v, *_ in parts
                    if len(t)]
        by_slot: dict[int, dict[int, list]] = defaultdict(lambda: defaultdict(list))
        for slot, tier, t, v, *_ in parts:
            if len(t):
                by_slot[slot][tier].append((t, v))
        out = []
        for slot, tiers in by_slot.items():
            t_cut = None
            for tier in sorted(tiers):
                t = np.concatenate([p[0] for p in tiers[tier]])
                v = np.concatenate([p[1] for p in tiers[tier]])
                if t_cut is not None:
                    keep = t < t_cut
                    t, v = t[keep], v[keep]
                if not len(t):
                    continue
                out.append((slot, t, v))
                lo = int(t.min())
                t_cut = lo if t_cut is None else min(t_cut, lo)
        return out

    def _eval_times(self, node, step_times) -> np.ndarray:
        """Per-step evaluation timestamps for a selector/subquery:
        offset shifts them; an @ modifier pins every step to one fixed
        instant (start()/end() resolve against the OUTER query range,
        upstream semantics — constant even inside subqueries)."""
        ts = np.asarray(step_times, dtype=np.int64)
        at = getattr(node, "at_nanos", None)
        if at is not None:
            if at in ("start", "end"):
                # per-THREAD query range: one Engine serves concurrent
                # HTTP queries (ThreadingHTTPServer), and eval runs
                # synchronously on the querying thread
                qrange = self._qrange_local.value
                at = qrange[0] if at == "start" else qrange[1]
            ts = np.full_like(ts, int(at))
        return ts - node.offset_nanos

    def _fetch_consolidated(self, node: promql.Selector, step_times):
        shifted = self._eval_times(node, step_times)
        lbs = self._ladder_lookbacks(shifted)
        if lbs is None:
            if self._device_serving_active():
                # instant-vector consolidation IS last_over_time with
                # the engine lookback as the window: ride the device
                # reduce pipeline, compressed blocks in,
                # [series, steps] out
                served = self._device_temporal(node, step_times,
                                               "last_over_time",
                                               range_nanos=self.lookback)
                if served is not None:
                    return Matrix(served[0], served[1])
            labels, times, values = self._fetch_raw(
                node.matchers, int(shifted[0]) - self.lookback,
                int(shifted[-1]))
            vals = cons.step_consolidate(times, values, shifted,
                                         self.lookback)
            return Matrix(labels, vals)
        # retention-ladder path: steps in coarse bands consolidate with
        # a widened lookback (seam re-anchoring); steps still inside
        # raw retention keep the base lookback, so results there stay
        # bit-identical to the raw-only evaluation
        labels, times, values = self._fetch_raw(
            node.matchers, int(shifted[0]) - int(lbs.max()),
            int(shifted[-1]))
        vals = np.empty((len(labels), len(shifted)), dtype=np.float64)
        for lb in np.unique(lbs):
            idx = np.nonzero(lbs == lb)[0]
            vals[:, idx] = cons.step_consolidate(
                times, values, shifted[idx], int(lb))
        return Matrix(labels, vals)

    # --- evaluation ---

    def eval(self, node, step_times: np.ndarray):
        if isinstance(node, (promql.Call, promql.Agg, promql.BinOp,
                             promql.Selector)):
            fused = self._try_fused(node, step_times)
            if fused is not None:
                return fused
        if isinstance(node, promql.Scalar):
            return node.value
        if isinstance(node, promql.Selector):
            if node.range_nanos:
                raise ValueError("range selector outside a temporal function")
            return self._fetch_consolidated(node, step_times)
        if isinstance(node, promql.Call):
            return self._eval_call(node, step_times)
        if isinstance(node, promql.Agg):
            return self._eval_agg(node, step_times)
        if isinstance(node, promql.BinOp):
            return self._eval_binop(node, step_times)
        if isinstance(node, promql.Subquery):
            raise ValueError("subquery outside a temporal function")
        raise ValueError(f"unknown node {node}")

    def _scalar_arg(self, node, step_times) -> float | np.ndarray:
        v = self.eval(node, step_times)
        if isinstance(v, Matrix):
            raise ValueError("expected a scalar argument")
        return v

    def _range_samples(self, arg, step_times):
        """Materialize raw samples for a range vector or subquery:
        -> (labels, times [L, N], values [L, N], range_nanos)."""
        if isinstance(arg, promql.Selector) and arg.range_nanos:
            shifted = self._eval_times(arg, step_times)
            rng = arg.range_nanos
            labels, times, values = self._fetch_raw(
                arg.matchers, int(shifted[0]) - rng, int(shifted[-1])
            )
            return labels, times, values, rng, shifted
        if isinstance(arg, promql.Subquery):
            shifted = self._eval_times(arg, step_times)
            rng = arg.range_nanos
            sub_step = arg.step_nanos or DEFAULT_SUBQUERY_STEP
            lo = int(shifted[0]) - rng
            hi = int(shifted[-1])
            # inner grid aligned to the subquery step (upstream aligns
            # to absolute multiples of the step)
            first = lo - lo % sub_step + (sub_step if lo % sub_step else 0)
            sub_times = np.arange(first, hi + 1, sub_step, dtype=np.int64)
            if len(sub_times) == 0:
                sub_times = np.asarray([hi], dtype=np.int64)
            inner = self.eval(arg.expr, sub_times)
            if not isinstance(inner, Matrix):
                inner = Matrix([{}], np.full((1, len(sub_times)), float(inner)))
            grid_t = np.tile(sub_times, (len(inner.labels), 1))
            times, values, _ = cons.pack_valid(
                grid_t, inner.values, ~np.isnan(inner.values)
            )
            return inner.labels, times, values, rng, shifted
        raise ValueError("expected a range vector, e.g. x[5m]")

    def _eval_call(self, node: promql.Call, step_times):
        fn = node.fn
        step_times = np.asarray(step_times, dtype=np.int64)
        if fn in promql.TEMPORAL_FNS:
            return self._eval_temporal(node, step_times)
        if fn in promql.SCALAR_FNS:
            return self._eval_scalar_fn(node, step_times)
        if fn == "time":
            return step_times.astype(np.float64) / 1e9
        if fn == "scalar":
            mat = self.eval(node.args[0], step_times)
            if not isinstance(mat, Matrix) or len(mat.labels) != 1:
                return np.full(len(step_times), np.nan)
            return mat.values[0]
        if fn == "vector":
            v = self._scalar_arg(node.args[0], step_times)
            row = np.broadcast_to(np.asarray(v, dtype=np.float64),
                                  (len(step_times),))
            return Matrix([{}], row[None, :].copy())
        if fn == "absent":
            mat = self.eval(node.args[0], step_times)
            present = (
                ~np.isnan(mat.values).all(axis=0)
                if isinstance(mat, Matrix) and len(mat.labels)
                else np.zeros(len(step_times), dtype=bool)
            )
            vals = np.where(present, np.nan, 1.0)[None, :]
            return Matrix([{}], vals)
        if fn == "histogram_quantile":
            return self._histogram_quantile(node, step_times)
        if fn == "absent_over_time":
            labels, times, values, rng, shifted = self._range_samples(
                node.args[0], step_times)
            left, right = cons._window_bounds(
                times, cons._range_left(shifted, rng), shifted)
            any_present = (
                (right > left).any(axis=0)
                if len(labels)
                else np.zeros(len(step_times), dtype=bool)
            )
            vals = np.where(any_present, np.nan, 1.0)[None, :]
            return Matrix([{}], vals)
        if fn in ("label_replace", "label_join"):
            return self._eval_label_fn(node, step_times)
        if fn in ("sort", "sort_desc"):
            mat = self.eval(node.args[0], step_times)
            if not isinstance(mat, Matrix) or not len(mat.labels):
                return mat
            # prometheus sorts instant vectors by value; for a range
            # result the last step's value is the sort key
            last = np.where(np.isnan(mat.values[:, -1]),
                            -np.inf if fn == "sort_desc" else np.inf,
                            mat.values[:, -1])
            order = np.argsort(last, kind="stable")
            if fn == "sort_desc":
                order = order[::-1]
            return Matrix([mat.labels[i] for i in order], mat.values[order])
        if fn in promql.CALENDAR_FNS:
            return self._eval_calendar(node, step_times)
        raise ValueError(f"unsupported function {fn}")

    def _eval_label_fn(self, node: promql.Call, step_times):
        def s(i):
            a = node.args[i]
            if not isinstance(a, promql.StringLit):
                raise ValueError(f"{node.fn}() argument {i} must be a string")
            return a.value

        mat = self.eval(node.args[0], step_times)
        if not isinstance(mat, Matrix):
            raise ValueError(f"{node.fn}() expects an instant vector")
        if node.fn == "label_replace":
            dst, repl, src, regex = s(1), s(2), s(3), s(4)
            rx = re.compile(regex)
            out_labels = []
            for ls in mat.labels:
                val = ls.get(src.encode(), b"").decode("utf-8", "replace")
                m = rx.fullmatch(val)
                new = dict(ls)
                if m is not None:
                    expanded = expand_go(m, repl)
                    if expanded:
                        new[dst.encode()] = expanded.encode()
                    else:
                        new.pop(dst.encode(), None)
                out_labels.append(new)
            return Matrix(out_labels, mat.values)
        # label_join(v, dst, sep, src...)
        dst, sep = s(1), s(2)
        srcs = [s(i) for i in range(3, len(node.args))]
        out_labels = []
        for ls in mat.labels:
            joined = sep.join(
                ls.get(n.encode(), b"").decode("utf-8", "replace")
                for n in srcs)
            new = dict(ls)
            if joined:
                new[dst.encode()] = joined.encode()
            else:
                new.pop(dst.encode(), None)
            out_labels.append(new)
        return Matrix(out_labels, mat.values)

    def _eval_calendar(self, node: promql.Call, step_times):
        """minute/hour/day_of_week/day_of_month/days_in_month/month/year
        — batched UTC calendar decomposition of epoch-second values
        (default argument: vector(time()))."""
        if node.args:
            mat = self.eval(node.args[0], step_times)
            if not isinstance(mat, Matrix):
                raise ValueError(f"{node.fn}() expects an instant vector")
            labels, secs = mat.labels, mat.values
        else:
            labels = [{}]
            secs = (np.asarray(step_times, np.float64) / 1e9)[None, :]
        nan = np.isnan(secs)
        s64 = np.where(nan, 0, np.floor(secs)).astype(np.int64)
        days = s64 // 86400
        fn = node.fn
        if fn == "minute":
            out = (s64 // 60) % 60
        elif fn == "hour":
            out = (s64 // 3600) % 24
        elif fn == "day_of_week":
            out = (days + 4) % 7  # 1970-01-01 was a Thursday
        else:
            d64 = days.astype("datetime64[D]")
            m64 = d64.astype("datetime64[M]")
            if fn == "month":
                out = m64.astype(np.int64) % 12 + 1
            elif fn == "year":
                out = 1970 + d64.astype("datetime64[Y]").astype(np.int64)
            elif fn == "day_of_month":
                out = (d64 - m64.astype("datetime64[D]")).astype(np.int64) + 1
            else:  # days_in_month
                out = ((m64 + 1).astype("datetime64[D]")
                       - m64.astype("datetime64[D]")).astype(np.int64)
        vals = np.where(nan, np.nan, out.astype(np.float64))
        return Matrix(labels, vals).drop_name()

    def _try_fused(self, node, step_times):
        """Whole-query fused device execution (query/plan.py): lower
        this subtree into ONE compiled program — decode, consolidate,
        and the full op-tree run on device with a single host transfer.
        Returns None when the planner declines (unsupported node,
        host-only payloads, too small to pay off) and the caller's
        per-node paths serve exactly as before.  Hooked at the top of
        every eval() recursion, so a query that splits at an
        unsupported node (subquery, topk, label_replace, ...) retries
        fusion on each supported subtree underneath it."""
        if not self._device_serving_active():
            return None
        cost = self._cost()
        if cost.fused_poisoned:
            # a fused attempt already hit a decode-error fallback this
            # query: serve the rest on the host instead of re-running
            # the failing device program for every subtree
            return None
        if self.planner is not None and self._ladder_lookbacks(
                np.asarray(step_times, dtype=np.int64)) is not None:
            # steps land in coarse rung bands: the fused pipeline
            # consolidates with the base lookback only, so the host
            # path's per-band widening must serve this query
            cost.split("retention_coarse_lookback")
            return None
        try:
            return qplan.serve_fused(self, node, step_times)
        except qplan.Unsupported as exc:
            # every host split is countable by cause: a bounded slug
            # per decline reason (the slowlog only shows examples)
            cost.split(getattr(exc, "reason", "unknown_node"))
            return None
        except (observe.QueryCancelled, QueryDeadlineExceeded):
            # cooperative cancel / deadline raised inside the fused
            # path (e.g. a batch-window wait): abort the query — a
            # host retry would just burn more of a dead budget
            raise
        except Exception as exc:  # noqa: BLE001 — never fail a query
            # that the host tier can still answer; keep the reason for
            # the slow-query record
            cost.fused_error = f"{type(exc).__name__}: {exc}"[:200]
            return None

    def _device_serving_active(self) -> bool:
        """Whether rate() fan-outs route through the on-device pipeline.

        Explicit True/False (ctor / M3_DEVICE_SERVING) wins.  Auto mode
        asks JAX which backend this process runs on: an accelerator
        enables the device tier; on the CPU backend (JAX_PLATFORMS=cpu
        deployments, the test suite) the native host tier is faster
        than XLA:CPU, so auto never picks cpu."""
        if self.device_serving is not None:
            return self.device_serving
        import jax
        return jax.default_backend() != "cpu"

    @staticmethod
    def _bucket(n: int, q: int) -> int:
        """Round up to a multiple of q — static jit shapes must bucket
        or every query size compiles a fresh program."""
        return max(q, ((n + q - 1) // q) * q)

    # temporal functions with a device form (the full family).
    # quantile_over_time is absent from this set only because its
    # selector sits at args[1] — it takes its own device gate in
    # _eval_temporal, size-capped by _QOT_MAX_ELEMENTS (its window
    # grid is O(lanes*steps*samples); big fan-outs keep the host
    # native kernel)
    _DEVICE_TEMPORAL = frozenset(
        ("rate", "increase", "delta", "sum_over_time", "avg_over_time",
         "count_over_time", "present_over_time", "last_over_time",
         "irate", "idelta", "min_over_time", "max_over_time",
         "changes", "resets", "deriv", "predict_linear",
         "stddev_over_time", "stdvar_over_time", "holt_winters"))

    def _device_gather_pack(self, rv, step_times, range_nanos=None,
                            bucket=None):
        """Shared front half of every device serving path: gather the
        compressed blocks for a selector and pack them into the padded,
        statically-bucketed arrays the jitted pipelines take.
        `range_nanos` overrides the selector's range (instant-vector
        serving passes the engine lookback).  `bucket` overrides the
        shape quantizer (the fused whole-query compiler passes its
        power-of-two bucketing so a cardinality sweep lands in a
        handful of compiled programs; default: linear _bucket).

        Returns (pk, None), pk a dict with the packed numpy arrays plus
        the shape metadata, or (None, reason) for what cannot be
        packed: ``cold_overlay`` (a block the shard merged on the host
        because a cold write lies beside its sealed stream),
        ``open_multi_tier`` (array rows in a multi-namespace fan-out),
        ``empty``, ``unknown_counts``.  The per-node tier then falls
        back to the host and counts the reason (``QueryCost.decline``);
        the fused planner tries its arrays bridge first.  The gather is
        the ``fetch`` phase, laying the rows that arrive as arrays
        (open buffers, decoded blocks) out beside the words is
        ``open_read`` like the reading of them, and everything else
        here is ``pack``."""
        bucket = self._bucket if bucket is None else bucket
        shifted = self._eval_times(rv, step_times)
        rng = rv.range_nanos if range_nanos is None else range_nanos
        # cached: on fallback, _range_samples -> _fetch_raw reuses this
        # exact gather (same matchers, same range) for free
        lo, hi = int(shifted[0]) - rng, int(shifted[-1])
        gathered = self._gather_cached(rv.matchers, lo, hi)
        cost = self._cost()
        with cost.phase("pack"):
            pk, why = self._pack_gathered(rv, shifted, rng, lo, hi,
                                          gathered, bucket)
        if pk is not None and gathered[1]:
            with cost.phase("open_read"):
                self._pack_array_rows(pk, gathered[1], bucket)
        return pk, why

    @staticmethod
    def _pack_array_rows(pk, parts, bucket) -> None:
        """Lay the rows that arrived as arrays out for the program, in
        ``pk["open"]``: times, values [R, n_dp] (a row's samples first,
        the row width of the decoded sealed rows, so that the program's
        shape does not follow the tail's length), counts and slots [R],
        and the order [M + R] that puts the decoded rows and these, laid
        end to end, back into the gather's: grouped by slot, each
        lane's rows block-ascending."""
        n_dp, m_pad = pk["n_dp"], len(pk["nbits"])
        r_pad = bucket(len(parts), 64)
        times = np.zeros((r_pad, n_dp), dtype=np.int64)
        values = np.zeros((r_pad, n_dp), dtype=np.float64)
        counts = np.zeros(r_pad, dtype=np.int32)
        # padding rows hold nothing and park on the last padding lane
        slots = np.full(r_pad, pk["lanes_pad"] - 1, dtype=np.int64)
        # sealed row k sorts at 2k + 1, an array row emitted after k of
        # them at 2k, padding last
        key = np.full(m_pad + r_pad, 2 * (m_pad + r_pad), dtype=np.int64)
        key[:pk["n_streams"]] = 2 * np.arange(pk["n_streams"]) + 1
        n = len(parts)
        slots[:n] = [p[0] for p in parts]
        counts[:n] = [len(p[2]) for p in parts]
        key[m_pad:m_pad + n] = [2 * p[5] for p in parts]
        # all rows at once: the cells a row fills, in row-major order,
        # are the rows' samples end to end
        cells = np.arange(n_dp)[None, :] < counts[:n, None]
        times[:n][cells] = np.concatenate([p[2] for p in parts])
        values[:n][cells] = np.concatenate([p[3] for p in parts])
        order = np.argsort(key, kind="stable")
        pk["open"] = (times, values, counts, slots, order)
        pk["n_rows"] = pk["n_streams"] + len(parts)
        pk["open_rows"] = sum(p[4] == "open" for p in parts)
        pk["datapoints"] += int(counts.sum())

    def _pack_gathered(self, rv, shifted, rng, lo, hi, gathered,
                       bucket):
        labels, parts, rows = gathered
        if any(p[4] == "cold" for p in parts):
            # the shard already decoded and merged this block on the
            # host: the host answers
            return None, "cold_overlay"
        if not (rows or parts) or not labels:
            return None, "empty"
        counts_np = rows.known_counts()
        if counts_np is None:
            return None, "unknown_counts"
        # the walk's own columns: nothing is unpicked a row
        streams, slots_np = rows.streams, rows.slots
        uniq_tiers = np.unique(rows.tiers)
        n_tiers = max(len(uniq_tiers), 1)
        ranks_np = None
        if parts and len(set(uniq_tiers.tolist())
                         | {p[1] for p in parts}) > 1:
            # the device's tier cut reads the decoded rows alone
            return None, "open_multi_tier"
        if n_tiers > 1:
            # multi-tier fan-out: the device pipelines run the stitch
            # cut themselves (_tier_cut).  Rows must arrive grouped by
            # slot with COARSEST tier first within a slot (the cut
            # guarantees coarse samples precede the finest tier's
            # earliest sample, keeping merged lanes time-ascending) and
            # block-ascending within (slot, tier) — the gather's
            # original order, preserved by the stable lexsort
            ranks_np = np.searchsorted(uniq_tiers, rows.tiers)
            order = np.lexsort(
                (np.arange(len(streams)), -ranks_np, slots_np))
            streams = list(map(streams.__getitem__, order.tolist()))
            slots_np = slots_np[order]
            counts_np = counts_np[order]
            ranks_np = ranks_np[order]
        n_lanes = len(labels)
        # rows that arrive as arrays count towards the same budgets
        part_counts = np.asarray([len(p[2]) for p in parts], dtype=np.int64)
        part_slots = np.asarray([p[0] for p in parts], dtype=np.int64)
        per_lane = (
            np.bincount(slots_np, counts_np, n_lanes)
            + np.bincount(part_slots, part_counts, n_lanes)).astype(np.int64)
        # the widest lane's rows: the rounds of the merge's inner loop
        rows_per_lane = int((np.bincount(slots_np, minlength=n_lanes)
                             + np.bincount(part_slots, minlength=n_lanes)
                             ).max(initial=0))
        # static shape buckets (jit cache keys): stream count, words
        # width, lanes, per-stream and per-lane sample budgets, steps
        n_dp = bucket(int(max(counts_np.max(initial=0),
                              part_counts.max(initial=0))), 128)
        n_cap = bucket(int(per_lane.max()), 128)
        lanes_pad = bucket(n_lanes, 64)
        m_pad = bucket(len(streams), 64)
        s_pad = bucket(len(shifted), 64)
        # pack memo: the multi-tier reorder above is deterministic from
        # the memoized gather, so the gather key and the bucket identify
        # the packed words, which come padded to [m_pad, bucketed width]
        # (a repeated selector skips the host-side re-pack too)
        words_p, nbits_p = self._pack_streams_cached(
            rv.matchers, lo, hi, streams, bucket)
        # padding streams (nbits=0, immediately done) park on the last
        # padding lane; lanes_pad > n_lanes is guaranteed only when
        # padding streams exist, so force one spare lane if needed
        # (re-bucketed so pow2 quantizers stay pow2)
        if ((m_pad > len(streams) or bucket(len(parts), 64) > len(parts) > 0)
                and lanes_pad == n_lanes):
            lanes_pad = bucket(n_lanes + 1, 64)
        slots_p = np.full(m_pad, lanes_pad - 1, dtype=np.int64)
        slots_p[:len(streams)] = slots_np
        steps_p = np.full(s_pad, shifted[-1], dtype=np.int64)
        steps_p[:len(shifted)] = shifted
        tiers_p = None
        if ranks_np is not None:
            # padding rows decode to zero valid cells: any rank is inert
            tiers_p = np.zeros(m_pad, dtype=np.int64)
            tiers_p[:len(streams)] = ranks_np
        return {
            "labels": labels, "shifted": shifted, "rng": rng,
            "words": words_p, "nbits": nbits_p, "slots": slots_p,
            "steps": steps_p, "n_dp": n_dp, "n_cap": n_cap,
            "lanes_pad": lanes_pad, "n_lanes": n_lanes,
            "rows_per_lane": rows_per_lane,
            "n_streams": len(streams),
            "datapoints": int(counts_np.sum()),
            "tiers": tiers_p, "n_tiers": n_tiers,
            # the rows handed to the program, and how many of them come
            # from open buffers (_pack_array_rows)
            "open": None, "n_rows": len(streams), "open_rows": 0,
        }, None

    @staticmethod
    def _rows_flagged(pk, err_np) -> bool:
        """Whether the program flagged a row it was really given: the
        packed streams, then (past the stream padding) the rows that
        arrived as arrays."""
        real = pk.get("real_rows")
        if real is not None:
            return bool(err_np[real].any())
        m_pad = len(pk["nbits"])
        return bool(err_np[:pk["n_streams"]].any()
                    or err_np[m_pad:m_pad + pk["n_rows"]
                              - pk["n_streams"]].any())

    def _shard_repack(self, pk, n_shards: int):
        """Re-lay a packed batch for the shard_map'd pipelines: equal
        lanes and equal stream rows per shard.  Lanes partition into
        contiguous ranges (shard = lane // local_lanes), and since the
        gather emits streams slot-grouped ascending, each shard's
        stream rows are a contiguous range of the packed array.
        Padding rows (nbits=0, decode to zero samples) park on each
        shard's last local lane; `real_rows` marks the original
        streams for the error-flag check."""
        m = pk["n_streams"]
        words, nbits = pk["words"][:m], pk["nbits"][:m]
        slots = pk["slots"][:m]
        tiers = None if pk["tiers"] is None else pk["tiers"][:m]
        local_lanes = self._shard_lanes(pk["lanes_pad"], n_shards)
        lanes_pad = local_lanes * n_shards
        shard_ids = slots // local_lanes
        counts = np.bincount(shard_ids, minlength=n_shards)
        per_m = self._bucket(max(int(counts.max()), 1), 8)
        words_s = np.zeros((n_shards * per_m, words.shape[1]),
                           dtype=words.dtype)
        nbits_s = np.zeros(n_shards * per_m, dtype=nbits.dtype)
        slots_s = np.full(n_shards * per_m, local_lanes - 1,
                          dtype=np.int64)
        tiers_s = (None if tiers is None
                   else np.zeros(n_shards * per_m, dtype=np.int64))
        real = np.zeros(n_shards * per_m, dtype=bool)
        start = 0
        for k in range(n_shards):
            c = int(counts[k])
            src = slice(start, start + c)
            dst = slice(k * per_m, k * per_m + c)
            words_s[dst] = words[src]
            nbits_s[dst] = nbits[src]
            slots_s[dst] = slots[src] - k * local_lanes
            if tiers_s is not None:
                tiers_s[dst] = tiers[src]
            real[dst] = True
            start += c
        return {**pk, "words": words_s, "nbits": nbits_s,
                "slots": slots_s, "lanes_pad": lanes_pad,
                "tiers": tiers_s, "real_rows": real}

    def _shard_lanes(self, lanes_pad: int, n_shards: int) -> int:
        """Lanes a shard holds of a packed batch: all of them on one
        chip, an even split once _shard_repack has re-laid it."""
        if n_shards == 1:
            return lanes_pad
        return self._bucket(-(-lanes_pad // n_shards), 8)

    def _serving_shards(self) -> int:
        from m3_tpu.parallel.mesh import SERIES_AXIS
        mesh = self.serving_mesh
        if mesh is None or SERIES_AXIS not in mesh.shape:
            return 1
        return int(mesh.shape[SERIES_AXIS])

    # quantile_over_time materializes a [lanes, steps, samples] window
    # grid on device — and not just once: _quantile_window_device
    # holds ~5 grid-shaped temporaries live at peak (the int64 window
    # index grid, the gathered f64 value grid, the in-window presence
    # mask promoted to the sort key width, and the XLA sort's
    # input+output copies of the value grid).  Budget the PEAK, not
    # one f64 grid: 256MB HBM budget / (8B * 5 grids) ≈ 6.7M elements
    # per device; bigger fan-outs keep the host native kernel
    _QOT_HBM_BUDGET_BYTES = 256 * 1024 * 1024
    _QOT_GRID_TEMPORARIES = 5
    _QOT_MAX_ELEMENTS = _QOT_HBM_BUDGET_BYTES // (8 * _QOT_GRID_TEMPORARIES)

    def _device_pack(self, rv, step_times, range_nanos):
        """Front half of the per-node device tier's one dispatch:
        gather and pack `rv`.  Returns (pk, n_shards), the batch as it
        was packed (before any shard re-lay) and the serving mesh's
        series shards, or None to fall back to the host tier (what
        _device_gather_pack cannot pack; open rows with a mesh), the
        cause counted."""
        pk, why = self._device_gather_pack(rv, step_times, range_nanos)
        if pk is None:
            self._cost().decline(why)
            return None
        self._check_deadline("device decode")
        n_shards = self._serving_shards()
        if n_shards > 1 and pk["open"] is not None:
            self._cost().decline("open_rows_sharded")
            return None
        return pk, n_shards

    def _device_run(self, pk, n_shards: int, fn: str, stats: dict,
                    groups=None, **params):
        """Back half of the dispatch, the one place that calls the
        per-node programs (models/query_pipeline): lay the batch over
        the serving mesh when there is one, stage it, run the entry
        point (with the mesh, the same program under shard_map over
        its series axis) and bring the answer back.  With `groups` (the
        group id of each real lane) the grouped entry point runs, else
        the temporal one; `params` are the entry point's own keywords
        and `stats` the caller's own fields of the published stats.

        Returns the program's matrix or None to fall back to the host
        tier (a device runtime error, or any per-stream decode error
        flagged by the device)."""
        import jax.numpy as jnp

        # looked up where it is called: a test may stand another
        # program in on the module
        from m3_tpu.models import query_pipeline

        cost = self._cost()
        if n_shards > 1:
            with cost.phase("pack"):
                pk = self._shard_repack(pk, n_shards)
        if groups is not None:
            # padding lanes are all-NaN rows (no streams: the caller
            # asserts it): they contribute to no group, so parking them
            # on group 0 is harmless — for the quantile sort layout
            # this is load-bearing (see _grouped_quantile's
            # padded-lanes-are-NaN invariant)
            groups_p = np.zeros(pk["lanes_pad"], dtype=np.int64)
            groups_p[:len(groups)] = groups
        try:
            with cost.phase("device"):
                # not fenced: a block_until_ready on the arguments
                # would change what is measured
                with cost.phase("h2d"):
                    staged = [jnp.asarray(pk[k]) for k in
                              ("words", "nbits", "slots", "steps")]
                    if groups is not None:
                        staged.append(jnp.asarray(groups_p))
                    tiers_d = (None if pk["tiers"] is None
                               else jnp.asarray(pk["tiers"]))
                    open_d = (None if pk["open"] is None else tuple(
                        jnp.asarray(a) for a in pk["open"]))
                entry = (query_pipeline.device_temporal_pipeline
                         if groups is None
                         else query_pipeline.device_grouped_pipeline)
                served = entry(
                    *staged, n_lanes=pk["lanes_pad"], n_cap=pk["n_cap"],
                    range_nanos=pk["rng"], fn=fn, n_dp=pk["n_dp"],
                    tiers=tiers_d, n_tiers=pk["n_tiers"],
                    open_rows=open_d,
                    mesh=self.serving_mesh if n_shards > 1 else None,
                    **params)
                out_d, err = served
                with cost.phase("d2h"):
                    out = np.asarray(out_d)
                    err_np = np.asarray(err)
                    # (a stand-in that hands back a plain pair: None)
                    windows = getattr(served, "windows", None)
                    if windows is not None:
                        windows = np.asarray(windows)
        except Exception as exc:  # noqa: BLE001 - serving must not
            # hard-fail on a device runtime error (HBM OOM on a huge
            # fan-out): the host tier can still answer
            cost.decline("device_error")
            cost.stats = {
                "device_serving": False,
                "device_error": f"{type(exc).__name__}: {exc}"[:200],
            }
            return None
        if self._rows_flagged(pk, err_np):
            cost.decline("decode_error")
            return None  # corrupt/unsorted stream: host tier re-decodes
        cost.publish(
            **qcost.program_shape(
                [pk], n_shards, len(pk["steps"]),
                [pk] if fn in qcost.RATE_FAMILY else ()),
            open_rows=pk["open_rows"],
            # the rounds in which a shard's merge and windowed stage go
            # through its lanes
            lane_chunks=query_pipeline.lane_chunks(
                pk["lanes_pad"] // n_shards),
            band_served_pct=qcost.count_band_served(windows),
            **stats)
        return out

    def _device_temporal(self, rv, step_times, fn: str,
                         range_nanos=None, horizon: float = 0.0,
                         hw_sf: float = 0.5, hw_tf: float = 0.5,
                         phi: float = 0.5):
        """Serve a temporal function entirely on the accelerator: the
        fused decode -> merge -> windowed kernel pipeline
        (models/query_pipeline), compressed blocks in,
        [series, steps] out — the HBM-resident read path.  With a
        serving_mesh, lanes spread over the series axis of the mesh.

        Returns (labels, out) or None to fall back to the host tier
        (the dispatch's causes, or the quantile_over_time gate)."""
        packed = self._device_pack(rv, step_times, range_nanos)
        if packed is None:
            return None
        pk, n_shards = packed
        if fn == "quantile_over_time":
            elements = (self._shard_lanes(pk["lanes_pad"], n_shards)
                        * len(pk["steps"]) * pk["n_cap"])
            # pressure = fraction of the per-device HBM window-grid
            # budget the last QOT demanded; sustained >1.0 means the
            # device tier is routinely bouncing to host
            instrument.gauge("m3_device_hbm_gate_pressure").set(
                elements / self._QOT_MAX_ELEMENTS)
            if elements > self._QOT_MAX_ELEMENTS:
                instrument.counter(
                    "m3_device_hbm_gate_rejections_total").inc()
                self._cost().decline("hbm_gate")
                return None  # PER-DEVICE window grid too large: host
                # native kernel (sharded meshes split the lane axis, so
                # each device materializes only its shard's slice)
        out = self._device_run(
            pk, n_shards, fn,
            # fn: which temporal actually ran on device — the
            # differential suite keys its tolerance on this
            {"device_serving": True, "fn": fn},
            horizon=horizon, hw_sf=hw_sf, hw_tf=hw_tf, phi=phi)
        if out is None:
            return None
        return pk["labels"], out[:pk["n_lanes"], :len(pk["shifted"])]

    # aggregations with a device grouped form (topk/bottomk/count_values
    # need the full per-series matrix host-side; quantile joins via the
    # lane-sort form — sharded meshes all_gather the reduced
    # [lanes, steps] matrix over ICI first — gated on a scalar
    # in-range phi, handled separately in _eval_agg)
    _DEVICE_AGGS = frozenset(
        ("sum", "avg", "min", "max", "count", "group", "stddev",
         "stdvar"))

    def _device_grouped(self, node, step_times, phi: float = 0.5):
        """Serve `agg by (...) (fn(x[range]))` with the fused grouped
        pipeline: the temporal kernel AND the cross-series aggregation
        run on device, so only the [groups, steps] result crosses back
        — the transfer-optimal form for dashboard fan-outs where
        thousands of lanes collapse into a handful of groups (the
        reference evaluates the same shape as per-series goroutine
        decode + a host aggregation pass,
        src/query/functions/aggregation/function.go).

        Returns a Matrix or None to fall back (host _eval_agg re-uses
        the gather via the memo, and its child eval may still serve the
        temporal part per-lane on device)."""
        if isinstance(node.expr, promql.Call):
            rv, fn, rng_override = node.expr.args[0], node.expr.fn, None
        else:  # plain Selector: instant-vector consolidation =
            # last_over_time over the engine lookback
            rv, fn, rng_override = node.expr, "last_over_time", \
                self.lookback
        packed = self._device_pack(rv, step_times, rng_override)
        if packed is None:
            return None
        pk, n_shards = packed
        with self._cost().phase("pack"):
            # padded-lanes-are-NaN invariant (models/query_pipeline
            # _grouped_quantile sort layout depends on it): every real
            # stream row targets a real lane and every padding row is
            # zero-length, so lanes >= n_lanes can only decode to
            # all-NaN rows and are inert wherever the dispatch parks
            # them
            m_real = pk["n_streams"]
            assert (int(pk["slots"][:m_real].max(initial=-1))
                    < pk["n_lanes"]
                    and not pk["nbits"][m_real:].any()), \
                "device pack violated the padded-lanes-are-NaN invariant"
            labels = pk["labels"]
            if isinstance(node.expr, promql.Call):
                # group keys over name-dropped labels: the host path
                # aggregates the drop_name()'d temporal matrix
                # (_eval_temporal return)
                key_labels = [
                    {k: v for k, v in ls.items() if k != b"__name__"}
                    for ls in labels]
            else:
                # a plain selector keeps __name__ (host
                # _fetch_consolidated does not drop it, so
                # `by (__name__)` groups on it)
                key_labels = labels
            keys = self._group_keys(Matrix(key_labels, None), node)
            uniq = sorted(set(keys))
            group_of = {k: i for i, k in enumerate(uniq)}
            groups = np.asarray([group_of[k] for k in keys],
                                dtype=np.int64)
        out = self._device_run(
            pk, n_shards, fn,
            # fn, agg: device-served temporal + aggregation — the
            # differential suite keys tolerance on these
            {"n_groups": len(uniq), "device_serving": True,
             "device_grouped": True, "fn": fn, "agg": node.op},
            groups=groups, n_groups=self._bucket(len(uniq), 8),
            agg=node.op, phi=phi)
        if out is None:
            return None
        return Matrix([dict(k) for k in uniq],
                      out[:len(uniq), :len(pk["shifted"])])

    def _eval_temporal(self, node: promql.Call, step_times):
        fn = node.fn
        if (fn in self._DEVICE_TEMPORAL
                and isinstance(node.args[0], promql.Selector)
                and node.args[0].range_nanos
                and self._device_serving_active()):
            horizon, device_ok = 0.0, True
            hw_sf = hw_tf = 0.5
            if fn == "predict_linear":
                h = self._scalar_arg(node.args[1], step_times)
                if isinstance(h, (int, float)):
                    horizon = float(h)
                else:  # per-step scalar expression: host path handles
                    device_ok = False
            elif fn == "holt_winters":
                sf_ = self._scalar_arg(node.args[1], step_times)
                tf_ = self._scalar_arg(node.args[2], step_times)
                # static compile keys: only literal in-range factors
                # (the host path validates and raises for the rest)
                if (isinstance(sf_, (int, float))
                        and isinstance(tf_, (int, float))
                        and 0 < sf_ < 1 and 0 < tf_ < 1):
                    hw_sf, hw_tf = float(sf_), float(tf_)
                else:
                    device_ok = False
            if device_ok:
                served = self._device_temporal(node.args[0], step_times,
                                               fn, horizon=horizon,
                                               hw_sf=hw_sf, hw_tf=hw_tf)
                if served is not None:
                    return Matrix(served[0], served[1]).drop_name()
        if fn == "quantile_over_time":
            phi = self._scalar_arg(node.args[0], step_times)
            if (isinstance(node.args[1], promql.Selector)
                    and node.args[1].range_nanos
                    and self._device_serving_active()
                    and isinstance(phi, (int, float))
                    and 0.0 <= phi <= 1.0):
                served = self._device_temporal(node.args[1], step_times,
                                               fn, phi=float(phi))
                if served is not None:
                    return Matrix(served[0], served[1]).drop_name()
            labels, times, values, rng, shifted = self._range_samples(
                node.args[1], step_times
            )
            out = cons.window_quantile(times, values, shifted, rng, float(phi))
            return Matrix(labels, out).drop_name()
        rv = node.args[0]
        labels, times, values, rng, shifted = self._range_samples(rv, step_times)
        if fn in ("rate", "increase", "delta"):
            out = cons.extrapolated_rate(
                times, values, shifted, rng,
                is_counter=fn != "delta", is_rate=fn == "rate",
            )
        elif fn in ("irate", "idelta"):
            out = self._instant_delta(times, values, shifted, rng,
                                      is_rate=fn == "irate")
        elif fn == "last_over_time":
            out = cons.step_consolidate(times, values, shifted, rng)
        elif fn in ("changes", "resets"):
            out = cons.window_changes(times, values, shifted, rng,
                                      resets_only=fn == "resets")
        elif fn == "deriv":
            out, _, _ = cons.window_linreg(times, values, shifted, rng)
        elif fn == "predict_linear":
            horizon = float(self._scalar_arg(node.args[1], step_times))
            slope, intercept, _ = cons.window_linreg(times, values, shifted, rng)
            out = intercept + slope * horizon
        elif fn == "holt_winters":
            sf = float(self._scalar_arg(node.args[1], step_times))
            tf = float(self._scalar_arg(node.args[2], step_times))
            if not (0 < sf < 1 and 0 < tf < 1):
                raise ValueError("holt_winters factors must be in (0, 1)")
            out = cons.window_holt_winters(times, values, shifted, rng, sf, tf)
        else:
            out = cons.window_reduce(times, values, shifted, rng, fn)
        return Matrix(labels, out).drop_name()

    _ELEMWISE = {
        "abs": np.abs, "ceil": np.ceil, "floor": np.floor,
        "exp": np.exp, "sqrt": np.sqrt, "sgn": np.sign,
        # IEEE semantics like Go's math.Log: log(0) = -Inf,
        # log(negative) = NaN — zero must NOT collapse into NaN
        "ln": np.log,
        "log2": np.log2,
        "log10": np.log10,
    }

    def _eval_scalar_fn(self, node: promql.Call, step_times):
        fn = node.fn
        mat = self.eval(node.args[0], step_times)
        if not isinstance(mat, Matrix):
            raise ValueError(f"{fn}() expects an instant vector")
        v = mat.values
        if fn in self._ELEMWISE:
            with np.errstate(invalid="ignore", divide="ignore"):
                v = self._ELEMWISE[fn](v)
        elif fn == "round":
            to = float(self._scalar_arg(node.args[1], step_times)) if len(node.args) > 1 else 1.0
            # upstream rounds half UP via the INVERSE multiply
            # (Floor(v*(1/to)+0.5)/(1/to)) — v/to accumulates opposite
            # rounding error and flips exact .5 boundaries
            inv = 1.0 / to
            v = np.floor(v * inv + 0.5) / inv
        elif fn == "clamp_min":
            v = np.maximum(v, self._scalar_arg(node.args[1], step_times))
        elif fn == "clamp_max":
            v = np.minimum(v, self._scalar_arg(node.args[1], step_times))
        elif fn == "clamp":
            lo = self._scalar_arg(node.args[1], step_times)
            hi = self._scalar_arg(node.args[2], step_times)
            v = np.clip(v, lo, hi)
            if np.isscalar(lo) and np.isscalar(hi) and lo > hi:
                v = np.full_like(mat.values, np.nan)
        elif fn == "timestamp":
            v = np.where(np.isnan(v), np.nan,
                         np.asarray(step_times, dtype=np.float64)[None, :] / 1e9)
        else:
            raise ValueError(f"unsupported function {fn}")
        return Matrix(mat.labels, v).drop_name()

    @staticmethod
    def _instant_delta(times, values, step_times, rng, is_rate):
        step_times = np.asarray(step_times)
        left, right = cons._window_bounds(
            times, cons._range_left(step_times, rng), step_times
        )
        has2 = right - left >= 2
        n = times.shape[1]
        i_last = np.clip(right - 1, 0, n - 1)
        i_prev = np.clip(right - 2, 0, n - 1)
        v_last = np.take_along_axis(values, i_last, 1)
        dv = v_last - np.take_along_axis(values, i_prev, 1)
        if is_rate:
            # irate counter-reset: a drop means the counter restarted,
            # so the delta is the post-reset value (upstream irate)
            dv = np.where(dv < 0, v_last, dv)
        dt = (np.take_along_axis(times, i_last, 1) -
              np.take_along_axis(times, i_prev, 1)).astype(np.float64) / 1e9
        out = dv / np.maximum(dt, 1e-9) if is_rate else dv
        return np.where(has2, out, np.nan)

    # --- histogram_quantile (ref: src/query/functions/linear/
    #     histogram_quantile.go) ---

    def _histogram_quantile(self, node: promql.Call, step_times):
        phi = self._scalar_arg(node.args[0], step_times)
        mat = self.eval(node.args[1], step_times)
        if not isinstance(mat, Matrix):
            raise ValueError("histogram_quantile expects bucket vectors")
        groups: dict[tuple, list[tuple[float, int]]] = defaultdict(list)
        for i, ls in enumerate(mat.labels):
            le = ls.get(b"le")
            if le is None:
                continue
            try:
                ub = float(le)
            except ValueError:
                continue
            key = tuple(sorted(
                (k, v) for k, v in ls.items() if k not in (b"le", b"__name__")
            ))
            groups[key].append((ub, i))
        labels, rows = [], []
        S = mat.values.shape[1]
        for key, buckets in sorted(groups.items()):
            buckets.sort()
            ubs = np.asarray([b[0] for b in buckets])
            if len(ubs) < 2 or not math.isinf(ubs[-1]):
                continue
            counts = mat.values[[b[1] for b in buckets], :]  # [B, S]
            counts = np.maximum.accumulate(np.nan_to_num(counts), axis=0)
            total = counts[-1]
            rank = phi * total
            # first bucket with cumulative count >= rank
            idx = (counts < rank[None, :]).sum(axis=0)
            idx = np.clip(idx, 0, len(ubs) - 1)
            hi_ub = ubs[idx]
            # the lowest bucket interpolates from 0 only when its upper
            # bound is positive; a negative upper bound IS the answer
            # (upstream bucketQuantile's first-bucket rule)
            lo_ub = np.where(idx > 0, ubs[np.maximum(idx - 1, 0)], 0.0)
            hi_c = np.take_along_axis(counts, idx[None, :], axis=0)[0]
            lo_c = np.where(
                idx > 0,
                np.take_along_axis(counts, np.maximum(idx - 1, 0)[None, :], axis=0)[0],
                0.0,
            )
            # highest finite bucket caps the interpolation (upstream)
            with np.errstate(invalid="ignore", divide="ignore"):
                frac = (rank - lo_c) / np.maximum(hi_c - lo_c, 1e-12)
                val = lo_ub + (hi_ub - lo_ub) * np.clip(frac, 0.0, 1.0)
                val = np.where((idx == 0) & (hi_ub <= 0), hi_ub, val)
                # only the +Inf TOP bucket caps to the highest finite
                # bound; a -Inf FIRST bucket is itself the answer
                val = np.where(np.isposinf(hi_ub), ubs[-2], val)
            val = np.where(total > 0, val, np.nan)
            # out-of-range quantiles (upstream): phi < 0 -> -Inf,
            # phi > 1 -> +Inf, NaN phi -> NaN
            phi_arr = np.broadcast_to(np.asarray(phi, dtype=float), val.shape)
            val = np.where(phi_arr < 0, -np.inf,
                           np.where(phi_arr > 1, np.inf, val))
            val = np.where(np.isnan(phi_arr), np.nan, val)
            labels.append(dict(key))
            rows.append(val)
        values = np.asarray(rows) if rows else np.zeros((0, S))
        return Matrix(labels, values)

    # --- aggregations ---

    def _group_keys(self, mat: Matrix, node: promql.Agg):
        keys = []
        for ls in mat.labels:
            if node.without:
                drop = set(g.encode() for g in node.grouping) | {b"__name__"}
                key = tuple(sorted((k, v) for k, v in ls.items() if k not in drop))
            else:
                keep = set(g.encode() for g in node.grouping)
                key = tuple(sorted((k, v) for k, v in ls.items() if k in keep))
            keys.append(key)
        return keys

    def _eval_agg(self, node: promql.Agg, step_times):
        grouped_child = (
            (isinstance(node.expr, promql.Call)
             and node.expr.fn in self._DEVICE_TEMPORAL
             and len(node.expr.args) == 1
             and isinstance(node.expr.args[0], promql.Selector)
             and node.expr.args[0].range_nanos)
            or (isinstance(node.expr, promql.Selector)
                and not node.expr.range_nanos))
        if (node.op in self._DEVICE_AGGS and grouped_child
                and self._device_serving_active()):
            served = self._device_grouped(node, step_times)
            if served is not None:
                return served
        elif (node.op == "quantile" and grouped_child
              and self._device_serving_active()):
            phi = self._scalar_arg(node.param, step_times)
            if isinstance(phi, (int, float)) and 0.0 <= phi <= 1.0:
                served = self._device_grouped(node, step_times,
                                              phi=float(phi))
                if served is not None:
                    return served
        mat = self.eval(node.expr, step_times)
        keys = self._group_keys(mat, node)
        if node.op in ("topk", "bottomk"):
            return self._eval_topk(node, mat, keys, step_times)
        if node.op == "count_values":
            return self._eval_count_values(node, mat, keys)
        uniq = sorted(set(keys))
        group_of = {k: i for i, k in enumerate(uniq)}
        G, S = len(uniq), mat.values.shape[1]
        sums = np.zeros((G, S))
        sqs = np.zeros((G, S))
        mins = np.full((G, S), np.inf)
        maxs = np.full((G, S), -np.inf)
        counts = np.zeros((G, S))
        for i, key in enumerate(keys):
            g = group_of[key]
            v = mat.values[i]
            m = ~np.isnan(v)
            vz = np.where(m, v, 0.0)
            sums[g] += vz
            sqs[g] += vz * vz
            mins[g][m] = np.minimum(mins[g][m], v[m])
            maxs[g][m] = np.maximum(maxs[g][m], v[m])
            counts[g] += m
        empty = counts == 0
        n = np.maximum(counts, 1)
        if node.op == "sum":
            out = sums
        elif node.op == "avg":
            out = sums / n
        elif node.op == "min":
            out = mins
        elif node.op == "max":
            out = maxs
        elif node.op == "count":
            out = counts
        elif node.op == "group":
            out = np.ones((G, S))
        elif node.op in ("stddev", "stdvar"):
            # two-pass variance: naive E[x^2]-E[x]^2 cancels for
            # large-magnitude values (1e9-scale counters read 0)
            mean = sums / n
            sq_dev = np.zeros((G, S))
            for i, key in enumerate(keys):
                g = group_of[key]
                v = mat.values[i]
                m = ~np.isnan(v)
                d = np.where(m, v - mean[g], 0.0)
                sq_dev[g] += d * d
            var = sq_dev / n
            out = np.sqrt(var) if node.op == "stddev" else var
        elif node.op == "quantile":
            phi = float(self._scalar_arg(node.param, step_times))
            out = np.full((G, S), np.nan)
            vals = mat.values
            oob = np.inf if phi > 1 else (-np.inf if phi < 0 else None)
            rows_of: list[list[int]] = [[] for _ in range(G)]
            for i, k in enumerate(keys):  # one pass, not one per group
                rows_of[group_of[k]].append(i)
            for g in range(G):
                sub = vals[rows_of[g]]
                any_m = ~np.isnan(sub).all(axis=0)
                if oob is not None:  # upstream: out-of-range phi -> +/-Inf
                    out[g] = np.where(any_m, oob, np.nan)
                    continue
                with np.errstate(invalid="ignore"):
                    q = np.nanquantile(np.where(any_m[None, :], sub, 0.0),
                                       phi, axis=0)
                out[g] = np.where(any_m, q, np.nan)
        else:
            raise ValueError(f"unsupported aggregation {node.op}")
        out = np.where(empty, np.nan, out)
        labels = [dict(k) for k in uniq]
        return Matrix(labels, out)

    def _eval_count_values(self, node: promql.Agg, mat: Matrix, keys):
        """count_values("label", v): one output series per (group,
        distinct value), counting occurrences per step (the value is
        rendered into the given label, Go %g formatting)."""
        if not isinstance(node.param, promql.StringLit):
            raise ValueError("count_values requires a string label param")
        dst = node.param.value.encode()
        out_labels, out_rows = [], []
        for key in sorted(set(keys)):
            rows = mat.values[[i for i, k in enumerate(keys) if k == key]]
            distinct = np.unique(rows[~np.isnan(rows)])
            for v in distinct:
                cnt = (rows == v).sum(axis=0).astype(np.float64)
                labels = dict(key)
                # full-precision positional rendering (Go's
                # FormatFloat(v, 'f', -1, 64)); %g's 6 significant
                # digits would collapse distinct values into
                # duplicate-labeled series
                labels[dst] = np.format_float_positional(
                    v, trim="-").encode()
                out_labels.append(labels)
                out_rows.append(np.where(cnt > 0, cnt, np.nan))
        if not out_labels:
            return Matrix([], np.zeros((0, mat.values.shape[1])))
        return Matrix(out_labels, np.stack(out_rows))

    def _eval_topk(self, node: promql.Agg, mat: Matrix, keys, step_times):
        k = int(self._scalar_arg(node.param, step_times))
        if k < 1:
            return Matrix([], np.zeros((0, mat.values.shape[1])))
        v = mat.values
        # NaN sorts away from the top AND the bottom, but a NaN-valued
        # series is still selected once the real values run out
        # (upstream topk/bottomk semantics).  Known approximation: NaN
        # encodes both "sample with value NaN" and "no sample at this
        # step", so a series that is index-active in the range but
        # sampleless can surface as an all-NaN row when k exceeds the
        # group's live cardinality — distinguishing the two would need
        # a presence channel alongside the value grid.
        sortable = np.where(np.isnan(v), -np.inf if node.op == "topk" else np.inf, v)
        out = np.full_like(v, np.nan)
        selected = np.zeros_like(v, dtype=bool)
        rank = np.full(len(keys), np.iinfo(np.int64).max, dtype=np.int64)
        rows_by_key: dict = {}
        for i, kk in enumerate(keys):  # one pass, not one per group
            rows_by_key.setdefault(kk, []).append(i)
        for key, row_list in rows_by_key.items():
            rows = np.asarray(row_list)
            sub = sortable[rows]  # [R, S]
            if node.op == "topk":
                order = np.argsort(-sub, axis=0, kind="stable")
            else:
                order = np.argsort(sub, axis=0, kind="stable")
            keep_rows = order[: min(k, len(rows))]  # [k', S]
            sel = np.zeros(sub.shape, dtype=bool)
            np.put_along_axis(sel, keep_rows, True, axis=0)
            selected[rows] = sel
            out[rows] = np.where(sel, v[rows], np.nan)
            if v.shape[1]:  # rows ranked by final-step position
                for pos, r in enumerate(keep_rows[:, -1]):
                    rank[rows[r]] = pos
        present = selected.any(axis=1)
        # rows ordered by final-step rank (eval_ordered semantics)
        idx = [i for i in np.argsort(rank, kind="stable") if present[i]]
        return Matrix([mat.labels[i] for i in idx], out[idx])

    # --- binary operators ---

    _ARITH = {
        "+": np.add, "-": np.subtract, "*": np.multiply,
        # IEEE-754 like Prometheus: x/0 = +-Inf, 0/0 = NaN, x%0 = NaN;
        # fmod (truncated, sign of dividend) matches Go's math.Mod —
        # np.mod is floored and would flip signs for negative dividends
        "/": np.divide,
        "%": np.fmod,
        "^": np.power,
    }
    _CMP = {
        "==": np.equal, "!=": np.not_equal, ">": np.greater,
        "<": np.less, ">=": np.greater_equal, "<=": np.less_equal,
    }

    def _eval_binop(self, node: promql.BinOp, step_times):
        if node.op in promql.SET_OPS:
            return self._eval_setop(node, step_times)
        lhs = self.eval(node.lhs, step_times)
        rhs = self.eval(node.rhs, step_times)
        is_cmp = node.op in self._CMP
        op = self._CMP[node.op] if is_cmp else self._ARITH[node.op]

        def apply(a, b):
            with np.errstate(invalid="ignore", divide="ignore"):
                return op(a, b)

        l_mat, r_mat = isinstance(lhs, Matrix), isinstance(rhs, Matrix)
        if l_mat and r_mat:
            return self._vector_vector(node, lhs, rhs, step_times)
        if not l_mat and not r_mat:
            res = apply(np.asarray(lhs, dtype=float), np.asarray(rhs, dtype=float))
            if is_cmp:
                if not node.bool_mod:
                    raise ValueError("comparisons between scalars need bool")
                return np.where(res, 1.0, 0.0)
            return res
        mat, other, mat_on_left = (lhs, rhs, True) if l_mat else (rhs, lhs, False)
        a = mat.values if mat_on_left else np.asarray(other)
        b = np.asarray(other) if mat_on_left else mat.values
        res = apply(a, b)
        if is_cmp:
            keep = res & ~np.isnan(mat.values)
            if node.bool_mod:
                vals = np.where(np.isnan(mat.values), np.nan,
                                np.where(keep, 1.0, 0.0))
                return Matrix(mat.labels, vals).drop_name()
            return Matrix(mat.labels, np.where(keep, mat.values, np.nan))
        return Matrix(mat.labels, np.asarray(res, dtype=float)).drop_name()

    def _vector_vector(self, node, lhs: Matrix, rhs: Matrix, step_times):
        m = node.matching
        is_cmp = node.op in self._CMP
        op = self._CMP[node.op] if is_cmp else self._ARITH[node.op]
        group = m.group if m else ""
        # the "many" side carries result labels: lhs for group_left /
        # one-to-one, rhs for group_right (operator orientation is
        # preserved by re-ordering operands below)
        swap = group == "right"
        many_side, one_side = (rhs, lhs) if swap else (lhs, rhs)
        one_by_sig: dict[tuple, list[int]] = defaultdict(list)
        for j, ls in enumerate(one_side.labels):
            one_by_sig[signature(ls, m)].append(j)

        labels, rows = [], []
        include = {l.encode() for l in (m.include if m else ())}
        for i, ls in enumerate(many_side.labels):
            sig = signature(ls, m)
            js = one_by_sig.get(sig)
            if not js:
                continue
            j = js[0]
            a = many_side.values[i]
            b = one_side.values[j]
            lhs_v, rhs_v = (b, a) if swap else (a, b)
            with np.errstate(invalid="ignore", divide="ignore"):
                res = op(lhs_v, rhs_v)
            nanmask = np.isnan(a) | np.isnan(b)
            if is_cmp:
                if node.bool_mod:
                    vals = np.where(nanmask, np.nan, np.where(res, 1.0, 0.0))
                else:
                    vals = np.where(res & ~nanmask, lhs_v, np.nan)
            else:
                vals = np.where(nanmask, np.nan, res)
            if group:
                out_ls = dict(ls)
                # non-bool comparison filters keep the metric name
                if not (is_cmp and not node.bool_mod):
                    out_ls.pop(b"__name__", None)
                for inc in include:
                    if inc in one_side.labels[j]:
                        out_ls[inc] = one_side.labels[j][inc]
                    else:
                        out_ls.pop(inc, None)
            elif is_cmp and not node.bool_mod:
                out_ls = dict(ls)
            else:
                out_ls = dict(sig)
            labels.append(out_ls)
            rows.append(vals)
        S = lhs.values.shape[1]
        return Matrix(labels, np.asarray(rows) if rows else np.zeros((0, S)))

    def _eval_setop(self, node: promql.BinOp, step_times):
        lhs = self.eval(node.lhs, step_times)
        rhs = self.eval(node.rhs, step_times)
        if not isinstance(lhs, Matrix) or not isinstance(rhs, Matrix):
            raise ValueError(f"{node.op} requires vector operands")
        m = node.matching
        S = lhs.values.shape[1] if len(lhs.labels) else rhs.values.shape[1]
        rhs_present: dict[tuple, np.ndarray] = {}
        for j, ls in enumerate(rhs.labels):
            sig = signature(ls, m)
            p = ~np.isnan(rhs.values[j])
            rhs_present[sig] = rhs_present.get(sig, np.zeros(S, bool)) | p
        if node.op == "and":
            labels, rows = [], []
            for i, ls in enumerate(lhs.labels):
                p = rhs_present.get(signature(ls, m))
                if p is None:
                    continue
                labels.append(dict(ls))
                rows.append(np.where(p, lhs.values[i], np.nan))
            return Matrix(labels, np.asarray(rows) if rows else np.zeros((0, S)))
        if node.op == "unless":
            labels, rows = [], []
            for i, ls in enumerate(lhs.labels):
                p = rhs_present.get(signature(ls, m), np.zeros(S, bool))
                vals = np.where(p, np.nan, lhs.values[i])
                labels.append(dict(ls))
                rows.append(vals)
            return Matrix(labels, np.asarray(rows) if rows else np.zeros((0, S)))
        # or: lhs plus rhs elements whose sig has no lhs value at the step
        lhs_present: dict[tuple, np.ndarray] = {}
        for i, ls in enumerate(lhs.labels):
            sig = signature(ls, m)
            p = ~np.isnan(lhs.values[i])
            lhs_present[sig] = lhs_present.get(sig, np.zeros(S, bool)) | p
        labels = [dict(ls) for ls in lhs.labels]
        rows = [lhs.values[i] for i in range(len(lhs.labels))]
        for j, ls in enumerate(rhs.labels):
            shadow = lhs_present.get(signature(ls, m), np.zeros(S, bool))
            vals = np.where(shadow, np.nan, rhs.values[j])
            if not np.isnan(vals).all():
                labels.append(dict(ls))
                rows.append(vals)
        return Matrix(labels, np.asarray(rows) if rows else np.zeros((0, S)))

    # --- public API ---

    def query_range(self, query: str, start_nanos: int, end_nanos: int,
                    step_nanos: int, limits=None):
        """Prometheus query_range: -> (step_times, Matrix | scalar)."""
        step_times, result, _meta = self.query_range_with_meta(
            query, start_nanos, end_nanos, step_nanos, limits=limits)
        return step_times, result

    def query_range_with_meta(self, query: str, start_nanos: int,
                              end_nanos: int, step_nanos: int,
                              limits=None):
        """query_range carrying degraded-mode metadata:
        -> (step_times, Matrix | scalar, ResultMeta).

        ``limits`` (storage.limits.QueryLimits) rides the per-thread
        query state down through every gather this query performs;
        warnings and exhaustiveness from storage truncation and
        session/remote fan-out degradation accumulate in the returned
        meta (ref: src/query/block/meta.go ResultMetadata threading)."""
        meta = ResultMeta()
        t0 = time.perf_counter_ns()
        with tracing.span(tracing.ENGINE_QUERY_RANGE, query=query[:200]):
            ctx = tracing.current_context()
            task = observe.task_ledger().begin_query(
                query,
                tenant=tracing.current_tenant() or self.ns,
                trace_id=(f"{ctx.trace_id:032x}" if ctx is not None
                          else ""),
                namespace=self.ns)
            task.set_phase("parse")
            task.device_tier = ("device" if self._device_serving_active()
                                else "host")
            self._qrange_local.limits = limits
            self._qrange_local.meta = meta
            try:
                # inside the span, so the query's trace_id lands in
                # the slow-query log
                with self._query_scope(query, t0, meta, task) as cost:
                    step_times, result = self._query_range(
                        query, start_nanos, end_nanos, step_nanos)
                    cost.series = len(result.labels)
                return step_times, result, meta
            finally:
                self._qrange_local.limits = None
                self._qrange_local.meta = None
                task.finish()

    @contextlib.contextmanager
    def _query_scope(self, expr: str, t0_ns: int, meta=None, task=None):
        """One query on the calling thread, PromQL or Graphite: arm its
        cost object (yielded), the gather memo, the plan cache and the
        cache scoreboard, charge what the thread waits for between two
        phases (the engine's self time) to it, and on the way out,
        whatever was raised, cut its record and release the memos."""
        ql = self._qrange_local
        cost = self._begin_cost(live=tracing.current_context() is not None)
        cost.task = task
        # the gather memo exists ONLY inside this scope;
        # _gather_cached bypasses memoization when it is None
        ql.gather_cache = {}
        ql.plan_cache = {}
        error = None
        cache_stats.begin()  # per-query cache hit/miss scoreboard
        try:
            with tracing.sink_scope(cost.phases):
                yield cost
        except Exception as e:
            error = f"{type(e).__name__}: {e}"[:300]
            raise
        finally:
            qcost.record(cost, expr, self.ns, t0_ns, meta, error)
            cache_stats.end()
            # release the per-thread gather memo: reuse is scoped
            # to ONE query on purpose (a later query must see a
            # fresh storage snapshot — cross-query caching belongs
            # to m3_tpu/cache, which sees invalidations), and the
            # memo would otherwise pin every raw payload and packed
            # words batch of the last fan-out on an idle thread
            ql.gather_cache = None
            ql.plan_cache = None
            cost.task = None

    def _query_range(self, query: str, start_nanos: int, end_nanos: int,
                     step_nanos: int):
        cost = self._cost()
        with cost.phase("parse"):
            ast = promql.parse(query)
        cost.ast_nodes = promql.ast_size(ast)
        # @ start()/end() resolve against the outer query range,
        # regardless of subquery nesting (upstream semantics)
        self._qrange_local.value = (int(start_nanos), int(end_nanos))
        n_steps = (end_nanos - start_nanos) // step_nanos + 1
        step_times = start_nanos + np.arange(n_steps, dtype=np.int64) * step_nanos
        result = self.eval(ast, step_times)
        if isinstance(result, (int, float)):
            result = Matrix([{}], np.full((1, n_steps), float(result)))
        elif isinstance(result, np.ndarray):
            row = np.broadcast_to(
                np.asarray(result, dtype=np.float64), (n_steps,)
            ).copy()
            result = Matrix([{}], row[None, :])
        return step_times, result

    def query_instant(self, query: str, t_nanos: int, limits=None):
        step_times, result = self.query_range(query, t_nanos, t_nanos, 1,
                                              limits=limits)
        return result

    def query_instant_with_meta(self, query: str, t_nanos: int,
                                limits=None):
        _times, result, meta = self.query_range_with_meta(
            query, t_nanos, t_nanos, 1, limits=limits)
        return result, meta
