"""Database: namespaces -> shards, write/fetch, tick/flush, bootstrap.

Mirrors storage.Database (ref: src/dbnode/storage/database.go:643 Write,
namespace.go:674, bootstrap chain SURVEY.md §3.1) minus the cluster
edge: shard routing is murmur3-exact with the reference
(ref: sharding/shardset.go:149), durability is commitlog + filesets,
and bootstrap replays filesets first then the commit log — the fs ->
commitlog bootstrapper chain (ref: src/dbnode/storage/bootstrap/
bootstrapper/base.go:78).
"""

from __future__ import annotations

import array
import dataclasses
import functools
import itertools
import operator
import pathlib
import threading
import time
from collections import defaultdict, deque
from typing import NamedTuple

import numpy as np

from m3_tpu import attribution
from m3_tpu.cache import CacheOptions, DecodedBlockCache, SeekManager
from m3_tpu.storage.buffer import OpenRow, open_rows_samples
from m3_tpu.storage.commitlog import CommitLog
from m3_tpu.storage.fileset import (FilesetReader, FilesetWriter,
                                    list_fileset_volumes, list_filesets,
                                    read_fileset_info, remove_fileset)
from m3_tpu.storage.index import IndexOptions, TagIndex
from m3_tpu.storage.namespace import NamespaceOptions
from m3_tpu.storage.shard import ARRAYS, OPEN, STREAMS, BlockRows, Shard
from m3_tpu.utils import clock, faultpoints, instrument, tracing
from m3_tpu.utils.hash import shard_for

_log = instrument.logger("storage")

# m3_bootstrap_phase gauge codes (docs/observability.md): the restart
# state machine as plottable integers
_BOOTSTRAP_PHASES = {"idle": 0, "index": 1, "snapshots": 2,
                     "wal-replay": 3, "done": 4}


class ColdWriteError(ValueError):
    """Per-sample cold-write rejection (the reference's RWError analog,
    ingest/write.go BadRequestError): carries which batch indices were
    rejected and how many in-window samples were written, so callers can
    report partial success instead of blindly retrying the whole batch.
    Subclasses ValueError so existing 400-mapping handlers keep working.

    ``rejected_indices`` are positions in the ids/times/values lists of
    the ``write_batch`` call that raised — meaningful to DIRECT callers
    only.  Indirect paths that transform the batch first (the
    DownsamplerAndWriter's keep_raw filter, the insert queue's
    coalescing) would need their own index mapping; they should rely on
    the counts, not the indices."""

    def __init__(self, msg: str, rejected_indices, n_written: int):
        super().__init__(msg)
        self.rejected_indices = rejected_indices
        self.n_written = n_written


class ResourceExhaustedError(ValueError):
    """Transient server-side limit (new-series insert rate): the write
    may succeed on retry, so HTTP layers must map this to 429, never to
    400 (Prometheus drops batches on 4xx but honors 429 as retryable;
    the reference returns 429 for limit errors, x/net/http errors.go)."""


class _Hold:
    """One acquisition of the database lock (``Database.hold``).

    The acquisition is tried without blocking first: an uncontended or
    re-entrant one reads no clock.  Else the wait is clocked where it
    happens (``tracing.wait("db_lock")``: the calling query's
    ``db_lock_wait_s``, ``m3_wait_seconds_total{on="db_lock"}``, an
    ``m3:wait:db_lock`` annotation, the live span's ``lock_wait_ms``).

    A thread's outermost acquisition also says what it held, under its
    entry's name (``m3_db_lock_held_seconds_total{entry}``), wherever
    that costs one clock reading and no more: from its own acquisition
    if it had waited for the lock (it stamped then), else from the
    moment the first thread came to wait for it.  A hold that nobody
    waited for and that had not queued itself counts nothing."""

    __slots__ = ("_db", "_entry", "_outermost", "_since_ns")

    def __init__(self, db: "Database", entry: str):
        self._db = db
        self._entry = entry

    def __enter__(self) -> None:
        db = self._db
        self._since_ns = 0
        if not db._lock.acquire(blocking=False):
            with tracing.wait("db_lock") as w:
                if not db._lock_wanted_ns:
                    db._lock_wanted_ns = w.t0_ns
                db._lock.acquire()
            self._since_ns = w.t1_ns
            tracing.tag_current(
                lock_wait_ms=round((w.t1_ns - w.t0_ns) / 1e6, 3))
        # read and written under the lock alone
        self._outermost = db._lock_entry is None
        if self._outermost:
            db._lock_entry = self._entry

    def __exit__(self, exc_type, exc, tb) -> bool:
        db = self._db
        since_ns = 0
        if self._outermost:
            since_ns = self._since_ns or db._lock_wanted_ns
            if since_ns:
                held_ns = time.perf_counter_ns() - since_ns
                db._lock_wanted_ns = 0
            db._lock_entry = None
        db._lock.release()
        if since_ns:    # counted once the lock is free again
            instrument.counter("m3_db_lock_held_seconds_total",
                               entry=self._entry).inc(held_ns / 1e9)
        return False


def _locked(fn):
    """Serialize a Database entry point on the instance lock, held
    under the method's name (``_write_columns_locked`` as
    ``write_columns``)."""
    entry = fn.__name__.strip("_").removesuffix("_locked")

    @functools.wraps(fn)
    def wrapper(self, *args, **kwargs):
        with _Hold(self, entry):
            return fn(self, *args, **kwargs)
    return wrapper


class Gathered(NamedTuple):
    """What ``fetch_tagged`` hands the engine's bulk gather
    (``with_counts=True, defer_open=True``): columns, no container a
    series.  ``sids`` / ``lanes`` are the matched series' ids and index
    ordinals, sids ascending: a series' place in them is its slot.
    ``shards`` holds one (slots, blocks) per shard the fetch passed, in
    the order it passed them: ``blocks`` are the shard's ``BlockRows``,
    block starts ascending, and row k of each belongs to the series
    at ``slots[k]`` (a payload of None: nothing in that block).  A
    series whose shard the datapoint budget cut off has a slot and no
    row."""

    sids: list[bytes]
    lanes: list[int]
    shards: list[tuple[list[int], list[BlockRows]]]
    # directory scans this fetch had to make (a shard not yet listed)
    fileset_scans: int = 0


def _take(seq, places: list[int]) -> list:
    """``[seq[i] for i in places]`` by one C-level call."""
    if not places:
        return []
    # a -1 behind the places: an itemgetter of one index alone would
    # hand back the item, not a tuple of it
    return list(operator.itemgetter(*places, -1)(seq)[:-1])


def _rows_cost(block: BlockRows) -> tuple[int, int, list]:
    """-> (datapoints, bytes, the rows only named) of one block's rows.
    A stream without a stored count is estimated at ~2 bytes/sample
    (m3tsz averages ~1.4B/sample, so this undercounts conservatively
    rather than rejecting queries early); an ``OpenRow`` among other
    rows (a ``MIXED`` block) is counted by the caller, one search a
    view (``open_rows_samples``)."""
    _bs, kind, payloads, counts = block
    if kind is OPEN:
        # the rows of one view (Shard.read_many names a buffer's lanes
        # on one): one search for all of them
        n = int(payloads[0].view.counts(
            list(map(operator.itemgetter(1), payloads))).sum())
        return n, 16 * n, []
    if kind is STREAMS and counts is not None:
        # every stream with its stored count (sealed blocks, v2
        # filesets): two sums, no row looked at in the interpreter
        try:
            return sum(counts), sum(map(len, payloads)), []
        except TypeError:
            # a None among them: a series the block lacks
            if counts.count(None) == payloads.count(None):
                return (sum(filter(None, counts)),
                        sum(map(len, filter(None, payloads))), [])
    dps = nbytes = 0
    named = []
    for i, p in enumerate(payloads):
        if p is None:
            continue
        if isinstance(p, (bytes, bytearray, memoryview)):
            n_dp = counts[i] if counts is not None else None
            dps += max(1, len(p) // 2) if n_dp is None else int(n_dp)
            nbytes += len(p)
        elif isinstance(p, OpenRow):
            named.append(p)
        else:  # decoded (times, values) array pair
            dps += len(p[0])
            nbytes += (getattr(p[0], "nbytes", 0)
                       + getattr(p[1], "nbytes", 0))
    return dps, nbytes, named


@dataclasses.dataclass(frozen=True)
class DatabaseOptions:
    path: str = "/tmp/m3tpu-db"
    num_shards: int = 64
    commit_log_enabled: bool = True
    # opt-in group-commit durability: the WAL writer fsyncs once per
    # drained batch and write_batch/write_columns block on that fsync
    # generation before returning — "200 means durable", amortized
    # (ref: commitlog StrategyWriteWait vs StrategyWriteBehind)
    commit_log_fsync_every_batch: bool = False
    # flushed-block read cache (the WiredList analog — ref: src/dbnode/
    # storage/block/wired_list.go:77, series cache policies
    # storage/series/policy.go:37-52): "lru" keeps the most recently
    # read fileset readers mmap'd, "all" never evicts, "none" re-opens
    # per read.  CI-style behavioral axis like the reference's
    # lru|recently_read suites.
    cache_policy: str = "lru"
    fileset_cache_size: int = 128
    # full read-path cache settings (m3_tpu.cache.CacheOptions); None
    # falls back to the two legacy knobs above with the decoded-block
    # cache off — existing callers see identical behavior
    cache: CacheOptions | None = None
    # reverse-index tuning (storage.index.IndexOptions): background
    # segment compaction, segment-count bounds, daemon poll interval;
    # None takes the IndexOptions defaults (background compaction on)
    index: IndexOptions | None = None


class _Namespace:
    def __init__(self, opts: NamespaceOptions, db_opts: DatabaseOptions):
        self.opts = opts
        self.index = TagIndex(
            postings_cache_capacity=(db_opts.cache.postings_capacity
                                     if db_opts.cache else None),
            options=db_opts.index)
        self.shards = {
            s: Shard(s, opts) for s in range(db_opts.num_shards)
        }
        # lazily-built shard -> ordinals map, refreshed as the index
        # grows (avoids full-index scans per per-shard metadata call)
        self._shard_ordinals: dict[int, list[int]] = {}
        self._shard_ordinals_upto = 0
        # ordinal -> shard id memo, an array as long as the index with
        # -1 where nobody has asked yet: it grows with the index but is
        # FILLED only where asked (a dense fill would force an
        # O(total-series) hash storm on the first write after
        # bootstrapping a large recovered index)
        self._lane_shards = array.array("i")

    def shard_of(self, series_id: bytes) -> Shard:
        return self.shards[shard_for(series_id, len(self.shards))]

    def shards_of_lanes(self, lanes: list[int]) -> list[int]:
        """Shard id of each index ordinal, memoized: shard placement
        is a pure function of the series id, and the pure-Python
        murmur3 dominates both steady-state ingest and a fan-out's
        grouping when recomputed.  One C-level pass over the memo;
        the interpreter goes round only the ordinals asked for the
        first time."""
        memo = self._lane_shards
        if len(memo) < len(self.index):
            memo.extend(itertools.repeat(
                -1, max(len(self.index), 2 * len(memo)) - len(memo)))
        shards = list(map(memo.__getitem__, lanes))
        if -1 in shards:
            new = list(itertools.compress(
                lanes, map(operator.eq, shards, itertools.repeat(-1))))
            for lane, sid in zip(new, self.index.ids_of(new)):
                memo[lane] = shard_for(sid, len(self.shards))
            shards = list(map(memo.__getitem__, lanes))
        return shards

    def ordinals_for_shard(self, shard_id: int) -> list[int]:
        n = len(self.index)
        while self._shard_ordinals_upto < n:
            o = self._shard_ordinals_upto
            # computed inline, NOT via shards_of_lanes: this scan walks
            # every ordinal, and routing it through the memo would
            # fill all of it, the hash storm its filling only where
            # asked exists to avoid (the result lives in _shard_ordinals)
            self._shard_ordinals.setdefault(
                shard_for(self.index.id_of(o), len(self.shards)),
                []).append(o)
            self._shard_ordinals_upto += 1
        return self._shard_ordinals.get(shard_id, [])


class Database:
    def __init__(self, opts: DatabaseOptions | None = None):
        self.opts = opts or DatabaseOptions()
        self.path = pathlib.Path(self.opts.path)
        self._namespaces: dict[str, _Namespace] = {}
        self._struct_stores: dict[str, "object"] = {}
        self._fileset_writer = FilesetWriter(self.path / "data")
        self._commitlog: CommitLog | None = None
        if self.opts.commit_log_enabled:
            self._commitlog = CommitLog(
                self.path / "commitlog",
                fsync_every_batch=self.opts.commit_log_fsync_every_batch)
        self._bootstrapping = False
        self._bootstrap_in_flight = False
        # graceful-restart drain flag: health surfaces report it so the
        # session/health layers stop routing before the process exits
        self._draining = False
        # bootstrap progress for /health + the rolling-restart gate
        self._bootstrap_progress: dict = {"phase": "idle",
                                          "entries_replayed": 0,
                                          "bytes_replayed": 0}
        self._open = True
        # serializes all state-touching entry points: serving threads
        # (DatabaseNode), background bootstrap/repair, flush loops
        # (the reference uses fine-grained per-shard locks; one RLock
        # is the honest equivalent for this structure)
        self._lock = threading.RLock()
        # what _Hold keeps: the entry of the thread that holds the
        # lock (outermost), and since when some thread has waited for it
        self._lock_entry: str | None = None
        self._lock_wanted_ns = 0
        # read-path caches (m3_tpu.cache): the seek manager pools open
        # fileset readers; the decoded-block cache serves warm reads
        # without M3TSZ decode under per-namespace series cache
        # policies.  Legacy DatabaseOptions knobs map onto the seek
        # manager so pre-CacheOptions callers keep their semantics.
        co = self.opts.cache or CacheOptions(
            seek_policy=self.opts.cache_policy,
            seek_capacity=self.opts.fileset_cache_size)
        self.cache_opts = co
        self._seek = SeekManager(policy=co.seek_policy,
                                 capacity=co.seek_capacity,
                                 ttl_nanos=co.seek_ttl)
        self._decoded_cache = DecodedBlockCache(
            max_bytes=co.decoded_max_bytes,
            default_policy=co.decoded_policy,
            policies=co.decoded_policies,
            recently_read_ttl_nanos=co.recently_read_ttl)
        # per-subsystem counters (ref: x/instrument per-struct metrics);
        # tagged per instance — several Databases can share one process
        # (tests, embedded coordinator + dbnode) and must not clobber
        # each other's series
        db_tag = {"db": str(self.path)}
        self._m_samples = instrument.counter("m3_ingest_samples_total",
                                             **db_tag)
        self._m_series = instrument.gauge("m3_series_count", **db_tag)
        self._m_flush = instrument.counter("m3_flush_blocks_total", **db_tag)
        self._m_snapshot = instrument.counter("m3_snapshot_blocks_total",
                                              **db_tag)
        self._m_sealed = instrument.counter("m3_tick_sealed_blocks_total",
                                            **db_tag)
        # how a shard's data filesets were listed: from the listing
        # kept on the shard, or by a directory scan (once a shard)
        self._m_listing_kept = instrument.counter(
            "m3_fileset_listing_total", source="kept", **db_tag)
        self._m_listing_scan = instrument.counter(
            "m3_fileset_listing_total", source="scan", **db_tag)
        # bootstrap/restart observability (warm-restart PR): phase is a
        # numeric code (see _BOOTSTRAP_PHASES) so dashboards can plot
        # the state machine; entries/bytes advance as WAL chunks replay
        self._m_bootstrap_phase = instrument.gauge("m3_bootstrap_phase",
                                                   **db_tag)
        self._m_bootstrap_entries = instrument.counter(
            "m3_bootstrap_entries_replayed_total", **db_tag)
        self._m_bootstrap_bytes = instrument.counter(
            "m3_bootstrap_bytes_replayed_total", **db_tag)
        self._m_bootstrap_seconds = instrument.histogram(
            "m3_bootstrap_seconds", **db_tag)

    # --- runtime options (hot-reloadable; ref: src/dbnode/runtime/
    #     runtime_options.go, kvconfig new-series insert limits) ---

    def set_runtime_options(self, opts) -> None:
        """Apply hot-reloaded options (RuntimeOptionsManager listener)."""
        self._runtime = opts
        rate = getattr(opts, "trace_sample_1_in", 0)
        if rate:
            tracing.set_sampling(rate)

    _runtime = None
    _new_series_sec = 0
    _new_series_count = 0

    def _check_new_series_limit(self, n_new: int) -> None:
        limit = getattr(self._runtime, "write_new_series_limit_per_sec", 0)
        if not limit or n_new == 0:
            return
        sec = time.monotonic_ns() // 1_000_000_000
        if sec != self._new_series_sec:
            self._new_series_sec = sec
            self._new_series_count = 0
        if self._new_series_count + n_new > limit:
            instrument.counter("m3_new_series_limited_total").inc(n_new)
            raise ResourceExhaustedError(
                f"new-series insert limit {limit}/s exceeded")
        self._new_series_count += n_new

    # --- admin ---

    @_locked
    def create_namespace(self, ns_opts: NamespaceOptions) -> None:
        if ns_opts.name in self._namespaces:
            raise ValueError(f"namespace {ns_opts.name} exists")
        self._namespaces[ns_opts.name] = _Namespace(ns_opts, self.opts)
        if ns_opts.schema is not None:
            from m3_tpu.storage.structured import StructStore

            store = StructStore(
                self.path, ns_opts.name, ns_opts.schema,
                ns_opts.retention.block_size)
            self._struct_stores[ns_opts.name] = store
            # re-register recovered series (filesets + WAL tail) into
            # the tag index so matchers find them after a restart
            n = self._namespaces[ns_opts.name]
            for sid, tags, blocks in store.series():
                lane = n.index.insert(sid, tags)
                for bs in blocks:
                    n.index.mark_active(lane, bs)

    def namespaces(self) -> list[str]:
        return sorted(self._namespaces)

    def namespace_options(self, ns: str) -> NamespaceOptions:
        return self._ns(ns).opts

    def hold(self, entry: str) -> _Hold:
        """``with db.hold("write_columnar"):`` the database lock for a
        caller that is no method of this class (the columnar ingest
        fast path), clocked and named as ``_locked`` entries are."""
        return _Hold(self, entry)

    def _ns(self, name: str) -> _Namespace:
        if name not in self._namespaces:
            raise KeyError(f"unknown namespace {name}")
        return self._namespaces[name]

    # --- write path (ref: database.go:643 -> namespace.go:674 ->
    #     shard.go:910) ---

    def write_batch(
        self,
        ns: str,
        ids: list[bytes],
        tags: list[dict[bytes, bytes]],
        times_nanos: list[int] | np.ndarray,
        values: list[float] | np.ndarray,
    ) -> None:
        """Row-wise write: one id/tags entry per sample.  Thin adapter
        over the columnar core (identity uniq mapping)."""
        self.write_columns(ns, ids, tags, times_nanos, values)

    @tracing.traced(tracing.DB_WRITE_BATCH)
    def write_columns(
        self,
        ns: str,
        uniq_ids: list[bytes],
        uniq_tags: list[dict[bytes, bytes]] | None,
        times_nanos: list[int] | np.ndarray,
        values: list[float] | np.ndarray,
        uniq_idx: np.ndarray | None = None,
    ) -> None:
        """Columnar write: ``uniq_ids``/``uniq_tags`` are per-SERIES
        tables; ``uniq_idx[i]`` names sample ``i``'s row (None =
        identity, one row per sample — the write_batch shape).  The
        caller hands over ownership of every argument: arrays and
        lists must not be mutated after the call (the WAL writer
        thread encodes them asynchronously)."""
        seq = self._write_columns_locked(
            ns, uniq_ids, uniq_tags, times_nanos, values, uniq_idx)
        if seq is not None and self.opts.commit_log_fsync_every_batch:
            # block on the group-commit fsync generation OUTSIDE the
            # database lock: concurrent writers keep filling the next
            # batch while this one waits on the disk
            self._commitlog.wait_durable(seq)

    @_locked
    def _write_columns_locked(
        self, ns, uniq_ids, uniq_tags, times_nanos, values, uniq_idx
    ) -> int | None:
        n = self._ns(ns)
        u = len(uniq_ids)
        # the O(batch) new-series scan only runs when a limit is SET
        # (a registered manager with default options must not tax the
        # hot ingest path)
        if (getattr(self._runtime, "write_new_series_limit_per_sec", 0)
                and not self._bootstrapping):
            n_new = sum(1 for sid in set(uniq_ids)
                        if n.index.ordinal(sid) is None)
            self._check_new_series_limit(n_new)
        times_nanos = np.asarray(times_nanos, dtype=np.int64)
        values = np.asarray(values, dtype=np.float64)
        if uniq_idx is not None:
            uniq_idx = np.asarray(uniq_idx, dtype=np.int64)
        bsize = n.opts.retention.block_size
        if (not n.opts.cold_writes_enabled and len(times_nanos)
                and not self._bootstrapping):
            # reference posture: without cold writes, a sample must land
            # inside [now - buffer_past, now + buffer_future] or the
            # currently-open block (namespace/types.go ColdWritesEnabled;
            # storage/shard.go write-window checks).  Rejection is
            # PER SAMPLE like the reference: in-window samples in the
            # same batch still land, then the caller gets the error.
            now = clock.now_nanos()
            ok = n.opts.retention.writable_mask(times_nanos, now)
            if not ok.all():
                n_bad = int((~ok).sum())
                bad = int(times_nanos[~ok][0])
                instrument.counter("m3_cold_writes_rejected_total").inc(
                    n_bad)
                n_written = 0
                if ok.any():
                    sel = np.flatnonzero(ok)
                    if uniq_idx is None:
                        keep = sel
                        sub_idx = None
                    else:
                        # compact the uniq table to surviving rows so a
                        # series whose every sample was rejected never
                        # enters the index (matches the row-wise path)
                        keep, sub_idx = np.unique(uniq_idx[sel],
                                                  return_inverse=True)
                        keep = keep.tolist()
                    self._write_columns_locked(
                        ns, [uniq_ids[i] for i in keep],
                        ([uniq_tags[i] for i in keep]
                         if uniq_tags is not None else None),
                        times_nanos[sel], values[sel], sub_idx)
                    n_written = len(sel)
                raise ColdWriteError(
                    f"cold write rejected (cold_writes_enabled=false): "
                    f"{n_bad} sample(s) outside the write window, e.g. "
                    f"t={bad} around now={now}; {n_written} in-window "
                    "sample(s) in this batch were written",
                    rejected_indices=np.flatnonzero(~ok).tolist(),
                    n_written=n_written)
        block_starts = times_nanos - times_nanos % bsize
        # per-UNIQUE-series Python (index insert + shard routing are
        # dict-backed and irreducibly per-object); everything per-sample
        # below this loop is numpy
        lanes_u = np.empty(u, dtype=np.int64)
        insert = n.index.insert
        idx_before = len(n.index)  # new-series delta for attribution
        if uniq_tags is None:
            for i, sid in enumerate(uniq_ids):
                lanes_u[i] = insert(sid, {})
        else:
            for i, (sid, tg) in enumerate(zip(uniq_ids, uniq_tags)):
                lanes_u[i] = insert(sid, tg)
        shards_u = np.fromiter(n.shards_of_lanes(lanes_u.tolist()),
                               dtype=np.int64, count=u)
        if uniq_idx is None:
            lanes, shard_ids = lanes_u, shards_u
        else:
            lanes, shard_ids = lanes_u[uniq_idx], shards_u[uniq_idx]
        n_samples = len(times_nanos)
        if n_samples:
            # activity marking per unique (lane, block) pair, not per
            # sample — same end state, batch-sized fewer dict probes
            pairs = np.unique(
                np.stack([lanes, block_starts], axis=1), axis=0)
            mark = n.index.mark_active
            for lane, bs in pairs.tolist():
                mark(lane, bs)
            # shard dispatch: one stable sort + group boundaries, so
            # each shard gets a single contiguous slice per batch
            order = np.argsort(shard_ids, kind="stable")
            s_sorted = shard_ids[order]
            l_sorted = lanes[order]
            t_sorted = times_nanos[order]
            v_sorted = values[order]
            bounds = np.flatnonzero(np.diff(s_sorted)) + 1
            grp_starts = np.concatenate(([0], bounds))
            grp_ends = np.concatenate((bounds, [n_samples]))
            for a, b in zip(grp_starts.tolist(), grp_ends.tolist()):
                n.shards[int(s_sorted[a])].write_batch(
                    l_sorted[a:b], t_sorted[a:b], v_sorted[a:b])
            if len(self._decoded_cache):
                # writes into an open block shadow the fileset copy on
                # the read path already (_overlapping_filesets);
                # dropping the decoded entries eagerly keeps the byte
                # budget honest and the staleness guarantee checkable
                inv = np.unique(
                    np.stack([shard_ids, block_starts], axis=1), axis=0)
                for s, bs in inv.tolist():
                    self._decoded_cache.invalidate_block(ns, s, bs)
        seq = None
        if (
            self._commitlog is not None
            and n.opts.writes_to_commit_log
            and not self._bootstrapping
        ):
            seq = self._commitlog.write_columns(
                uniq_ids, times_nanos, values, uniq_tags=uniq_tags,
                uniq_idx=uniq_idx, ns=ns)
        self._m_samples.inc(n_samples)
        self._m_series.set(sum(len(x.index) for x in
                               self._namespaces.values()))
        if attribution.enabled():
            # per-BATCH attribution: tenant rides the trace baggage
            # from the originating edge; namespace is the fallback
            # (e.g. the insert-queue drain thread)
            n_new = len(n.index) - idx_before
            tenant = tracing.current_tenant() or ns
            attribution.account_write(tenant, samples=n_samples,
                                      new_series=n_new)
            if n_new and uniq_tags is not None:
                # new lanes are assigned past the pre-insert ordinal
                # watermark; offer their label NAMES to the
                # cardinality-offender sketch
                for i in np.flatnonzero(lanes_u >= idx_before).tolist():
                    attribution.note_label_keys(uniq_tags[i].keys())
        return seq

    def write(self, ns: str, series_id: bytes, tags, t_nanos: int, value: float):
        self.write_batch(ns, [series_id], [tags], [t_nanos], [value])

    # --- structured (schema'd) namespaces -------------------------------

    @_locked
    def write_struct(self, ns: str, series_id: bytes,
                     tags: dict[bytes, bytes], t_nanos: int,
                     msg: dict) -> None:
        """One structured datapoint into a schema'd namespace; the
        series registers in the tag index like any other so matchers
        discover it."""
        store = self._struct_stores.get(ns)
        if store is None:
            raise KeyError(f"namespace {ns} has no schema")
        n = self._ns(ns)
        if (not n.opts.cold_writes_enabled
                and not n.opts.retention.writable(t_nanos, clock.now_nanos())):
            instrument.counter("m3_cold_writes_rejected_total").inc()
            raise ValueError(
                "cold write rejected (cold_writes_enabled=false): "
                f"t={t_nanos} outside the write window")
        # store first: a rejected write (sealed block) must not leave a
        # phantom series in the index that matchers then discover
        store.write(series_id, t_nanos, msg, tags)
        lane = n.index.insert(series_id, tags)
        bs = t_nanos - t_nanos % n.opts.retention.block_size
        n.index.mark_active(lane, bs)

    @_locked
    def update_namespace_schema(self, ns: str, schema) -> None:
        """Roll a structured namespace's schema forward in place (the
        reference's dynamic schema registry / kvadmin SetSchema);
        existing blobs self-describe, new writes use the new schema."""
        store = self._struct_stores.get(ns)
        if store is None:
            raise KeyError(f"namespace {ns} has no schema")
        store.update_schema(schema)
        self._namespaces[ns].opts = dataclasses.replace(
            self._namespaces[ns].opts, schema=schema)

    @_locked
    def fetch_struct(
        self, ns: str, matchers, start_nanos: int, end_nanos: int
    ) -> dict[bytes, tuple]:
        """Index query + structured read: sid -> (timestamps, messages)."""
        store = self._struct_stores.get(ns)
        if store is None:
            raise KeyError(f"namespace {ns} has no schema")
        sids = self.query_ids(ns, matchers, start_nanos, end_nanos)
        return store.read_many(sids, start_nanos, end_nanos)

    # --- read path ---

    def _query_ordinals(self, n: _Namespace, matchers, start_nanos,
                        end_nanos, limits=None, meta=None) -> np.ndarray:
        return n.index.query_conjunction(
            matchers, start_nanos, end_nanos, n.opts.retention.block_size,
            limits=limits, meta=meta,
        )

    @_locked
    def query_ids(
        self,
        ns: str,
        matchers,
        start_nanos: int | None = None,
        end_nanos: int | None = None,
        limits=None,
        meta=None,
    ) -> list[bytes]:
        n = self._ns(ns)
        ords = self._query_ordinals(n, matchers, start_nanos, end_nanos,
                                    limits, meta)
        return n.index.ids_of(ords)

    @_locked
    def fetch_series(
        self, ns: str, series_id: bytes, start_nanos: int, end_nanos: int,
    ) -> list[tuple[int, object]]:
        """All (block_start, payload) for one series: flushed filesets,
        sealed in-memory blocks, open buffers."""
        n = self._ns(ns)
        lane = n.index.ordinal(series_id)
        shard = n.shard_of(series_id)
        out: list[tuple[int, object]] = []
        # flushed filesets first (oldest data)
        for bs, reader in self._overlapping_filesets(
                ns, n, shard, start_nanos, end_nanos):
            blob = reader.read(series_id)
            if blob:
                out.append((bs, blob))
        if lane is not None:
            out.extend(shard.read_series(series_id, lane, start_nanos, end_nanos))
        return sorted(out, key=lambda p: p[0])

    def _scan_filesets(self, ns: str, shard: Shard) -> dict[int, int]:
        """List the shard's data directory and keep the listing on the
        shard: {block_start: latest volume}."""
        self._m_listing_scan.inc()
        shard.filesets = dict(list_filesets(self.path / "data", ns,
                                            shard.shard_id))
        return shard.filesets

    def _data_filesets(self, ns: str, shard: Shard) -> dict[int, int]:
        """The shard's data filesets, {block_start: latest volume}, from
        the listing kept on the shard (``Shard.filesets``).  This
        database is the only writer of its ``data/`` tree: bootstrap
        sets the listing from the directory, ``Shard.flush`` renews it
        with every fileset written, cleanup removes only volumes a
        newer one supersedes (never a listed one) and a dropped shard
        starts empty.  A shard not yet listed is scanned once."""
        if shard.filesets is None:
            return self._scan_filesets(ns, shard)
        self._m_listing_kept.inc()
        return shard.filesets

    def _overlapping_filesets(self, ns: str, n, shard, start_nanos: int,
                              end_nanos: int):
        """Yield (block_start, reader) for flushed filesets overlapping
        [start, end) and not shadowed by an in-memory copy — the ONE
        implementation of the read path's block-selection rules, shared
        by single-series and fan-out fetches.  Readers are asked for
        only where memory does not hold the block; no directory is
        read (``_data_filesets``)."""
        bsize = n.opts.retention.block_size
        for bs, vol in sorted(self._data_filesets(ns, shard).items()):
            if not (start_nanos < bs + bsize and bs < end_nanos):
                continue
            if shard.holds_block(bs):
                continue  # memory copy wins (not yet evicted)
            yield bs, self._cached_reader(ns, shard.shard_id, bs, vol)

    @property
    def _reader_cache(self):
        """The seek manager's pool (len()-compatible view kept for
        callers/tests that sized the pre-subsystem OrderedDict)."""
        return self._seek

    def _cached_reader(self, ns: str, shard_id: int, bs: int,
                       vol: int) -> FilesetReader:
        """Pooled fileset reader via the seek manager (ref: persist/
        fs/seek_manager.go): repeated reads skip digest validation +
        index parse.  Superseded volumes are unreachable by key (vol
        is part of it)."""
        return self._seek.acquire(
            (ns, shard_id, bs, vol),
            lambda: FilesetReader(self.path / "data", ns, shard_id,
                                  bs, vol))

    # NOTE: @traced sits OUTSIDE @_locked on both entry points so span
    # durations consistently include lock-wait (contention is exactly
    # what the tracepoints exist to expose).
    @tracing.traced(tracing.DB_FETCH_TAGGED)
    @_locked
    def fetch_tagged(
        self, ns: str, matchers, start_nanos: int, end_nanos: int,
        with_counts: bool = False, limits=None, meta=None,
        defer_open: bool = False,
    ) -> dict[bytes, list[tuple]] | Gathered:
        """Index query + block fetch of the matched series — FetchTagged
        (ref: tchannelthrift/node/service.go:614).  The index query is
        time-pruned to blocks overlapping [start, end).

        The walk is block-major: the matched series are grouped by
        shard, and each shard is passed once — its filesets for block
        starts memory does not hold (bulk-read through the reader's
        seek index, listed from ``Shard.filesets``: no directory scan),
        then ``Shard.read_many`` over what memory holds (sealed rows
        through the table the seal built, one view an open buffer).
        Nothing is kept from one fetch to the next; all of it is read
        under the database lock, so a fetch sees the storage of its
        own moment.

        Default: {sid: [(block_start, payload)]}, blocks ascending —
        the public 2-tuple shape (TCP RPC / session compatibility).

        ``with_counts=True`` carries a datapoint count beside each
        payload (v2 filesets and sealed blocks store it, letting the
        reader size its decode grid without a count pass; None =
        unknown).  ``defer_open=True`` hands plain open-buffer reads
        back as ``OpenRow`` payloads (``Shard.read_many``), consistent
        with the rest of this fetch, for the caller to read in bulk
        once the lock is released.  With both (the engine's gather) the
        answer is a ``Gathered``: the same rows, handed over in the
        gather's own order and without per-series containers.

        ``limits``/``meta`` (storage.limits) bound the fetch: time
        range clamped at admission, matched series truncated at the
        index lookup, and the block-fetch loop stops once the
        datapoint budget is spent — each either truncate-with-warning
        (recorded in ``meta``) or, under require-exhaustive, a
        QueryLimitExceeded abort.  The per-query deadline is checked
        between shards so a huge fan-out cannot overstay its budget
        while holding the fetch thread."""
        if limits is not None:
            start_nanos = limits.clamp_time_range(
                start_nanos, end_nanos, meta)
        n = self._ns(ns)
        lanes = self._query_ordinals(n, matchers, start_nanos, end_nanos,
                                     limits, meta).tolist()
        sids = n.index.ids_of(lanes)
        limit = getattr(self._runtime, "max_fetch_series", 0)
        if limit and len(sids) > limit:
            raise ValueError(
                f"query matched {len(sids)} series > limit {limit}")
        if meta is not None:
            meta.fetched_series += len(sids)
        # group by shard: a series' place in `lanes` appended to its
        # shard's list (the shard from the lane memo: no pure-Python
        # murmur3 per sid), by C-level passes; a shard's series stay
        # in the index's order, and the shards are passed in the order
        # of their first match.  Builtins throughout, no array call:
        # under other threads' load every array call on more than a
        # few hundred elements lets go of the interpreter lock and
        # waits a switch interval to get it back
        shard_ids = n.shards_of_lanes(lanes)
        places_of: dict[int, list[int]] = {
            shard_id: [] for shard_id in dict.fromkeys(shard_ids)}
        deque(map(list.append, map(places_of.__getitem__, shard_ids),
                  range(len(lanes))), maxlen=0)
        # (the places of a shard's series in `lanes`, its blocks)
        passed: list[tuple[list[int], list[BlockRows]]] = []

        count_cost = attribution.enabled()
        budget = limits is not None and limits.max_fetched_datapoints
        dp_fetched = nbytes = scans = 0
        # series cache policy for this namespace: anything but "none"
        # routes v2 fileset reads through the decoded-block cache so a
        # warm repeat serves device-ready (times, values) arrays with
        # zero M3TSZ decode work
        dec_policy = self._decoded_cache.policy_for(ns)
        for shard_id, places in places_of.items():
            if limits is not None:
                limits.check_deadline("block fetch")
                if budget and limits.datapoints_exceeded(dp_fetched, meta):
                    break  # budget spent: remaining shards truncated
            shard = n.shards[shard_id]
            shard_sids = _take(sids, places)
            blocks: list[BlockRows] = []
            scans += shard.filesets is None
            for bs, reader in self._overlapping_filesets(
                    ns, n, shard, start_nanos, end_nanos):
                if not with_counts:
                    blocks.append(BlockRows(
                        bs, STREAMS, reader.read_batch(shard_sids), None))
                    continue
                blobs, counts = reader.read_batch_with_counts(
                    shard_sids, zero_copy=True)
                if dec_policy != "none":
                    decoded = self._decoded_cache.get_or_decode(
                        ns, shard.shard_id, bs, reader.volume,
                        dec_policy, shard_sids, blobs, counts)
                    blocks.append(BlockRows(
                        bs, ARRAYS, decoded,
                        [None if d is None else len(d[0])
                         for d in decoded]))
                else:
                    blocks.append(BlockRows(bs, STREAMS, blobs, counts))
            on_disk = len(blocks)
            blocks.extend(shard.read_many(
                shard_sids, _take(lanes, places),
                start_nanos, end_nanos,
                with_counts=with_counts, defer_open=defer_open))
            if 0 < on_disk < len(blocks):
                blocks.sort(key=lambda b: b.block_start)
            passed.append((places, blocks))
            if budget or count_cost:
                # datapoints scanned + bytes decoded, a pass a block
                # (never per sample); sids are partitioned by shard, so
                # each row is counted exactly once
                for block in blocks:
                    b_dps, b_bytes, b_named = _rows_cost(block)
                    if b_named:
                        n_open = open_rows_samples(b_named)
                        b_dps += n_open
                        b_bytes += 16 * n_open
                    dp_fetched += b_dps
                    nbytes += b_bytes
        if count_cost:
            # per-QUERY attribution, credited to the propagated tenant
            # (fan-out RPC work) or the namespace
            attribution.account_read(tracing.current_tenant() or ns,
                                     datapoints=dp_fetched,
                                     decoded_bytes=nbytes)
        if meta is not None and budget:
            meta.fetched_datapoints += dp_fetched
        if with_counts and defer_open:
            # slots: the series by sid (they are distinct)
            by_sid = sorted(range(len(sids)), key=sids.__getitem__)
            slot_of = [0] * len(sids)
            deque(map(slot_of.__setitem__, by_sid, range(len(sids))),
                  maxlen=0)
            return Gathered(
                _take(sids, by_sid), _take(lanes, by_sid),
                [(_take(slot_of, places), blocks)
                 for places, blocks in passed], scans)
        # the public shapes, a container a series: its shard's blocks
        # and its row in them (none: the budget cut its shard off)
        rows_of: list[tuple] = [((), 0)] * len(sids)
        for places, blocks in passed:
            for k, i in enumerate(places):
                rows_of[i] = (blocks, k)
        if with_counts:
            return {sid: [(b.block_start, b.payloads[k],
                           b.counts[k] if b.counts else None)
                          for b in blocks if b.payloads[k] is not None]
                    for sid, (blocks, k) in zip(sids, rows_of)}
        return {sid: [(b.block_start, b.payloads[k])
                      for b in blocks if b.payloads[k] is not None]
                for sid, (blocks, k) in zip(sids, rows_of)}

    # --- lifecycle (ref: storage/mediator.go tick+flush loops) ---

    @_locked
    def load_batch(self, ns: str, ids, tags, times_nanos, values) -> None:
        """Row-wise load: one id/tags entry per sample.  Thin adapter
        over :meth:`load_columns` (identity uniq mapping)."""
        self.load_columns(ns, ids, tags, times_nanos, values, None)

    @_locked
    def load_columns(self, ns: str, uniq_ids, uniq_tags, times_nanos,
                     values, uniq_idx=None) -> None:
        """Write without the commit log — peer-bootstrap / repair loads
        of already-replicated data (ref: bootstrap result loads skip
        the WAL, storage/bootstrap data accumulators).  Columnar shape
        matches :meth:`write_columns`: per-SERIES uniq tables plus a
        per-sample row index (None = identity).

        Loads that touch sealed or flushed blocks first UNSEAL them
        back into open buffers so the points merge instead of
        shadowing: the next tick re-seals and the next flush writes a
        new fileset volume (ref: the cold-flush merger rewriting
        merged block filesets, persist/fs/merger.go)."""
        n = self._ns(ns)
        bsize = n.opts.retention.block_size
        times_arr = np.asarray(times_nanos, dtype=np.int64)
        if len(times_arr):
            num_shards = len(n.shards)
            shards_u = np.fromiter(
                (shard_for(sid, num_shards) for sid in uniq_ids),
                dtype=np.int64, count=len(uniq_ids))  # per-series work
            shard_ids = (shards_u if uniq_idx is None
                         else shards_u[np.asarray(uniq_idx, np.int64)])
            bss = times_arr - times_arr % bsize
            pairs = np.unique(np.stack([shard_ids, bss], axis=1), axis=0)
            for s, bs in pairs.tolist():
                self._unseal_for_load(ns, n, n.shards[int(s)], int(bs))
        was = self._bootstrapping
        self._bootstrapping = True
        try:
            self.write_columns(ns, uniq_ids, uniq_tags, times_arr,
                               values, uniq_idx)
        finally:
            self._bootstrapping = was

    def _unseal_for_load(self, ns: str, n, shard, bs: int) -> None:
        lane_of = lambda sid: n.index.insert(sid, {})  # noqa: E731
        if shard.unseal(bs, lane_of):
            self._decoded_cache.invalidate_block(ns, shard.shard_id, bs)
            return
        if bs in shard.open_block_starts():
            return  # already an open buffer: merges naturally
        # flushed-on-disk only (e.g. after a restart): pull the fileset
        # contents into a buffer and supersede it with the next volume
        vol = self._data_filesets(ns, shard).get(bs)
        if vol is None:
            return
        reader = FilesetReader(self.path / "data", ns, shard.shard_id,
                               bs, vol)
        self._load_reader_into_buffers(n, shard, reader, bs)
        shard._volume[bs] = vol + 1
        # flush-version bump: volume vol is superseded, its decoded
        # entries must never serve again
        self._decoded_cache.invalidate_block(ns, shard.shard_id, bs)

    @staticmethod
    def _load_reader_into_buffers(n, shard, reader, bs: int) -> int:
        """Decode every series of one fileset/snapshot reader into the
        shard's open buffer (indexing as it goes); returns rows loaded.

        Decodes ALL streams in one batched call (native/device with a
        scalar fallback per lane) — the per-series scalar decode this
        replaces made warm bootstrap O(samples) of Python and slower
        than cold WAL replay at scale."""
        from m3_tpu.ops.m3tsz_decode import decode_streams_adaptive

        sids, tgs, blobs = [], [], []
        for sid, tg in zip(reader.ids, reader.tags):  # per-series
            blob = reader.read(sid)
            if not blob:
                continue
            sids.append(sid)
            tgs.append(tg)
            blobs.append(blob)
        if not sids:
            return 0
        ts, vs, valid = decode_streams_adaptive(blobs)
        lanes = n.index.insert_batch(sids, tgs)
        n.index.mark_active_batch(lanes, bs)
        counts = valid.sum(axis=1).astype(np.int64)
        # row-major masking keeps each lane's samples contiguous and
        # in stream order, matching the repeated lane column
        shard.write_batch(np.repeat(lanes, counts),
                          np.asarray(ts[valid], dtype=np.int64),
                          np.asarray(vs[valid], dtype=np.float64))
        return int(counts.sum())

    @_locked
    def series_streams_for_block(self, ns: str, block_start: int
                                 ) -> list[tuple[bytes, dict, bytes]]:
        """[(sid, tags, compressed_stream)] for every series with a
        sealed/flushed copy of the block — the AggregateTiles input
        gather (ref: shard.go:2659 reads flushed source blocks).  Runs
        under the database lock (the lazy shard-ordinal cache must not
        race serving writes)."""
        n = self._ns(ns)
        out = []
        for shard_id in sorted(n.shards):
            for ordinal in n.ordinals_for_shard(shard_id):
                sid = n.index.id_of(ordinal)
                for b, payload in self.fetch_series(
                        ns, sid, block_start, block_start + 1):
                    if b != block_start:
                        continue
                    if isinstance(payload, (bytes, bytearray)):
                        out.append((sid, n.index.tags_of(ordinal),
                                    bytes(payload)))
        return out

    @_locked
    def block_metadata(self, ns: str, shard_id: int, start_nanos: int,
                       end_nanos: int):
        """{series_id: (tags, [(block_start, size, checksum)])} for one
        shard (ref: rpc.thrift fetchBlocksMetadataRawV2 ->
        service.go FetchBlocksMetadataRawV2)."""
        from m3_tpu.storage.peers import payload_checksum

        n = self._ns(ns)
        out = {}
        for ordinal in n.ordinals_for_shard(shard_id):
            sid = n.index.id_of(ordinal)
            blocks = [
                (bs, *payload_checksum(payload))
                for bs, payload in self.fetch_series(
                    ns, sid, start_nanos, end_nanos)]
            if blocks:
                out[sid] = (n.index.tags_of(ordinal), blocks)
        return out

    @_locked
    def drop_shard(self, ns: str, shard_id: int) -> dict:
        """Free all local data for one shard — the donor's drain step
        after cutover (ref: the reference's shard cleanup once a
        LEAVING copy's receiver goes AVAILABLE).  Open buffers and
        sealed blocks are discarded, flushed filesets (and snapshots)
        are deleted, and the read caches are invalidated so a stale
        reader cannot serve the freed copy.  Index entries remain (the
        series may still live on other shards of other nodes; reads of
        the dropped shard simply find no blocks).

        Caveat: commit-log entries for the shard are NOT rewritten; a
        restart before the WAL rotates can resurrect the data, and the
        next placement pass will not re-drain it (the reconciler's
        held-shard tracking starts from the post-restart placement).
        Anti-entropy repair never re-spreads it — the shard is no
        longer in this node's placement entry.

        Returns ``{"blocks": freed_blocks, "bytes": freed_file_bytes}``.
        """
        n = self._ns(ns)
        shard = n.shards[shard_id]
        blocks = set(shard.sealed_block_starts()) | set(
            shard.open_block_starts())
        freed_bytes = 0
        for root in (self.path / "data", self.path / "snapshot"):
            for bs, vol in list_fileset_volumes(root, ns, shard_id):
                blocks.add(bs)
                d = pathlib.Path(root) / ns / str(shard_id)
                for f in d.glob(f"fileset-{bs}-{vol}-*.db"):
                    try:
                        freed_bytes += f.stat().st_size
                    except OSError:
                        pass
                remove_fileset(root, ns, shard_id, bs, vol)
        for bs in blocks:
            self._decoded_cache.invalidate_block(ns, shard_id, bs)
        self._seek.invalidate_where(
            lambda key: key[0] == ns and key[1] == shard_id)
        n.shards[shard_id] = Shard(shard_id, n.opts)
        n.shards[shard_id].filesets = {}  # every volume was removed above
        return {"blocks": len(blocks), "bytes": freed_bytes}

    @_locked
    def tick(self, now_nanos: int | None = None) -> dict[str, list[int]]:
        now_nanos = now_nanos if now_nanos is not None else clock.now_nanos()
        sealed = defaultdict(list)
        for name, n in self._namespaces.items():
            ids = n.index._ids
            for shard in n.shards.values():
                sealed[name].extend(shard.tick(now_nanos, ids))
            store = self._struct_stores.get(name)
            if store is not None:
                cutoff = now_nanos - n.opts.retention.buffer_past
                sealed[name].extend(store.seal_before(cutoff))
            # sealed blocks take no more writes: freeze their activity
            # sets; expire index time-slices past retention
            self._m_sealed.inc(len(sealed[name]))
            for bs in set(sealed[name]):
                n.index.freeze_block(bs)
            if n.opts.cleanup_enabled:
                n.index.drop_blocks_before(
                    now_nanos - n.opts.retention.retention_period,
                    n.opts.retention.block_size,
                )
        return dict(sealed)

    @_locked
    def flush(self) -> dict[str, list[int]]:
        faultpoints.check("flush.begin")
        flushed = defaultdict(list)
        for name, n in self._namespaces.items():
            if not n.opts.flush_enabled:
                continue

            def tags_of(sid, n=n):
                return n.index.tags_of(n.index.ordinal(sid))

            for shard in n.shards.values():
                flushed[name].extend(
                    shard.flush(self._fileset_writer, name, tags_of)
                )
            if flushed[name]:
                faultpoints.check("flush.index_persist")
                # persist the index snapshot alongside the filesets it
                # covers, so restart mmaps segments instead of
                # re-reading every fileset's metadata
                covered = [
                    [shard.shard_id, bs, vol]
                    for shard in n.shards.values()
                    for bs, vol in sorted(
                        self._data_filesets(name, shard).items())
                ]
                n.index.persist(self.path / "index" / name, covered)
            store = self._struct_stores.get(name)
            if store is not None:
                flushed[name].extend(store.flush())
        total = sum(len(v) for v in flushed.values())
        if total:
            self._m_flush.inc(total)
            _log.info("flushed blocks", blocks=total)
            faultpoints.check("flush.cleanup")
            # warm-flushed blocks obsolete their snapshots
            self._cleanup_filesets()
        return dict(flushed)

    @_locked
    def snapshot(self) -> dict[str, list[int]]:
        """Snapshot filesets: persist every block whose ONLY durability
        is the WAL (open buffers + sealed-unflushed blocks), then drop
        the WAL files the snapshot covers — crash recovery becomes
        snapshot load + WAL-tail replay instead of unbounded full
        replay (ref: src/dbnode/storage/flush.go:206 dataSnapshot,
        persist/fs/snapshot_metadata_write.go, storage/cleanup.go).

        Only namespaces with ``snapshot_enabled`` participate; WAL
        files are deleted only when every WAL-writing namespace is
        snapshot-enabled (entries interleave namespaces in one file).
        """
        # coverage depends only on namespace options: a WAL file may be
        # deleted only if EVERY WAL-writing namespace is snapshotted.
        # When it can't be, don't rotate either (rotating would just
        # accumulate undeletable files).
        all_covered = all(
            n.opts.snapshot_enabled
            for n in self._namespaces.values()
            if n.opts.writes_to_commit_log
        )
        faultpoints.check("snapshot.begin")
        old_wal: list = []
        if self._commitlog is not None and all_covered:
            old_wal = self._commitlog.rotate()
            faultpoints.check("snapshot.rotated")
        writer = FilesetWriter(self.path / "snapshot")
        done = defaultdict(list)
        for name, n in self._namespaces.items():
            if not n.opts.snapshot_enabled:
                continue
            ids = n.index._ids
            lane_of = n.index.ordinal
            for shard in n.shards.values():
                volumes = dict(list_filesets(self.path / "snapshot", name,
                                             shard.shard_id))
                for bs, (sids, streams) in shard.snapshot_pending(
                        ids, lane_of).items():
                    writer.write(
                        name, shard.shard_id, bs, sids, streams,
                        volume=volumes.get(bs, -1) + 1,
                        block_size=n.opts.retention.block_size,
                        tags=[n.index.tags_of(n.index.ordinal(s))
                              for s in sids],
                    )
                    done[name].append(bs)
        for p in old_wal:
            faultpoints.check("snapshot.wal_unlink")
            p.unlink(missing_ok=True)
        faultpoints.check("snapshot.cleanup")
        self._cleanup_filesets()
        total = sum(len(v) for v in done.values())
        if total:
            self._m_snapshot.inc(total)
            _log.info("snapshot", blocks=total,
                      wal_dropped=len(old_wal))
        return dict(done)

    def _cleanup_filesets(self) -> None:
        """Drop superseded snapshot/data volumes and snapshots of
        blocks whose state is on disk in a data fileset (the warm flush
        supersedes them) — ref: src/dbnode/storage/cleanup.go."""
        for name, n in self._namespaces.items():
            for shard in n.shards.values():
                flushed = self._data_filesets(name, shard)
                latest = dict(list_filesets(self.path / "snapshot", name,
                                            shard.shard_id))
                # memory still holds WAL-only data for these blocks
                pending_mem = set(shard.open_block_starts()) | {
                    bs for bs in shard.sealed_block_starts()
                    if bs not in shard._flushed
                }
                for bs, vol in list_fileset_volumes(
                        self.path / "snapshot", name, shard.shard_id):
                    obsolete = vol < latest.get(bs, -1) or (
                        bs in flushed and bs not in pending_mem
                    )
                    if obsolete:
                        faultpoints.check("cleanup.remove_snapshot")
                        remove_fileset(self.path / "snapshot", name,
                                       shard.shard_id, bs, vol)
                # superseded data volumes (unseal-merge re-flushes)
                for bs, vol in list_fileset_volumes(
                        self.path / "data", name, shard.shard_id):
                    if vol < flushed.get(bs, -1):
                        faultpoints.check("cleanup.remove_data")
                        remove_fileset(self.path / "data", name,
                                       shard.shard_id, bs, vol)

    def bootstrap(self) -> int:
        """fs bootstrapper: flushed blocks stay on disk and are served from
        filesets; commitlog bootstrapper: replay WAL entries whose blocks
        have no fileset yet.  Returns datapoints recovered from the WAL.

        The readiness flag flips OUTSIDE the db lock so health probes
        (node ``health`` RPC, coordinator ``/health``) can report
        bootstrap-in-flight without blocking on the lock bootstrap
        holds — readiness surfaces answer 503 instead of hanging.
        """
        self._bootstrap_in_flight = True
        try:
            faultpoints.check("db.bootstrap")
            return self._bootstrap_locked()
        finally:
            self._bootstrap_in_flight = False

    @property
    def bootstrap_in_flight(self) -> bool:
        return self._bootstrap_in_flight

    @property
    def bootstrapped(self) -> bool:
        """False only while ``bootstrap()`` is in flight — a node
        serving a store it never needed to bootstrap is still ready."""
        return not self._bootstrap_in_flight

    @property
    def bootstrap_progress(self) -> dict:
        """{"phase", "entries_replayed", "bytes_replayed"} — read
        lock-free by health surfaces while bootstrap holds the db
        lock."""
        return dict(self._bootstrap_progress)

    def _set_bootstrap_phase(self, phase: str) -> None:
        self._bootstrap_progress["phase"] = phase
        self._m_bootstrap_phase.set(_BOOTSTRAP_PHASES.get(phase, 0))

    @_locked
    def _bootstrap_locked(self) -> int:
        t0 = time.perf_counter()
        self._bootstrap_progress.update(entries_replayed=0,
                                        bytes_replayed=0)
        self._set_bootstrap_phase("index")
        recovered = 0
        # index bootstrap: mmap the persisted index snapshot, then the
        # fs index pass reads ONLY filesets the snapshot doesn't cover
        # (the reference's fs bootstrapper index pass; with snapshots
        # a restart avoids the full metadata rebuild)
        # coverage is tracked PER (shard, block): a crash can land
        # between two shards' fileset writes for the same block, and a
        # namespace-level "block is flushed" test would silently drop
        # the unflushed shard's WAL entries (found by the kill-point
        # sweep at fileset.done; the TLA invariant this serves is
        # AllAckedWritesAreBootstrappable, SnapshotsSpec.tla:219)
        flushed: dict[str, dict[int, set[int]]] = {}
        covers: dict[str, dict[tuple[int, int], int]] = {}
        for name, n in self._namespaces.items():
            covered = {
                tuple(c) for c in n.index.load(self.path / "index" / name)
            }
            shard_blocks: dict[int, set[int]] = {}
            shard_covers: dict[tuple[int, int], int] = {}
            for shard in n.shards.values():
                # the directory as the last process left it: from
                # here on the shard's listing follows what is written
                for bs, vol in sorted(
                        self._scan_filesets(name, shard).items()):
                    shard_blocks.setdefault(shard.shard_id, set()).add(bs)
                    info = read_fileset_info(self.path / "data", name,
                                             shard.shard_id, bs, vol) or {}
                    shard_covers[(shard.shard_id, bs)] = info.get(
                        "covers_until", 0)
                    if (shard.shard_id, bs, vol) in covered:
                        continue
                    reader = FilesetReader(
                        self.path / "data", name, shard.shard_id, bs, vol
                    )
                    if reader.ids:
                        lanes = n.index.insert_batch(reader.ids,
                                                     reader.tags)
                        n.index.mark_active_batch(lanes, bs)
            flushed[name] = shard_blocks
            covers[name] = shard_covers
        # snapshot pass: blocks whose only durability was a snapshot
        # load into buffers; blocks with BOTH a fileset and a newer
        # snapshot (late writes) merge via the unseal path so the next
        # flush writes a superseding volume (the cold-flush merge,
        # ref: persist/fs/merger.go)
        self._set_bootstrap_phase("snapshots")
        recovered += self._bootstrap_snapshots()
        if self._commitlog is not None:
            self._set_bootstrap_phase("wal-replay")
            recovered += self._replay_commitlog_columnar(flushed, covers)
        self._set_bootstrap_phase("done")
        self._m_bootstrap_seconds.observe(time.perf_counter() - t0)
        return recovered

    # accumulated replay samples flush to the write path in slabs: big
    # enough to amortize shard dispatch, small enough to bound memory
    _REPLAY_FLUSH_SAMPLES = 1 << 19

    def _replay_commitlog_columnar(self, flushed, covers) -> int:
        """Columnar WAL-tail replay (warm-bootstrap tentpole): each
        chunk arrives from :meth:`CommitLog.replay_chunks` already in
        the slot-router shape (uniq-series table + sample columns) and
        is classified per unique (shard, block) pair — the chunk's
        single ``written_at`` stamp makes the fileset-coverage test
        per-PAIR scalar work, never per-sample.  Samples route to the
        batch path (no fileset yet) or the cold-merge path (fileset
        exists, entry stamped after its seal) via columnar selections;
        a given pair always routes to exactly one destination, so
        accumulators flush independently without reordering."""
        recovered = 0
        # (name, dest) -> [ids, tags, idx_parts, t_parts, v_parts, base]
        acc: dict[tuple, list] = {}
        pending = 0

        def _flush():
            nonlocal pending
            for (name, dest), a in list(acc.items()):
                ids_l, tags_l, idx_l, t_l, v_l, _base = a
                uniq_idx = np.concatenate(idx_l)
                times = np.concatenate(t_l)
                vals = np.concatenate(v_l)
                if dest == "batch":
                    was = self._bootstrapping
                    self._bootstrapping = True
                    try:
                        self.write_columns(name, ids_l, tags_l, times,
                                           vals, uniq_idx)
                    finally:
                        self._bootstrapping = was
                else:
                    self.load_columns(name, ids_l, tags_l, times, vals,
                                      uniq_idx)
                pending -= len(times)
                del acc[(name, dest)]

        for chunk in CommitLog.replay_chunks(self.path / "commitlog"):
            faultpoints.check("bootstrap.replay_chunk")
            self._m_bootstrap_bytes.inc(chunk.nbytes)
            self._bootstrap_progress["bytes_replayed"] += chunk.nbytes
            for name, n in self._namespaces.items():
                # entries apply only to their own namespace; legacy
                # (pre-v3, ns None) chunks carry no namespace and
                # replay into every WAL-writing one — never into
                # namespaces that do not write the commit log at all
                # (those would grow phantom series)
                if not n.opts.writes_to_commit_log:
                    continue
                if chunk.ns is not None and chunk.ns != name:
                    continue
                bsize = n.opts.retention.block_size
                num_shards = len(n.shards)
                shards_u = np.fromiter(
                    (shard_for(sid, num_shards)
                     for sid in chunk.uniq_ids),
                    dtype=np.int64, count=len(chunk.uniq_ids))
                shard_ids = shards_u[chunk.uniq_idx]
                bss = chunk.times - chunk.times % bsize
                pairs, inv = np.unique(
                    np.stack([shard_ids, bss], axis=1), axis=0,
                    return_inverse=True)
                fl = flushed[name]
                cv = covers[name]
                # 0 = batch (no fileset), 1 = cold merge, 2 = covered
                dest = np.empty(len(pairs), dtype=np.int8)
                for pi, (s, bs) in enumerate(pairs.tolist()):
                    if bs in fl.get(s, ()):
                        dest[pi] = (2 if chunk.written_at
                                    <= cv.get((s, bs), 0) else 1)
                    else:
                        dest[pi] = 0
                sample_dest = dest[inv]
                for d, key in ((0, "batch"), (1, "merge")):
                    sel = np.flatnonzero(sample_dest == d)
                    if not len(sel):
                        continue
                    recovered += len(sel)
                    # compact the uniq table to referenced rows only:
                    # phantom series must not enter the index
                    rows, sub_idx = np.unique(chunk.uniq_idx[sel],
                                              return_inverse=True)
                    a = acc.setdefault((name, key),
                                       [[], [], [], [], [], 0])
                    base = a[5]
                    a[0].extend(chunk.uniq_ids[r] for r in rows.tolist())
                    a[1].extend(chunk.uniq_tags[r] for r in rows.tolist())
                    a[2].append(sub_idx.astype(np.int64) + base)
                    a[3].append(chunk.times[sel])
                    a[4].append(chunk.values[sel])
                    a[5] = base + len(rows)
                    pending += len(sel)
                    if pending >= self._REPLAY_FLUSH_SAMPLES:
                        _flush()
            self._m_bootstrap_entries.inc(recovered
                                          - self._bootstrap_progress[
                                              "entries_replayed"])
            self._bootstrap_progress["entries_replayed"] = recovered
        _flush()
        return recovered

    def _bootstrap_snapshots(self) -> int:
        """Load snapshot filesets written by `snapshot()`.  Returns
        datapoints recovered."""
        recovered = 0
        snap_root = self.path / "snapshot"
        for name, n in self._namespaces.items():
            for shard in n.shards.values():
                on_disk = self._data_filesets(name, shard)
                for bs, vol in list_filesets(snap_root, name, shard.shard_id):
                    try:
                        reader = FilesetReader(snap_root, name,
                                               shard.shard_id, bs, vol)
                    except (FileNotFoundError, ValueError):
                        continue
                    if bs in on_disk:
                        # block has BOTH a data fileset and a snapshot:
                        # merge, loading the OLDER artifact first so
                        # last-write-wins favors the newer one (a stale
                        # snapshot left by a crash mid-cleanup must not
                        # resurrect overwritten values; a post-flush
                        # cold-write snapshot must win)
                        data_reader = FilesetReader(
                            self.path / "data", name, shard.shard_id,
                            bs, on_disk[bs])
                        snap_at = reader.info.get("written_at", 0)
                        data_at = data_reader.info.get("written_at", 0)
                        if snap_at <= data_at:
                            # stale snapshot: load it first, newer
                            # fileset last (last-write-wins)
                            recovered += self._load_reader_into_buffers(
                                n, shard, reader, bs)
                            self._load_reader_into_buffers(
                                n, shard, data_reader, bs)
                            shard._volume[bs] = on_disk[bs] + 1
                            self._decoded_cache.invalidate_block(
                                name, shard.shard_id, bs)
                            continue
                        self._unseal_for_load(name, n, shard, bs)
                    recovered += self._load_reader_into_buffers(
                        n, shard, reader, bs)
        return recovered

    @property
    def draining(self) -> bool:
        """True once :meth:`prepare_shutdown` (or :meth:`begin_drain`)
        has run — health surfaces report it so routers stop sending
        work here before the process exits."""
        return self._draining

    def begin_drain(self) -> None:
        """Flip readiness to draining WITHOUT the database lock: health
        probes must see the flag even while a long snapshot holds the
        lock."""
        self._draining = True

    def prepare_shutdown(self) -> dict[str, list[int]]:
        """Graceful-restart seam (ref: the dbnode's deferred shutdown
        in server.go: drain, snapshot, then exit): flip to draining,
        drain the commitlog group-commit so every acked write is on
        disk, then snapshot so the next bootstrap's replay window is
        the seconds since rotation instead of hours of WAL.  Wired to
        SIGTERM by services.run.  Crash-safe at every seam — the
        killpoint sweep crashes mid-drain/mid-snapshot and recovery
        still serves every acked write, because durability never
        depends on this path (the WAL already has everything)."""
        self.begin_drain()
        faultpoints.check("shutdown.drain")
        if self._commitlog is not None:
            self._commitlog.flush()
        faultpoints.check("shutdown.snapshot")
        done = self.snapshot()
        faultpoints.check("shutdown.done")
        _log.info("prepare_shutdown",
                  snapshot_blocks=sum(len(v) for v in done.values()))
        return done

    def close(self) -> None:
        self._seek.clear()
        self._decoded_cache.clear()
        if self._commitlog is not None:
            self._commitlog.close()
        for store in self._struct_stores.values():
            store.close()
        for n in self._namespaces.values():
            n.index.close()  # stop the background compaction daemon
        self._open = False


class Mediator:
    """Background tick / flush / snapshot loops over one Database
    (ref: src/dbnode/storage/mediator.go:141 — tick + flush/snapshot/
    clean driver).  Intervals in seconds; snapshot_every=0 disables
    snapshots (e.g. when every namespace has them off)."""

    def __init__(self, db: Database, tick_every: float = 10.0,
                 snapshot_every: float = 60.0):
        self.db = db
        self.tick_every = tick_every
        self.snapshot_every = snapshot_every
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self.last_error: BaseException | None = None

    def start(self) -> "Mediator":
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()
        return self

    def _run(self) -> None:
        from jax.profiler import TraceAnnotation

        from m3_tpu import observe
        hb = observe.task_ledger().register_daemon(
            "mediator", interval_hint_s=self.tick_every)
        last_snapshot = time.monotonic()
        while not self._stop.wait(self.tick_every):
            hb.beat()
            try:
                # named in a device trace, so that a reader of one can
                # tell the device's idle time under a pass from the rest
                with TraceAnnotation("m3:mediator"):
                    self.db.tick()
                    self.db.flush()
                    if (self.snapshot_every
                            and time.monotonic() - last_snapshot
                            >= self.snapshot_every):
                        self.db.snapshot()
                        last_snapshot = time.monotonic()
            except Exception as exc:  # noqa: BLE001 - the loop must survive
                self.last_error = exc
                instrument.counter("m3_mediator_errors_total").inc()
                _log.error("mediator pass failed", error=exc)
        hb.close()

    def stop(self) -> None:
        """Blocks until the loop exits — the caller closes the database
        next, and an in-flight snapshot must not race that."""
        self._stop.set()
        if self._thread is not None:
            # an in-flight flush/snapshot pass may take a while, but a
            # wedged pass must not hang stop() forever — close proceeds
            # and the daemon thread is abandoned
            self._thread.join(timeout=60.0)
            if self._thread.is_alive():
                _log.error("mediator thread did not exit within 60s; "
                           "proceeding with close")
