"""Tile compaction daemon: aged raw blocks -> rung namespaces, on
device, off the write path.

(ref: src/dbnode/storage/database.go:1277 AggregateTiles — the
reference runs large-tile aggregation as an operator-driven batch job
over flushed filesets; here a background daemon drives it
continuously: every sealed/flushed raw block older than the ladder's
hot window is rolled into EACH rung at that rung's resolution, then
the raw source falls out via normal retention GC.)

Design points:

- **Off the write path.**  The daemon reads only sealed/flushed
  blocks (``series_streams_for_block``) and writes through
  ``load_batch`` (WAL-less unseal-merge upsert) — ingest acks never
  wait on it.
- **Resumable + idempotent.**  Progress is CAS-published to the
  cluster KV store, one marker per (source, target, block): a
  ``running`` claim before the batch, a ``done`` record after.  A
  crash mid-batch leaves a ``running`` marker; the next pass re-runs
  the block — safe because ``load_batch`` is a last-write-wins upsert
  keyed on (series, timestamp) and tile output is deterministic for
  sealed input — and CASes it to ``done``.  Losing the CAS means a
  peer finished first; the result is identical either way.
- **Identity-preserving output.**  Tiles are emitted with each
  series' kind-default aggregation (``AggregationType.LAST`` — the
  GAUGE default, which carries no id suffix), so a rolled-up series
  keeps its raw series id and the engine's per-series finest-wins
  stitch merges raw + rung tiers into one continuous series.
"""

from __future__ import annotations

import threading
import time

from m3_tpu.cluster.kv import (ErrAlreadyExists, ErrNotFound,
                               ErrVersionMismatch, MemStore)
from m3_tpu.metrics.policy import format_duration
from m3_tpu.ops.downsample import AggregationType
from m3_tpu.storage.tiles import AggregateTilesOptions, TileAggregator
from m3_tpu.utils import clock, instrument

from .ladder import RetentionLadder

log = instrument.logger("retention.compactor")

_MARKER_PREFIX = "_retention/compaction"


def _metrics():
    return {
        "compactions": instrument.counter("m3_retention_compactions_total"),
        "errors": instrument.counter("m3_retention_compaction_errors_total"),
        "tiles": instrument.counter("m3_retention_tiles_written_total"),
        "seconds": instrument.histogram("m3_retention_compaction_seconds"),
    }


class TileCompactionDaemon:
    """Background loop rolling aged raw blocks into ladder rungs.

    ``run_once(now_nanos)`` is the whole state machine and is public
    so tests (and operators, via an admin hook) drive it with a fixed
    clock; ``start()``/``close()`` wrap it in a ledger-registered
    daemon thread modeled on the index compactor."""

    def __init__(self, db, ladder: RetentionLadder,
                 source_namespace: str = "default",
                 kv_store: MemStore | None = None,
                 hot_window_nanos: int = 0,
                 poll_s: float = 30.0,
                 max_blocks_per_pass: int = 64,
                 now_fn=clock.now_nanos):
        self._db = db
        self._ladder = ladder
        self._src = source_namespace
        self._kv = kv_store if kv_store is not None else MemStore()
        self._poll_s = max(float(poll_s), 0.01)
        self._max_blocks = max(int(max_blocks_per_pass), 1)
        self._now_fn = now_fn
        self._tiler = TileAggregator(db)
        retention = db.namespace_options(source_namespace).retention
        self._block_size = retention.block_size
        self._raw_retention = retention.retention_period
        # Hot window: how long a raw block stays exclusively raw.
        # Must cover at least one full block beyond the open one, or
        # we would race the seal path.
        floor = 2 * self._block_size
        self._hot_window = max(int(hot_window_nanos), floor)
        for rung in ladder:
            if self._block_size % rung.resolution:
                raise ValueError(
                    f"rung {rung} resolution does not divide the "
                    f"source block size "
                    f"({format_duration(self._block_size)})")
        self._lag_s = 0.0
        instrument.gauge_fn("m3_retention_compaction_lag_seconds",
                            lambda: self._lag_s)
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._lock = threading.Lock()

    # -- work discovery / CAS markers ------------------------------

    def _marker_key(self, target_ns: str, block_start: int) -> str:
        return (f"{_MARKER_PREFIX}/{self._src}/{target_ns}/"
                f"{block_start}")

    def pending(self, now_nanos: int | None = None
                ) -> list[tuple[str, int]]:
        """[(target_namespace, block_start)] not yet marked done,
        oldest first — eligible blocks are sealed (older than the hot
        window) but still within raw retention."""
        now = self._now_fn() if now_nanos is None else now_nanos
        lo = now - self._raw_retention
        lo -= lo % self._block_size
        hi = now - self._hot_window
        hi -= hi % self._block_size
        out = []
        bs = lo
        while bs < hi:
            for rung in self._ladder:
                key = self._marker_key(rung.namespace, bs)
                try:
                    if self._kv.get(key).json().get("status") == "done":
                        continue
                except ErrNotFound:
                    pass
                out.append((rung.namespace, bs))
            bs += self._block_size
        return out

    def run_once(self, now_nanos: int | None = None) -> int:
        """One compaction pass; returns the number of (rung, block)
        jobs completed (including re-runs of crashed claims)."""
        now = self._now_fn() if now_nanos is None else now_nanos
        work = self.pending(now)
        m = _metrics()
        done = 0
        for target_ns, bs in work[:self._max_blocks]:
            if self._stop.is_set():
                break
            if self._compact_block(target_ns, bs, m):
                done += 1
        self._update_lag(now)
        return done

    def _compact_block(self, target_ns: str, block_start: int,
                       m) -> bool:
        key = self._marker_key(target_ns, block_start)
        try:
            version = self._kv.set_if_not_exists(
                key, b'{"status": "running"}')
        except ErrAlreadyExists:
            val = self._kv.get(key)
            if val.json().get("status") == "done":
                return False  # raced: a peer finished it
            version = val.version  # crashed claim: adopt and re-run
        rung = next(r for r in self._ladder
                    if r.namespace == target_ns)
        t0 = time.perf_counter()
        try:
            res = self._tiler.aggregate_tiles(
                self._src, target_ns, block_start,
                block_start + self._block_size,
                AggregateTilesOptions(
                    tile_nanos=rung.resolution,
                    agg_types=(AggregationType.LAST,)))
        except Exception:
            m["errors"].inc()
            raise
        try:
            self._kv.check_and_set_json(key, version, {
                "status": "done",
                "series": res.n_series,
                "tiles": res.n_tiles_written,
                "errors": res.n_errors,
            })
        except ErrVersionMismatch:
            # A peer re-claimed and published while we ran; identical
            # output either way (idempotent upsert), nothing to undo.
            pass
        m["compactions"].inc()
        m["tiles"].inc(res.n_tiles_written)
        if res.n_errors:
            m["errors"].inc(res.n_errors)
        m["seconds"].observe(time.perf_counter() - t0)
        log.info("compacted block", source=self._src, target=target_ns,
                 block_start=block_start, series=res.n_series,
                 tiles=res.n_tiles_written, errors=res.n_errors)
        return True

    def _update_lag(self, now_nanos: int) -> None:
        """Lag = age of the oldest eligible-but-unfinished block past
        the hot-window cutoff (0 when fully caught up)."""
        rest = self.pending(now_nanos)
        if not rest:
            self._lag_s = 0.0
            return
        oldest = min(bs for _, bs in rest)
        cutoff = now_nanos - self._hot_window
        self._lag_s = max(0.0, (cutoff - oldest) / 1e9)

    # -- daemon plumbing (index-compactor idiom) -------------------

    def start(self) -> None:
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                return
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._loop, name="m3-retention-compactor",
                daemon=True)
            self._thread.start()

    def wake(self) -> None:
        self._wake.set()

    def _loop(self) -> None:
        from m3_tpu import observe
        hb = observe.task_ledger().register_daemon(
            "retention_compaction", interval_hint_s=self._poll_s)
        try:
            while not self._stop.is_set():
                self._wake.wait(timeout=self._poll_s)
                self._wake.clear()
                hb.beat()
                if self._stop.is_set():
                    return
                try:
                    self.run_once()
                except Exception as exc:  # noqa: BLE001 - daemon must survive
                    log.error("retention compaction pass failed",
                              error=exc)
        finally:
            hb.close()

    def close(self) -> None:
        self._stop.set()
        self._wake.set()
        t = self._thread
        if t is not None:
            t.join(timeout=5.0)
        self._thread = None
