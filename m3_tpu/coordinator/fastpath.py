"""Columnar ingest fast path — one hot loop for every protocol.

The steady-state ingest loop — parse -> series lookup -> shard
partition — runs with NO per-sample Python work: a C++ parser emits
columnar arrays (native/prom_wire.cc for Prometheus remote write,
native/text_wire.cc for carbon and InfluxDB line protocol), the C++
series router maps each series' raw label bytes to a persistent slot,
and numpy expands per-slot attributes (lane, shard) to per-sample
arrays.  Python code runs only per NEW series (index insert, canonical
id) and per shard group (buffer write), mirroring how the reference
splits its ingest between the Go protobuf runtime + sharded write path
(ref: src/query/api/v1/handler/prometheus/remote/write.go,
src/cmd/services/m3coordinator/ingest/carbon/ingest.go,
src/query/api/v1/handler/influxdb/write.go, src/dbnode/sharding,
ingest/write.go:138).

Eligibility is re-checked per request; anything unusual (bootstrapping
node, insert queue enabled, active downsampling rules, cold-write gate
with out-of-window samples, native toolchain missing) falls back to the
general DownsamplerAndWriter path, which remains the semantic
reference.  The text decoders additionally defer individual lines
outside their strict grammar to the scalar reference parsers, so a few
odd lines never knock a whole batch off the fast path."""

from __future__ import annotations

import ctypes

import numpy as np

from m3_tpu import attribution
from m3_tpu.query.remote_write import (labels_from_offsets,
                                       series_id_from_labels)
from m3_tpu.utils import instrument, tracing


class ColumnarFastPath:
    """Per-coordinator columnar ingest state (router + slot tables),
    shared by every protocol front end.  Subclasses decode their wire
    format into the prom_wire columnar shape and hand it to
    ``write_columnar``."""

    protocol = "columnar"

    def __init__(self, db, namespace: str):
        from m3_tpu.utils.native import load

        self._db = db
        self._ns_name = namespace
        lib = load("prom_wire")
        self._lib = lib
        if not getattr(lib.prom_router_new, "_typed", False):
            i64p = np.ctypeslib.ndpointer(np.int64)
            u8p = ctypes.c_char_p
            lib.prom_router_new.restype = ctypes.c_void_p
            lib.prom_router_new.argtypes = []
            lib.prom_router_free.restype = None
            lib.prom_router_free.argtypes = [ctypes.c_void_p]
            lib.prom_router_resolve.restype = ctypes.c_int64
            lib.prom_router_resolve.argtypes = [
                ctypes.c_void_p, i64p, i64p, u8p, ctypes.c_int64,
                i64p, i64p]
            lib.prom_router_assign.restype = None
            lib.prom_router_assign.argtypes = [
                ctypes.c_void_p, i64p, i64p, u8p, i64p, i64p,
                ctypes.c_int64]
            lib.prom_router_expand.restype = None
            lib.prom_router_expand.argtypes = [i64p, i64p,
                                               ctypes.c_int64, i64p]
            lib.prom_router_drop_pending.restype = None
            lib.prom_router_drop_pending.argtypes = [ctypes.c_void_p]
            lib.prom_router_new._typed = True
        self._router = lib.prom_router_new()
        # per-slot tables (numpy grown amortized + python sidecars);
        # the object arrays let the WAL handoff gather per-series
        # python objects with one fancy-index + tolist instead of a
        # per-series listcomp
        self._lane_of_slot = np.empty(1024, dtype=np.int64)
        self._shard_of_slot = np.empty(1024, dtype=np.int64)
        self._idlen_of_slot = np.empty(1024, dtype=np.int64)
        self._sid_of_slot = np.empty(1024, dtype=object)
        self._tags_of_slot = np.empty(1024, dtype=object)
        self._n_slots = 0
        self._m_samples = instrument.counter("m3_ingest_samples_total",
                                             protocol=self.protocol)

    def __del__(self):  # pragma: no cover - interpreter teardown
        try:
            self._lib.prom_router_free(self._router)
        except Exception:
            pass

    # -- eligibility -----------------------------------------------------

    def eligible(self, dsw) -> bool:
        """Cheap per-request re-check: the fast path must be a
        semantic no-op replacement for dsw.write_batch."""
        db = self._db
        if getattr(db, "_bootstrapping", False):
            return False
        if getattr(db.opts, "insert_queue_enabled", False):
            return False
        try:
            if not db._ns(self._ns_name).opts.cold_writes_enabled:
                return False  # gate semantics live in the reference path
        except KeyError:
            return False
        d = getattr(dsw, "_downsampler", None)
        if d is not None:
            rs = d.matcher._ruleset
            if rs.mapping_rules or rs.rollup_rules:
                return False
        return True

    # -- hot path --------------------------------------------------------

    def write_columnar(self, ls, ss, off, blob, ts_ns, vals) -> int:
        """Route + write one decoded columnar batch (prom_wire shape:
        label_start, sample_start, label_off, blob, ts NANOS, values).
        Returns the sample count.  Raises on gate/limit rejections
        (never partially writes in that case)."""
        n_series = len(ls) - 1
        if n_series == 0:
            return 0
        n = self._db._ns(self._ns_name)
        ls = np.ascontiguousarray(ls, dtype=np.int64)
        ss = np.ascontiguousarray(ss, dtype=np.int64)
        off_flat = np.ascontiguousarray(off.reshape(-1), dtype=np.int64)
        slots = np.empty(n_series, dtype=np.int64)
        new_idx = np.empty(n_series, dtype=np.int64)
        db = self._db
        wal_seq = None
        new_labels = None
        with db.hold("write_columnar"):
            n_new = int(self._lib.prom_router_resolve(
                self._router, ls, off_flat, blob, n_series, slots,
                new_idx))
            if n_new:
                try:
                    slot_ids = self._register(n, ls, off, blob,
                                              new_idx[:n_new])
                except Exception:
                    # roll back resolve's placeholders: stale negatives
                    # would alias the next request's new-series indices
                    self._lib.prom_router_drop_pending(self._router)
                    raise
                self._lib.prom_router_assign(
                    self._router, ls, off_flat, blob, new_idx[:n_new],
                    slot_ids, n_new)
                new_labels = self._tags_of_slot[slot_ids].tolist()
                pending = np.where(slots < 0, -slots - 1, 0)
                slots = np.where(slots < 0, slot_ids[pending], slots)
            # per-sample expansion, all numpy
            n_samples = len(ts_ns)
            rep = np.diff(ss)
            per_sample_slot = np.repeat(slots, rep)
            lanes = self._lane_of_slot[per_sample_slot]
            shards = self._shard_of_slot[per_sample_slot]
            bsize = n.opts.retention.block_size
            block_starts = ts_ns - ts_ns % bsize
            # index liveness: batched per block (almost always ONE
            # block per request), vectorized inside the index
            for bs in np.unique(block_starts).tolist():
                n.index.mark_active_batch(
                    lanes[block_starts == bs], int(bs))
            # shard partition: one stable sort + contiguous slices
            # instead of a boolean mask per shard (stability keeps
            # last-write-wins insertion order within a shard)
            order = np.argsort(shards, kind="stable")
            sh_sorted = shards[order]
            lanes_o, ts_o, vals_o = (lanes[order], ts_ns[order],
                                     vals[order])
            cuts = np.flatnonzero(sh_sorted[1:] != sh_sorted[:-1]) + 1
            lo = 0
            for hi in list(cuts) + [n_samples]:
                n.shards[int(sh_sorted[lo])].write_batch(
                    lanes_o[lo:hi], ts_o[lo:hi], vals_o[lo:hi])
                lo = hi
            if (db._commitlog is not None
                    and n.opts.writes_to_commit_log):
                # columnar WAL handoff: Python objects per SERIES in
                # this request, never per sample — the uniq table is
                # this request's slot list (object-array gather, no
                # listcomp) and the repeat index maps each sample to
                # its series row
                wal_seq = db._commitlog.write_columns(
                    self._sid_of_slot[slots].tolist(), ts_ns, vals,
                    uniq_tags=self._tags_of_slot[slots].tolist(),
                    uniq_idx=np.repeat(
                        np.arange(n_series, dtype=np.int64), rep),
                    ns=self._ns_name,
                    uniq_lens=self._idlen_of_slot[slots])
            db._m_samples.inc(n_samples)
            self._m_samples.inc(n_samples)
            if n_new:  # keep the series-count gauge live (dashboards)
                db._m_series.set(sum(
                    len(x.index) for x in db._namespaces.values()))
        if attribution.enabled():
            # per-REQUEST attribution, outside the db lock (this path
            # never goes through db.write_columns, so it accounts its
            # own samples/new-series)
            tenant = tracing.current_tenant() or self._ns_name
            attribution.account_write(tenant, samples=n_samples,
                                      new_series=n_new)
            if new_labels:
                for labels in new_labels:
                    attribution.note_label_keys(labels.keys())
        if wal_seq is not None and db.opts.commit_log_fsync_every_batch:
            # block on the group-commit fsync OUTSIDE the db lock so
            # concurrent requests fill the next batch during the wait
            db._commitlog.wait_durable(wal_seq)
        return n_samples

    def _register(self, n, ls, off, blob, new_idx: np.ndarray):
        """Index-insert each new series; returns their slot ids.  The
        new-series rate limit is checked BEFORE any insert (router-new
        is not index-new: after a restart the router is empty while the
        index is bootstrapped, and pre-checking keeps the rejection
        atomic like the reference path)."""
        parsed = []
        for s in new_idx.tolist():
            labels = labels_from_offsets(off, blob, int(ls[s]),
                                         int(ls[s + 1]))
            labels.setdefault(b"__name__", b"")
            parsed.append((series_id_from_labels(labels), labels))
        if getattr(self._db._runtime, "write_new_series_limit_per_sec", 0):
            truly_new = sum(1 for sid, _ in parsed
                            if n.index.ordinal(sid) is None)
            self._db._check_new_series_limit(truly_new)
        slot_ids = np.empty(len(new_idx), dtype=np.int64)
        for j, (sid, labels) in enumerate(parsed):
            lane = n.index.insert(sid, labels)
            slot = self._n_slots
            if slot >= len(self._lane_of_slot):
                grow = len(self._lane_of_slot) * 2
                self._lane_of_slot = np.resize(self._lane_of_slot, grow)
                self._shard_of_slot = np.resize(self._shard_of_slot,
                                                grow)
                self._idlen_of_slot = np.resize(self._idlen_of_slot,
                                                grow)
                self._sid_of_slot = np.resize(self._sid_of_slot, grow)
                self._tags_of_slot = np.resize(self._tags_of_slot, grow)
            self._lane_of_slot[slot] = lane
            self._idlen_of_slot[slot] = len(sid)
            self._sid_of_slot[slot] = sid
            self._tags_of_slot[slot] = labels
            self._n_slots = slot + 1
            slot_ids[j] = slot
        # the new series' shards, asked for once
        self._shard_of_slot[slot_ids] = n.shards_of_lanes(
            self._lane_of_slot[slot_ids].tolist())
        return slot_ids


class PromIngestFastPath(ColumnarFastPath):
    """Prometheus remote-write front end (native/prom_wire.cc)."""

    protocol = "prom_fast"

    def write(self, raw: bytes) -> int | None:
        """Parse + route + write one WriteRequest body.  Returns the
        sample count, or None when the caller must use the fallback
        path (never partially writes in that case).  Raises ValueError
        on malformed payloads."""
        from m3_tpu.utils.native import decode_write_request_native

        ls, ss, off, blob, ts_ms, vals = decode_write_request_native(raw)
        return self.write_columnar(ls, ss, off, blob, ts_ms * 1_000_000,
                                   vals)


class CarbonFastPath(ColumnarFastPath):
    """Carbon (Graphite) line-protocol front end
    (native/text_wire.cc carbon_decode_lines)."""

    protocol = "carbon_fast"

    def __init__(self, db, namespace: str):
        super().__init__(db, namespace)
        from m3_tpu.utils.native import load

        load("text_wire")  # fail construction early, not per batch
        self._m_fallback = instrument.counter(
            "m3_ingest_protocol_fallback_lines_total", protocol="carbon")

    def write(self, data: bytes, now_nanos: int
              ) -> tuple[int, list[tuple[int, int]]]:
        """Decode + route + write one batch of carbon lines.  Returns
        (sample count written columnar, fallback line byte ranges) —
        the caller runs the scalar reference parser on the fallback
        slices (malformed-line counting included)."""
        from m3_tpu.utils.native import decode_carbon_native

        ls, ss, off, blob, ts_ns, vals, fb = decode_carbon_native(
            data, now_nanos)
        if fb:
            self._m_fallback.inc(len(fb))
        return self.write_columnar(ls, ss, off, blob, ts_ns, vals), fb


class InfluxFastPath(ColumnarFastPath):
    """InfluxDB line-protocol front end
    (native/text_wire.cc influx_decode_lines)."""

    protocol = "influx_fast"

    def __init__(self, db, namespace: str):
        super().__init__(db, namespace)
        from m3_tpu.utils.native import load

        load("text_wire")  # fail construction early, not per batch
        self._m_fallback = instrument.counter(
            "m3_ingest_protocol_fallback_lines_total", protocol="influx")

    def write(self, data: bytes, mult: int, now_nanos: int
              ) -> tuple[int, list[tuple[int, int]]]:
        """Decode + route + write one influx line-protocol body.
        Returns (sample count written columnar, fallback line byte
        ranges); ``mult`` is the precision->nanos multiplier."""
        from m3_tpu.utils.native import decode_influx_native

        ls, ss, off, blob, ts_ns, vals, fb = decode_influx_native(
            data, mult, now_nanos)
        if fb:
            self._m_fallback.inc(len(fb))
        return self.write_columnar(ls, ss, off, blob, ts_ns, vals), fb
