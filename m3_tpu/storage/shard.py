"""One shard: open block buffers + sealed blocks + filesets.

Mirrors dbShard (ref: src/dbnode/storage/shard.go:910 writeAndIndex,
:704 Tick) with the series hot path columnar: writes land in a
per-block columnar buffer; Tick seals expired blocks by sorting the
buffer and encoding every series' stream (batch encode); flush writes
the sealed block as an immutable fileset.
"""

from __future__ import annotations

import dataclasses
import itertools
import operator
from typing import Callable, NamedTuple

import numpy as np

from m3_tpu.ops import m3tsz_scalar
from m3_tpu.storage.buffer import BlockBuffer, OpenRow, open_rows
from m3_tpu.storage.fileset import FilesetWriter
from m3_tpu.storage.namespace import NamespaceOptions
from m3_tpu.utils import clock


def encode_block_scalar(
    block_start: int, lanes, times, values, n_lanes: int
) -> list[bytes]:
    """Batch-encode consolidated columnar triples into per-lane streams.

    Host scalar path; the device batched encoder slots in here once the
    write path is device-resident.
    """
    streams = [b""] * n_lanes
    bounds = np.searchsorted(lanes, np.arange(n_lanes + 1))
    for lane in range(n_lanes):
        lo, hi = bounds[lane], bounds[lane + 1]
        if lo == hi:
            continue
        streams[lane] = m3tsz_scalar.encode_series(
            times[lo:hi].tolist(), values[lo:hi].tolist(), block_start
        )
    return streams


def _encode_block_native(block_start: int, lanes, times, values,
                         n_lanes: int) -> list[bytes]:
    """CPU seal path: threaded C++ ragged encode from the columnar
    (lane-sorted) seal layout — no dense [L, T] scatter."""
    from m3_tpu.utils.native import encode_columnar_native

    lanes = np.asarray(lanes)
    bounds = np.searchsorted(lanes, np.arange(n_lanes + 1))
    starts = np.full(n_lanes, block_start, dtype=np.int64)
    return encode_columnar_native(bounds, np.asarray(times),
                                  np.asarray(values), starts)


def _pow2_at_least(n: int, floor: int) -> int:
    p = floor
    while p < n:
        p *= 2
    return p


def encode_block_device(
    block_start: int, lanes, times, values, n_lanes: int
) -> list[bytes]:
    """Seal one block on device: batched M3TSZ encode of all series lanes.

    Columnar (lanes, times, values) — lanes sorted — is scattered into a
    padded [L, T] tensor and encoded in one jit call (m3tsz_encode).
    Shapes are bucketed to powers of two to bound recompiles.  Streams
    with sub-second timestamps take the scalar wire edge (the batched
    grammar covers the fixed-unit production shape).
    """
    from m3_tpu.utils import xtime

    sec = xtime.SECOND
    if n_lanes == 0:
        return []
    if len(times) == 0:
        return [b""] * n_lanes
    if block_start % sec or (np.asarray(times) % sec).any():
        return encode_block_scalar(block_start, lanes, times, values, n_lanes)

    import jax

    if jax.default_backend() == "cpu":
        # CPU serving: the scalar C++ encoder beats the branchless
        # XLA kernel on a host core by a wide margin (same reasoning
        # as the decode side, m3tsz_decode.decode_streams); both paths
        # are byte-exact against the same oracle
        try:
            return _encode_block_native(block_start, lanes, times,
                                        values, n_lanes)
        except Exception:  # toolchain unavailable: device kernel below
            pass

    from m3_tpu.ops.m3tsz_encode import encode_to_streams

    lanes = np.asarray(lanes)
    times = np.asarray(times)
    values = np.asarray(values)
    bounds = np.searchsorted(lanes, np.arange(n_lanes + 1))
    counts = np.diff(bounds).astype(np.int32)

    # Bucket lanes by padded length so one dense series doesn't inflate
    # the whole shard to O(L x T_max) memory: each bucket encodes at its
    # own power-of-two T (still a handful of compiled shapes).
    t_bucket = np.maximum(
        8, 1 << np.ceil(np.log2(np.maximum(counts, 1))).astype(np.int64))
    streams: list[bytes] = [b""] * n_lanes
    col_of_point = np.arange(len(times)) - bounds[lanes]
    for T in np.unique(t_bucket[counts > 0]):
        members = np.flatnonzero((t_bucket == T) & (counts > 0))
        L = _pow2_at_least(len(members), 8)
        tsm = np.full((L, int(T)), block_start, dtype=np.int64)
        vsm = np.zeros((L, int(T)), dtype=np.float64)
        n_valid = np.zeros((L,), dtype=np.int32)
        n_valid[: len(members)] = counts[members]
        # One vectorized scatter for the whole bucket: every point whose
        # lane is a member lands at (row_of_lane, its offset in the lane).
        row_of_lane = np.full(n_lanes, -1, dtype=np.int64)
        row_of_lane[members] = np.arange(len(members))
        pmask = row_of_lane[lanes] >= 0
        rows = row_of_lane[lanes[pmask]]
        cols = col_of_point[pmask]
        tsm[rows, cols] = times[pmask]
        vsm[rows, cols] = values[pmask]
        starts = np.full((L,), block_start, dtype=np.int64)
        encoded = encode_to_streams(tsm, vsm, starts, n_valid)
        for row, lane in enumerate(members):
            streams[int(lane)] = encoded[row]
    return streams


@dataclasses.dataclass
class SealedBlock:
    block_start: int
    ids: list[bytes]
    streams: list[bytes]
    # wall-clock seal time: the fileset written from this block covers
    # every WAL entry stamped at/before it (bootstrap's skip rule)
    sealed_at: int = 0
    # datapoints per stream (known at seal time); rides into the
    # fileset index (v2) so batch readers size decode grids exactly
    counts: list[int] | None = None
    # sid -> row of ids/streams/counts: built with the block (Shard.seal
    # makes every SealedBlock) and dropped with it (unseal), so the read
    # path looks a row up and never searches `ids`
    row_of: dict[bytes, int] = dataclasses.field(init=False, repr=False)
    # streams and counts with a None behind the last row: what a bulk
    # read takes rows from, -1 standing for a series the block lacks
    _streams_or_none: tuple = dataclasses.field(init=False, repr=False)
    _counts_or_none: tuple | None = dataclasses.field(init=False,
                                                      repr=False)

    def __post_init__(self):
        self.row_of = {sid: row for row, sid in enumerate(self.ids)}
        self._streams_or_none = (*self.streams, None)
        self._counts_or_none = (None if self.counts is None
                                else (*self.counts, None))

    def take(self, sids: list[bytes], with_counts: bool
             ) -> tuple[list, list | None]:
        """-> (streams, counts) aligned with `sids`, None where the
        block lacks a series (`counts` None: not asked for, or not
        stored).  Three C-level passes: nothing is done once a row in
        the interpreter."""
        # a -1 behind the rows: an itemgetter of one index alone would
        # hand back the item, not a tuple of it
        pick = operator.itemgetter(
            *map(self.row_of.get, sids, itertools.repeat(-1)), -1)
        streams = list(pick(self._streams_or_none)[:-1])
        if not with_counts or self._counts_or_none is None:
            return streams, None
        return streams, list(pick(self._counts_or_none)[:-1])


# what the rows of a BlockRows hold (every payload that is not None)
STREAMS = "streams"  # compressed M3TSZ streams, `counts` beside them
OPEN = "open"        # OpenRow: an open buffer's lane, named, not yet read
ARRAYS = "arrays"    # (times, values) arrays, decoded or read from a buffer
MIXED = "mixed"      # a cold write beside sealed streams: any, row by row


class BlockRows(NamedTuple):
    """One block's rows for the series a bulk read asked for: `payloads`
    and `counts` are aligned with the series (None where a series has
    nothing in the block; `counts` None where no row has a count)."""

    block_start: int
    kind: str
    payloads: list
    counts: list | None


class Shard:
    def __init__(
        self,
        shard_id: int,
        opts: NamespaceOptions,
        fileset_root: str | None = None,
        encode_fn: Callable = encode_block_device,
    ):
        self.shard_id = shard_id
        self.opts = opts
        self.encode_fn = encode_fn
        self.fileset_root = fileset_root
        self._buffers: dict[int, BlockBuffer] = {}
        self._sealed: dict[int, SealedBlock] = {}
        self._flushed: set[int] = set()
        # next fileset volume per block start; bumped when a flushed
        # block is unsealed for a merge (repair / peer loads), so the
        # re-flush writes a NEW volume and readers pick the latest
        self._volume: dict[int, int] = {}
        # the data filesets of this (namespace, shard) on disk,
        # {block_start: latest volume}; None until the database, the
        # only writer of that directory, has listed it once.  `flush`
        # renews it with every fileset it writes, so reads never scan.
        self.filesets: dict[int, int] | None = None
        from m3_tpu.utils import instrument
        # wall-clock distance of the newest accepted sample from now:
        # a rising value means writers are falling behind real time
        self._m_lag = instrument.gauge(
            "m3_ingest_lag_seconds", ns=opts.name, shard=str(shard_id))

    # --- write path ---

    def write_batch(self, lanes, times_nanos, values) -> None:
        """Route a columnar batch into per-block buffers."""
        times_nanos = np.asarray(times_nanos, dtype=np.int64)
        lanes = np.asarray(lanes, dtype=np.int64)
        values = np.asarray(values, dtype=np.float64)
        if len(times_nanos):
            self._m_lag.set(
                (clock.now_nanos() - int(times_nanos.max())) / 1e9)
        starts = times_nanos - (times_nanos % self.opts.retention.block_size)
        uniq = np.unique(starts)
        if len(uniq) == 1:
            # steady-state ingest lands every sample in the live block:
            # hand the columns over whole, no mask/gather round
            bs = int(uniq[0])
            buf = self._buffers.get(bs)
            if buf is None:
                buf = self._buffers[bs] = BlockBuffer(bs)
            buf.write_batch(lanes, times_nanos, values)
            return
        for bs in uniq:
            sel = starts == bs
            buf = self._buffers.get(int(bs))
            if buf is None:
                buf = self._buffers[int(bs)] = BlockBuffer(int(bs))
            buf.write_batch(lanes[sel], times_nanos[sel], values[sel])

    # --- lifecycle ---

    def seal(self, block_start: int, ids: list[bytes]) -> SealedBlock | None:
        """Sort + encode one block's buffer into immutable streams.
        `ids` maps lane ordinal -> series id (from the shard's index).

        Re-seal of a block that was already sealed (a cold write landed
        after the first seal) MERGES the prior sealed content instead
        of overwriting it — otherwise the new sealed block would hold
        only the cold points while shadowing the on-disk fileset, and
        flush would skip it as already-flushed: the flushed points
        vanish from reads and the cold points never persist (found by
        the round-5 concurrency-stress tier).  The merge rides
        ``unseal``, which also bumps the fileset volume so the next
        flush writes a superseding volume (the reference's cold-flush
        merger, ref: persist/fs/merger.go)."""
        from m3_tpu.utils import xtime

        if block_start in self._sealed and block_start in self._buffers:
            # merge order matters: the old sealed chunks must sort
            # BEFORE the cold-write chunks so consolidated()'s
            # keep-LAST-duplicate rule lets the newer write win a
            # rewritten (lane, time) — the same winner read_series and
            # snapshot_pending produce (shard.go upsert semantics)
            cold = self._buffers.pop(block_start)
            sid_lane = {sid: i for i, sid in enumerate(ids)}
            self.unseal(block_start, lambda sid: sid_lane[sid])
            merged = self._buffers.get(block_start)
            if merged is None:
                self._buffers[block_start] = cold
            else:
                merged._lanes.extend(cold._lanes)
                merged._times.extend(cold._times)
                merged._values.extend(cold._values)
                merged._total += cold._total
        buf = self._buffers.pop(block_start, None)
        if buf is None or buf.num_datapoints == 0:
            return None
        lanes, times, values = buf.consolidated()
        streams = self.encode_fn(block_start, lanes, times, values, len(ids))
        present = [i for i, s in enumerate(streams) if s]
        # per-lane datapoint counts (lanes are sorted): stored in the
        # fileset index so batch readers skip the count pass
        lane_counts = np.bincount(lanes, minlength=len(ids))
        sealed = SealedBlock(
            block_start=block_start,
            ids=[ids[i] for i in present],
            streams=[streams[i] for i in present],
            # same stamp authority as commit-log chunks (clock-step-
            # safe ordering for bootstrap's covered-entry test)
            sealed_at=xtime.stamp_ns(),
            counts=[int(lane_counts[i]) for i in present],
        )
        self._sealed[block_start] = sealed
        return sealed

    def unseal(self, block_start: int, lane_of) -> bool:
        """Decode a sealed block back into an open buffer so late data
        (repair, peer loads) can merge; the next tick re-seals and the
        next flush writes a new fileset volume.  The reference's
        equivalent is the cold-flush merger rewriting a block's fileset
        with merged data (ref: persist/fs/merger.go)."""
        blk = self._sealed.pop(block_start, None)
        if blk is None:
            return False
        from m3_tpu.ops import m3tsz_scalar as tsz

        lanes, times, values = [], [], []
        for sid, stream in zip(blk.ids, blk.streams):
            t, v = tsz.decode_series(stream)
            lane = lane_of(sid)
            lanes.extend([lane] * len(t))
            times.extend(t)
            values.extend(v)
        if lanes:
            self.write_batch(lanes, times, values)
        if block_start in self._flushed:
            self._flushed.discard(block_start)
            self._volume[block_start] = self._volume.get(block_start, 0) + 1
        return True

    def tick(self, now_nanos: int, ids: list[bytes]) -> list[int]:
        """Seal every buffer whose block can no longer take writes
        (block end + buffer_past elapsed) — the reference's tick/merge
        (ref: shard.go:704)."""
        ret = self.opts.retention
        sealed = []
        for bs in sorted(self._buffers):
            if bs + ret.block_size + ret.buffer_past <= now_nanos:
                if self.seal(bs, ids):
                    sealed.append(bs)
        return sealed

    def snapshot_pending(self, ids, lane_of) -> dict[int, tuple[list[bytes], list[bytes]]]:
        """{block_start: (ids, streams)} for every block whose ONLY
        durability is the WAL: open buffers and sealed-unflushed
        blocks.  A block with BOTH (a cold write after seal) merges
        them — the cold write must not be dropped from the snapshot
        (the covering WAL files get deleted afterwards)."""
        out: dict[int, tuple[list[bytes], list[bytes]]] = {}
        unflushed_sealed = {
            bs: blk for bs, blk in self._sealed.items()
            if bs not in self._flushed
        }
        for bs in sorted(set(self._buffers) | set(unflushed_sealed)):
            buf = self._buffers.get(bs)
            blk = unflushed_sealed.get(bs)
            if buf is None or buf.num_datapoints == 0:
                if blk is not None:
                    out[bs] = (list(blk.ids), list(blk.streams))
                continue
            if blk is None:
                lanes, times, values = buf.consolidated()
            else:
                from m3_tpu.ops import m3tsz_scalar as tsz

                merged = BlockBuffer(bs)
                for sid, stream in zip(blk.ids, blk.streams):
                    t, v = tsz.decode_series(stream)
                    merged.write_batch([lane_of(sid)] * len(t), t, v)
                # buffer writes later: they win duplicate timestamps
                b_lanes, b_times, b_values = buf.consolidated()
                merged.write_batch(b_lanes, b_times, b_values)
                lanes, times, values = merged.consolidated()
            if not len(lanes):
                continue
            streams = self.encode_fn(bs, lanes, times, values, len(ids))
            present = [i for i, s in enumerate(streams) if s]
            out[bs] = ([ids[i] for i in present],
                       [streams[i] for i in present])
        return out

    def flush(self, writer: FilesetWriter, ns: str, tags_of=None) -> list[int]:
        """Persist sealed blocks not yet on disk (warm flush,
        ref: storage/flush.go:120).  tags_of(id) supplies series metadata
        for the on-disk index."""
        flushed = []
        for bs, blk in sorted(self._sealed.items()):
            if bs in self._flushed:
                continue
            writer.write(
                ns,
                self.shard_id,
                bs,
                blk.ids,
                blk.streams,
                block_size=self.opts.retention.block_size,
                tags=[tags_of(sid) for sid in blk.ids] if tags_of else None,
                volume=self._volume.get(bs, 0),
                covers_until=blk.sealed_at,
                counts=blk.counts,
            )
            if self.filesets is not None:
                self.filesets[bs] = self._volume.get(bs, 0)
            self._flushed.add(bs)
            flushed.append(bs)
        return flushed

    # --- read path ---

    def read_many(
        self, sids: list[bytes], lanes: list[int], start_nanos: int,
        end_nanos: int, with_counts: bool = False,
        defer_open: bool = False,
    ) -> list[BlockRows]:
        """In-memory data of [start, end) for the series `sids` (index
        ordinals `lanes`), block by block, block starts ascending: one
        ``BlockRows`` per block in which any of them has data.  Flushed
        filesets are read at the Database level (it owns the paths).

        The ONE implementation of the in-memory block-selection and
        merge rules; ``read_series`` is its one-series call.  A sealed
        block's rows come through the table the seal built
        (``SealedBlock.row_of``), an open buffer's from one ``view()``
        of it.  ``defer_open=True`` (the engine's bulk gather) names a
        plain open-buffer read as an ``OpenRow`` on that view instead
        of cutting it out here: the caller reads all its lanes of a
        view in one call, after the database lock, and drops the rows
        that turn out empty.  A series with a sealed stream AND samples
        in a buffer of the same block (a cold write after the seal) is
        merged here either way, buffer winning a duplicate timestamp.

        ``with_counts=True`` fills ``counts`` with a sealed stream's
        datapoint count, produced HERE beside the payload it describes,
        never re-derived by a caller from separate state."""
        first = start_nanos - start_nanos % self.opts.retention.block_size
        sealed, buffers = self._sealed, self._buffers
        out: list[BlockRows] = []
        # only block starts that hold data: walking [start, end) block
        # by block is O(range/block_size), and an open-ended query
        # (end = +inf sentinel) would spin through millions of empty
        # 2h steps
        for bs in sorted(bs for bs in sealed.keys() | buffers.keys()
                         if first <= bs < end_nanos):
            blk, buf = sealed.get(bs), buffers.get(bs)
            streams = counts = None
            if blk is not None:
                streams, counts = blk.take(sids, with_counts)
                if not any(streams):    # a sealed stream is never empty
                    streams = counts = None
            if buf is None:
                if streams is not None:
                    out.append(BlockRows(bs, STREAMS, streams, counts))
            elif streams is None:
                view = buf.view()
                if defer_open:
                    out.append(BlockRows(bs, OPEN, open_rows(view, lanes),
                                         None))
                    continue
                read = [tv if len(tv[0]) else None
                        for tv in view.read_lanes(lanes)]
                if read.count(None) < len(read):
                    out.append(BlockRows(bs, ARRAYS, read, None))
            else:
                # a cold write after seal lands in a fresh buffer
                # alongside the sealed block — reads must see both
                # (ref: buffer bucket versions, buffer.go:221)
                out.append(self._read_cold_overlay(
                    bs, buf.view(), lanes, streams, counts, defer_open))
        return out

    @staticmethod
    def _read_cold_overlay(bs: int, view, lanes, streams, counts,
                           defer_open: bool) -> BlockRows:
        """The rows of a block that holds sealed streams and a buffer:
        a series in both is merged at read time, duplicate timestamps
        resolving to the buffer (the newer write) — the reference's
        bucket-version merge; without it a rewrite-after-seal would
        surface two values at one timestamp."""
        payloads: list = list(streams)
        counts = list(counts) if counts is not None else [None] * len(lanes)
        for i, (lane, stream, (buf_ts, buf_vs)) in enumerate(
                zip(lanes, streams, view.read_lanes(lanes))):
            if stream is None and defer_open:
                payloads[i] = OpenRow(view, lane)
            elif not len(buf_ts):
                continue  # the sealed stream alone, or nothing
            elif stream is None:
                payloads[i] = (buf_ts, buf_vs)
            else:
                st, sv = m3tsz_scalar.decode_series(stream)
                mt = np.concatenate([np.asarray(st, np.int64), buf_ts])
                mv = np.concatenate([np.asarray(sv, np.float64), buf_vs])
                order = np.argsort(mt, kind="stable")
                mt, mv = mt[order], mv[order]
                if len(mt) > 1:
                    keep = np.concatenate([mt[:-1] != mt[1:], [True]])
                    mt, mv = mt[keep], mv[keep]
                payloads[i], counts[i] = (mt, mv), None
        return BlockRows(bs, MIXED, payloads, counts)

    def read_series(
        self, series_id: bytes, lane: int, start_nanos: int, end_nanos: int,
        with_counts: bool = False, defer_open: bool = False,
    ) -> list[tuple]:
        """``read_many`` for one series: (block_start, payload) pairs,
        payload a compressed stream from a sealed block, (times, values)
        arrays from an open buffer (an ``OpenRow`` under ``defer_open``)
        or the read-time merge of both; with ``with_counts=True``
        (block_start, payload, n_dp_or_None) triples."""
        out = []
        for bs, _kind, payloads, counts in self.read_many(
                [series_id], [lane], start_nanos, end_nanos,
                with_counts=with_counts, defer_open=defer_open):
            if payloads[0] is None:
                continue
            out.append((bs, payloads[0], counts[0] if counts else None)
                       if with_counts else (bs, payloads[0]))
        return out

    def holds_block(self, block_start: int) -> bool:
        """Memory has a copy of the block (sealed or open): it wins
        over the fileset of the same block start."""
        return block_start in self._sealed or block_start in self._buffers

    def open_block_starts(self) -> list[int]:
        return sorted(self._buffers)

    def sealed_block_starts(self) -> list[int]:
        return sorted(self._sealed)
