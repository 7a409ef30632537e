"""Session-backed storage adapter: PromQL over a replicated cluster.

The coordinator's Engine evaluates against the Database read surface
(``fetch_tagged`` / ``_ns().index`` / ``namespaces``).  This adapter
implements that surface on top of a client ``Session``, so a
coordinator can serve the QUORUM-replicated cluster read path instead
of one local store (ref: src/query/storage/m3/storage.go — the
coordinator's m3 storage is a session client, not an embedded dbnode).

Labels are recovered from series ids: the remote-write ingest derives
``sid = b",".join(k + b"=" + v for sorted labels)`` (see
query/remote_write.series_id_from_labels), a reversible encoding, so
the adapter needs no tag-carrying RPC.  Series whose label VALUES
contain ``,`` or ``=`` are not representable through this adapter
(they never are through remote-write ingest either).

Degraded-mode: the session's per-fetch ResultMeta (dead/timed-out
replicas, per-host outcomes) merges into the engine's per-query meta,
and the engine's per-query deadline rides into the session fan-out —
the two hops the tentpole wires between HTTP edge and replica
transport.
"""

from __future__ import annotations

import threading

from m3_tpu.storage.database import Gathered
from m3_tpu.storage.shard import ARRAYS, MIXED, STREAMS, BlockRows


def _labels_of_sid(sid: bytes) -> dict[bytes, bytes]:
    out: dict[bytes, bytes] = {}
    if not sid:
        return out
    for pair in sid.split(b","):
        k, _, v = pair.partition(b"=")
        out[k] = v
    return out


class _SidIndex:
    """The slice of TagIndex the engine's read path consumes, backed
    by sid-interning: ordinals exist for any sid seen by a fetch."""

    def __init__(self):
        self._lock = threading.Lock()
        self._ord: dict[bytes, int] = {}
        self._sids: list[bytes] = []

    def ordinal(self, sid: bytes) -> int:
        with self._lock:
            o = self._ord.get(sid)
            if o is None:
                o = self._ord[sid] = len(self._sids)
                self._sids.append(sid)
            return o

    def id_of(self, ordinal: int) -> bytes:
        with self._lock:
            return self._sids[ordinal]

    def tags_of(self, ordinal: int):
        with self._lock:
            sid = self._sids[ordinal]
        return tuple(_labels_of_sid(sid).items())

    def tags_of_many(self, ordinals: list[int]) -> list:
        return list(map(self.tags_of, ordinals))

    def __len__(self) -> int:
        with self._lock:
            return len(self._sids)

    # best-effort label surface (from sids this adapter has seen; the
    # authoritative index lives on the storage nodes)
    def label_names(self) -> list[bytes]:
        with self._lock:
            sids = list(self._sids)
        names: set[bytes] = set()
        for sid in sids:
            names.update(_labels_of_sid(sid))
        return sorted(names)

    def label_values(self, name: bytes) -> list[bytes]:
        with self._lock:
            sids = list(self._sids)
        vals: set[bytes] = set()
        for sid in sids:
            v = _labels_of_sid(sid).get(name)
            if v is not None:
                vals.add(v)
        return sorted(vals)


class _NsView:
    def __init__(self, index: _SidIndex, opts):
        self.index = index
        self.opts = opts


class SessionStorage:
    """Database read surface over a Session (query path only: writes
    keep going through the session's own write API)."""

    def __init__(self, session, namespace: str = "default",
                 namespace_opts=None):
        self.session = session
        self.ns = namespace
        self._opts = namespace_opts
        self._index = _SidIndex()

    # -- namespace surface --

    def namespaces(self) -> list[str]:
        return [self.ns]

    def namespace_options(self, name: str):
        if name != self.ns:
            raise KeyError(name)
        return self._opts

    def _ns(self, name: str) -> _NsView:
        if name != self.ns:
            raise KeyError(name)
        return _NsView(self._index, self._opts)

    # -- read surface --

    def query_ids(self, ns: str, matchers, start_nanos=None,
                  end_nanos=None, limits=None, meta=None) -> list[bytes]:
        if ns != self.ns:
            raise KeyError(ns)
        # metadata via the data path: the session RPC has no
        # index-only call, so /series pays a fetch (bounded by limits)
        fetched = self.fetch_tagged(
            ns, matchers, start_nanos or 0, end_nanos or 2**62,
            limits=limits, meta=meta)
        return sorted(fetched)

    @staticmethod
    def _block_rows(sids, merged) -> list[BlockRows]:
        """The merged rows of `sids` block by block, block starts
        ascending, each block's rows aligned with `sids` (None: the
        series has nothing there)."""
        rows_of: dict[int, list] = {}
        for k, sid in enumerate(sids):
            for bs, payload in merged[sid]:
                rows_of.setdefault(bs, [None] * len(sids))[k] = payload
        blocks = []
        for bs, rows in sorted(rows_of.items()):
            is_stream = [isinstance(p, (bytes, memoryview)) for p in rows]
            n_streams = sum(is_stream)
            kind = (STREAMS if n_streams == len(rows) - rows.count(None)
                    else MIXED if n_streams else ARRAYS)
            blocks.append(BlockRows(
                bs, kind, rows, None if kind is STREAMS else
                [None if p is None or s else len(p[0])
                 for p, s in zip(rows, is_stream)]))
        return blocks

    def fetch_tagged(self, ns: str, matchers, start_nanos: int,
                     end_nanos: int, with_counts: bool = False,
                     limits=None, meta=None, defer_open: bool = False):
        # defer_open: nothing to defer, a session's rows arrive read
        if ns != self.ns:
            raise KeyError(ns)
        deadline = limits.deadline if limits is not None else None
        merged, fetch_meta = self.session.fetch_tagged_with_meta(
            ns, matchers, start_nanos, end_nanos, deadline=deadline)
        if meta is not None:
            meta.merge(fetch_meta)
        sids = sorted(merged)
        if limits is not None:
            # the node RPC carries no limits, so the series cap is
            # enforced client-side on the deterministic sorted order
            # (same truncate-or-abort contract as the index lookup)
            keep = limits.enforce_series(len(sids), meta)
            sids = sids[:keep]
        if meta is not None:
            meta.fetched_series += len(sids)
        if with_counts and defer_open:
            # the engine's gather: the series by sid, the rows as one
            # shard's blocks.  Replica-diverged blocks arrive as
            # (times, values) arrays with an exact count; identical
            # compressed copies stay opaque (count unknown -> host
            # decode)
            return Gathered(
                sids, list(map(self._index.ordinal, sids)),
                [(list(range(len(sids))), self._block_rows(sids, merged))])
        out: dict[bytes, list[tuple]] = {}
        for sid in sids:
            self._index.ordinal(sid)  # intern for tags_of
            blocks = merged[sid]
            if with_counts:
                out[sid] = [
                    (bs, payload,
                     None if isinstance(payload, (bytes, memoryview))
                     else len(payload[0]))
                    for bs, payload in blocks]
            else:
                out[sid] = list(blocks)
        return out
