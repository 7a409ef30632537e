"""The gather's walk, block by block over tables the writers keep
(``Database.fetch_tagged`` -> ``Shard.read_many``), against a plain
per-series oracle of the read rules kept here: equal in labels, order,
payload bytes, counts and ``ns_bytes``, in every state a shard-block can
be in.  And its structure: no directory listed, no id list searched, one
view an open buffer, one body for ``read_series`` and ``read_many``;
readers beside a writer and the mediator's tick + flush."""

import inspect
import pathlib
import threading

import numpy as np
import pytest

from m3_tpu.ops import m3tsz_scalar as tsz
from m3_tpu.query.engine import Engine
from m3_tpu.storage import (Database, DatabaseOptions, NamespaceOptions,
                            RetentionOptions)
from m3_tpu.storage import database as database_mod
from m3_tpu.storage import fileset as fileset_mod
from m3_tpu.storage.buffer import BlockBuffer, OpenRow
from m3_tpu.storage.fileset import FilesetReader, list_filesets
from m3_tpu.storage.limits import QueryLimits, ResultMeta
from m3_tpu.storage.shard import Shard
from m3_tpu.utils import xtime

SEC = xtime.SECOND
BLOCK = 2 * xtime.HOUR
T0 = (1_600_000_000 * SEC // BLOCK) * BLOCK
CADENCE = 30 * SEC
PER_BLOCK = BLOCK // CADENCE            # 240
SERIES = 12
SHARDS = 4
NS = "default"
MATCH = [("eq", b"__name__", b"m")]


def _open_db(path, cache=None, shards=SHARDS):
    db = Database(DatabaseOptions(path=str(path), num_shards=shards,
                                  commit_log_enabled=False, cache=cache))
    db.create_namespace(NamespaceOptions(
        name=NS, retention=RetentionOptions(block_size=BLOCK)))
    return db


def _sid(i: int) -> bytes:
    return b"m|h%02d" % i


def _tags(i: int) -> dict:
    return {b"__name__": b"m", b"host": b"h%02d" % i, b"dc": b"dc%d" % (i % 3)}


def _value(i: int, col: int, bump: float = 0.0) -> float:
    return float((col % 200) * (1 + i)) + bump


def _write(db, cols, series=range(SERIES), bump: float = 0.0, load=False,
           ns=NS):
    cols = list(cols)
    for i in series:
        ts = [T0 + c * CADENCE for c in cols]
        vs = [_value(i, c, bump) for c in cols]
        (db.load_batch if load else db.write_batch)(
            ns, [_sid(i)] * len(ts), [_tags(i)] * len(ts), ts, vs)


def _write_fleet(db, cols, series):
    """Many series at once: one request a column."""
    series = list(series)
    sids, tags = [_sid(i) for i in series], [_tags(i) for i in series]
    for c in cols:
        db.write_batch(NS, sids, tags, [T0 + c * CADENCE] * len(series),
                       [_value(i, c) for i in series])


def _tick(db, n_blocks: int):
    db.tick(now_nanos=T0 + n_blocks * BLOCK + 11 * 60 * SEC)


def _forget_memory(db):
    """The in-memory copies of flushed blocks go, as on a node that
    evicts them: reads must come from the filesets."""
    for shard in db._ns(NS).shards.values():
        for bs in list(shard._sealed):
            if bs in shard._flushed:
                del shard._sealed[bs]


# --- the oracle: the read rules, one series at a time -------------------

def _lane_of_buffer(buf: BlockBuffer, lane: int):
    lanes, times, values = buf.consolidated()       # sorted, last write wins
    sel = lanes == lane
    return times[sel], values[sel]


def _oracle_series(db, sid: bytes, lo: int, hi: int, defer_open: bool,
                   counted: bool = True, ns: str = NS):
    """[(block_start, kind, payload, n_dp)] of one series in [lo, hi):
    flushed filesets not shadowed by memory (read from the directory
    itself), then what memory holds, block starts ascending."""
    n = db._ns(ns)
    lane = n.index.ordinal(sid)
    shard = n.shard_of(sid)
    in_memory = set(shard._sealed) | set(shard._buffers)
    rows = []
    for bs, vol in list_filesets(db.path / "data", ns, shard.shard_id):
        if not (lo < bs + BLOCK and bs < hi) or bs in in_memory:
            continue
        reader = FilesetReader(db.path / "data", ns, shard.shard_id, bs, vol)
        if sid not in reader.ids:
            continue
        if counted and db._decoded_cache.policy_for(ns) != "none":
            # a series cache policy: the fileset's rows arrive decoded
            ts, vs = tsz.decode_series(reader.read(sid))
            rows.append((bs, "decoded", (np.asarray(ts, np.int64),
                                         np.asarray(vs, np.float64)),
                         len(ts)))
        else:
            rows.append((bs, "stream", reader.read(sid),
                         reader._counts[reader.ids.index(sid)]))
    first = lo - lo % BLOCK
    for bs in sorted(in_memory):
        if not first <= bs < hi:
            continue
        stream = count = None
        blk = shard._sealed.get(bs)
        if blk is not None and sid in list(blk.ids):
            at = list(blk.ids).index(sid)
            stream, count = blk.streams[at], blk.counts[at]
        buf = shard._buffers.get(bs)
        if buf is not None:
            ts, vs = _lane_of_buffer(buf, lane)
            if stream is None:
                if defer_open or len(ts):
                    rows.append((bs, "open" if defer_open else "arrays",
                                 (ts, vs), None))
                continue
            if len(ts):
                # buffer beside a sealed stream: merged, buffer wins a
                # duplicate timestamp
                merged = dict(zip(*tsz.decode_series(stream)))
                merged.update(zip(ts.tolist(), vs.tolist()))
                mt = np.asarray(sorted(merged), dtype=np.int64)
                mv = np.asarray([merged[t] for t in mt.tolist()],
                                dtype=np.float64)
                rows.append((bs, "cold", (mt, mv), None))
                continue
        if stream is not None:
            rows.append((bs, "stream", stream, count))
    return sorted(rows, key=lambda r: r[0])


def _ndp(row) -> int:
    _bs, kind, payload, count = row
    if count is not None:
        return int(count)
    if kind == "stream":
        return max(1, len(payload) // 2)
    return len(payload[0])


def _oracle_fetch(db, lo, hi, defer_open, limits=None, meta=None,
                  counted=True, ns=NS):
    """{sid: rows}, sids in index order: the fetch's own rules (series
    truncated at the index, shards in order of first match, the
    datapoint budget checked between shards)."""
    if limits is not None:
        lo = limits.clamp_time_range(lo, hi, meta)
    sids = db.query_ids(ns, MATCH, lo, hi, limits=limits, meta=meta)
    if meta is not None:
        meta.fetched_series += len(sids)
    n = db._ns(ns)
    by_shard: dict[int, list[bytes]] = {}
    for sid in sids:
        by_shard.setdefault(n.shard_of(sid).shard_id, []).append(sid)
    out = {sid: [] for sid in sids}
    fetched = 0
    for group in by_shard.values():
        if limits is not None and limits.datapoints_exceeded(fetched, meta):
            break
        for sid in group:
            out[sid] = _oracle_series(db, sid, lo, hi, defer_open, counted,
                                      ns)
            if limits is not None and limits.max_fetched_datapoints:
                fetched += sum(_ndp(r) for r in out[sid])
    if meta is not None:
        meta.fetched_datapoints += fetched
    return out


def _oracle_walk(db, lo, hi, limits=None, meta=None):
    """-> (labels, [(slot, kind, payload, n_dp, tier)] in the gather's
    order, ns_bytes): the per-series loop the engine's walk was until
    PR 44, a tier after the other in the fetch plan's order, slots
    numbered by first sight."""
    labels, rows, ns_bytes, slot_of = [], [], {}, {}
    for tier, ns in enumerate(Engine(db, NS)._resolve_namespaces()):
        fetched = _oracle_fetch(db, lo, hi + 1, True, limits, meta, ns=ns)
        n = db._ns(ns)
        nbytes = 0
        for sid in sorted(fetched):
            slot = slot_of.get(sid)
            if slot is None:
                slot = slot_of[sid] = len(labels)
                labels.append(dict(n.index.tags_of(n.index.ordinal(sid))))
            for _bs, kind, payload, count in fetched[sid]:
                if kind == "stream":
                    nbytes += len(payload)
                elif len(payload[0]):
                    nbytes += 16 * len(payload[0])
                else:
                    continue        # an open row that turned out empty
                rows.append((slot, kind, payload, count, tier))
        if nbytes:
            ns_bytes[ns] = nbytes
    return labels, rows, ns_bytes


# --- the walk under test, laid out the same way -------------------------

def _walk(engine, lo, hi, limits=None, meta=None):
    engine._qrange_local.limits, engine._qrange_local.meta = limits, meta
    try:
        labels, parts, stream_rows, named, ns_bytes = (
            engine._gather_walk(MATCH, lo, hi))
    finally:
        engine._qrange_local.limits = engine._qrange_local.meta = None
    # the columns, read back a row at a time (the old triple view)
    compressed = list(stream_rows.triples())
    counts = [None if c < 0 else c for c in stream_rows.counts.tolist()]
    assert len(compressed) == len(stream_rows) == len(counts)
    for _at, _ns, _slot, _tier, _after, row in named:
        assert isinstance(row, OpenRow)
    Engine._read_open_rows(parts, named, ns_bytes)
    rows, ci = [], 0

    def streams_up_to(n):
        nonlocal ci
        while ci < n:
            slot, tier, payload = compressed[ci]
            assert isinstance(payload, (bytes, memoryview))
            rows.append((slot, "stream", payload, counts[ci], tier))
            ci += 1

    for slot, tier, ts, vs, kind, after in parts:
        streams_up_to(after)
        rows.append((slot, kind, (ts, vs),
                     len(ts) if kind == "decoded" else None, tier))
    streams_up_to(len(compressed))
    return labels, rows, ns_bytes


def _assert_same_payload(kind, a, b):
    if kind == "stream":
        assert isinstance(a, (bytes, memoryview)) and bytes(a) == bytes(b)
    else:
        np.testing.assert_array_equal(a[0], b[0])
        np.testing.assert_array_equal(a[1], b[1])
        assert a[0].dtype == np.int64 and a[1].dtype == np.float64


def _assert_same_rows(got, want):
    assert [(r[0], r[1], r[3], r[4]) for r in got] == [
        (r[0], r[1], r[3], r[4]) for r in want]
    for (_, kind, a, *_), (_, _, b, *_) in zip(got, want):
        _assert_same_payload(kind, a, b)


def _assert_same_fetch(db, lo, hi):
    """The public forms of the same fetch, and the one-series reads."""
    want = _oracle_fetch(db, lo, hi, False)
    want_plain = _oracle_fetch(db, lo, hi, False, counted=False)
    plain = db.fetch_tagged(NS, MATCH, lo, hi)
    counted = db.fetch_tagged(NS, MATCH, lo, hi, with_counts=True)
    assert list(plain) == list(counted) == list(want)
    n = db._ns(NS)
    for sid, rows in want.items():
        assert [(bs, c) for bs, _p, c in counted[sid]] == [
            (r[0], r[3]) for r in rows]
        for got, wanted in ((plain[sid], want_plain[sid]),
                            ([e[:2] for e in counted[sid]], rows),
                            (db.fetch_series(NS, sid, lo, hi),
                             want_plain[sid])):
            assert [bs for bs, _p in got] == [r[0] for r in wanted]
            for (_bs, payload), row in zip(got, wanted):
                _assert_same_payload(row[1], payload, row[2])
        shard = n.shard_of(sid)
        mem = shard.read_series(sid, n.index.ordinal(sid), lo, hi,
                                with_counts=True)
        assert [(bs, c) for bs, _p, c in mem] == [
            (r[0], r[3]) for r in rows if shard.holds_block(r[0])]


# --- the states a shard-block can be in ---------------------------------

def _sealed_only(db, tmp):
    _write(db, range(2 * PER_BLOCK))
    _tick(db, 2)
    return db, 20, 2 * PER_BLOCK - 20


def _sealed_and_flushed(db, tmp):
    _write(db, range(2 * PER_BLOCK))
    _tick(db, 2)
    db.flush()
    return db, 20, 2 * PER_BLOCK - 20


def _open_only(db, tmp):
    _write(db, range(100, 300))
    return db, 110, 300


def _sealed_and_open_tail(db, tmp):
    _write(db, range(2 * PER_BLOCK))
    _tick(db, 2)
    db.flush()
    _write(db, range(2 * PER_BLOCK, 2 * PER_BLOCK + 60), series=range(1, 9))
    return db, 20, 2 * PER_BLOCK + 60


def _cold_write_after_seal(db, tmp):
    _write(db, range(2 * PER_BLOCK), series=range(SERIES - 2))
    _tick(db, 2)
    db.flush()
    # into the sealed second block: a rewrite of ten timestamps (the
    # buffer wins), new ones between, a series the seal never saw, and
    # most series left with their sealed stream alone
    _write(db, range(PER_BLOCK + 30, PER_BLOCK + 40), series=(0, 3),
           bump=0.25)
    db.write_batch(NS, [_sid(3)] * 2, [_tags(3)] * 2,
                   [T0 + (PER_BLOCK + 50) * CADENCE + 7 * SEC,
                    T0 + (PER_BLOCK + 51) * CADENCE + 7 * SEC], [1.5, 2.5])
    _write(db, range(PER_BLOCK + 60, PER_BLOCK + 70), series=(SERIES - 1,))
    return db, 20, 2 * PER_BLOCK - 20


def _series_absent_from_a_block(db, tmp):
    _write(db, range(PER_BLOCK), series=range(0, SERIES, 2))
    _write(db, range(PER_BLOCK, 2 * PER_BLOCK))
    _write(db, range(2 * PER_BLOCK, 2 * PER_BLOCK + 30), series=(1, 2))
    _tick(db, 2)
    return db, 0, 2 * PER_BLOCK + 30


def _only_on_disk_after_restart(db, tmp):
    _write(db, range(2 * PER_BLOCK))
    _tick(db, 2)
    db.flush()
    db.close()
    db = _open_db(tmp)
    db.bootstrap()
    assert not any(s._sealed or s._buffers
                   for s in db._ns(NS).shards.values())
    return db, 20, 2 * PER_BLOCK - 20


def _memory_copy_gone_and_open_tail(db, tmp):
    _write(db, range(2 * PER_BLOCK))
    _tick(db, 2)
    db.flush()
    _forget_memory(db)
    _write(db, range(2 * PER_BLOCK, 2 * PER_BLOCK + 40))
    return db, 20, 2 * PER_BLOCK + 40


def _unseal_and_reflush(db, tmp):
    _write(db, range(2 * PER_BLOCK))
    _tick(db, 2)
    db.flush()
    # a repair load into the flushed first block: unsealed, merged,
    # re-sealed, flushed as volume 1
    _write(db, range(40, 50), series=(0, 5), bump=0.75, load=True)
    _tick(db, 2)
    db.flush()
    bumped = [s for s in db._ns(NS).shards.values() if s._volume.get(T0)]
    assert bumped and all(s.filesets[T0] == 1 for s in bumped)
    _forget_memory(db)
    return db, 20, 2 * PER_BLOCK - 20


def _after_cleanup(db, tmp):
    db, lo, hi = _unseal_and_reflush(db, tmp)
    db._cleanup_filesets()
    for shard in db._ns(NS).shards.values():
        assert shard.filesets == dict(
            list_filesets(db.path / "data", NS, shard.shard_id))
        assert dict(fileset_mod.list_fileset_volumes(
            db.path / "data", NS, shard.shard_id)) == shard.filesets
    return db, lo, hi


def _series_cache_policy(db, tmp):
    """Filesets read through the decoded-block cache: the rows of a
    block on disk arrive as arrays with their count."""
    from m3_tpu.cache import CacheOptions
    db.close()
    db = _open_db(tmp, CacheOptions(decoded_policy="all"))
    db, lo, hi = _memory_copy_gone_and_open_tail(db, tmp)
    return db, lo, hi


FLEET = 600
FLEET_SHARDS = 8
FLEET_COLS = 16         # a block's samples: every 15th column


def _fleet_with_gaps_a_cold_write_and_a_tail(db, tmp):
    """600 series over 8 shards (sids that do not sort as the index's
    ordinals do: h100 < h11): every third series absent from the first
    block, the second block MIXED in the shards two cold writes land
    in, an open tail for a fifth of the fleet."""
    db.close()
    db = _open_db(tmp, shards=FLEET_SHARDS)
    _write_fleet(db, range(0, PER_BLOCK, PER_BLOCK // FLEET_COLS),
                 (i for i in range(FLEET) if i % 3))
    _write_fleet(db, range(PER_BLOCK, 2 * PER_BLOCK,
                           PER_BLOCK // FLEET_COLS), range(FLEET))
    _tick(db, 2)
    db.flush()
    _write(db, range(PER_BLOCK + 30, PER_BLOCK + 34), series=(7, 301),
           bump=0.25)
    _write_fleet(db, range(2 * PER_BLOCK, 2 * PER_BLOCK + 3),
                 range(0, FLEET, 5))
    return db, 0, 2 * PER_BLOCK + 3


def _two_namespaces(db, tmp):
    """A second tier: an aggregated namespace that holds every series,
    the raw one half of them, so the second tier's series take slots
    between and behind the first's."""
    db.create_namespace(NamespaceOptions(
        name="agg", retention=RetentionOptions(block_size=BLOCK),
        aggregated=True, aggregation_resolution=60 * SEC))
    raw = [i for i in range(SERIES) if i % 4 >= 2]
    _write(db, range(PER_BLOCK, 2 * PER_BLOCK), series=raw)
    _write(db, range(0, 2 * PER_BLOCK, 2), ns="agg")
    _write(db, range(2 * PER_BLOCK, 2 * PER_BLOCK + 20, 2), series=(1, 2),
           ns="agg")
    _tick(db, 2)
    return db, 0, 2 * PER_BLOCK + 20


STATES = [_sealed_only, _sealed_and_flushed, _open_only,
          _sealed_and_open_tail, _cold_write_after_seal,
          _series_absent_from_a_block, _only_on_disk_after_restart,
          _memory_copy_gone_and_open_tail, _unseal_and_reflush,
          _after_cleanup, _series_cache_policy,
          _fleet_with_gaps_a_cold_write_and_a_tail, _two_namespaces]


@pytest.mark.parametrize("state", STATES, ids=lambda f: f.__name__[1:])
def test_walk_equals_the_per_series_oracle(state, tmp_path):
    db, lo, hi = state(_open_db(tmp_path), tmp_path)
    try:
        lo, hi = T0 + lo * CADENCE, T0 + hi * CADENCE
        labels, rows, ns_bytes = _walk(Engine(db, NS), lo, hi)
        want_labels, want_rows, want_bytes = _oracle_walk(db, lo, hi)
        assert rows, "the state holds nothing in the range"
        assert labels == want_labels
        _assert_same_rows(rows, want_rows)
        assert ns_bytes == want_bytes
        _assert_same_fetch(db, lo, hi + 1)
    finally:
        db.close()


def test_cold_write_rows_are_merged_and_the_buffer_wins(tmp_path):
    """What the oracle is trusted with, checked by hand once."""
    db, lo, hi = _cold_write_after_seal(_open_db(tmp_path), tmp_path)
    try:
        _labels, rows, _ = _walk(Engine(db, NS), T0, T0 + 2 * BLOCK)
        cold = [r for r in rows if r[1] == "cold"]
        assert sorted(r[0] for r in cold) == [0, 3]
        ts, vs = next(r[2] for r in cold if r[0] == 3)
        assert len(ts) == PER_BLOCK + 2 and (np.diff(ts) > 0).all()
        at = np.searchsorted(ts, T0 + (PER_BLOCK + 35) * CADENCE)
        assert vs[at] == _value(3, PER_BLOCK + 35, 0.25)
        assert vs[at - 10] == _value(3, PER_BLOCK + 25)
        # the series the seal never saw (the last slot: one series was
        # never written) is an open row of that block
        assert [r[1] for r in rows if r[0] == SERIES - 2] == ["open"]
    finally:
        db.close()


@pytest.mark.parametrize("limits,state", [
    (QueryLimits(max_fetched_series=5), _sealed_and_open_tail),
    (QueryLimits(max_fetched_datapoints=900), _sealed_and_open_tail),
    (QueryLimits(max_fetched_series=7, max_fetched_datapoints=1500),
     _sealed_and_open_tail),
    (QueryLimits(max_time_range_nanos=BLOCK), _sealed_and_open_tail),
    (QueryLimits(max_fetched_datapoints=6000),
     _fleet_with_gaps_a_cold_write_and_a_tail),
], ids=["series", "datapoints", "both", "time_range",
        "fleet_between_shards"])
def test_walk_with_limits_truncates_as_the_oracle(limits, state, tmp_path):
    db, lo, hi = state(_open_db(tmp_path), tmp_path)
    try:
        lo, hi = T0 + lo * CADENCE, T0 + hi * CADENCE
        meta, want_meta = ResultMeta(), ResultMeta()
        labels, rows, ns_bytes = _walk(Engine(db, NS), lo, hi, limits, meta)
        want_labels, want_rows, want_bytes = _oracle_walk(
            db, lo, hi, limits, want_meta)
        assert labels == want_labels
        _assert_same_rows(rows, want_rows)
        assert ns_bytes == want_bytes
        assert not meta.exhaustive and meta.warning_strings()
        assert meta.warning_strings() == want_meta.warning_strings()
        assert (meta.fetched_series, meta.fetched_datapoints) == (
            want_meta.fetched_series, want_meta.fetched_datapoints)
        full = _oracle_walk(db, lo, hi)
        assert len(rows) < len(full[1])
        if state is _fleet_with_gaps_a_cold_write_and_a_tail:
            # cut between two shards: some have every row, some none
            full_of, got_of = ({}, {})
            for out, all_rows in ((full_of, full[1]), (got_of, rows)):
                for slot, *_ in all_rows:
                    out[slot] = out.get(slot, 0) + 1
            assert 0 < len(got_of) < len(full_of) == len(labels)
            assert all(full_of[slot] == k for slot, k in got_of.items())
    finally:
        db.close()


def test_require_exhaustive_aborts(tmp_path):
    from m3_tpu.storage.limits import QueryLimitExceeded
    db, lo, hi = _sealed_only(_open_db(tmp_path), tmp_path)
    try:
        with pytest.raises(QueryLimitExceeded):
            _walk(Engine(db, NS), T0, T0 + 2 * BLOCK,
                  QueryLimits(max_fetched_datapoints=10,
                              require_exhaustive=True), ResultMeta())
    finally:
        db.close()


# --- the counter that says the columns engaged ---------------------------

def _walk_rows_total(form: str) -> float:
    from m3_tpu.utils import instrument
    return instrument.bounded_counter(
        "m3_query_walk_rows_total").labels(form=form).value


@pytest.mark.parametrize("state,cold", [
    (_sealed_only, False), (_sealed_and_flushed, False), (_open_only, False),
    (_sealed_and_open_tail, False), (_only_on_disk_after_restart, False),
    (_series_absent_from_a_block, False), (_two_namespaces, False),
    (_cold_write_after_seal, True),
    (_fleet_with_gaps_a_cold_write_and_a_tail, True),
], ids=lambda v: v.__name__[1:] if callable(v) else "")
def test_walk_rows_by_row_only_beside_a_cold_write(state, cold, tmp_path):
    """Sealed and open rows travel as columns; only the rows of a MIXED
    block (a cold write beside a sealed stream) are told apart one by
    one, and the record and the registry say how many."""
    from m3_tpu.query import slowlog
    db, lo, hi = state(_open_db(tmp_path), tmp_path)
    try:
        engine = Engine(db, NS)
        lo, hi = T0 + lo * CADENCE, T0 + hi * CADENCE
        before = {f: _walk_rows_total(f) for f in ("columns", "by_row")}
        cost = engine._begin_cost()
        _labels, _parts, rows, named, _ = engine._gather_walk(MATCH, lo, hi)
        walked = dict(cost.walk_rows)
        # every row the walk was handed: streams, arrays, named open rows
        assert walked["columns"] + walked["by_row"] == len(rows) + len(
            _parts)
        assert (walked["by_row"] > 0) == cold
        if cold:
            mixed = sum(
                len(b.payloads) - b.payloads.count(None)
                for _slots, blocks in db.fetch_tagged(
                    NS, MATCH, lo, hi + 1, with_counts=True,
                    defer_open=True).shards
                for b in blocks if b.kind == "mixed")
            assert walked["by_row"] == mixed < walked["columns"]
        for form, n in walked.items():
            assert _walk_rows_total(form) - before[form] == n
        engine.query_range("sum(m)", lo + 600 * SEC, lo + 1200 * SEC,
                           60 * SEC)
        rec = slowlog.log().records(limit=1)[0]
        assert set(rec["walk_rows"]) == {"columns", "by_row"}
        assert rec["walk_rows"]["columns"] > 0
    finally:
        db.close()


def _rows_by_series(gathered):
    """{sid: [(block_start, payload bytes or arrays)]} of a Gathered."""
    out = {sid: [] for sid in gathered.sids}
    assert gathered.sids == sorted(gathered.sids)
    assert len(gathered.lanes) == len(gathered.sids)
    for slots, blocks in gathered.shards:
        assert [b.block_start for b in blocks] == sorted(
            b.block_start for b in blocks)
        for b in blocks:
            assert len(b.payloads) == len(slots)
            for slot, payload in zip(slots, b.payloads):
                if payload is not None:
                    out[gathered.sids[slot]].append((b.block_start, payload))
    return {sid: sorted(rows, key=lambda r: r[0])
            for sid, rows in out.items()}


@pytest.mark.parametrize("state", [_sealed_and_flushed,
                                   _series_absent_from_a_block,
                                   _cold_write_after_seal],
                         ids=lambda f: f.__name__[1:])
def test_session_storage_gathers_the_same_rows(state, tmp_path):
    """``SessionStorage.fetch_tagged`` builds the database's ``Gathered``
    (one shard's worth) from the rows a session hands it: the same
    series in the same order, the same rows, and the engine's walk over
    it emits them as it emits the database's."""
    from m3_tpu.query.session_storage import SessionStorage

    db, lo, hi = state(_open_db(tmp_path), tmp_path)

    class _Session:
        def fetch_tagged_with_meta(self, ns, matchers, start, end,
                                   deadline=None):
            return db.fetch_tagged(ns, matchers, start, end), ResultMeta()

    try:
        lo, hi = T0 + lo * CADENCE, T0 + hi * CADENCE
        storage = SessionStorage(_Session(), NS,
                                 db.namespace_options(NS))
        mine = storage.fetch_tagged(NS, MATCH, lo, hi + 1, with_counts=True,
                                    defer_open=True)
        # the database's own, its open rows read as the session reads them
        theirs = db.fetch_tagged(NS, MATCH, lo, hi + 1, with_counts=True)
        got = _rows_by_series(mine)
        assert list(got) == sorted(theirs)
        for sid, rows in got.items():
            assert [bs for bs, _p in rows] == [e[0] for e in theirs[sid]]
            for (_bs, a), (_bs2, b, _c) in zip(rows, theirs[sid]):
                _assert_same_payload(
                    "stream" if isinstance(b, (bytes, memoryview))
                    else "arrays", a, b)
        # and through the two engines' walks: the same slots and rows
        labels, rows, _ = _walk(Engine(storage, NS), lo, hi)
        want_labels, want_rows, _ = _walk(Engine(db, NS), lo, hi)
        assert len(labels) == len(want_labels)
        assert [(r[0], r[4]) for r in rows] == [
            (r[0], r[4]) for r in want_rows]
        for (_, kind, a, *_), (_, want_kind, b, *_) in zip(rows, want_rows):
            assert (kind == "stream") == (want_kind == "stream")
            _assert_same_payload("stream" if kind == "stream" else "arrays",
                                 a, b)
    finally:
        db.close()


# --- structure -----------------------------------------------------------

class _CountingIds(list):
    searched = 0

    def index(self, *a):
        type(self).searched += 1
        return super().index(*a)

    def __contains__(self, item):
        type(self).searched += 1
        return super().__contains__(item)


def _scan_counter(db):
    return db._m_listing_scan.value, db._m_listing_kept.value


def test_walk_lists_no_directory_when_memory_holds_every_block(
        tmp_path, monkeypatch):
    db, lo, hi = _sealed_and_open_tail(_open_db(tmp_path), tmp_path)
    try:
        engine = Engine(db, NS)
        lo, hi = T0 + lo * CADENCE, T0 + hi * CADENCE
        _walk(engine, lo, hi)       # a shard not yet listed is scanned once
        scans, kept = _scan_counter(db)
        assert scans <= SHARDS
        calls = []
        monkeypatch.setattr(database_mod, "list_filesets",
                            lambda *a: calls.append(a) or [])
        monkeypatch.setattr(fileset_mod, "list_filesets",
                            lambda *a: calls.append(a) or [])
        monkeypatch.setattr(pathlib.Path, "glob",
                            lambda *a: calls.append(a) or iter(()))
        monkeypatch.setattr(pathlib.Path, "exists",
                            lambda *a: calls.append(a) or False)
        for _ in range(3):
            _labels, rows, _ = _walk(engine, lo, hi)
            assert len(rows) == 2 * SERIES + 8
        assert calls == []
        assert _scan_counter(db) == (scans, kept + 3 * SHARDS)
        assert engine._cost().fileset_scans == 0
    finally:
        db.close()


def test_flush_renews_the_listing_and_bootstrap_sets_it(tmp_path):
    db = _open_db(tmp_path)
    db.bootstrap()
    shards = db._ns(NS).shards.values()
    assert all(s.filesets == {} for s in shards)
    scans = db._m_listing_scan.value
    assert scans == SHARDS
    _write(db, range(2 * PER_BLOCK))
    _tick(db, 2)
    db.flush()
    for s in shards:
        assert s.filesets == dict(list_filesets(
            db.path / "data", NS, s.shard_id)) != {}
    _forget_memory(db)
    engine = Engine(db, NS)
    _labels, rows, _ = _walk(engine, T0, T0 + 2 * BLOCK)
    assert len(rows) == 2 * SERIES
    assert db._m_listing_scan.value == scans        # read from disk, unscanned
    db.close()
    db = _open_db(tmp_path)
    assert all(s.filesets is None for s in db._ns(NS).shards.values())
    db.bootstrap()
    assert all(s.filesets for s in db._ns(NS).shards.values())
    db.drop_shard(NS, 0)
    assert db._ns(NS).shards[0].filesets == {}
    assert list_filesets(db.path / "data", NS, 0) == []
    db.close()


def test_an_unlisted_shard_is_scanned_once_and_the_record_says_so(tmp_path):
    db, lo, hi = _sealed_and_flushed(_open_db(tmp_path), tmp_path)
    try:
        for shard in db._ns(NS).shards.values():
            shard.filesets = None
        scans = db._m_listing_scan.value    # the flush listed them once
        engine = Engine(db, NS)
        cost = engine._begin_cost()
        engine._gather(MATCH, T0, T0 + 2 * BLOCK)
        assert cost.fileset_scans == SHARDS
        cost = engine._begin_cost()
        engine._gather(MATCH, T0, T0 + 2 * BLOCK)
        assert cost.fileset_scans == 0
        assert db._m_listing_scan.value == scans + SHARDS
    finally:
        db.close()


def test_slow_query_record_carries_fileset_scans(tmp_path):
    from m3_tpu.query import slowlog
    db, lo, hi = _sealed_and_flushed(_open_db(tmp_path), tmp_path)
    try:
        engine = Engine(db, NS)
        for shard in db._ns(NS).shards.values():
            shard.filesets = None
        for want in (SHARDS, 0):
            engine.query_range("sum(m)", T0 + 600 * SEC, T0 + 1200 * SEC,
                               60 * SEC)
            rec = slowlog.log().records(limit=1)[0]
            assert rec["fileset_scans"] == want and "rows" in rec
    finally:
        db.close()


def test_sealed_rows_come_through_the_table(tmp_path):
    db, lo, hi = _cold_write_after_seal(_open_db(tmp_path), tmp_path)
    try:
        for shard in db._ns(NS).shards.values():
            for blk in shard._sealed.values():
                assert blk.row_of == {s: i for i, s in enumerate(blk.ids)}
                blk.ids = _CountingIds(blk.ids)
        engine = Engine(db, NS)
        _labels, rows, _ = _walk(engine, T0, T0 + 2 * BLOCK)
        db.fetch_tagged(NS, MATCH, T0, T0 + 2 * BLOCK)
        for i in range(SERIES):
            db.fetch_series(NS, _sid(i), T0, T0 + 2 * BLOCK)
        assert rows and _CountingIds.searched == 0
        # the table goes with the block
        shard = next(s for s in db._ns(NS).shards.values() if s._sealed)
        bs = min(shard._sealed)
        assert shard.unseal(bs, db._ns(NS).index.ordinal)
        assert bs not in shard._sealed
    finally:
        db.close()


def _python_calls(fn) -> int:
    """Python-level function calls (C calls are not counted) of fn()."""
    import sys
    calls = [0]

    def count(_frame, event, _arg):
        if event == "call":
            calls[0] += 1

    sys.setprofile(count)
    try:
        fn()
    finally:
        sys.setprofile(None)
    return calls[0]


def test_a_gather_runs_no_python_a_series(tmp_path, monkeypatch):
    """The interpreter goes round shards and blocks, never series or
    rows: four times the series, same shards and blocks, costs under a
    tenth more Python calls; the index's one-at-a-time answers are not
    asked for at all."""
    from m3_tpu.storage import index as index_mod

    def fleet(path, n):
        db = _open_db(path, shards=FLEET_SHARDS)
        _write_fleet(db, range(0, 2 * PER_BLOCK, PER_BLOCK // 4), range(n))
        _tick(db, 2)
        db.flush()
        return db

    calls = {}
    for n in (500, 2000):
        db = fleet(tmp_path / str(n), n)
        try:
            engine = Engine(db, NS)
            walk = lambda: engine._gather_walk(     # noqa: E731
                MATCH, T0, T0 + 2 * BLOCK - 1)
            _labels, _parts, rows, _named, _ = walk()   # the memos fill
            assert len(rows) == 2 * n
            one_at_a_time = []
            monkeypatch.setattr(
                index_mod.TagIndex, "id_of",
                lambda *a: one_at_a_time.append("id_of"))
            monkeypatch.setattr(
                index_mod.SeriesRegistry, "id_of",
                lambda *a: one_at_a_time.append("registry.id_of"))
            calls[n] = _python_calls(walk)
            monkeypatch.undo()
            assert one_at_a_time == []
        finally:
            db.close()
    # the shard of a lane comes from the array memo alone
    assert not hasattr(database_mod._Namespace, "shard_of_lane")
    assert calls[2000] < 1.1 * calls[500], calls
    assert calls[500] < 2000, calls      # some dozens a shard-block


def test_one_view_an_open_buffer_and_walk(tmp_path, monkeypatch):
    db, lo, hi = _sealed_and_open_tail(_open_db(tmp_path), tmp_path)
    try:
        _write(db, range(2 * PER_BLOCK + 60, 2 * PER_BLOCK + 70))
        viewed = []
        view = BlockBuffer.view
        monkeypatch.setattr(BlockBuffer, "view",
                            lambda self: viewed.append(id(self)) or view(self))
        engine = Engine(db, NS)
        _labels, rows, _ = _walk(engine, T0 + lo * CADENCE,
                                 T0 + (hi + 10) * CADENCE)
        buffers = [id(b) for s in db._ns(NS).shards.values()
                   for b in s._buffers.values()]
        assert sum(r[1] == "open" for r in rows) == SERIES > len(buffers)
        assert sorted(viewed) == sorted(buffers)
    finally:
        db.close()


def test_read_series_is_read_many_for_one_series(tmp_path, monkeypatch):
    db, lo, hi = _cold_write_after_seal(_open_db(tmp_path), tmp_path)
    try:
        calls = []
        read_many = Shard.read_many
        monkeypatch.setattr(
            Shard, "read_many",
            lambda self, sids, *a, **kw: calls.append(sids)
            or read_many(self, sids, *a, **kw))
        n = db._ns(NS)
        shard = n.shard_of(_sid(3))
        got = shard.read_series(_sid(3), n.index.ordinal(_sid(3)), T0,
                                T0 + 2 * BLOCK)
        assert calls == [[_sid(3)]] and len(got) == 2
        # and nothing of the old per-series walk is left beside it
        src = inspect.getsource(Shard.read_series)
        assert "_sealed" not in src and "_buffers" not in src
        assert "_filesets" not in inspect.signature(
            Database.fetch_series).parameters
        assert ".index(" not in inspect.getsource(Shard.read_many)
    finally:
        db.close()


def test_readers_beside_a_writer_and_the_mediators_pass(tmp_path):
    """Four readers, a writer and tick() + flush() running: every read
    holds each series' acknowledged prefix, sample for sample (the
    host evaluator's decode of the gather), while blocks pass from
    open to sealed to flushed under it; the tables and the listing are
    only touched under the database lock."""
    db = _open_db(tmp_path)
    db.bootstrap()
    total = 2 * PER_BLOCK + 120
    acked = [-1]
    done = threading.Event()
    errors: list = []
    hi = T0 + (total + 1) * CADENCE

    def guarded(fn):
        def run():
            try:
                fn()
            except BaseException as e:  # noqa: BLE001 - reported below
                errors.append(e)
                done.set()
        return threading.Thread(target=run, daemon=True)

    def writer():
        for c in range(total):
            if done.is_set():
                return
            db.write_batch(NS, [_sid(i) for i in range(SERIES)],
                           [_tags(i) for i in range(SERIES)],
                           [T0 + c * CADENCE] * SERIES,
                           [_value(i, c) for i in range(SERIES)])
            acked[0] = c
        done.set()

    def mediator():
        while not done.is_set():
            # the node's now is the newest acknowledged sample's time:
            # a block seals only once the writer has left it behind
            db.tick(now_nanos=T0 + max(acked[0], 0) * CADENCE)
            db.flush()

    reads = [0, 0, 0, 0]

    def reader(k):
        engine = Engine(db, NS, device_serving=False)

        def loop():
            while not done.is_set() or not reads[k]:
                upto = acked[0]
                labels, times, values = engine._fetch_raw(MATCH, T0, hi)
                if upto < 0:
                    continue
                assert len(labels) == SERIES
                want_t = T0 + np.arange(upto + 1) * CADENCE
                for row, lab in enumerate(labels):
                    i = int(lab[b"host"][1:])
                    t = np.asarray(times[row])
                    v = np.asarray(values[row])
                    keep = t < cons_inf
                    t, v = t[keep][:upto + 1], v[keep][:upto + 1]
                    np.testing.assert_array_equal(t, want_t)
                    np.testing.assert_array_equal(
                        v, [_value(i, c) for c in range(upto + 1)])
                reads[k] += 1
        return loop

    from m3_tpu.ops import consolidate
    cons_inf = consolidate._INF
    threads = [guarded(writer), guarded(mediator)] + [
        guarded(reader(k)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not any(t.is_alive() for t in threads)
    if errors:
        raise errors[0]
    assert all(reads) and acked[0] == total - 1
    shards = db._ns(NS).shards.values()
    assert sum(len(s._flushed) for s in shards) == 2 * SHARDS
    assert all(len(s.filesets) == 2 for s in shards)
    assert db._m_listing_scan.value == SHARDS       # bootstrap's, no more
    db.close()
