"""The open edge on the normal path: a range that ends in the open
buffers is served by the device tier from sealed blocks and open rows
together and equals the host tier; the buffer's consolidated view; an
acknowledged sample is in the next answer; a cold write after seal is
the host's; the node's clock."""

import json
import pathlib
import re
import threading
import time
import urllib.parse
import urllib.request

import numpy as np
import pytest

from m3_tpu.query import slowlog
from m3_tpu.query.engine import Engine
from m3_tpu.query.http import CoordinatorServer
from m3_tpu.storage import (Database, DatabaseOptions, NamespaceOptions,
                            RetentionOptions)
from m3_tpu.storage import buffer
from m3_tpu.storage.buffer import BlockBuffer
from m3_tpu.utils import clock, instrument, xtime

SEC = xtime.SECOND
BLOCK = 2 * xtime.HOUR
T0 = (1_600_000_000 * SEC // BLOCK) * BLOCK
CADENCE = 30 * SEC
PER_BLOCK = BLOCK // CADENCE            # 240
SERIES = 6
STEP = 60 * SEC


def _open_db(path, cold_writes: bool = True):
    db = Database(DatabaseOptions(path=str(path), num_shards=4,
                                  commit_log_enabled=False))
    db.create_namespace(NamespaceOptions(
        name="default", cold_writes_enabled=cold_writes,
        retention=RetentionOptions(block_size=BLOCK)))
    return db


def _tags(i: int) -> dict:
    return {b"__name__": b"m", b"host": b"h%02d" % i, b"dc": b"dc%d" % (i % 3)}


def _write(db, cols, series=range(SERIES), bump: float = 0.0):
    """Samples `cols` (indices on the 30 s grid from T0) of `series`;
    a counter with a reset every 200 samples."""
    cols = np.asarray(list(cols), dtype=np.int64)
    for i in series:
        ts = (T0 + cols * CADENCE).tolist()
        vs = ((cols % 200) * (1.0 + i) + bump).tolist()
        db.write_batch("default", [b"m|h%02d" % i] * len(ts),
                       [_tags(i)] * len(ts), ts, vs)


def _seal(db, n_blocks: int):
    db.tick(now_nanos=T0 + n_blocks * BLOCK + 11 * 60 * SEC)
    db.flush()


# each scenario: (blocks to seal, what to write before the seal, what to
# write after it, the query range in samples from T0)
def _sealed_only(db):
    _write(db, range(2 * PER_BLOCK))
    _seal(db, 2)
    return 20, 2 * PER_BLOCK - 20


def _open_only(db):
    _write(db, range(100, 300))
    return 110, 300


def _sealed_and_open(db, tail: int = 60):
    _write(db, range(2 * PER_BLOCK))
    _seal(db, 2)
    _write(db, range(2 * PER_BLOCK, 2 * PER_BLOCK + tail))
    return 20, 2 * PER_BLOCK + tail


def _two_open_blocks(db):
    _write(db, range(PER_BLOCK))
    _seal(db, 1)
    _write(db, range(PER_BLOCK, 2 * PER_BLOCK + 50))     # both open
    return 20, 2 * PER_BLOCK + 50


def _series_only_open(db):
    _write(db, range(2 * PER_BLOCK), series=range(SERIES - 2))
    _seal(db, 2)
    _write(db, range(2 * PER_BLOCK, 2 * PER_BLOCK + 60))
    return 20, 2 * PER_BLOCK + 60


def _series_without_open(db):
    _write(db, range(2 * PER_BLOCK))
    _seal(db, 2)
    _write(db, range(2 * PER_BLOCK, 2 * PER_BLOCK + 60), series=(0, 2, 3))
    return 20, 2 * PER_BLOCK + 60


def _duplicate_in_buffer(db):
    _write(db, range(2 * PER_BLOCK))
    _seal(db, 2)
    _write(db, range(2 * PER_BLOCK, 2 * PER_BLOCK + 60))
    # the same timestamps again, other values: the last write wins
    _write(db, range(2 * PER_BLOCK + 10, 2 * PER_BLOCK + 20), bump=0.5)
    return 20, 2 * PER_BLOCK + 60


def _out_of_order_in_buffer(db):
    _write(db, range(2 * PER_BLOCK))
    _seal(db, 2)
    _write(db, range(2 * PER_BLOCK + 30, 2 * PER_BLOCK + 60))
    _write(db, range(2 * PER_BLOCK, 2 * PER_BLOCK + 30))
    return 20, 2 * PER_BLOCK + 60


SCENARIOS = {
    "sealed_only": _sealed_only,
    "open_only": _open_only,
    "sealed_and_open": _sealed_and_open,
    "two_open_blocks": _two_open_blocks,
    "tail_1": lambda db: _sealed_and_open(db, 1),
    "tail_127": lambda db: _sealed_and_open(db, 127),
    "tail_128": lambda db: _sealed_and_open(db, 128),
    "tail_129": lambda db: _sealed_and_open(db, 129),
    "series_only_in_open_buffer": _series_only_open,
    "series_without_open_samples": _series_without_open,
    "duplicate_timestamp_in_buffer": _duplicate_in_buffer,
    "out_of_order_in_buffer": _out_of_order_in_buffer,
}
QUERIES = ["rate(m[5m])", "increase(m[5m])", "sum_over_time(m[5m])",
           "max_over_time(m[5m])", "sum by (dc) (rate(m[5m]))"]


def _by_labels(mat):
    return {tuple(sorted(ls.items())): np.asarray(mat.values)[i]
            for i, ls in enumerate(mat.labels)}


@pytest.mark.parametrize("scenario", SCENARIOS)
def test_device_tier_equals_host_tier_at_the_open_edge(tmp_path, scenario):
    db = _open_db(tmp_path)
    try:
        lo, hi = SCENARIOS[scenario](db)
        start, end = T0 + lo * CADENCE, T0 + hi * CADENCE
        dev = Engine(db, "default", device_serving=True)
        host = Engine(db, "default", device_serving=False)
        for query in QUERIES:
            _, got = dev.query_range(query, start, end, STEP)
            stats = dict(dev.last_fetch_stats or {})
            assert stats.get("device_serving") is True, (query, stats)
            _, want = host.query_range(query, start, end, STEP)
            got, want = _by_labels(got), _by_labels(want)
            assert set(got) == set(want), query
            for key in want:
                np.testing.assert_allclose(got[key], want[key], rtol=1e-9,
                                           equal_nan=True, err_msg=query)
            if "rows" in stats and scenario not in ("sealed_only",
                                                    "open_only"):
                # the per-node tier: sealed streams and open rows in
                # one program
                assert 0 < stats["open_rows"] < stats["rows"], stats
    finally:
        db.close()


def test_open_rows_are_counted_in_the_record_and_the_registry(tmp_path):
    db = _open_db(tmp_path)
    try:
        _sealed_and_open(db)
        fam = instrument.counter("m3_query_open_rows_total")
        before = fam.value
        query = "sum by (dc) (rate(m[5m]))"
        Engine(db, "default", device_serving=True).query_range(
            query, T0 + 20 * CADENCE, T0 + (2 * PER_BLOCK + 60) * CADENCE,
            STEP)
        rec = next(r for r in slowlog.log().records() if r["expr"] == query)
        assert rec["device_serving"] and "device_declines" not in rec
        assert (rec["rows"], rec["open_rows"]) == (3 * SERIES, SERIES)
        assert rec["phases"]["open_read_s"] > 0
        tiling = ("parse_s", "fetch_s", "open_read_s", "pack_s", "decode_s",
                  "merge_s", "device_s", "self_s")
        assert sum(rec["phases"][k] for k in tiling) == pytest.approx(
            rec["phases"]["total_s"])
        assert fam.value - before == SERIES
    finally:
        db.close()


def test_cold_write_after_seal_is_declined_and_the_host_answers(tmp_path):
    db = _open_db(tmp_path)
    try:
        _write(db, range(2 * PER_BLOCK))
        _seal(db, 2)
        _write(db, range(2 * PER_BLOCK, 2 * PER_BLOCK + 60))
        # into a sealed block: a sample between two of the grid, and one
        # of the grid's rewritten
        db.write_batch("default", [b"m|h01"] * 2, [_tags(1)] * 2,
                       [T0 + 300 * CADENCE + 7 * SEC, T0 + 310 * CADENCE],
                       [1e6, 2e6])
        fam = instrument.bounded_counter("m3_query_device_decline_total")
        before = fam.labels(reason="cold_overlay").value
        start, end = T0 + 20 * CADENCE, T0 + (2 * PER_BLOCK + 60) * CADENCE
        query = "sum by (dc) (max_over_time(m[5m]))"
        _, got = Engine(db, "default", device_serving=True).query_range(
            query, start, end, STEP)
        rec = next(r for r in slowlog.log().records() if r["expr"] == query)
        assert rec["device_declines"] == {"cold_overlay": 2}
        assert not rec["device_serving"]
        assert fam.labels(reason="cold_overlay").value - before == 2
        _, want = Engine(db, "default", device_serving=False).query_range(
            query, start, end, STEP)
        got, want = _by_labels(got), _by_labels(want)
        assert set(got) == set(want)
        for key in want:
            np.testing.assert_array_equal(got[key], want[key])
        # the rewritten sample is in the answer (h01 and h04 sum in dc1)
        assert 2e6 <= np.nanmax(want[((b"dc", b"dc1"),)]) < 2e6 + 1e4
    finally:
        db.close()


# ---- the buffer's consolidated view ---------------------------------------

@pytest.mark.parametrize("seed", range(6))
def test_view_after_interleaved_writes_and_reads_is_consolidated(
        seed, monkeypatch):
    # odd seeds fold the recent run into the base at every other read
    monkeypatch.setattr(buffer, "_RECENT_MIN", 1024 if seed % 2 else 8)
    rng = np.random.default_rng(seed)
    buf = BlockBuffer(0)
    for _ in range(int(rng.integers(2, 14))):
        n = int(rng.integers(0, 40))
        buf.write_batch(rng.integers(0, 7, n), rng.integers(0, 20, n),
                        rng.random(n))
        if rng.random() < 0.7:
            lane = int(rng.integers(0, 8))
            t, v = buf.read_lane(lane)
            lanes, times, values = buf.consolidated()
            np.testing.assert_array_equal(t, times[lanes == lane])
            np.testing.assert_array_equal(v, values[lanes == lane])
    view = buf.view()
    for got, want in zip(view.base.merged(*view.recent), buf.consolidated()):
        np.testing.assert_array_equal(got, want)


def test_a_read_between_two_writes_sees_the_first_and_not_the_second():
    buf = BlockBuffer(0)
    buf.write_batch([3, 3, 5], [10, 20, 10], [1.0, 2.0, 3.0])
    view = buf.view()
    t, v = buf.read_lane(3)
    buf.write_batch([3, 3], [15, 20], [9.0, 8.0])
    # what was read stays as it was, and so does the view it came from
    assert (t.tolist(), v.tolist()) == ([10, 20], [1.0, 2.0])
    assert view.counts([3, 4, 5]).tolist() == [2, 0, 1]
    assert view.read_lanes([3])[0][1].tolist() == [1.0, 2.0]
    t, v = buf.read_lane(3)
    assert (t.tolist(), v.tolist()) == ([10, 15, 20], [1.0, 9.0, 8.0])


# ---- acknowledged, then read -------------------------------------------------

def _get(port: int, path: str, **params):
    url = f"http://127.0.0.1:{port}{path}?" + urllib.parse.urlencode(params)
    with urllib.request.urlopen(url, timeout=60) as r:
        return json.loads(r.read())


def _remote_write(port: int, series: list, t_ms: int, value: float) -> None:
    import sys
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]
                           / "benchmark"))
    from harness import wire
    body = wire.write_request(
        [wire.label_bytes(_tags(i)) for i in series],
        np.asarray([t_ms], dtype=np.int64),
        np.full((len(series), 1), value))
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/api/v1/prom/remote/write", data=body,
        headers={"Content-Encoding": "snappy",
                 "Content-Type": "application/x-protobuf"})
    with urllib.request.urlopen(req, timeout=60) as r:
        assert 200 <= r.status < 300


@pytest.mark.parametrize("device", [True, False], ids=["device", "host"])
def test_an_acknowledged_sample_is_in_the_next_answer(tmp_path, device):
    """Writer and reader on two threads: whatever the writer has had
    acknowledged when the reader sends, the reader's answer holds."""
    db = _open_db(tmp_path)
    _write(db, range(2 * PER_BLOCK))
    _seal(db, 2)
    base = 2 * PER_BLOCK
    _set_now(T0 + base * CADENCE)
    srv = CoordinatorServer(db, port=0, engine=Engine(
        db, "default", device_serving=device)).start()
    acked, stop, failures = [0], threading.Event(), []

    def writer():
        try:
            for k in range(40):
                _remote_write(srv.port, range(SERIES),
                              (T0 + (base + k) * CADENCE) // 10**6, 1e3 + k)
                acked[0] = k + 1
        except Exception as e:  # noqa: BLE001 - reported by the test
            failures.append(e)
        finally:
            stop.set()

    def reader():
        try:
            while not stop.is_set():
                sure = acked[0]
                doc = _get(srv.port, "/api/v1/query_range",
                           query="sum(count_over_time(m[1h]))",
                           start=(T0 + (base + 40) * CADENCE) // SEC,
                           end=(T0 + (base + 40) * CADENCE) // SEC,
                           step=60)
                got = float(doc["data"]["result"][0]["values"][-1][1])
                # an hour back from the end: 79 sealed samples a series
                if got < SERIES * (79 + sure):
                    failures.append((sure, got))
        except Exception as e:  # noqa: BLE001
            failures.append(e)

    threads = [threading.Thread(target=f) for f in (writer, reader)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not failures, failures[:3]
        assert acked[0] == 40
    finally:
        clock.set_offset_nanos(0)
        srv.stop()
        db.close()


# ---- the node's clock --------------------------------------------------------

def _set_now(nanos: int) -> None:
    clock.set_offset_nanos(nanos - time.time_ns())


@pytest.fixture
def set_now():
    yield _set_now
    clock.set_offset_nanos(0)


def test_a_tick_seals_by_the_offset_clock(tmp_path, set_now):
    db = _open_db(tmp_path)
    try:
        _write(db, range(PER_BLOCK + 10))
        ns = db._ns("default")
        opened = lambda: sorted(          # noqa: E731
            {bs for sh in ns.shards.values() for bs in sh.open_block_starts()})
        # ten minutes short of the first block's seal, then past it
        set_now(T0 + BLOCK + 9 * 60 * SEC)
        assert db.tick() in ({}, {"default": []})
        assert opened() == [T0, T0 + BLOCK]
        set_now(T0 + BLOCK + 11 * 60 * SEC)
        assert set(db.tick()["default"]) == {T0}
        assert opened() == [T0 + BLOCK]
    finally:
        db.close()


def test_a_write_is_accepted_by_the_offset_clock(tmp_path, set_now):
    db = _open_db(tmp_path, cold_writes=False)
    try:
        set_now(T0 + 30 * 60 * SEC)
        assert abs(clock.now_s() - (T0 + 30 * 60 * SEC) / 1e9) < 5
        _write(db, [58, 59], series=[0])         # the clock's own minute
        with pytest.raises(ValueError):
            # the wall clock's now: years past the offset clock's window
            db.write_batch("default", [b"m|h00"], [_tags(0)],
                           [time.time_ns()], [1.0])
        clock.set_offset_nanos(0)
        db.write_batch("default", [b"m|h00"], [_tags(0)], [time.time_ns()],
                       [1.0])
    finally:
        db.close()


# sites that read the wall clock and why each is not data time
WALL_CLOCK_SITES = {
    "m3_tpu/dtest/harness.py": 2,        # a wait's deadline
    "m3_tpu/em/agent.py": 2,             # a wait's deadline
    "m3_tpu/observe/devmem.py": 1,       # an entry's last use, for eviction
    "m3_tpu/query/slowlog.py": 1,        # when a record was cut
    "m3_tpu/storage/fileset.py": 2,      # durability stamps beside stamp_ns
    "m3_tpu/tools/__main__.py": 5,       # the load tool's own pacing
    "m3_tpu/utils/tracing.py": 1,        # places a span; never subtracted
    "m3_tpu/utils/xtime.py": 2,          # stamp_ns, the durability clock
    "m3_tpu/utils/instrument.py": 3,     # exemplar, log line, scrape stamps
}


def test_data_time_is_read_through_the_clock_module():
    root = pathlib.Path(__file__).resolve().parents[1]
    found = {}
    for path in sorted((root / "m3_tpu").rglob("*.py")):
        rel = path.relative_to(root).as_posix()
        if rel == "m3_tpu/utils/clock.py":
            continue
        n = len(re.findall(r"time\.time(?:_ns)?\(", path.read_text()))
        if n:
            found[rel] = n
    assert found == WALL_CLOCK_SITES
