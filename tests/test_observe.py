"""Flight recorder (m3_tpu/observe): task ledger + watchdog under
fake clocks, the continuous profiler's window ring, the device-memory
ledger, kernel-telemetry result-byte accounting, the fused-query
upload/kernel-bytes reconciliation, and a 2-node e2e that stalls the
index-compaction daemon and watches the stall surface in
``/debug/tasks`` and as ``m3_watchdog_stalled_total`` via
self-scrape -> PromQL out of ``_m3_internal``."""

import gc
import json
import threading
import time
import urllib.parse
import urllib.request

import numpy as np
import pytest

from m3_tpu import observe
from m3_tpu.observe.devmem import DeviceMemLedger
from m3_tpu.observe.recorder import ProfileRecorder, render
from m3_tpu.observe.tasks import QueryCancelled, TaskLedger, Watchdog
from m3_tpu.utils import instrument


# --- task ledger + watchdog (fake clocks) -------------------------


def test_watchdog_flags_stall_and_recovery():
    clk = [0.0]
    led = TaskLedger(clock=lambda: clk[0])
    wd = Watchdog(led, default_deadline_s=5.0, clock=lambda: clk[0])
    hb = led.register_daemon("index_compaction")
    ctr = wd._stalls.labels(job="index_compaction")
    base = ctr.value

    clk[0] = 4.9
    assert wd.check_once() == []
    assert not hb.stalled
    clk[0] = 5.1
    newly = wd.check_once()
    assert [h.job for h in newly] == ["index_compaction"]
    assert hb.stalled and ctr.value == base + 1
    # already-stalled entries are not re-counted every sweep
    clk[0] = 9.0
    assert wd.check_once() == []
    assert ctr.value == base + 1
    # a beat clears the flag; a later stall counts again (edge count)
    hb.beat()
    assert not hb.stalled
    clk[0] = 20.0
    assert [h.job for h in wd.check_once()] == ["index_compaction"]
    assert ctr.value == base + 2
    hb.close()
    assert wd.check_once() == []


def test_watchdog_deadline_from_hint_and_explicit():
    clk = [0.0]
    led = TaskLedger(clock=lambda: clk[0])
    wd = Watchdog(led, default_deadline_s=5.0, clock=lambda: clk[0])
    # a slow-ticking daemon gets 3x its hint, not the short default
    slow = led.register_daemon("flush", interval_hint_s=10.0)
    # an explicit deadline wins over both
    tight = led.register_daemon("scrape", interval_hint_s=10.0,
                                deadline_s=2.0)
    clk[0] = 6.0
    assert [h.job for h in wd.check_once()] == ["scrape"]
    clk[0] = 29.0
    assert wd.check_once() == []
    assert not slow.stalled
    clk[0] = 31.0
    assert [h.job for h in wd.check_once()] == ["flush"]
    slow.close()
    tight.close()


def test_query_registration_view_and_cancel():
    clk = [100.0]
    led = TaskLedger(clock=lambda: clk[0])
    qt = led.begin_query("sum(up)", tenant="team-a", trace_id="cafe",
                         namespace="default")
    clk[0] = 101.5
    view = led.view()
    (row,) = view["queries"]
    assert row["query"] == "sum(up)"
    assert row["tenant"] == "team-a"
    assert row["trace_id"] == "cafe"
    assert row["namespace"] == "default"
    assert row["phase"] == "queued"
    assert row["elapsed_s"] == pytest.approx(1.5)
    assert row["cancelled"] is False

    qt.set_phase("fetch")
    qt.device_tier = "device"
    assert led.view()["queries"][0]["phase"] == "fetch"
    assert led.view()["queries"][0]["device_tier"] == "device"

    # cancel is cooperative: flag flips, the engine raises at its
    # next deadline checkpoint
    assert led.cancel(qt.task_id) is True
    with pytest.raises(QueryCancelled):
        qt.check_cancelled()
    qt.finish()
    assert led.view()["queries"] == []
    assert led.cancel(qt.task_id) is False  # already gone


def test_task_ledger_prunes_daemons_of_dead_threads():
    led = TaskLedger()

    def crashy():
        led.register_daemon("ephemeral")  # dies without close()

    t = threading.Thread(target=crashy, daemon=True)
    t.start()
    t.join(timeout=5.0)
    assert not t.is_alive()
    jobs = [d["job"] for d in led.view()["daemons"]]
    assert "ephemeral" not in jobs


# --- continuous profiler ------------------------------------------


def test_recorder_ring_windows_merge_and_diff():
    stop = threading.Event()

    def busy():  # a recognizable non-idle frame to sample
        while not stop.is_set():
            sum(range(200))

    t = threading.Thread(target=busy, daemon=True)
    t.start()
    rec = ProfileRecorder(interval_s=0.005, window_s=0.06, retention=3,
                          max_duty=1.0)
    rec.start()
    try:
        deadline = time.monotonic() + 10.0
        while (len(rec.windows()) < 3 or rec.latest() is None
               or not rec.latest().samples):
            assert time.monotonic() < deadline, "recorder made no windows"
            time.sleep(0.02)
    finally:
        rec.stop()
        stop.set()
        t.join(timeout=5.0)

    wins = rec.windows()
    assert len(wins) == 3  # ring bounded at retention
    seqs = [w.seq for w in wins]
    assert seqs == sorted(seqs) and wins[-1].seq >= 2
    meta = wins[-1].meta()
    assert set(meta) >= {"window", "duration_s", "ticks", "samples",
                         "stacks"}

    # per-seq lookup + expired windows answer None (the ring dropped
    # seq 0 once windows_total passed retention)
    assert rec.window(seqs[-1]) is wins[-1]
    if seqs[0] > 0:
        assert rec.window(0) is None
    assert rec.diff(10_000, seqs[-1]) is None

    counts, metas = rec.merged(None)
    assert len(metas) == len(wins)
    assert sum(counts.values()) == sum(w.samples for w in wins)
    assert any("busy" in stack for stack in counts), counts
    d = rec.diff(seqs[0], seqs[-1])
    assert d is not None
    dcounts, meta_a, meta_b = d
    assert meta_a["window"] == seqs[0] and meta_b["window"] == seqs[-1]
    assert all(v > 0 for v in dcounts.values())  # negatives dropped

    text = render(counts)
    line = text.splitlines()[0]
    stack, _, n = line.rpartition(" ")
    assert stack and int(n) > 0


# --- device-memory ledger -----------------------------------------


def _owner_row(led, owner):
    return {b["owner"]: b for b in led.view()["buffers"]}[owner]


def _kernel_peaks(view):
    return {k["kernel"]: k["peak_hbm_bytes"]
            for k in view["kernel_peaks"]}


def test_devmem_borrow_track_and_pool_accounting():
    led = DeviceMemLedger()
    up = instrument.counter("m3_device_upload_bytes_total",
                            owner="query_megabatch")
    up0 = up.value
    with led.borrow("query_megabatch", 1000, count=3):
        row = _owner_row(led, "query_megabatch")
        assert row["bytes"] == 1000 and row["buffers"] == 3
    assert _owner_row(led, "query_megabatch")["bytes"] == 0
    assert up.value == up0 + 1000  # uploads are cumulative

    # weakref tracking: bytes drop when the arrays are collected
    arr = np.zeros(100, dtype=np.float64)
    assert led.track("decoded_block_bridge", [arr]) == 800
    assert _owner_row(led, "decoded_block_bridge")["bytes"] == 800
    del arr
    gc.collect()
    assert _owner_row(led, "decoded_block_bridge")["bytes"] == 0

    # resizable pool handle: set() replaces, close() zeroes
    h = led.register("aggregator_pool")
    h.set(5000, count=2)
    assert _owner_row(led, "aggregator_pool")["bytes"] == 5000
    h.set(2000, count=1)
    row = _owner_row(led, "aggregator_pool")
    assert row["bytes"] == 2000 and row["buffers"] == 1
    h.close()
    assert _owner_row(led, "aggregator_pool")["bytes"] == 0
    assert led.total_bytes() == 0


def test_devmem_kernel_peaks_and_compile_cache_inventory():
    led = DeviceMemLedger()
    led.note_kernel("t_k", 1000, 500)
    led.note_kernel("t_k", 200, 100)  # smaller call: peak unchanged
    assert _kernel_peaks(led.view())["t_k"] == 1500

    led.compile_cache_note("t_cc", "fp1", bucket="64x32", hit=False)
    led.compile_cache_note("t_cc", "fp1", bucket="64x32", hit=True)
    led.compile_cache_note("t_cc", "fp2", bucket="128x32", hit=False)
    rows = led.view()["compile_caches"]["t_cc"]
    by_fp = {r["fingerprint"]: r for r in rows}
    assert by_fp["fp1"]["hits"] == 1 and by_fp["fp1"]["compiles"] == 1
    assert by_fp["fp2"]["compiles"] == 1
    assert by_fp["fp1"]["bucket"] == "64x32"

    calls = []
    led.compile_cache_register_evictor("t_cc", lambda: calls.append(1))
    out = led.compile_cache_evict("t_cc")
    assert out["t_cc"] == 2 and calls == [1]
    assert "t_cc" not in led.view()["compile_caches"]


# --- kernel telemetry: result bytes feed the ledger ----------------


def test_kernel_telemetry_result_bytes_and_ledger_feed():
    jnp = pytest.importorskip("jax.numpy")
    from m3_tpu.ops import kernel_telemetry as kt

    @kt.instrument_kernel("t_obs_probe")
    def double_up(x):
        return jnp.concatenate([x, x])

    x = jnp.zeros(16, dtype=jnp.float32)  # 64 in, 128 out
    double_up(x)
    st = kt.kernels()["t_obs_probe"].stats()
    assert st["bytes"] == 64
    assert st["result_bytes"] == 128
    assert instrument.counter("m3_kernel_result_bytes_total",
                              kernel="t_obs_probe").value == 128
    # the working-set estimate (args + result resident together)
    # lands in the device ledger as the kernel's peak
    assert _kernel_peaks(observe.device_ledger().view())[
        "t_obs_probe"] == 192


# --- fused query: upload counter reconciles with kernel bytes ------


@pytest.fixture(scope="module")
def small_fused_db(tmp_path_factory):
    from m3_tpu.storage.database import Database, DatabaseOptions
    from m3_tpu.storage.namespace import (NamespaceOptions,
                                          RetentionOptions)
    from m3_tpu.utils import xtime

    BLOCK = 2 * xtime.HOUR
    T0 = (1_600_000_000 * xtime.SECOND // BLOCK) * BLOCK
    db = Database(DatabaseOptions(
        path=str(tmp_path_factory.mktemp("obsfused")), num_shards=4,
        commit_log_enabled=False))
    db.create_namespace(NamespaceOptions(
        name="default", retention=RetentionOptions(block_size=BLOCK)))
    for job in ("api", "db"):
        sid = f"http_req||{job}".encode()
        tags = {b"__name__": b"http_req", b"job": job.encode()}
        ts = [T0 + i * 10 * xtime.SECOND for i in range(360)]
        vs = [float(i) for i in range(360)]
        db.write_batch("default", [sid] * len(ts), [tags] * len(ts),
                       ts, vs)
    db.tick(now_nanos=T0 + 2 * BLOCK)
    db.flush()
    yield db, T0
    db.close()


def test_fused_upload_reconciles_with_kernel_bytes(small_fused_db):
    """Acceptance: per-owner upload bytes reconcile with the
    kernel-telemetry transfer counters within 10% — the megabatch
    borrow measures the same leaves/params/grid pytree the kernel
    wrapper's _arg_volume walks."""
    from m3_tpu.query.engine import Engine
    from m3_tpu.utils import xtime

    db, T0 = small_fused_db
    eng = Engine(db, "default", lookback_nanos=5 * 60 * xtime.SECOND,
                 device_serving=True)
    up = instrument.counter("m3_device_upload_bytes_total",
                            owner="query_megabatch")
    kb = [instrument.counter("m3_kernel_bytes_total", kernel=k)
          for k in ("device_expr_pipeline", "device_expr_pipeline_sharded")]
    up0 = up.value
    kb0 = sum(c.value for c in kb)
    _, mat = eng.query_range(
        '(rate(http_req[5m]) > 0.5) * 60',
        T0 + 10 * 60 * xtime.SECOND, T0 + 50 * 60 * xtime.SECOND,
        60 * xtime.SECOND)
    assert (eng.last_fetch_stats or {}).get("device_fused") is True, (
        eng._cost().fused_error)
    assert len(mat.labels)
    d_up = up.value - up0
    d_kb = sum(c.value for c in kb) - kb0
    assert d_up > 0 and d_kb > 0
    assert abs(d_up - d_kb) <= 0.10 * max(d_up, d_kb), (d_up, d_kb)


# --- engine integration: phase/cancel via the process ledger -------


def test_engine_registers_query_and_cancel_aborts(small_fused_db):
    from m3_tpu.query.engine import Engine
    from m3_tpu.utils import xtime

    db, T0 = small_fused_db
    eng = Engine(db, "default", lookback_nanos=5 * 60 * xtime.SECOND,
                 device_serving=False)
    led = observe.task_ledger()

    seen = {}
    started = threading.Event()
    release = threading.Event()
    orig = eng._fetch_raw

    def slow_fetch(*a, **kw):
        (qrow,) = [q for q in led.view()["queries"]
                   if q["query"].startswith("sum(rate(http_req")]
        seen.update(qrow)
        started.set()
        release.wait(timeout=10.0)
        return orig(*a, **kw)

    eng._fetch_raw = slow_fetch
    try:
        err = []

        def run():
            try:
                eng.query_range('sum(rate(http_req[5m]))',
                                T0 + 10 * 60 * xtime.SECOND,
                                T0 + 50 * 60 * xtime.SECOND,
                                60 * xtime.SECOND)
            except Exception as e:  # noqa: BLE001 - captured for assert
                err.append(e)

        t = threading.Thread(target=run, daemon=True)
        t.start()
        assert started.wait(timeout=10.0)
        assert led.cancel(seen["task_id"]) is True
        release.set()
        t.join(timeout=10.0)
        assert err and isinstance(err[0], QueryCancelled)
    finally:
        eng._fetch_raw = orig
        release.set()
    # in-flight registration carried the namespace + a live phase
    assert seen["namespace"] == "default"
    assert seen["phase"] in ("parse", "fetch", "eval", "queued")
    # and the ledger is clean again
    assert not [q for q in led.view()["queries"]
                if q["task_id"] == seen["task_id"]]


# --- 2-node e2e: stall -> /debug/tasks + self-scrape -> PromQL -----


def _get_json(url, timeout=10.0):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return json.load(resp)


def test_two_node_flight_recorder_e2e(tmp_path):
    """DB node + coordinator in one process (the ledgers are
    process-global).  The coordinator's debug surface shows the db
    node's daemons; a deliberately wedged index compaction flips to
    stalled within one watchdog deadline, and the stall counter rides
    self-scrape into ``_m3_internal`` where PromQL can see it."""
    from m3_tpu.services import (CoordinatorService, DBNodeService,
                                 load_coordinator_config,
                                 load_dbnode_config)

    db_yml = tmp_path / "db.yml"
    db_yml.write_text(f"""
db:
  path: {tmp_path}/data-db
  num_shards: 4
  tick_every: 0
  observe:
    enabled: true
    recorder_interval: 5ms
    recorder_window: 250ms
    recorder_retention: 8
    watchdog_interval: 100ms
    watchdog_deadline: 1s
""")
    co_yml = tmp_path / "co.yml"
    co_yml.write_text(f"""
coordinator:
  path: {tmp_path}/data-co
  num_shards: 4
  instance_id: coord-obs
  self_scrape:
    enabled: true
    interval: 100ms
  observe:
    enabled: true
    watchdog_deadline: 1s
""")
    svc_db = DBNodeService(load_dbnode_config(str(db_yml))).start()
    svc_co = CoordinatorService(load_coordinator_config(str(co_yml))).start()
    release = threading.Event()
    try:
        base = f"http://127.0.0.1:{svc_co.http_port}"

        # -- /debug/profile: instant, from the ring, >= 3 windows --
        deadline = time.monotonic() + 20.0
        while True:
            meta = _get_json(f"{base}/debug/profile?list=1")
            if len(meta["data"]["windows"]) >= 3:
                break
            assert time.monotonic() < deadline, meta
            time.sleep(0.1)
        t0 = time.monotonic()
        with urllib.request.urlopen(f"{base}/debug/profile",
                                    timeout=10.0) as resp:
            assert resp.status == 200
            resp.read()
        # the legacy on-demand path blocked for the full capture
        # window (default 5s); the ring answers immediately
        assert time.monotonic() - t0 < 2.0

        # -- /debug/device + /debug/tasks shapes --
        dev = _get_json(f"{base}/debug/device")["data"]
        assert set(dev) >= {"total_bytes", "buffers", "kernel_peaks",
                            "compile_caches"}
        tasks = _get_json(f"{base}/debug/tasks")["data"]
        jobs = {d["job"] for d in tasks["daemons"]}
        # both nodes' daemons in one ledger: the recorder + watchdog
        # (started by the db node) and the coordinator's self-scrape
        assert {"profile_recorder", "watchdog", "selfscrape"} <= jobs, jobs

        # -- wedge index compaction on the DB NODE --
        idx = svc_db.db._namespaces["default"].index
        idx.compact = lambda: release.wait(timeout=60.0)
        idx._compact_wake.set()
        idx._ensure_compactor()

        deadline = time.monotonic() + 20.0
        row = None
        while time.monotonic() < deadline:
            tasks = _get_json(f"{base}/debug/tasks")["data"]
            rows = [d for d in tasks["daemons"]
                    if d["job"] == "index_compaction"]
            if rows and rows[0]["stalled"]:
                row = rows[0]
                break
            time.sleep(0.1)
        assert row is not None, "compaction stall never flagged"

        # -- the stall counter reaches PromQL via self-scrape --
        q = urllib.parse.urlencode({
            "query": 'm3_watchdog_stalled_total{job="index_compaction"}',
            "start": f"{time.time() - 60:.3f}",
            "end": f"{time.time() + 5:.3f}",
            "step": "1",
            "namespace": "_m3_internal",
        })
        deadline = time.monotonic() + 20.0
        vals = []
        while time.monotonic() < deadline:
            body = _get_json(f"{base}/api/v1/query_range?{q}")
            result = body["data"]["result"]
            if result:
                vals = [float(v) for _, v in result[0]["values"]]
                if vals and max(vals) >= 1.0:
                    break
            time.sleep(0.2)
        assert vals and max(vals) >= 1.0, vals
    finally:
        release.set()
        svc_co.stop()
        svc_db.stop()
        # observe.start/release is refcounted process-wide, and other
        # tests in the suite start services without stopping them —
        # their leaked refs would keep THIS test's recorder/watchdog
        # threads alive for the rest of the session, flipping
        # /debug/profile into ring mode for later tests that expect
        # the legacy inline capture.  Drain to zero.
        while observe.recorder() is not None or observe.watchdog() is not None:
            observe.release()


if __name__ == "__main__":
    import sys
    sys.exit(pytest.main([__file__, "-v"]))
