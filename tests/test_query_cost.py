"""The query path's one cost clock: per-thread cost objects, phases
that tile a query's time, the device queue counted at dispatch, scope
names in the device programs, the decline counter, the front end's
phase, and spans on a clock that cannot step."""

import json
import threading
import time
import urllib.parse
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from m3_tpu.models import query_pipeline as qp
from m3_tpu.ops import kernel_telemetry
from m3_tpu.query import slowlog
from m3_tpu.query.engine import Engine
from m3_tpu.query.http import CoordinatorServer
from m3_tpu.storage import (Database, DatabaseOptions, NamespaceOptions,
                            RetentionOptions)
from m3_tpu.utils import instrument, tracing, xtime

SEC = xtime.SECOND
BLOCK = 2 * xtime.HOUR
T0 = (1_600_000_000 * SEC // BLOCK) * BLOCK
START, END, STEP = T0 + 10 * 60 * SEC, T0 + 100 * 60 * SEC, 60 * SEC
# from the sealed block into the mutable buffer
MIXED_LO, MIXED_HI = T0 + 30 * 60 * SEC, T0 + 2 * BLOCK + 20 * 60 * SEC
PHASE_KEYS = {"parse_s", "plan_s", "fetch_s", "open_read_s", "pack_s",
              "decode_s", "merge_s", "device_s", "h2d_s", "d2h_s", "self_s",
              "frontend_s", "render_s", "total_s"}
WAIT_KEYS = {"db_lock_wait_s", "device_wait_s", "gc_pause_s"}


def _write(db, name: bytes, n_series: int = 12, n: int = 120):
    for i in range(n_series):
        tags = {b"__name__": name, b"host": b"h%02d" % i,
                b"dc": b"dc%d" % (i % 3)}
        ts = [T0 + (k + 1) * 30 * SEC for k in range(n)]
        vs = np.cumsum(np.full(n, 1.0 + i)).tolist()
        db.write_batch("default", [name + b"|h%02d" % i] * n,
                       [tags] * n, ts, vs)


def _make_db(path: str, also_sealed=None) -> Database:
    """`sealed` lives in a flushed block and, for four of its hosts,
    goes on in the mutable buffer of a later one: a range that reaches
    both holds open rows beside sealed streams.  `cold` lives in the
    same flushed block with a cold write beside it, which the shard
    merges on the host: the device tier declines it.  `also_sealed`
    writes what else the flushed block is to hold."""
    db = Database(DatabaseOptions(path=path, num_shards=4,
                                  commit_log_enabled=False))
    db.create_namespace(NamespaceOptions(
        name="default", retention=RetentionOptions(block_size=BLOCK)))
    _write(db, b"sealed")
    _write(db, b"cold", n_series=3)
    if also_sealed is not None:
        also_sealed(db)
    db.tick(now_nanos=T0 + 2 * BLOCK)
    db.flush()
    db.write_batch("default", [b"cold|h00"],
                   [{b"__name__": b"cold", b"host": b"h00", b"dc": b"dc0"}],
                   [T0 + 45 * SEC], [0.5])
    n = 60
    for i in range(4):
        tags = {b"__name__": b"sealed", b"host": b"h%02d" % i,
                b"dc": b"dc%d" % (i % 3)}
        ts = [T0 + 2 * BLOCK + (k + 1) * 30 * SEC for k in range(n)]
        db.write_batch("default", [b"sealed|h%02d" % i] * n, [tags] * n,
                       ts, (1e6 + np.arange(n, dtype=float)).tolist())
    return db


@pytest.fixture
def db(tmp_path):
    db = _make_db(str(tmp_path))
    yield db
    db.close()


def _record_of(expr: str) -> dict:
    return next(r for r in slowlog.log().records() if r["expr"] == expr)


def test_two_threads_keep_their_own_stats(db):
    """One engine, two server threads: a device-served query and a
    host-served one, both finished before either reads its stats."""
    eng = Engine(db, "default", device_serving=True)
    queries = {
        "device": ("sum by (dc) (rate(sealed[5m]))", START, END),
        "host": ("rate(cold[5m])", START, END),
    }
    barrier = threading.Barrier(2, timeout=120)
    seen, errors = {}, []

    def serve(which):
        expr, lo, hi = queries[which]
        try:
            eng.query_range(expr, lo, hi, STEP)
            barrier.wait()
            seen[which] = dict(eng.last_fetch_stats or {})
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(f"{which}: {type(e).__name__}: {e}")

    threads = [threading.Thread(target=serve, args=(w,)) for w in queries]
    for t in threads:
        t.start()
    for t in threads:
        t.join(180)
        assert not t.is_alive()
    assert not errors, errors
    assert seen["device"].get("device_serving") is True
    assert seen["device"]["device_s"] > 0.0
    assert not seen["host"].get("device_serving")
    assert seen["host"].get("device_s", 0.0) == 0.0
    assert seen["host"]["decode_s"] > 0.0
    assert _record_of(queries["device"][0])["device_serving"] is True
    host_rec = _record_of(queries["host"][0])
    assert host_rec["device_serving"] is False
    assert host_rec["phases"]["device_s"] == 0.0
    assert host_rec["device_declines"]["cold_overlay"] >= 1


def test_grouped_device_phases_sum_to_total(db):
    eng = Engine(db, "default", device_serving=True)
    expr = "sum by (dc) (rate(sealed[7m]))"
    eng.query_range(expr, START, END, STEP)
    assert eng.last_fetch_stats["device_grouped"] is True
    ph = _record_of(expr)["phases"]
    assert set(ph) == PHASE_KEYS | WAIT_KEYS
    for key in ("parse_s", "fetch_s", "pack_s", "device_s", "h2d_s",
                "d2h_s", "self_s"):
        assert ph[key] > 0.0, key
    assert ph["decode_s"] == ph["merge_s"] == ph["frontend_s"] == 0.0
    assert ph["h2d_s"] + ph["d2h_s"] < ph["device_s"]
    tiled = sum(ph[k] for k in ("parse_s", "fetch_s", "pack_s",
                                "device_s", "self_s"))
    assert tiled == pytest.approx(ph["total_s"], rel=1e-9)
    # the thread's stats carry the same stamps, unrounded
    assert eng.last_fetch_stats["device_s"] == ph["device_s"]
    assert eng.last_fetch_stats["fetch_s"] == ph["fetch_s"]


def test_host_phases_sum_to_total(db):
    eng = Engine(db, "default", device_serving=False)
    expr = "sum by (dc) (rate(sealed[9m]))"
    eng.query_range(expr, START, END, STEP)
    ph = _record_of(expr)["phases"]
    assert set(ph) == PHASE_KEYS | WAIT_KEYS
    assert ph["decode_s"] > 0.0 and ph["device_s"] == 0.0
    tiled = sum(ph[k] for k in ("parse_s", "fetch_s", "decode_s",
                                "merge_s", "self_s"))
    assert tiled == pytest.approx(ph["total_s"], rel=1e-9)


def test_queued_ahead_counts_calls_in_flight():
    entered, release = threading.Event(), threading.Event()

    def slow(x, first):
        if first:
            entered.set()
            assert release.wait(60)
        return x

    ker = kernel_telemetry.InstrumentedKernel(slow, "test_queue_depth")
    before = kernel_telemetry._inflight
    t = threading.Thread(target=ker, args=(np.ones(4), True))
    t.start()
    assert entered.wait(60)
    try:
        assert kernel_telemetry._inflight == before + 1
        ker(np.ones(4), False)
        st = ker.stats()
        assert st["invocations"] == 1 and st["queued_ahead"] == before + 1
    finally:
        release.set()
        t.join(60)
    assert not t.is_alive()
    st = ker.stats()
    assert st["invocations"] == 2 and st["queued_ahead"] == 2 * before + 1
    assert st["execute_s"] == pytest.approx(
        st["dispatch_s"] + st["wait_s"])
    assert kernel_telemetry._inflight == before


def _lowered(which: str) -> str:
    spec = jax.ShapeDtypeStruct
    m = w = lanes = steps = 64
    args = (spec((m, w), jnp.uint64), spec((m,), jnp.int64),
            spec((m,), jnp.int64), spec((steps,), jnp.int64))
    rng = np.int64(300 * SEC)
    if which == "device_temporal_pipeline":
        low = qp.device_temporal_pipeline.lower(
            *args, n_lanes=lanes, n_cap=128, range_nanos=rng, n_dp=128)
    elif which == "device_grouped_pipeline":
        low = qp.device_grouped_pipeline.lower(
            *args, spec((lanes,), jnp.int64), n_lanes=lanes, n_groups=8,
            n_cap=128, range_nanos=rng, n_dp=128)
    else:
        leaf = {"words": args[0], "nbits": args[1], "slots": args[2],
                "tiers": spec((m,), jnp.int64), "steps": args[3],
                "rng": spec((), jnp.int64),
                "valid": spec((lanes,), jnp.bool_)}
        # abs(sum by (..)(rate(..))): the op-tree's own scope is the
        # call's, the stages under it keep theirs
        plan = ("call", "abs", 2,
                ("agg", "sum", 8, 1,
                 ("leaf", 0, 0, "words", "rate", lanes, 128, 128, 1, m, w,
                  steps, 0.5, 0.5)))
        params = ((spec((), jnp.float64), spec((), jnp.float64)),
                  (spec((lanes,), jnp.int64), spec((8,), jnp.bool_),
                   spec((), jnp.float64)), ())
        low = qp.device_expr_pipeline.lower(plan, (leaf,), params,
                                            args[3])
    return low.as_text(debug_info=True)


@pytest.mark.parametrize("which,scopes", [
    ("device_temporal_pipeline", ("m3.decode", "m3.merge", "m3.temporal")),
    ("device_grouped_pipeline",
     ("m3.decode", "m3.merge", "m3.temporal", "m3.group")),
    ("device_expr_pipeline",
     ("m3.expr", "m3.decode", "m3.merge", "m3.temporal", "m3.group")),
])
def test_device_programs_name_their_stages(which, scopes):
    text = _lowered(which)
    for scope in scopes:
        assert f"/{scope}/" in text, scope


def test_cold_overlay_counts_one_decline(db):
    eng = Engine(db, "default", device_serving=True)
    fam = instrument.bounded_counter("m3_query_device_decline_total")
    cold = fam.labels(reason="cold_overlay")
    before = cold.value
    expr = "rate(cold[6m])"
    _, mat = eng.query_range(expr, START, END, STEP)
    assert len(mat.labels) == 3
    assert cold.value == before + 1
    rec = _record_of(expr)
    assert rec["device_serving"] is False
    assert rec["device_declines"] == {"cold_overlay": 1}


def test_mutable_range_is_served_by_the_device_tier(db):
    """Open rows beside sealed streams are no longer a decline."""
    eng = Engine(db, "default", device_serving=True)
    expr = "rate(sealed[6m])"
    _, mat = eng.query_range(expr, MIXED_LO, MIXED_HI, STEP)
    assert len(mat.labels) == 12
    rec = _record_of(expr)
    assert rec["device_serving"] is True and "device_declines" not in rec
    assert (rec["rows"], rec["open_rows"]) == (16, 4)


def _forget_per_node_programs():
    """Programs traced under another constant of query_pipeline."""
    qp.device_temporal_pipeline.__wrapped__.clear_cache()
    qp.device_grouped_pipeline.__wrapped__.clear_cache()


def _form_is_counted_and_recorded(db, monkeypatch, expr, counter, field,
                                  forms, form, constant, value):
    """A device-served `expr` counts `form` of `forms` once under
    `counter`, its record says the same word under `field`, and its
    answer is the host's; `constant` of query_pipeline patched to
    `value` (unless None) to put the call on the other form."""
    forget = _forget_per_node_programs
    if value is not None:
        monkeypatch.setattr(qp, constant, value)
        forget()
    counters = {f: instrument.counter(counter, form=f) for f in forms}
    before = {f: c.value for f, c in counters.items()}
    eng = Engine(db, "default", device_serving=True)
    _, mat = eng.query_range(expr, START, END, STEP)
    rec = _record_of(expr)
    assert rec["device_serving"] is True
    assert rec[field] == form
    assert {f: c.value - before[f] for f, c in counters.items()} == {
        f: int(f == form) for f in counters}
    _, host = Engine(db, "default", device_serving=False).query_range(
        expr, START, END, STEP)
    np.testing.assert_allclose(np.asarray(mat.values),
                               np.asarray(host.values), rtol=1e-9,
                               equal_nan=True)
    if value is not None:
        forget()


@pytest.mark.parametrize("expr,form,max_n", [
    ("sum by (dc) (rate(sealed[8m]))", "select", None),
    ("increase(sealed[8m])", "select", None),
    ("delta(sealed[8m])", "gather", 0),
    ("sum by (dc) (rate(sealed[11m]))", "gather", 0),
    ("max_over_time(sealed[8m])", None, None),
])
def test_window_form_is_counted_and_recorded(db, monkeypatch, expr, form,
                                             max_n):
    """A per-node call of the rate family counts the form its n_cap
    bucket takes (query_pipeline.window_form) and the record says the
    same word; another temporal function reads no window ends and
    counts none.  The answer does not depend on the form."""
    _form_is_counted_and_recorded(
        db, monkeypatch, expr, "m3_device_window_form_total", "window_form",
        ("select", "gather"), form, "_SELECT_MAX_N", max_n)


@pytest.mark.parametrize("expr,form,min_rows", [
    ("sum by (dc) (rate(sealed[8m]))", "rotate", None),
    ("max_over_time(sealed[8m])", "rotate", None),
    ("sum by (dc) (rate(sealed[9m]))", "window", 0),
    ("delta(sealed[9m])", "window", 0),
])
def test_merge_form_is_counted_and_recorded(db, monkeypatch, expr, form,
                                            min_rows):
    """Every per-node call counts the form its n_cap and n_dp buckets
    take (query_pipeline.merge_form) and the record says the same word.
    The answer does not depend on the form."""
    _form_is_counted_and_recorded(
        db, monkeypatch, expr, "m3_device_merge_form_total", "merge_form",
        ("rotate", "window"), form, "_WINDOW_MIN_ROWS", min_rows)


@pytest.mark.parametrize("expr,band,served", [
    ("sum by (dc) (rate(sealed[2m]))", None, "full"),
    ("sum by (dc) (rate(sealed[2m]))", (8, 8, 16), "band"),
    ("max_over_time(sealed[2m])", (8, 8, 16), "band"),
    ("sum by (dc) (rate(sealed[12m]))", (8, 8, 16), "full"),
], ids=["no_band_at_this_shape", "in_the_band", "bounds_alone_in_the_band",
        "a_range_past_the_span"])
def test_band_is_counted_and_recorded(db, monkeypatch, expr, band, served):
    """A per-node call counts its windowed stage's lane chunks by how
    they were searched, as the program said (a band of the lane, or its
    full width: a shape the band is not taken at, or windows that do not
    fit their span: 12 m of samples 30 s apart where a span holds 32),
    and the record carries the band's share.  The answer does not
    depend on it."""
    forget = _forget_per_node_programs
    if band is not None:
        for name, value in zip(("_BAND_STEPS", "_BAND_TILE", "_BAND_SLACK"),
                               band):
            monkeypatch.setattr(qp, name, value)
        forget()
    counters = {w: instrument.counter("m3_device_window_band_total",
                                      served=w) for w in ("band", "full")}
    before = {w: c.value for w, c in counters.items()}
    eng = Engine(db, "default", device_serving=True)
    _, mat = eng.query_range(expr, START, END, STEP)
    rec = _record_of(expr)
    assert rec["device_serving"] is True and rec["lane_chunks"] == 1
    assert (qp.band_width(rec["n_cap"], rec["steps_pad"]) is None) == (
        band is None)
    assert {w: c.value - before[w] for w, c in counters.items()} == {
        w: int(w == served) for w in counters}
    assert rec["band_served_pct"] == (100.0 if served == "band" else 0.0)
    _, host = Engine(db, "default", device_serving=False).query_range(
        expr, START, END, STEP)
    np.testing.assert_allclose(np.asarray(mat.values),
                               np.asarray(host.values), rtol=1e-9,
                               equal_nan=True)
    if band is not None:
        forget()


def test_a_stand_ins_plain_pair_counts_no_band(db, monkeypatch):
    """A program stood in on the module (a benchmark's control) may hand
    back the plain (out, error) pair: the call is served, nothing is
    counted and the record has no share."""
    real = qp.device_grouped_pipeline

    def plain(*args, **kwargs):
        out, err = real(*args, **kwargs)
        return out, err

    monkeypatch.setattr(qp, "device_grouped_pipeline", plain)
    counters = [instrument.counter("m3_device_window_band_total", served=w)
                for w in ("band", "full")]
    before = [c.value for c in counters]
    expr = "sum by (dc) (rate(sealed[3m]))"
    Engine(db, "default", device_serving=True).query_range(
        expr, START, END, STEP)
    rec = _record_of(expr)
    assert rec["device_serving"] is True
    assert "band_served_pct" not in rec
    assert [c.value for c in counters] == before


def test_http_query_leaves_frontend_in_its_record(db):
    srv = CoordinatorServer(db, port=0).start()
    try:
        expr = "sum by (dc) (rate(sealed[11m]))"
        q = urllib.parse.urlencode({
            "query": expr, "start": START / 1e9, "end": END / 1e9,
            "step": "60"})
        t0 = time.perf_counter()
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/api/v1/query_range?{q}") as r:
            doc = json.loads(r.read())
        seconds = time.perf_counter() - t0
        assert doc["status"] == "success" and doc["data"]["result"]
        # the handler finishes its stamp just after the last byte
        deadline = time.monotonic() + 10
        while (_record_of(expr)["phases"]["frontend_s"] == 0.0
               and time.monotonic() < deadline):
            time.sleep(0.01)
        ph = _record_of(expr)["phases"]
        assert 0.0 < ph["frontend_s"] < seconds
        assert ph["frontend_s"] + ph["total_s"] <= seconds
    finally:
        srv.stop()


def test_span_duration_survives_a_wall_clock_step(monkeypatch):
    walls = iter([1_000_000.0, 999_000.0, 998_000.0])
    monkeypatch.setattr(tracing.time, "time", lambda: next(walls))
    tr = tracing.Tracer(sample_1_in=1)
    with tr.span(tracing.ENGINE_QUERY_RANGE) as sp:
        time.sleep(0.002)
    assert sp.start == 1_000_000.0
    assert sp.duration >= 0.002
    assert tr.finished()[-1]["duration_ms"] >= 2.0


def test_phase_feeds_record_span_and_annotation():
    tr = tracing.tracer()
    sink = {}
    with tracing.activate(tracing.TraceContext(7, 9, True)):
        with tracing.phase("pack", sink):
            time.sleep(0.001)
        with tracing.phase("pack", sink):
            pass
    assert sink["pack_s"] >= 0.001
    spans = [s for s in tr.finished() if s["name"] == tracing.ENGINE_PACK
             and s["trace_id"] == f"{7:032x}"]
    assert len(spans) == 2 and spans[0]["parent_id"] == f"{9:016x}"


# --- a phase's work apart from its waiting (PR 42) ---

LOCK_ONLY = ("parse_s", "plan_s", "fetch_s", "open_read_s", "pack_s",
             "decode_s", "merge_s", "self_s")


def _counted(monkeypatch, name: str) -> list:
    """Count the process's readings of `time.<name>` from here on."""
    real, calls = getattr(time, name), []

    def reading():
        calls.append(1)
        return real()

    monkeypatch.setattr(time, name, reading)
    return calls


def _unsampled(monkeypatch):
    """No root span is sampled: a query is clocked by the engine's
    count alone, or by a context the test activates."""
    monkeypatch.setattr(tracing.tracer(), "sample_1_in", 10 ** 9)
    with tracing.span(tracing.ENGINE_QUERY_RANGE):
        pass        # a name's first root span is its sampled one


def _held(db, entry: str, seconds: float) -> threading.Thread:
    """A thread that holds the database lock under `entry`; returns
    once it has it."""
    has_it = threading.Event()

    def hold():
        with db.hold(entry):
            has_it.set()
            time.sleep(seconds)

    t = threading.Thread(target=hold)
    t.start()
    assert has_it.wait(30)
    return t


def test_db_lock_wait_is_the_waiting_querys_and_the_holders_entry(
        db, monkeypatch):
    _unsampled(monkeypatch)
    eng = Engine(db, "default", device_serving=False)
    eng.query_range("rate(sealed[3m])", START, END, STEP)      # warm
    held = instrument.counter("m3_db_lock_held_seconds_total",
                              entry="tick")
    waited = instrument.counter("m3_wait_seconds_total", on="db_lock")
    waits = instrument.counter("m3_waits_total", on="db_lock")
    before = held.value, waited.value, waits.value
    expr = "rate(sealed[4m])"
    holder = _held(db, "tick", 0.05)
    with tracing.activate(tracing.TraceContext(11, 13, True)):
        eng.query_range(expr, START, END, STEP)
    holder.join(30)
    ph = _record_of(expr)["phases"]
    assert 0.04 <= ph["db_lock_wait_s"] <= 0.07
    assert ph["db_lock_wait_s"] <= ph["fetch_s"]
    assert waits.value == before[2] + 1
    assert waited.value - before[1] == pytest.approx(ph["db_lock_wait_s"])
    # the holder had not queued: it is charged from the moment the
    # query came to wait
    assert held.value - before[0] == pytest.approx(
        ph["db_lock_wait_s"], abs=0.005)
    # the span that covers the wait says the same
    span = next(s for s in tracing.tracer().finished()
                if s["name"] == tracing.DB_FETCH_TAGGED
                and s["trace_id"] == f"{11:032x}")
    assert float(span["tags"]["lock_wait_ms"]) == pytest.approx(
        ph["db_lock_wait_s"] * 1e3, abs=0.001)
    # another thread's query, once the lock is free, waited for nothing
    other = "rate(sealed[2m])"
    t = threading.Thread(target=eng.query_range,
                         args=(other, START, END, STEP))
    t.start()
    t.join(60)
    assert _record_of(other)["phases"]["db_lock_wait_s"] == 0.0
    assert waits.value == before[2] + 1


def test_a_waiter_that_queued_is_charged_its_whole_hold(db):
    """The second holder stamped when it got the lock: its hold counts
    from there, whether or not anyone waits for it."""
    held = instrument.counter("m3_db_lock_held_seconds_total",
                              entry="second")
    before = held.value
    first = _held(db, "first", 0.03)
    with db.hold("second"):
        time.sleep(0.02)
    first.join(30)
    assert 0.02 <= held.value - before <= 0.05


@pytest.mark.parametrize("how", ["uncontended", "reentrant", "entry"])
def test_a_free_database_lock_reads_no_clock(db, monkeypatch, how):
    from m3_tpu.storage import database

    seen = []

    @database._locked
    def _probe_locked(self):
        seen.append(self._lock_entry)

    readings = _counted(monkeypatch, "perf_counter_ns")
    if how == "uncontended":
        with db.hold("outer"):
            pass
    elif how == "reentrant":
        with db.hold("outer"):
            with db.hold("inner"):
                seen.append(db._lock_entry)
        assert seen == ["outer"]
    else:
        _probe_locked(db)
        assert seen == ["probe"]
    assert readings == []
    assert db._lock_entry is None


def test_a_wait_outside_any_query_moves_the_counters_alone():
    waits = instrument.counter("m3_waits_total", on="test_wait")
    seconds = instrument.counter("m3_wait_seconds_total", on="test_wait")
    with tracing.wait("test_wait") as w:
        time.sleep(0.002)
    assert waits.value == 1
    assert seconds.value == pytest.approx((w.t1_ns - w.t0_ns) / 1e9)
    assert seconds.value >= 0.002
    sink = {}
    with tracing.phase("fetch", sink):
        with tracing.wait("test_wait"):
            pass
    assert 0.0 < sink["test_wait_wait_s"] <= sink["fetch_s"]


def test_one_query_in_sixteen_reads_the_cpu_clock(db, monkeypatch):
    _unsampled(monkeypatch)
    eng = Engine(db, "default", device_serving=False)
    readings = _counted(monkeypatch, "thread_time_ns")
    exprs = [f"rate(sealed[{200 + i}s])" for i in range(17)]
    for expr in exprs[:15]:
        eng.query_range(expr, START, END, STEP)
    assert readings == []
    eng.query_range(exprs[15], START, END, STEP)
    assert len(readings) >= 4      # the engine call and a phase or more
    clocked = [e for e in exprs[:16] if "cpu" in _record_of(e)]
    assert clocked == [exprs[15]]
    assert [e for e in exprs[:16] if "interp_wait_s" in _record_of(e)
            ] == clocked
    # a live span forces one, and the count starts again from it
    del readings[:]
    with tracing.activate(tracing.TraceContext(17, 19, True)):
        eng.query_range(exprs[16], START, END, STEP)
    assert "cpu" in _record_of(exprs[16]) and readings
    del readings[:]
    for i in range(15):
        eng.query_range(f"rate(sealed[{300 + i}s])", START, END, STEP)
    assert readings == []


@pytest.mark.parametrize("device", [False, True])
def test_a_clocked_record_splits_work_from_waiting(db, monkeypatch,
                                                   device):
    _unsampled(monkeypatch)
    eng = Engine(db, "default", device_serving=device)
    expr = f"sum by (dc) (rate(sealed[{13 + device}m]))"
    with tracing.activate(tracing.TraceContext(23, 29 + device, True)):
        eng.query_range(expr, START, END, STEP)
    rec = _record_of(expr)
    ph, cpu = rec["phases"], rec["cpu"]
    assert set(cpu) == PHASE_KEYS and set(ph) == PHASE_KEYS | WAIT_KEYS
    assert all(v >= 0.0 for k, v in cpu.items() if k != "self_s")
    assert 0.0 < cpu["total_s"] <= ph["total_s"] + 0.011
    tiled = sum(cpu[k] for k in PHASE_KEYS - {
        "h2d_s", "d2h_s", "frontend_s", "total_s"})
    assert tiled == pytest.approx(cpu["total_s"], rel=1e-9)
    assert rec["interp_wait_s"] == pytest.approx(
        sum(ph[k] - cpu[k] for k in LOCK_ONLY) - ph["db_lock_wait_s"])
    assert (cpu["device_s"] > 0.0) == device
    # the live span of a clocked phase carries both
    span = next(s for s in tracing.tracer().finished()
                if s["name"] == tracing.ENGINE_GATHER
                and s["trace_id"] == f"{23:032x}"
                and s["parent_id"] is not None)
    assert float(span["tags"]["cpu_ms"]) >= 0.0
    assert "wait_ms" in span["tags"]


def test_device_wait_lies_inside_the_device_phase(db, monkeypatch):
    _unsampled(monkeypatch)
    eng = Engine(db, "default", device_serving=True)
    expr = "sum by (dc) (rate(sealed[17m]))"
    eng.query_range(expr, START, END, STEP)        # compiles: no wait
    ker = kernel_telemetry.kernels()["device_grouped_pipeline"]
    before = ker.stats()["wait_s"]
    eng.query_range(expr, START, END, STEP)
    ph = slowlog.log().records(limit=1)[0]["phases"]
    assert 0.0 < ph["device_wait_s"] <= ph["device_s"]
    assert ph["device_wait_s"] == pytest.approx(
        ker.stats()["wait_s"] - before)
    host = "sum by (dc) (rate(cold[17m]))"
    eng.query_range(host, START, END, STEP)
    assert _record_of(host)["phases"]["device_wait_s"] == 0.0


def test_a_full_collection_is_charged_to_the_phase_it_interrupted():
    import gc

    pauses = instrument.counter("m3_gc_pause_seconds_total")
    count = instrument.counter("m3_gc_collections_total")
    before = pauses.value, count.value
    tracing.watch_collector()
    tracing.watch_collector()        # one entry, however often asked
    assert gc.callbacks.count(tracing._COLLECTOR_WATCH) == 1
    try:
        sink = {}
        with tracing.phase("pack", sink):
            gc.collect()
            gc.collect(0)            # a young collection is not clocked
        gc.collect()                 # no phase open: the counters alone
    finally:
        gc.callbacks.remove(tracing._COLLECTOR_WATCH)
    assert 0.0 < sink["gc_pause_s"] <= sink["pack_s"]
    assert count.value == before[1] + 2
    assert pauses.value - before[0] > sink["gc_pause_s"]


def test_http_front_end_is_clocked_with_its_query(db, monkeypatch):
    _unsampled(monkeypatch)
    with tracing.span(tracing.HTTP_REQUEST):
        pass        # the front end's first root span is its sampled one
    srv = CoordinatorServer(db, port=0).start()
    try:
        expr = "sum by (dc) (rate(sealed[19m]))"
        q = urllib.parse.urlencode({
            "query": expr, "start": START / 1e9, "end": END / 1e9,
            "step": "60"})
        url = f"http://127.0.0.1:{srv.port}/api/v1/query_range?{q}"
        with urllib.request.urlopen(url) as r:
            r.read()
        req = urllib.request.Request(url, headers={
            "traceparent": f"00-{31:032x}-{37:016x}-01"})
        with urllib.request.urlopen(req) as r:
            r.read()
        deadline = time.monotonic() + 10
        while (slowlog.log().records(limit=1)[0]["phases"]["frontend_s"]
               == 0.0 and time.monotonic() < deadline):
            time.sleep(0.01)
        forced, plain = slowlog.log().records(limit=2)
        assert "cpu" not in plain and "interp_wait_s" not in plain
        assert forced["trace_id"] == f"{31:032x}"
        assert 0.0 <= forced["cpu"]["frontend_s"] <= (
            forced["phases"]["frontend_s"] + 0.011)
        assert set(forced["phases"]) == PHASE_KEYS | WAIT_KEYS
    finally:
        srv.stop()


def test_database_lock_bookkeeping_under_many_threads(db):
    """More threads than cores on a short switch interval: the lock
    still excludes, no hold is left named, and the holds that were
    clocked, being exclusive, sum to no more than the time that passed."""
    import sys

    threads, rounds = 16, 150
    shared = {"n": 0}
    held = [instrument.counter("m3_db_lock_held_seconds_total",
                               entry=f"stress{k}") for k in range(3)]
    waited = instrument.counter("m3_wait_seconds_total", on="db_lock")
    before = sum(c.value for c in held), waited.value
    errors = []

    def work(i):
        try:
            for r in range(rounds):
                with db.hold(f"stress{i % 3}"):
                    n = shared["n"]
                    if r % 7 == 0:
                        with db.hold("inner"):      # re-entrant
                            assert db._lock_entry == f"stress{i % 3}"
                    shared["n"] = n + 1
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(f"{type(e).__name__}: {e}")

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    t0 = time.perf_counter()
    try:
        pool = [threading.Thread(target=work, args=(i,))
                for i in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(60)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(interval)
    elapsed = time.perf_counter() - t0
    assert not errors, errors[:3]
    assert shared["n"] == threads * rounds
    assert db._lock_entry is None and db._lock_wanted_ns == 0
    assert db._lock.acquire(blocking=False)
    db._lock.release()
    assert 0.0 < sum(c.value for c in held) - before[0] <= elapsed
    assert waited.value > before[1]


# --- the record a query leaves, held to the letter (PR 50) ---

def _write_histogram_and_paths(db) -> None:
    """A histogram (`lat_bucket`: 6 hosts x 4 `le`) and three Graphite
    paths."""
    n = 120
    ts = [T0 + (k + 1) * 30 * SEC for k in range(n)]
    for i in range(6):
        for j, le in enumerate((b"0.1", b"0.5", b"1", b"+Inf")):
            tags = {b"__name__": b"lat_bucket", b"host": b"h%02d" % i,
                    b"dc": b"dc%d" % (i % 3), b"le": le}
            vs = np.cumsum(np.full(n, (1.0 + i) * (1 + j))).tolist()
            db.write_batch("default", [b"lat|h%02d|" % i + le] * n,
                           [tags] * n, ts, vs)
    for i in range(3):
        path_b = b"servers.host%d.cpu" % i
        tags = {b"__name__": path_b}
        tags.update({b"__g%d__" % k: c
                     for k, c in enumerate(path_b.split(b"."))})
        db.write_batch("default", [path_b] * n, [tags] * n, ts,
                       (np.arange(n, dtype=float) * (1 + i)).tolist())


def _pin_db(path: str) -> Database:
    """The `db` fixture's series, the histogram and the paths."""
    return _make_db(path, _write_histogram_and_paths)


@pytest.fixture
def pin_db(tmp_path):
    db = _pin_db(str(tmp_path))
    yield db
    db.close()


GROUPED = "sum by (dc) (rate(sealed[5m]))"
TOPK = "topk(2, sum by (host) (rate(sealed[5m])))"
HQ = "histogram_quantile(0.99, sum by (le, dc) (rate(lat_bucket[5m])))"
GRAPHITE = "highestAverage(scale(servers.*.cpu, 2), 2)"
# case -> (expression, device_serving, what else the case does)
PIN_CASES = {
    "grouped": (GROUPED, True, None),
    "temporal": ("rate(sealed[5m])", True, None),
    "topk": (TOPK, True, None),
    "hq": (HQ, True, None),
    "grouped_host": (GROUPED, False, None),
    "temporal_host": ("rate(sealed[5m])", False, None),
    "topk_host": (TOPK, False, None),
    "hq_host": (HQ, False, None),
    "open_rows": ("rate(sealed[6m])", True, "mixed_range"),
    "decline": ("rate(cold[5m])", True, None),
    "device_error": (GROUPED, True, "program_raises"),
    "fused_error": (TOPK, True, "program_raises"),
    "raises": ("sum by (dc) (rate(sealed[5m]", True, None),
    "clocked": (GROUPED, True, "traceparent"),
    "graphite": (GRAPHITE, True, "graphite"),
    "graphite_host": (GRAPHITE, False, "graphite"),
}
_TIMINGS = ("total_s", "ts", "interp_wait_s", "compile_s", "compile_cache")


def _pin_run(case: str, db, monkeypatch) -> tuple:
    """Run `case`'s one query on a fresh engine -> (its record with the
    timings blanked, the key set of the thread's last_fetch_stats)."""
    from m3_tpu.query.graphite import GraphiteEngine

    expr, device, how = PIN_CASES[case]
    _unsampled(monkeypatch)
    if how == "program_raises":
        def broken(*args, **kwargs):
            raise RuntimeError("the program is broken")

        for entry in ("device_grouped_pipeline", "device_temporal_pipeline",
                      "device_expr_pipeline"):
            monkeypatch.setattr(qp, entry, broken)
    lo, hi = (MIXED_LO, MIXED_HI) if how == "mixed_range" else (START, END)
    if how == "graphite":
        geng = GraphiteEngine(db, "default", device=device)
        geng.render(expr, lo, hi, STEP)
        eng, expr = geng._engine, f"graphite://{expr}"
    else:
        eng = Engine(db, "default", device_serving=device)
        try:
            if how == "traceparent":
                with tracing.activate(tracing.TraceContext(41, 43, True)):
                    eng.query_range(expr, lo, hi, STEP)
            else:
                eng.query_range(expr, lo, hi, STEP)
        except ValueError:
            assert case == "raises"
    rec = dict(slowlog.log().records(limit=1)[0])
    assert rec["expr"] == expr
    assert set(rec.pop("phases")) == PHASE_KEYS | WAIT_KEYS
    if "cpu" in rec:
        assert set(rec["cpu"]) == PHASE_KEYS
        rec["cpu"] = ...
    if "device_tier" in rec:
        rec["device_tier"] = dict(rec["device_tier"])
    for holder in (rec, rec.get("device_tier", {})):
        for key in _TIMINGS:
            if key in holder:
                holder[key] = ...
    return rec, sorted(eng.last_fetch_stats or ())


# taken at 6139ab6 (the parent of PR 50) by running _pin_run on each case
PINNED = {
    'grouped': (
        {'expr': GROUPED, 'tenant': 'default', 'initiator': 'http',
         'total_s': ..., 'series': 3, 'datapoints': 1440, 'rows': 12,
         'open_rows': 0, 'lanes': 12, 'lanes_pad': 64, 'lane_chunks': 1,
         'n_cap': 128, 'steps_pad': 128, 'rows_per_lane': 1,
         'decode_refills': 16, 'groups': 0, 'topk_k': 0, 'rows_out': 0,
         'hq_groups': 0, 'hq_buckets': 0, 'window_form': 'select',
         'merge_form': 'rotate', 'fileset_scans': 0,
         'walk_rows': {'columns': 9, 'by_row': 3}, 'device_serving': True,
         'fn': 'rate', 'n_shards': 1, 'warnings': [], 'exhaustive': True,
         'error': None, 'trace_id': None,
         'cache': {'postings_misses': 1, 'postings_miss_bytes': 8},
         'band_served_pct': 0.0, 'ts': ...},
        ['agg', 'band_served_pct', 'd2h_s', 'datapoints', 'db_lock_wait_s',
         'decode_refills', 'device_grouped', 'device_s', 'device_serving',
         'device_wait_s', 'fetch_s', 'fn', 'gc_pause_s', 'h2d_s',
         'lane_chunks', 'lanes', 'lanes_pad', 'merge_form', 'n_cap',
         'n_groups', 'n_shards', 'n_streams', 'open_rows', 'pack_s',
         'parse_s', 'rows', 'rows_per_lane', 'steps_pad', 'window_form']),
    'temporal': (
        {'expr': 'rate(sealed[5m])', 'tenant': 'default', 'initiator': 'http',
         'total_s': ..., 'series': 12, 'datapoints': 1440, 'rows': 12,
         'open_rows': 0, 'lanes': 12, 'lanes_pad': 64, 'lane_chunks': 1,
         'n_cap': 128, 'steps_pad': 128, 'rows_per_lane': 1,
         'decode_refills': 16, 'groups': 0, 'topk_k': 0, 'rows_out': 0,
         'hq_groups': 0, 'hq_buckets': 0, 'window_form': 'select',
         'merge_form': 'rotate', 'fileset_scans': 0,
         'walk_rows': {'columns': 9, 'by_row': 3}, 'device_serving': True,
         'fn': 'rate', 'n_shards': 1, 'warnings': [], 'exhaustive': True,
         'error': None, 'trace_id': None,
         'cache': {'postings_misses': 1, 'postings_miss_bytes': 8},
         'band_served_pct': 0.0, 'ts': ...},
        ['band_served_pct', 'd2h_s', 'datapoints', 'db_lock_wait_s',
         'decode_refills', 'device_s', 'device_serving', 'device_wait_s',
         'fetch_s', 'fn', 'gc_pause_s', 'h2d_s', 'lane_chunks', 'lanes',
         'lanes_pad', 'merge_form', 'n_cap', 'n_shards', 'n_streams',
         'open_rows', 'pack_s', 'parse_s', 'rows', 'rows_per_lane',
         'steps_pad', 'window_form']),
    'topk': (
        {'expr': TOPK, 'tenant': 'default', 'initiator': 'http',
         'total_s': ..., 'series': 4, 'datapoints': 1440, 'rows': 12,
         'open_rows': 0, 'lanes': 12, 'lanes_pad': 64, 'lane_chunks': 0,
         'n_cap': 128, 'steps_pad': 128, 'rows_per_lane': 1,
         'decode_refills': 16, 'groups': 12, 'topk_k': 2, 'rows_out': 4,
         'hq_groups': 0, 'hq_buckets': 0, 'window_form': 'select',
         'merge_form': 'rotate', 'fileset_scans': 0,
         'walk_rows': {'columns': 9, 'by_row': 3}, 'device_serving': True,
         'fn': 'rate', 'n_shards': 1, 'warnings': [], 'exhaustive': True,
         'error': None, 'trace_id': None,
         'cache':
          {'postings_misses': 1, 'postings_miss_bytes': 8,
           'device_bridge_misses': 1, 'device_bridge_miss_bytes': 16384},
         'band_served_pct': 0.0,
         'device_tier':
          {'compile_cache': ..., 'compile_s': ..., 'device_nodes': 5,
           'host_nodes': 0, 'transfer_bytes': 16592, 'n_shards': 1},
         'ts': ...},
        ['agg', 'band_served_pct', 'compile_cache', 'compile_s', 'compiled',
         'd2h_s', 'datapoints', 'db_lock_wait_s', 'decode_refills',
         'device_fused', 'device_s', 'device_serving', 'device_wait_s',
         'fetch_s', 'fn', 'fused_nodes', 'gc_pause_s', 'groups', 'hq_buckets',
         'hq_groups', 'lanes', 'lanes_pad', 'merge_form', 'n_cap', 'n_shards',
         'n_streams', 'pack_s', 'parse_s', 'plan_s', 'rows', 'rows_out',
         'rows_per_lane', 'steps_pad', 'topk_k', 'transfer_bytes',
         'window_form']),
    'hq': (
        {'expr': HQ, 'tenant': 'default', 'initiator': 'http', 'total_s': ...,
         'series': 3, 'datapoints': 2880, 'rows': 24, 'open_rows': 0,
         'lanes': 24, 'lanes_pad': 64, 'lane_chunks': 0, 'n_cap': 128,
         'steps_pad': 128, 'rows_per_lane': 1, 'decode_refills': 16,
         'groups': 12, 'topk_k': 0, 'rows_out': 3, 'hq_groups': 3,
         'hq_buckets': 4, 'window_form': 'select', 'merge_form': 'rotate',
         'fileset_scans': 0, 'walk_rows': {'columns': 21, 'by_row': 3},
         'device_serving': True, 'fn': 'rate', 'n_shards': 1, 'warnings': [],
         'exhaustive': True, 'error': None, 'trace_id': None,
         'cache':
          {'postings_misses': 1, 'postings_miss_bytes': 8,
           'device_bridge_misses': 1, 'device_bridge_miss_bytes': 16384},
         'band_served_pct': 0.0,
         'device_tier':
          {'compile_cache': ..., 'compile_s': ..., 'device_nodes': 5,
           'host_nodes': 0, 'transfer_bytes': 8256, 'n_shards': 1},
         'ts': ...},
        ['agg', 'band_served_pct', 'compile_cache', 'compile_s', 'compiled',
         'd2h_s', 'datapoints', 'db_lock_wait_s', 'decode_refills',
         'device_fused', 'device_s', 'device_serving', 'device_wait_s',
         'fetch_s', 'fn', 'fused_nodes', 'gc_pause_s', 'groups', 'hq_buckets',
         'hq_groups', 'lanes', 'lanes_pad', 'merge_form', 'n_cap', 'n_shards',
         'n_streams', 'pack_s', 'parse_s', 'plan_s', 'rows', 'rows_out',
         'rows_per_lane', 'steps_pad', 'topk_k', 'transfer_bytes',
         'window_form']),
    'grouped_host': (
        {'expr': GROUPED, 'tenant': 'default', 'initiator': 'http',
         'total_s': ..., 'series': 3, 'datapoints': 1440, 'rows': 0,
         'open_rows': 0, 'lanes': 0, 'lanes_pad': 0, 'lane_chunks': 0,
         'n_cap': 0, 'steps_pad': 0, 'rows_per_lane': 0, 'decode_refills': 0,
         'groups': 0, 'topk_k': 0, 'rows_out': 0, 'hq_groups': 0,
         'hq_buckets': 0, 'window_form': None, 'merge_form': None,
         'fileset_scans': 0, 'walk_rows': {'columns': 9, 'by_row': 3},
         'device_serving': False, 'fn': None, 'n_shards': 1, 'warnings': [],
         'exhaustive': True, 'error': None, 'trace_id': None,
         'cache': {'postings_misses': 1, 'postings_miss_bytes': 8},
         'ts': ...},
        ['datapoints', 'db_lock_wait_s', 'decode_s', 'device_wait_s',
         'fetch_s', 'gc_pause_s', 'n_streams', 'parse_s', 'read_bytes']),
    'temporal_host': (
        {'expr': 'rate(sealed[5m])', 'tenant': 'default', 'initiator': 'http',
         'total_s': ..., 'series': 12, 'datapoints': 1440, 'rows': 0,
         'open_rows': 0, 'lanes': 0, 'lanes_pad': 0, 'lane_chunks': 0,
         'n_cap': 0, 'steps_pad': 0, 'rows_per_lane': 0, 'decode_refills': 0,
         'groups': 0, 'topk_k': 0, 'rows_out': 0, 'hq_groups': 0,
         'hq_buckets': 0, 'window_form': None, 'merge_form': None,
         'fileset_scans': 0, 'walk_rows': {'columns': 9, 'by_row': 3},
         'device_serving': False, 'fn': None, 'n_shards': 1, 'warnings': [],
         'exhaustive': True, 'error': None, 'trace_id': None,
         'cache': {'postings_misses': 1, 'postings_miss_bytes': 8},
         'ts': ...},
        ['datapoints', 'db_lock_wait_s', 'decode_s', 'device_wait_s',
         'fetch_s', 'gc_pause_s', 'n_streams', 'parse_s', 'read_bytes']),
    'topk_host': (
        {'expr': TOPK, 'tenant': 'default', 'initiator': 'http',
         'total_s': ..., 'series': 4, 'datapoints': 1440, 'rows': 0,
         'open_rows': 0, 'lanes': 0, 'lanes_pad': 0, 'lane_chunks': 0,
         'n_cap': 0, 'steps_pad': 0, 'rows_per_lane': 0, 'decode_refills': 0,
         'groups': 0, 'topk_k': 0, 'rows_out': 0, 'hq_groups': 0,
         'hq_buckets': 0, 'window_form': None, 'merge_form': None,
         'fileset_scans': 0, 'walk_rows': {'columns': 9, 'by_row': 3},
         'device_serving': False, 'fn': None, 'n_shards': 1, 'warnings': [],
         'exhaustive': True, 'error': None, 'trace_id': None,
         'cache': {'postings_misses': 1, 'postings_miss_bytes': 8},
         'ts': ...},
        ['datapoints', 'db_lock_wait_s', 'decode_s', 'device_wait_s',
         'fetch_s', 'gc_pause_s', 'n_streams', 'parse_s', 'read_bytes']),
    'hq_host': (
        {'expr': HQ, 'tenant': 'default', 'initiator': 'http', 'total_s': ...,
         'series': 3, 'datapoints': 2880, 'rows': 0, 'open_rows': 0,
         'lanes': 0, 'lanes_pad': 0, 'lane_chunks': 0, 'n_cap': 0,
         'steps_pad': 0, 'rows_per_lane': 0, 'decode_refills': 0, 'groups': 0,
         'topk_k': 0, 'rows_out': 0, 'hq_groups': 0, 'hq_buckets': 0,
         'window_form': None, 'merge_form': None, 'fileset_scans': 0,
         'walk_rows': {'columns': 21, 'by_row': 3}, 'device_serving': False,
         'fn': None, 'n_shards': 1, 'warnings': [], 'exhaustive': True,
         'error': None, 'trace_id': None,
         'cache': {'postings_misses': 1, 'postings_miss_bytes': 8},
         'ts': ...},
        ['datapoints', 'db_lock_wait_s', 'decode_s', 'device_wait_s',
         'fetch_s', 'gc_pause_s', 'n_streams', 'parse_s', 'read_bytes']),
    'open_rows': (
        {'expr': 'rate(sealed[6m])', 'tenant': 'default', 'initiator': 'http',
         'total_s': ..., 'series': 12, 'datapoints': 1680, 'rows': 16,
         'open_rows': 4, 'lanes': 12, 'lanes_pad': 64, 'lane_chunks': 1,
         'n_cap': 256, 'steps_pad': 256, 'rows_per_lane': 2,
         'decode_refills': 16, 'groups': 0, 'topk_k': 0, 'rows_out': 0,
         'hq_groups': 0, 'hq_buckets': 0, 'window_form': 'select',
         'merge_form': 'rotate', 'fileset_scans': 0,
         'walk_rows': {'columns': 21, 'by_row': 3}, 'device_serving': True,
         'fn': 'rate', 'n_shards': 1, 'warnings': [], 'exhaustive': True,
         'error': None, 'trace_id': None,
         'cache': {'postings_misses': 1, 'postings_miss_bytes': 8},
         'band_served_pct': 0.0, 'ts': ...},
        ['band_served_pct', 'd2h_s', 'datapoints', 'db_lock_wait_s',
         'decode_refills', 'device_s', 'device_serving', 'device_wait_s',
         'fetch_s', 'fn', 'gc_pause_s', 'h2d_s', 'lane_chunks', 'lanes',
         'lanes_pad', 'merge_form', 'n_cap', 'n_shards', 'n_streams',
         'open_read_s', 'open_rows', 'pack_s', 'parse_s', 'rows',
         'rows_per_lane', 'steps_pad', 'window_form']),
    'decline': (
        {'expr': 'rate(cold[5m])', 'tenant': 'default', 'initiator': 'http',
         'total_s': ..., 'series': 3, 'datapoints': 333, 'rows': 0,
         'open_rows': 0, 'lanes': 0, 'lanes_pad': 0, 'lane_chunks': 0,
         'n_cap': 0, 'steps_pad': 0, 'rows_per_lane': 0, 'decode_refills': 0,
         'groups': 0, 'topk_k': 0, 'rows_out': 0, 'hq_groups': 0,
         'hq_buckets': 0, 'window_form': None, 'merge_form': None,
         'fileset_scans': 0, 'walk_rows': {'columns': 0, 'by_row': 3},
         'device_serving': False, 'fn': None, 'n_shards': 1, 'warnings': [],
         'exhaustive': True, 'error': None, 'trace_id': None,
         'cache': {'postings_misses': 1, 'postings_miss_bytes': 8},
         'device_declines': {'cold_overlay': 1}, 'ts': ...},
        ['datapoints', 'db_lock_wait_s', 'decode_s', 'device_wait_s',
         'fetch_s', 'gc_pause_s', 'n_streams', 'pack_s', 'parse_s',
         'read_bytes']),
    'device_error': (
        {'expr': GROUPED, 'tenant': 'default', 'initiator': 'http',
         'total_s': ..., 'series': 3, 'datapoints': 1440, 'rows': 0,
         'open_rows': 0, 'lanes': 0, 'lanes_pad': 0, 'lane_chunks': 0,
         'n_cap': 0, 'steps_pad': 0, 'rows_per_lane': 0, 'decode_refills': 0,
         'groups': 0, 'topk_k': 0, 'rows_out': 0, 'hq_groups': 0,
         'hq_buckets': 0, 'window_form': None, 'merge_form': None,
         'fileset_scans': 0, 'walk_rows': {'columns': 9, 'by_row': 3},
         'device_serving': False, 'fn': None, 'n_shards': 1, 'warnings': [],
         'exhaustive': True, 'error': None, 'trace_id': None,
         'cache': {'postings_misses': 1, 'postings_miss_bytes': 8},
         'device_declines': {'device_error': 2}, 'ts': ...},
        ['datapoints', 'db_lock_wait_s', 'decode_s', 'device_s',
         'device_wait_s', 'fetch_s', 'gc_pause_s', 'h2d_s', 'n_streams',
         'pack_s', 'parse_s', 'read_bytes']),
    'fused_error': (
        {'expr': TOPK, 'tenant': 'default', 'initiator': 'http',
         'total_s': ..., 'series': 4, 'datapoints': 1440, 'rows': 0,
         'open_rows': 0, 'lanes': 0, 'lanes_pad': 0, 'lane_chunks': 0,
         'n_cap': 0, 'steps_pad': 0, 'rows_per_lane': 0, 'decode_refills': 0,
         'groups': 0, 'topk_k': 0, 'rows_out': 0, 'hq_groups': 0,
         'hq_buckets': 0, 'window_form': None, 'merge_form': None,
         'fileset_scans': 0, 'walk_rows': {'columns': 9, 'by_row': 3},
         'device_serving': False, 'fn': None, 'n_shards': 1, 'warnings': [],
         'exhaustive': True, 'error': None, 'trace_id': None,
         'cache':
          {'postings_misses': 1, 'postings_miss_bytes': 8,
           'device_bridge_misses': 1, 'device_bridge_miss_bytes': 16384},
         'device_declines': {'device_error': 2},
         'device_tier_error': 'RuntimeError: the program is broken',
         'ts': ...},
        ['datapoints', 'db_lock_wait_s', 'decode_s', 'device_s',
         'device_wait_s', 'fetch_s', 'gc_pause_s', 'h2d_s', 'n_streams',
         'pack_s', 'parse_s', 'plan_s', 'read_bytes']),
    'raises': (
        {'expr': 'sum by (dc) (rate(sealed[5m]', 'tenant': 'default',
         'initiator': 'http', 'total_s': ..., 'series': 0, 'datapoints': 0,
         'rows': 0, 'open_rows': 0, 'lanes': 0, 'lanes_pad': 0,
         'lane_chunks': 0, 'n_cap': 0, 'steps_pad': 0, 'rows_per_lane': 0,
         'decode_refills': 0, 'groups': 0, 'topk_k': 0, 'rows_out': 0,
         'hq_groups': 0, 'hq_buckets': 0, 'window_form': None,
         'merge_form': None, 'fileset_scans': 0,
         'walk_rows': {'columns': 0, 'by_row': 0}, 'device_serving': False,
         'fn': None, 'n_shards': 1, 'warnings': [], 'exhaustive': True,
         'error': "ValueError: expected ')', got None", 'trace_id': None,
         'cache': {}, 'ts': ...},
        []),
    'clocked': (
        {'expr': GROUPED, 'tenant': 'default', 'initiator': 'http',
         'total_s': ..., 'series': 3, 'datapoints': 1440, 'rows': 12,
         'open_rows': 0, 'lanes': 12, 'lanes_pad': 64, 'lane_chunks': 1,
         'n_cap': 128, 'steps_pad': 128, 'rows_per_lane': 1,
         'decode_refills': 16, 'groups': 0, 'topk_k': 0, 'rows_out': 0,
         'hq_groups': 0, 'hq_buckets': 0, 'window_form': 'select',
         'merge_form': 'rotate', 'fileset_scans': 0,
         'walk_rows': {'columns': 9, 'by_row': 3}, 'device_serving': True,
         'fn': 'rate', 'n_shards': 1, 'warnings': [], 'exhaustive': True,
         'error': None, 'trace_id': '00000000000000000000000000000029',
         'cache': {'postings_misses': 1, 'postings_miss_bytes': 8},
         'cpu': ..., 'interp_wait_s': ..., 'band_served_pct': 0.0, 'ts': ...},
        ['agg', 'band_served_pct', 'd2h_s', 'datapoints', 'db_lock_wait_s',
         'decode_refills', 'device_grouped', 'device_s', 'device_serving',
         'device_wait_s', 'fetch_s', 'fn', 'gc_pause_s', 'h2d_s',
         'lane_chunks', 'lanes', 'lanes_pad', 'merge_form', 'n_cap',
         'n_groups', 'n_shards', 'n_streams', 'open_rows', 'pack_s',
         'parse_s', 'rows', 'rows_per_lane', 'steps_pad', 'window_form']),
    'graphite': (
        {'expr': "graphite://" + GRAPHITE, 'tenant': 'default',
         'initiator': 'http', 'total_s': ..., 'series': 0, 'datapoints': 360,
         'rows': 3, 'open_rows': 0, 'lanes': 3, 'lanes_pad': 64,
         'lane_chunks': 0, 'n_cap': 128, 'steps_pad': 128, 'rows_per_lane': 1,
         'decode_refills': 16, 'groups': 0, 'topk_k': 0, 'rows_out': 3,
         'hq_groups': 0, 'hq_buckets': 0, 'window_form': None,
         'merge_form': 'rotate', 'fileset_scans': 0,
         'walk_rows': {'columns': 1, 'by_row': 2}, 'device_serving': True,
         'fn': None, 'n_shards': 1, 'warnings': [], 'exhaustive': True,
         'error': None, 'trace_id': None,
         'cache':
          {'postings_misses': 2, 'regexp_misses': 2,
           'postings_miss_bytes': 16, 'regexp_hits': 4,
           'device_bridge_misses': 1, 'device_bridge_miss_bytes': 16384},
         'band_served_pct': 0.0,
         'device_tier':
          {'compile_cache': ..., 'compile_s': ..., 'device_nodes': 2,
           'host_nodes': 1, 'transfer_bytes': 8256, 'n_shards': 1,
           'host_splits': {'graphite_host_fn': 1}},
         'ts': ...},
        ['agg', 'band_served_pct', 'compile_cache', 'compile_s', 'compiled',
         'd2h_s', 'datapoints', 'db_lock_wait_s', 'decode_refills',
         'device_fused', 'device_s', 'device_serving', 'device_wait_s',
         'fetch_s', 'fn', 'fused_nodes', 'gc_pause_s', 'groups', 'hq_buckets',
         'hq_groups', 'lanes', 'lanes_pad', 'merge_form', 'n_cap', 'n_shards',
         'n_streams', 'pack_s', 'parse_s', 'plan_s', 'rows', 'rows_out',
         'rows_per_lane', 'steps_pad', 'topk_k', 'transfer_bytes',
         'window_form']),
    'graphite_host': (
        {'expr': "graphite://" + GRAPHITE, 'tenant': 'default',
         'initiator': 'http', 'total_s': ..., 'series': 0, 'datapoints': 360,
         'rows': 0, 'open_rows': 0, 'lanes': 0, 'lanes_pad': 0,
         'lane_chunks': 0, 'n_cap': 0, 'steps_pad': 0, 'rows_per_lane': 0,
         'decode_refills': 0, 'groups': 0, 'topk_k': 0, 'rows_out': 0,
         'hq_groups': 0, 'hq_buckets': 0, 'window_form': None,
         'merge_form': None, 'fileset_scans': 0,
         'walk_rows': {'columns': 1, 'by_row': 2}, 'device_serving': False,
         'fn': None, 'n_shards': 1, 'warnings': [], 'exhaustive': True,
         'error': None, 'trace_id': None,
         'cache':
          {'postings_misses': 2, 'regexp_hits': 6, 'postings_miss_bytes': 16},
         'ts': ...},
        ['datapoints', 'db_lock_wait_s', 'decode_s', 'device_wait_s',
         'fetch_s', 'gc_pause_s', 'n_streams', 'parse_s', 'read_bytes']),
}


@pytest.mark.parametrize("case", sorted(PIN_CASES))
def test_the_record_is_the_parents_to_the_letter(pin_db, monkeypatch, case):
    """One query of each served shape leaves the record the parent of
    PR 50 left: the same keys in the same order (`phases` aside, whose
    key set _pin_run holds), every value that is no timing, and the same
    keys in the thread's last_fetch_stats."""
    rec, stats_keys = _pin_run(case, pin_db, monkeypatch)
    want, want_stats_keys = PINNED[case]
    assert list(rec) == list(want)
    assert rec == want
    assert stats_keys == want_stats_keys
