"""A range query's reply is rendered outside the interpreter
(native/json_wire.cc) to the bytes `json.dumps` gives `_matrix_json`'s
document: the parent's renderer stays here as the reference, and as
what a machine without a compiler serves."""

import json
import time
import urllib.parse
import urllib.request

import numpy as np
import pytest

from m3_tpu.query import http, slowlog
from m3_tpu.query.engine import Matrix
from m3_tpu.query.http import (CoordinatorServer, _matrix_json,
                               _matrix_reply)
from m3_tpu.storage import (Database, DatabaseOptions, NamespaceOptions,
                            RetentionOptions)
from m3_tpu.storage.limits import QueryLimits
from m3_tpu.utils import instrument, native, xtime

SEC = xtime.SECOND
BLOCK = 2 * xtime.HOUR
T0 = (1_600_000_000 * SEC // BLOCK) * BLOCK
NAN, INF = float("nan"), float("inf")


def _reference(step_times, mat, warnings=None) -> bytes:
    body = {"status": "success", "data": _matrix_json(step_times, mat)}
    if warnings is not None:
        body["warnings"] = warnings
    return json.dumps(body).encode()


def _steps(n: int, start: int = T0, step: int = 60 * SEC) -> np.ndarray:
    return start + np.arange(n, dtype=np.int64) * step


def _labels(n: int) -> list[dict]:
    return [{b"__name__": b"up", b"instance": b"i%03d" % i}
            for i in range(n)]


def _random_bits(seed: int) -> np.ndarray:
    bits = np.random.default_rng(seed).integers(
        0, 2**64, size=(8, 2_000), dtype=np.uint64)
    return bits.view(np.float64)


def _powers_of_ten() -> np.ndarray:
    vals = [float(f"{m}e{e}") for e in range(-30, 30)
            for m in (1, 2, 9, 15, 123456789, -7)]
    return np.array(vals).reshape(6, -1)


_BOUNDARIES = [
    # where repr goes from fixed to the exponent's form, both sides
    1e16, 9999999999999998.0, 1e15, 123456789012345680.0, 1e-4,
    9.999999999999999e-05, 1e-5, 0.00011, -1e16, -1e-5,
    # whole numbers get their ".0"; 2**53 and past it
    1.0, -1.0, 100.0, 9007199254740992.0, 9007199254740994.0,
    # zeros, subnormals, the ends of the range
    0.0, -0.0, 5e-324, -5e-324, 2.2250738585072014e-308,
    2.225073858507201e-308, 1.7976931348623157e308,
    -1.7976931348623157e308, INF, -INF,
    0.1, 1 / 3, 2 / 3, 1e22, 1e23, 5e-5, 123.456, 0.001,
]

_VALUE_CASES = {
    "random_bits_0": lambda: _random_bits(0),
    "random_bits_1": lambda: _random_bits(1),
    "random_bits_2": lambda: _random_bits(2),
    "uniform_0_1000": lambda: np.random.default_rng(3).random(
        (100, 240)) * 1000,
    "whole_numbers": lambda: np.floor(
        np.random.default_rng(4).random((10, 500)) * 1e6),
    "powers_of_ten": _powers_of_ten,
    "boundaries": lambda: np.array([_BOUNDARIES, _BOUNDARIES[::-1]]),
    "nan_at_a_rows_start": lambda: np.array([[NAN, 1.0, 2.0]]),
    "nan_at_a_rows_end": lambda: np.array([[1.0, 2.0, NAN]]),
    "nan_inside_a_row": lambda: np.array([[1.0, NAN, NAN, 2.0]]),
    "a_whole_row_nan_first": lambda: np.array(
        [[NAN, NAN], [1.0, 2.0], [3.0, NAN]]),
    "a_whole_row_nan_middle": lambda: np.array(
        [[1.0, 2.0], [NAN, NAN], [3.0, 4.0]]),
    "a_whole_row_nan_last": lambda: np.array(
        [[1.0, 2.0], [3.0, 4.0], [NAN, NAN]]),
    "every_row_nan": lambda: np.full((3, 4), NAN),
    "one_step": lambda: np.array([[1.5], [NAN], [-INF]]),
    "zero_rows": lambda: np.zeros((0, 5)),
    "zero_steps": lambda: np.zeros((2, 0)),
    "float32_values": lambda: np.random.default_rng(5).random(
        (3, 50)).astype(np.float32),
    "a_strided_view": lambda: np.random.default_rng(6).random(
        (40, 6)).T[:, ::2],
}


@pytest.mark.parametrize("case", sorted(_VALUE_CASES))
def test_native_reply_is_json_dumps_byte_for_byte(case):
    values = _VALUE_CASES[case]()
    mat = Matrix(_labels(values.shape[0]), values)
    steps = _steps(values.shape[1])
    payload, form = _matrix_reply(steps, mat)
    assert form == "native"
    assert payload == _reference(steps, mat)
    if case == "every_row_nan" or case == "zero_rows":
        assert json.loads(payload)["data"]["result"] == []


_STEP_CASES = {
    # int64 -> double rounds past 2**53, and the division after it
    "past_2_53_ns": _steps(50, start=2**53 - 20, step=1),
    "odd_nanos": _steps(200, start=T0 + 1, step=999_999_937),
    "around_zero": _steps(41, start=-20 * SEC, step=SEC),
    "the_int64_range": np.array([-2**63, -2**63 + 1, -1, 0, 1,
                                 2**63 - 1], dtype=np.int64),
    "random_int64": np.random.default_rng(7).integers(
        -2**63, 2**63 - 1, size=3_000, dtype=np.int64),
    "millisecond_steps": _steps(500, start=1_700_000_000_123_000_000,
                                step=1_000_000),
}


@pytest.mark.parametrize("case", sorted(_STEP_CASES))
def test_a_steps_seconds_are_reprs_of_nanos_over_1e9(case):
    steps = _STEP_CASES[case]
    values = np.arange(2.0 * len(steps)).reshape(2, len(steps))
    mat = Matrix(_labels(2), values)
    assert _matrix_reply(steps, mat) == (_reference(steps, mat), "native")


@pytest.mark.parametrize("labels", [
    [{}],
    [{b"zone": "zürich".encode(), b"host": "\u65e5\u672c".encode()}],
    [{b"q": b'say "hi"', b"path": b"C:\\temp\\x", b"ctl": b"a\tb\nc\x01"}],
    [{"名".encode(): b"v", b"emoji": "\U0001f600".encode()}, {b"a": b""}],
], ids=["no_labels", "non_ascii", "quotes_and_escapes", "astral_and_empty"])
def test_label_values_keep_json_dumps_escaping(labels):
    values = np.arange(3.0 * len(labels)).reshape(len(labels), 3)
    mat, steps = Matrix(labels, values), _steps(3)
    payload, form = _matrix_reply(steps, mat)
    assert (payload, form) == (_reference(steps, mat), "native")
    assert [r["metric"] for r in json.loads(payload)["data"]["result"]] == [
        {k.decode(): v.decode() for k, v in ls.items()} for ls in labels]


@pytest.mark.parametrize("warnings", [
    [], ["fetch degraded: node2 down"],
    ['max_fetched_series "3" reached', "zürich: \u2026"],
], ids=["empty", "one", "quoted_and_non_ascii"])
def test_a_degraded_replys_warnings_follow_the_data(warnings):
    values = np.array([[1.0, NAN, 2.5], [NAN, NAN, NAN]])
    mat, steps = Matrix(_labels(2), values), _steps(3)
    payload, form = _matrix_reply(steps, mat, warnings)
    assert (payload, form) == (_reference(steps, mat, warnings), "native")
    assert json.loads(payload)["warnings"] == warnings


def test_values_that_are_no_rows_by_steps_block_go_to_python():
    # zip() in _matrix_json stops at the shorter side: the library
    # reads a [rows, steps] block and is not handed anything else
    mat, steps = Matrix(_labels(2), np.arange(12.0).reshape(3, 4)), _steps(3)
    payload, form = _matrix_reply(steps, mat)
    assert (payload, form) == (_reference(steps, mat), "python")


def _no_compiler(monkeypatch):
    def load(name):
        raise FileNotFoundError(2, "No such file or directory: 'g++'")
    monkeypatch.setattr(native, "load", load)


@pytest.mark.parametrize("case", ["boundaries", "a_whole_row_nan_middle",
                                  "every_row_nan", "uniform_0_1000"])
def test_without_a_compiler_python_renders_the_same_bytes(case, monkeypatch):
    values = _VALUE_CASES[case]()
    mat = Matrix(_labels(values.shape[0]), values)
    steps = _steps(values.shape[1])
    native_payload, _ = _matrix_reply(steps, mat, ["w"])
    _no_compiler(monkeypatch)
    payload, form = _matrix_reply(steps, mat, ["w"])
    assert form == "python"
    assert payload == native_payload == _reference(steps, mat, ["w"])


def test_a_failed_build_is_a_python_render_too(monkeypatch):
    import subprocess

    def load(name):
        raise subprocess.CalledProcessError(1, ["g++"])
    monkeypatch.setattr(native, "load", load)
    mat, steps = Matrix(_labels(1), np.array([[1.0]])), _steps(1)
    assert _matrix_reply(steps, mat) == (_reference(steps, mat), "python")


def test_the_buffer_bound_holds_the_longest_points():
    # every point 24 + 24 characters, every label long
    steps = np.full(64, -2**63 + 12_345, dtype=np.int64)
    values = np.full((5, 64), -1.2345678901234567e-300)
    labels = [{b"k%d" % i: b"v" * 300} for i in range(5)]
    mat = Matrix(labels, values)
    assert _matrix_reply(steps, mat) == (_reference(steps, mat), "native")
    lib = native.load("json_wire")
    metrics, offsets = native.blob_offsets([b"{}"] * 5)
    out = np.empty(1_000, dtype=np.uint8)
    # a buffer the reply may not fit is refused, not overrun
    assert lib.matrix_json_render(
        b"", 0, steps, 64, values, 5, metrics, offsets, b"", 0, out,
        len(out)) == -1


# ---- through the server ------------------------------------------------


@pytest.fixture
def db(tmp_path):
    db = Database(DatabaseOptions(path=str(tmp_path), num_shards=4,
                                  commit_log_enabled=False))
    db.create_namespace(NamespaceOptions(
        name="default", retention=RetentionOptions(block_size=BLOCK)))
    n = 90
    for i in range(6):
        tags = {b"__name__": b"reqs", b"host": b"h%02d" % i,
                b"zone": "zürich".encode() if i % 2 else b'"a"'}
        ts = [T0 + (k + 1) * 30 * SEC for k in range(n)]
        # host 5 stops early: NaN at its row's end under a 5m range
        vs = np.cumsum(np.full(n, 1.0 + i / 3))[:n if i < 5 else 30]
        db.write_batch("default", [b"reqs|h%02d" % i] * len(vs),
                       [tags] * len(vs), ts[:len(vs)], vs.tolist())
    yield db
    db.close()


START, END, STEP = T0 + 5 * 60 * SEC, T0 + 50 * 60 * SEC, 60 * SEC


def _get(srv, route: str, expr: str, headers=None):
    q = urllib.parse.urlencode({"query": expr, "start": START / 1e9,
                                "end": END / 1e9, "step": "60"})
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}{route}?{q}", headers=headers or {})
    with urllib.request.urlopen(req) as r:
        return r.read(), dict(r.headers)


def _record_of(expr: str) -> dict:
    """The query's record once the handler has amended it (just after
    the reply's last byte)."""
    deadline = time.monotonic() + 10
    while True:
        rec = next(r for r in slowlog.log().records() if r["expr"] == expr)
        if rec["phases"]["frontend_s"] > 0.0 or time.monotonic() > deadline:
            return rec
        time.sleep(0.01)


def _renders(form: str) -> float:
    return instrument.counter("m3_http_reply_render_total", form=form).value


@pytest.mark.parametrize("form", ["native", "python"])
def test_query_range_sends_the_reference_bytes(db, monkeypatch, form):
    if form == "python":
        _no_compiler(monkeypatch)
    srv = CoordinatorServer(db, port=0).start()
    try:
        expr = "rate(reqs[5m])" if form == "native" else "rate(reqs[4m])"
        before = {f: _renders(f) for f in ("native", "python")}
        payload, _ = _get(srv, "/api/v1/query_range", expr)
        rec = _record_of(expr)
        engine = srv.httpd.RequestHandlerClass.engine
        steps, mat = engine.query_range(expr, START, END, STEP)
        assert payload == _reference(steps, mat)
        rows = json.loads(payload)["data"]["result"]
        assert len(rows) == 6 and len(rows[5]["values"]) < len(
            rows[0]["values"])
        assert rec["reply_native_pct"] == (100.0 if form == "native"
                                           else 0.0)
        ph = rec["phases"]
        assert 0.0 < ph["render_s"] < ph["frontend_s"]
        other = "python" if form == "native" else "native"
        assert _renders(form) == before[form] + 1
        assert _renders(other) == before[other]
    finally:
        srv.stop()


def test_a_clocked_query_clocks_its_render(db):
    srv = CoordinatorServer(db, port=0).start()
    try:
        expr = "sum by (zone) (rate(reqs[7m]))"
        _get(srv, "/api/v1/query_range", expr, headers={
            "traceparent": f"00-{41:032x}-{43:016x}-01"})
        rec = _record_of(expr)
        assert set(rec["cpu"]) == set(rec["phases"]) - {
            "db_lock_wait_s", "device_wait_s", "gc_pause_s"}
        assert 0.0 <= rec["cpu"]["render_s"] <= rec["cpu"]["frontend_s"]
        from m3_tpu.utils import tracing
        span = next(s for s in tracing.tracer().finished()
                    if s["name"] == tracing.HTTP_RENDER
                    and s["trace_id"] == f"{41:032x}")
        assert "cpu_ms" in span["tags"]
    finally:
        srv.stop()


def test_a_limited_reply_keeps_warnings_and_header(db):
    srv = CoordinatorServer(
        db, port=0, query_limits=QueryLimits(max_fetched_series=3)).start()
    try:
        expr = "rate(reqs[6m])"
        payload, headers = _get(srv, "/api/v1/query_range", expr)
        doc = json.loads(payload)
        assert len(doc["data"]["result"]) == 3 and doc["warnings"]
        assert headers["M3-Results-Limited"]
        # json.dumps of the parsed document is the reply: the parent's
        # separators and key order, warnings after the data
        assert json.dumps(doc).encode() == payload
        assert list(doc) == ["status", "data", "warnings"]
        assert _record_of(expr)["reply_native_pct"] == 100.0
    finally:
        srv.stop()


def test_m3ql_takes_the_same_render(db):
    srv = CoordinatorServer(db, port=0).start()
    try:
        before = _renders("native")
        payload, _ = _get(srv, "/api/v1/m3ql", "fetch name:reqs")
        doc = json.loads(payload)
        assert len(doc["data"]["result"]) == 6
        assert json.dumps(doc).encode() == payload
        assert _renders("native") == before + 1
    finally:
        srv.stop()


def test_an_instant_query_renders_no_matrix(db):
    srv = CoordinatorServer(db, port=0).start()
    try:
        expr = "sum(reqs)"
        q = urllib.parse.urlencode({"query": expr,
                                    "time": (T0 + 20 * 60 * SEC) / 1e9})
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/api/v1/query?{q}") as r:
            assert json.loads(r.read())["data"]["resultType"] == "vector"
        rec = _record_of(expr)
        assert rec["phases"]["render_s"] == 0.0
        assert "reply_native_pct" not in rec
    finally:
        srv.stop()


def test_the_render_leaves_the_interpreter_to_other_threads():
    """While one thread renders a large reply, another's pure-Python
    loop goes on: ctypes has dropped the interpreter lock."""
    import sys
    import threading

    values = np.random.default_rng(8).random((400, 2_000)) * 1000
    mat, steps = Matrix(_labels(400), values), _steps(2_000)
    ticks, stop = [0], threading.Event()

    def spin():
        while not stop.is_set():
            ticks[0] += 1

    old = sys.getswitchinterval()
    # a render that held the lock would hold it across switch requests
    # only between bytecodes; one long C call lets none through
    sys.setswitchinterval(1e-4)
    t = threading.Thread(target=spin)
    t.start()
    try:
        while ticks[0] == 0:
            time.sleep(0.001)
        seen = []
        for _ in range(3):
            at = ticks[0]
            _, form = _matrix_reply(steps, mat)
            seen.append(ticks[0] - at)
        assert form == "native"
    finally:
        stop.set()
        t.join()
        sys.setswitchinterval(old)
    # 800,000 points are tens of milliseconds of native work
    assert max(seen) > 1_000, seen


def test_http_module_keeps_the_parents_renderer():
    # the reference of every test above, and the fallback's source
    assert http._matrix_json is _matrix_json
    doc = _matrix_json(_steps(2), Matrix(_labels(1),
                                         np.array([[1.0, NAN]])))
    assert doc == {"resultType": "matrix", "result": [{
        "metric": {"__name__": "up", "instance": "i000"},
        "values": [[T0 / 1e9, "1.0"]]}]}
