"""HTTP API end-to-end: remote-write in, PromQL out — the reference's
docker 'prometheus' integration test shape, in-process
(ref: scripts/docker-integration-tests/prometheus/)."""

import json
import urllib.request

import numpy as np
import pytest

from m3_tpu.query import remote_write
from m3_tpu.query.http import CoordinatorServer
from m3_tpu.storage import Database, DatabaseOptions, NamespaceOptions, RetentionOptions
from m3_tpu.utils import snappy, xtime

SEC = xtime.SECOND
BLOCK = 2 * xtime.HOUR
T0 = (1_600_000_000 * SEC // BLOCK) * BLOCK


@pytest.fixture
def server(tmp_path):
    db = Database(DatabaseOptions(path=str(tmp_path), num_shards=4,
                                  commit_log_enabled=False))
    db.create_namespace(NamespaceOptions(
        name="default", retention=RetentionOptions(block_size=BLOCK)))
    srv = CoordinatorServer(db, port=0).start()
    yield srv
    srv.stop()
    db.close()


def post(srv, path, body, headers=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}{path}", data=body,
        headers=headers or {}, method="POST")
    try:
        with urllib.request.urlopen(req) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def get(srv, path):
    try:
        with urllib.request.urlopen(f"http://127.0.0.1:{srv.port}{path}") as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def write_series(srv, name, host, n=60, start=T0, step_s=10, base=0.0, inc=1.0):
    labels = {b"__name__": name, b"host": host}
    samples = [((start + (i + 1) * step_s * SEC) // 1_000_000, base + i * inc)
               for i in range(n)]
    payload = snappy.compress(remote_write.encode_write_request([(labels, samples)]))
    code, body = post(srv, "/api/v1/prom/remote/write", payload,
                      {"Content-Encoding": "snappy"})
    assert code == 200, body
    return samples


def test_health(server):
    code, body = get(server, "/health")
    assert code == 200 and body["ok"]


def test_remote_write_and_query_range(server):
    write_series(server, b"http_requests", b"a", n=120, inc=5.0)
    write_series(server, b"http_requests", b"b", n=120, inc=10.0)
    start = (T0 + 10 * 60 * SEC) / 1e9
    end = (T0 + 15 * 60 * SEC) / 1e9
    code, body = get(
        server,
        f"/api/v1/query_range?query=rate(http_requests%5B5m%5D)"
        f"&start={start}&end={end}&step=60",
    )
    assert code == 200, body
    result = body["data"]["result"]
    assert len(result) == 2
    rates = {r["metric"]["host"]: float(r["values"][0][1]) for r in result}
    assert rates["a"] == pytest.approx(0.5, rel=1e-6)
    assert rates["b"] == pytest.approx(1.0, rel=1e-6)


def test_query_instant_and_aggregation(server):
    write_series(server, b"mem", b"x", n=30, base=100.0, inc=0.0)
    write_series(server, b"mem", b"y", n=30, base=200.0, inc=0.0)
    t = (T0 + 5 * 60 * SEC) / 1e9
    code, body = get(server, f"/api/v1/query?query=sum(mem)&time={t}")
    assert code == 200
    vec = body["data"]["result"]
    assert len(vec) == 1
    assert float(vec[0]["value"][1]) == 300.0


def test_labels_and_series(server):
    write_series(server, b"cpu", b"h1")
    write_series(server, b"cpu", b"h2")
    code, body = get(server, "/api/v1/labels")
    assert "host" in body["data"] and "__name__" in body["data"]
    code, body = get(server, "/api/v1/label/host/values")
    assert body["data"] == ["h1", "h2"]
    code, body = get(server, "/api/v1/series?match%5B%5D=cpu%7Bhost%3D%22h1%22%7D")
    assert body["data"] == [{"__name__": "cpu", "host": "h1"}]


def test_bad_requests(server):
    code, body = get(server, "/api/v1/query_range?query=up")
    assert code == 400 and "missing parameter" in body["error"]
    code, body = get(server,
                     "/api/v1/query_range?query=rate(up)&start=1&end=2&step=1")
    assert code == 400 and "range vector" in body["error"]
    code, body = post(server, "/api/v1/prom/remote/write", b"\xff\xfe garbage",
                      {"Content-Encoding": "snappy"})
    assert code == 400
    code, body = get(server, "/api/v1/nope")
    assert code == 404


def test_remote_write_cold_rejection_is_400(tmp_path):
    """Out-of-window samples with cold_writes_enabled=False must map to
    400 (bad input) on the remote-write path, never 500 — Prometheus
    retries 5xx forever, wedging its WAL on a permanently-stale sample.
    Covers both the plain-db and the DownsamplerAndWriter wiring
    (advisor r4: the dsw path returned 500)."""
    import time as _time

    from m3_tpu.coordinator.downsample import DownsamplerAndWriter

    db = Database(DatabaseOptions(path=str(tmp_path), num_shards=4,
                                  commit_log_enabled=False))
    db.create_namespace(NamespaceOptions(
        name="default", cold_writes_enabled=False,
        retention=RetentionOptions(block_size=BLOCK)))
    now_ms = _time.time_ns() // 1_000_000
    stale_ms = now_ms - 8 * 3600 * 1000
    labels = {b"__name__": b"m", b"host": b"a"}

    def stale_write(srv):
        payload = snappy.compress(remote_write.encode_write_request(
            [(labels, [(stale_ms, 1.0)])]))
        return post(srv, "/api/v1/prom/remote/write", payload,
                    {"Content-Encoding": "snappy"})

    srv = CoordinatorServer(db, port=0).start()
    try:
        code, body = stale_write(srv)
        assert code == 400 and "cold write rejected" in body["error"]
    finally:
        srv.stop()
    dsw = DownsamplerAndWriter(db, "default")
    srv = CoordinatorServer(db, port=0, downsampler_writer=dsw).start()
    try:
        code, body = stale_write(srv)
        assert code == 400 and "cold write rejected" in body["error"]
        # in-window samples still work through the same wiring
        payload = snappy.compress(remote_write.encode_write_request(
            [(labels, [(now_ms - 60_000, 1.0)])]))
        code, _ = post(srv, "/api/v1/prom/remote/write", payload,
                       {"Content-Encoding": "snappy"})
        assert code == 200
    finally:
        srv.stop()
        db.close()


def test_remote_write_series_limit_is_429(tmp_path):
    """A transient new-series rate limit must map to 429 (retryable),
    not 400 — a 400 makes Prometheus drop a batch that would succeed
    one second later (code-review r5 finding)."""
    from m3_tpu.cluster.runtime import RuntimeOptions

    db = Database(DatabaseOptions(path=str(tmp_path), num_shards=4,
                                  commit_log_enabled=False))
    db.create_namespace(NamespaceOptions(
        name="default", retention=RetentionOptions(block_size=BLOCK)))
    db.set_runtime_options(RuntimeOptions(write_new_series_limit_per_sec=1))
    srv = CoordinatorServer(db, port=0).start()
    try:
        import time as _time
        now_ms = _time.time_ns() // 1_000_000
        samples = [(now_ms - 60_000, 1.0)]
        payload = snappy.compress(remote_write.encode_write_request(
            [({b"__name__": b"m", b"host": b"h%d" % i}, samples)
             for i in range(5)]))
        code, body = post(srv, "/api/v1/prom/remote/write", payload,
                          {"Content-Encoding": "snappy"})
        assert code == 429 and "insert limit" in body["error"]
    finally:
        srv.stop()
        db.close()


def test_cold_write_error_is_structured(tmp_path):
    """ColdWriteError carries rejected indices + written count (the
    reference's per-sample RWError analog)."""
    import time as _time

    from m3_tpu.storage.database import ColdWriteError

    db = Database(DatabaseOptions(path=str(tmp_path), num_shards=2,
                                  commit_log_enabled=False))
    db.create_namespace(NamespaceOptions(
        name="warm", cold_writes_enabled=False,
        retention=RetentionOptions(block_size=BLOCK)))
    now = _time.time_ns()
    tags = {b"__name__": b"m"}
    with pytest.raises(ColdWriteError) as ei:
        db.write_batch("warm", [b"a", b"b", b"c"], [tags] * 3,
                       [now - 8 * xtime.HOUR, now - 2 * xtime.MINUTE,
                        now - 9 * xtime.HOUR],
                       [1.0, 2.0, 3.0])
    assert ei.value.rejected_indices == [0, 2]
    assert ei.value.n_written == 1
    db.close()


def test_snappy_roundtrip_and_golden():
    data = b"hello hello hello hello xyz" * 10 + b"tail"
    assert snappy.decompress(snappy.compress(data)) == data
    assert snappy.decompress(snappy.compress(b"")) == b""
    # literal-only frame from the spec: preamble varint + literal tag
    assert snappy.decompress(b"\x05\x10abcde"[:7]) == b"abcde"
    with pytest.raises(ValueError):
        snappy.decompress(b"\x05\x10ab")  # truncated


def test_write_request_codec_roundtrip():
    series = [
        ({b"__name__": b"a", b"x": b"1"}, [(1000, 1.5), (2000, -2.5)]),
        ({b"__name__": b"b"}, [(3000, float("nan"))]),
    ]
    blob = remote_write.encode_write_request(series)
    out = remote_write.decode_write_request(blob)
    assert out[0][0] == series[0][0]
    assert out[0][1] == series[0][1]
    assert out[1][1][0][0] == 3000 and np.isnan(out[1][1][0][1])


def test_graphite_render_max_datapoints(tmp_path):
    """Grafana sends maxDataPoints; the render handler must derive the
    step from it (ceil(range/points) aligned up to the 10s storage
    resolution), not read an invented parameter."""
    from m3_tpu.coordinator.carbon import graphite_tags
    from m3_tpu.query.remote_write import series_id_from_labels

    db = Database(DatabaseOptions(path=str(tmp_path), num_shards=4,
                                  commit_log_enabled=False))
    db.create_namespace(NamespaceOptions(
        name="default", retention=RetentionOptions(block_size=BLOCK)))
    server = CoordinatorServer(db, port=0).start()

    labels = dict(graphite_tags(b"foo.bar"))
    labels[b"__name__"] = b"foo.bar"
    sid = series_id_from_labels(labels)
    ts = [T0 + (i + 1) * 10 * SEC for i in range(360)]
    db.write_batch("default", [sid] * len(ts), [labels] * len(ts),
                   ts, [float(i) for i in range(len(ts))])

    frm, until = T0 // SEC, (T0 + 3600 * SEC) // SEC
    code, body = get(server,
                     f"/render?target=foo.bar&from={frm}&until={until}"
                     f"&maxDataPoints=100")
    assert code == 200
    assert len(body) == 1 and body[0]["target"] == "foo.bar"
    # 3600s / 100 pts = 36s -> aligned up to 40s -> 90 datapoints
    assert len(body[0]["datapoints"]) == 90
    assert 0 < len(body[0]["datapoints"]) <= 100

    # explicit step param still honored as an extension
    code, body = get(server,
                     f"/render?target=foo.bar&from={frm}&until={until}"
                     f"&step=60")
    assert code == 200
    assert len(body[0]["datapoints"]) == 60
    server.stop()
    db.close()


def test_prom_remote_read(server):
    """Remote READ: snappy+protobuf query -> raw samples back
    (ref: api/v1/handler/prometheus/remote/read.go)."""
    from m3_tpu.query import remote_write as rw

    write_series(server, b"temp", b"h0", n=60, base=20.0, inc=0.0)
    write_series(server, b"temp", b"h1", n=60, base=30.0, inc=0.0)
    # encode a ReadRequest with the same varint helpers
    m = (rw._field(1, 0) + rw._uvarint(0) +  # EQ
         rw._len_delim(2, b"__name__") + rw._len_delim(3, b"temp"))
    q = (rw._field(1, 0) + rw._uvarint(T0 // 10**6) +
         rw._field(2, 0) + rw._uvarint((T0 + 3600 * SEC) // 10**6) +
         rw._len_delim(3, m))
    body = snappy.compress(rw._len_delim(1, q))
    req = urllib.request.Request(
        f"http://127.0.0.1:{server.port}/api/v1/prom/remote/read",
        data=body, method="POST", headers={"Content-Encoding": "snappy"})
    with urllib.request.urlopen(req) as resp:
        assert resp.status == 200
        assert resp.headers["Content-Type"] == "application/x-protobuf"
        payload = snappy.decompress(resp.read())
    results = rw.decode_read_response(payload)
    assert len(results) == 1
    series = sorted(results[0], key=lambda s: s[0][b"host"])
    assert len(series) == 2
    assert series[0][0][b"host"] == b"h0"
    assert len(series[0][1]) == 60
    assert series[0][1][0] == ((T0 + 10 * SEC) // 10**6, 20.0)
    assert series[1][1][0][1] == 30.0


def test_json_write_and_search(server):
    """ref: src/query/api/v1/handler/json/write.go + search.go."""
    body = json.dumps({
        "tags": {"__name__": "jm", "host": "a"},
        "timestamp": str((T0 + 10 * SEC) / 1e9),
        "value": 42.5,
    }).encode()
    code, out = post(server, "/api/v1/json/write", body)
    assert code == 200, out
    code, out = post(server, "/search", json.dumps({
        "start": T0 / 1e9, "end": (T0 + 100 * SEC) / 1e9,
        "matchers": [["eq", "__name__", "jm"]],
    }).encode())
    assert code == 200, out
    assert out["results"] == [{"__name__": "jm", "host": "a"}]
    # the sample serves through PromQL
    code, out = get(server,
                    f"/api/v1/query_range?query=jm&start={(T0+10*SEC)/1e9}"
                    f"&end={(T0+60*SEC)/1e9}&step=30s")
    assert code == 200
    vals = out["data"]["result"][0]["values"]
    assert float(vals[0][1]) == 42.5
    # malformed bodies 400
    assert post(server, "/api/v1/json/write", b"{}")[0] == 400
    assert post(server, "/search", b"{}")[0] == 400


def test_ctl_ui_and_server_generated_rule_ids(tmp_path):
    """GET /ctl serves the operator console (ref: src/ctl/ui/), and
    rule creation without an id gets a server-generated one like the
    r2 service — then lists, hot-applies, and deletes through the same
    APIs the console calls."""
    from m3_tpu.cluster.kv import MemStore
    from m3_tpu.query.http import CoordinatorServer

    db = Database(DatabaseOptions(path=str(tmp_path), num_shards=4,
                                  commit_log_enabled=False))
    db.create_namespace(NamespaceOptions(
        name="default", retention=RetentionOptions(block_size=BLOCK)))
    srv = CoordinatorServer(db, port=0, kv_store=MemStore()).start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/ctl")
        with urllib.request.urlopen(req) as resp:
            page = resp.read()
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/html")
        assert b"m3_tpu console" in page and b"/api/v1/rules" in page

        code, out = post(srv, "/api/v1/rules", json.dumps({
            "mapping_rule": {"name": "ui-rule", "filter": "app:web*",
                             "aggregations": [7],
                             "storage_policies": ["10s:2d"]},
        }).encode())
        assert code == 200, out
        rid = out["rules"]["mapping_rules"][0]["id"]
        assert rid.startswith("mr-") and len(rid) > 5

        code, out = post(srv, "/api/v1/rules", json.dumps({
            "rollup_rule": {"name": "ui-roll", "filter": "app:web*",
                            "targets": [{
                                "pipeline": [{"t": 3, "n": "web_total",
                                              "g": ["dc"], "i": [7]}],
                                "storage_policies": ["1m:40d"]}]},
        }).encode())
        assert code == 200, out
        rrid = out["rules"]["rollup_rules"][0]["id"]
        assert rrid.startswith("rr-")

        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/api/v1/rules/{rid}",
            method="DELETE")
        with urllib.request.urlopen(req) as resp:
            out = json.loads(resp.read())
        assert out["rules"]["mapping_rules"] == []
        assert len(out["rules"]["rollup_rules"]) == 1
    finally:
        srv.stop()
        db.close()


def test_serving_mesh_env_end_to_end(tmp_path, monkeypatch):
    """M3_SERVING_MESH=<n> + M3_DEVICE_SERVING=1: the coordinator's
    engine routes queries through the shard_map'd device pipelines on
    an n-device series mesh; results over HTTP must match a host-tier
    coordinator on the same flushed data."""
    import jax

    if jax.device_count() < 8:
        pytest.skip("needs the virtual 8-device mesh")
    db = Database(DatabaseOptions(path=str(tmp_path), num_shards=4,
                                  commit_log_enabled=False))
    db.create_namespace(NamespaceOptions(
        name="default", retention=RetentionOptions(block_size=BLOCK)))
    rng = np.random.default_rng(83)
    for i in range(20):
        sid = b"mm|h%02d" % i
        tags = {b"__name__": b"mm", b"host": b"h%02d" % i,
                b"dc": b"dc%d" % (i % 2)}
        n = int(rng.integers(30, 120))
        ts = [T0 + (k + 1) * int(rng.integers(1, 3)) * 10 * SEC
              for k in range(n)]
        vs = np.cumsum(rng.random(n) * 4).tolist()
        db.write_batch("default", [sid] * n, [tags] * n, ts, vs)
    db.tick(now_nanos=T0 + 2 * BLOCK)
    db.flush()

    monkeypatch.setenv("M3_DEVICE_SERVING", "1")
    monkeypatch.setenv("M3_SERVING_MESH", "8")
    mesh_srv = CoordinatorServer(db, port=0).start()
    monkeypatch.setenv("M3_DEVICE_SERVING", "0")
    monkeypatch.delenv("M3_SERVING_MESH")
    host_srv = CoordinatorServer(db, port=0).start()
    try:
        start, end = T0 + 10 * 60 * SEC, T0 + 60 * 60 * SEC
        for q in ("rate(mm[5m])", "sum by (dc) (rate(mm[10m]))",
                  "max_over_time(mm[7m])", "mm"):
            import urllib.parse
            qs = urllib.parse.urlencode(
                {"query": q, "start": start / 1e9, "end": end / 1e9,
                 "step": 60})
            c1, b1 = get(mesh_srv, f"/api/v1/query_range?{qs}")
            c2, b2 = get(host_srv, f"/api/v1/query_range?{qs}")
            assert c1 == c2 == 200, (q, c1, c2)
            r1, r2 = b1["data"]["result"], b2["data"]["result"]
            assert [s["metric"] for s in r1] == \
                [s["metric"] for s in r2], q
            # tiers agree up to f64 associativity (different reduction
            # orders), so compare parsed floats, not rendered strings
            for s1, s2 in zip(r1, r2):
                v1 = np.array([float(v) for _, v in s1["values"]])
                v2 = np.array([float(v) for _, v in s2["values"]])
                t1 = [t for t, _ in s1["values"]]
                t2 = [t for t, _ in s2["values"]]
                assert t1 == t2, q
                np.testing.assert_allclose(v1, v2, rtol=1e-12,
                                           atol=1e-12, err_msg=q)
        # the mesh engine actually served on-device (its stats are the
        # calling thread's own, so ask it from this one)
        eng = mesh_srv.httpd.RequestHandlerClass.engine
        eng.query_range("rate(mm[5m])", start, end, 60 * SEC)
        st = eng.last_fetch_stats
        assert st and st.get("device_serving") is True
        assert st.get("n_shards") == 8
    finally:
        mesh_srv.stop()
        host_srv.stop()
        db.close()

    # a mesh alone is accepted: auto mode resolves the tier per query
    # from the backend (host here, the suite runs on the CPU backend)
    monkeypatch.setenv("M3_SERVING_MESH", "8")
    monkeypatch.delenv("M3_DEVICE_SERVING")
    srv = CoordinatorServer(db, port=0)
    try:
        eng = srv.httpd.RequestHandlerClass.engine
        assert eng.serving_mesh is not None
        assert eng._device_serving_active() is False
    finally:
        srv.stop()
