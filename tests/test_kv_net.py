"""Networked control plane: KV over TCP + coordinator admin APIs +
a three-role multi-process deployment sharing state over sockets only
(ref: src/cluster/kv/etcd/store.go, src/query/api/v1/handler/
{database,namespace,placement,topic}/)."""

import json
import urllib.parse
import os
import pathlib
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from m3_tpu.cluster.kv import (ErrAlreadyExists, ErrNotFound,
                               ErrVersionMismatch, MemStore)
from m3_tpu.cluster.kv_net import KVClient, KVServer


@pytest.fixture
def kv():
    srv = KVServer(MemStore()).start()
    client = KVClient(srv.endpoint)
    yield srv, client
    client.close()
    srv.stop()


def test_kv_roundtrip_over_sockets(kv):
    _, c = kv
    assert c.set("k", b"\x00binary\xff") == 1
    v = c.get("k")
    assert v.data == b"\x00binary\xff" and v.version == 1
    assert c.set("k", b"v2") == 2
    assert c.history("k", 1, 3)[0].data == b"\x00binary\xff"
    with pytest.raises(ErrAlreadyExists):
        c.set_if_not_exists("k", b"x")
    with pytest.raises(ErrVersionMismatch):
        c.check_and_set("k", 7, b"x")
    assert c.check_and_set("k", 2, b"v3") == 3
    assert c.delete("k").data == b"v3"
    with pytest.raises(ErrNotFound):
        c.get("k")


def test_kv_watch_long_poll(kv):
    srv, c = kv
    w = c.watch("topic")
    got = []

    def waiter():
        got.append(w.wait_for_update(timeout=5.0))

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.2)
    c.set("topic", b"v1")
    t.join(timeout=5)
    assert got and got[0].data == b"v1"
    # second update seen from the same watch
    c.set("topic", b"v2")
    v = w.wait_for_update(timeout=5.0)
    assert v.data == b"v2" and v.version == 2


def test_election_and_placement_over_network_kv(kv):
    """The full control-plane consumer stack rides the socket store."""
    from m3_tpu.cluster.election import LeaderService
    from m3_tpu.cluster.placement import Instance
    from m3_tpu.cluster.service import PlacementService

    srv, _ = kv
    c1, c2 = KVClient(srv.endpoint), KVClient(srv.endpoint)
    e1 = LeaderService(c1, "svc", "i1", ttl_seconds=0.5)
    e2 = LeaderService(c2, "svc", "i2", ttl_seconds=0.5)
    assert e1.campaign() and not e2.campaign()
    assert e1.is_leader() and not e2.is_leader()
    e1.resign()
    assert e2.campaign(block=True, timeout=3.0)

    ps = PlacementService(c1, key="_placement/m3db")
    ps.build_initial([Instance(id="a", endpoint="127.0.0.1:1")],
                     num_shards=8, replica_factor=1)
    placement, _ = PlacementService(c2, key="_placement/m3db").placement()
    assert {s.id for s in placement.instance("a").shards} == set(range(8))
    e2.resign()  # stop the renew thread before the server goes away
    c1.close()
    c2.close()


def test_admin_namespace_and_placement_api(tmp_path):
    from m3_tpu.coordinator import Coordinator
    from m3_tpu.storage.database import Database, DatabaseOptions

    db = Database(DatabaseOptions(path=str(tmp_path), num_shards=4))
    co = Coordinator(db)
    co.http.start()
    base = f"http://127.0.0.1:{co.http.port}"
    try:
        def post(path, body):
            req = urllib.request.Request(
                base + path, data=json.dumps(body).encode(),
                method="POST", headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req) as r:
                return json.loads(r.read())

        def get(path):
            with urllib.request.urlopen(base + path) as r:
                return json.loads(r.read())

        out = post("/api/v1/services/m3db/namespace", {
            "name": "agg_1h",
            "retention": {"retention_period": 720 * 3600 * 10**9},
            "aggregated": True, "aggregation_resolution": 3600 * 10**9})
        assert "agg_1h" in out["namespaces"]
        assert out["namespaces"]["agg_1h"]["aggregated"]
        ns = get("/api/v1/services/m3db/namespace")["namespaces"]
        assert set(ns) >= {"default", "agg", "agg_1h"}

        out = post("/api/v1/services/m3db/placement/init", {
            "instances": [{"id": "node-0", "endpoint": "127.0.0.1:9000"}],
            "num_shards": 8, "replication_factor": 1})
        assert out["status"] == "success"
        got = get("/api/v1/services/m3db/placement")
        assert got["placement"]["num_shards"] == 8

        out = post("/api/v1/topic/init", {
            "name": "t1", "number_of_shards": 8,
            "consumer_services": [{"service": "m3aggregator",
                                   "type": "replicated"}]})
        assert out["topic"]["name"] == "t1"
        got = get("/api/v1/topic?name=t1")
        assert got["topic"]["consumer_services"][0]["service_id"] == \
            "m3aggregator"
    finally:
        co.stop()
        db.close()


@pytest.mark.slow
def test_three_role_multiprocess_over_sockets(tmp_path):
    """VERDICT next-#8 done-criterion: kv + dbnode + coordinator as
    separate PROCESSES sharing the control plane over sockets only,
    driven via the coordinator admin API, with data flowing end to
    end (remote write -> query)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(pathlib.Path(__file__).resolve().parents[1])
    procs = []

    def spawn(*argv):
        p = subprocess.Popen(
            [sys.executable, "-m", "m3_tpu.services", *argv],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, env=env)
        procs.append(p)
        line = ""
        deadline = time.time() + 90
        while time.time() < deadline:
            line = p.stdout.readline()
            if " up: " in line:
                return line.strip().split(" up: ")[1]
            if p.poll() is not None:
                break
        raise AssertionError(
            f"service never came up: {line}{p.stdout.read()[:2000]}")

    try:
        kv_ep = spawn("kv")
        db_yaml = tmp_path / "db.yml"
        db_yaml.write_text(
            "db:\n"
            f"  path: {tmp_path}/dbnode\n"
            "  num_shards: 4\n"
            "  tick_every: 0\n")
        spawn("dbnode", "-f", str(db_yaml), "--kv", kv_ep)
        co_yaml = tmp_path / "co.yml"
        co_yaml.write_text(
            "coordinator:\n"
            f"  path: {tmp_path}/coord\n"
            "  num_shards: 4\n"
            "  http_port: 0\n")
        co_ep = spawn("coordinator", "-f", str(co_yaml), "--kv", kv_ep)
        port = co_ep if co_ep.isdigit() else co_ep.rsplit(":", 1)[-1]
        base = f"http://127.0.0.1:{port}"

        # drive the cluster via the admin API: namespace + placement
        # land in the NETWORKED kv (visible to other processes)
        req = urllib.request.Request(
            base + "/api/v1/services/m3db/placement/init",
            data=json.dumps({
                "instances": [{"id": "node-0",
                               "endpoint": "127.0.0.1:9999"}],
                "num_shards": 4, "replication_factor": 1}).encode(),
            method="POST")
        with urllib.request.urlopen(req, timeout=10) as r:
            assert json.loads(r.read())["status"] == "success"

        # a FOURTH process (this test) reads the placement back through
        # the kv socket — shared control plane, no shared filesystem
        c = KVClient(kv_ep)
        from m3_tpu.cluster.service import PlacementService
        placement, _ = PlacementService(
            c, key="_placement/m3db").placement()
        assert placement.num_shards == 4
        c.close()

        # data path: remote write then query over HTTP
        from m3_tpu.query import remote_write
        from m3_tpu.utils import snappy
        now_ms = int(time.time() * 1000)
        body = snappy.compress(remote_write.encode_write_request([
            ({b"__name__": b"up", b"job": b"x"}, [(now_ms, 1.0)])]))
        req = urllib.request.Request(
            base + "/api/v1/prom/remote/write", data=body, method="POST",
            headers={"Content-Encoding": "snappy"})
        with urllib.request.urlopen(req, timeout=10) as r:
            assert r.status == 200
        q = urllib.parse.urlencode({
            "query": "up", "start": now_ms / 1000 - 60,
            "end": now_ms / 1000 + 60, "step": "15s"})
        with urllib.request.urlopen(base + f"/api/v1/query_range?{q}",
                                    timeout=10) as r:
            out = json.loads(r.read())
        assert out["status"] == "success"
        assert out["data"]["result"], out
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()


