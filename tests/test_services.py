"""Service configs + role assembly + TCP node transport.

(ref: config structs cmd/services/*/config, x/config loader; TCP
parity: the Session must behave identically over in-proc and TCP
transports — the reference's thrift service contract.)
"""

import tempfile
import textwrap

import numpy as np
import pytest

from m3_tpu.client.node import DatabaseNode, NodeError
from m3_tpu.client.tcp import NodeClient, NodeServer
from m3_tpu.services.config import (CoordinatorConfig, DBNodeConfig,
                                    bind, load_dbnode_config, load_yaml)
from m3_tpu.storage.database import Database, DatabaseOptions
from m3_tpu.storage.namespace import NamespaceOptions

SEC = 1_000_000_000
T0 = 1_600_000_000 * SEC


def _write_cfg(td, text):
    p = f"{td}/cfg.yml"
    with open(p, "w") as f:
        f.write(textwrap.dedent(text))
    return p


# --- config loader ----------------------------------------------------------


def test_yaml_env_expansion_and_merge(monkeypatch, tmp_path):
    monkeypatch.setenv("DBPATH", "/data/x")
    a = tmp_path / "a.yml"
    a.write_text("db:\n  path: ${DBPATH}\n  num_shards: 8\n")
    b = tmp_path / "b.yml"
    b.write_text("db:\n  num_shards: 16\n")
    cfg = load_dbnode_config(str(a), str(b))
    assert cfg.path == "/data/x"
    assert cfg.num_shards == 16  # later file overrides


def test_env_default_and_missing(tmp_path):
    p = tmp_path / "c.yml"
    p.write_text("db:\n  path: ${NOPE_UNSET:/fallback}\n")
    assert load_dbnode_config(str(p)).path == "/fallback"
    p.write_text("db:\n  path: ${NOPE_UNSET}\n")
    with pytest.raises(ValueError, match="NOPE_UNSET"):
        load_dbnode_config(str(p))


def test_unknown_key_rejected(tmp_path):
    p = tmp_path / "c.yml"
    p.write_text("db:\n  pathh: /oops\n")
    with pytest.raises(ValueError, match="pathh"):
        load_dbnode_config(str(p))


def test_duration_strings_bind():
    cfg = bind(CoordinatorConfig, {"flush_interval": "10s"})
    assert cfg.flush_interval == 10 * SEC


# --- TCP node transport -----------------------------------------------------


@pytest.fixture
def tcp_node():
    with tempfile.TemporaryDirectory() as td:
        db = Database(DatabaseOptions(path=td, num_shards=4))
        db.create_namespace(NamespaceOptions(name="default"))
        srv = NodeServer(DatabaseNode(db, "n1")).start()
        client = NodeClient(srv.endpoint, "n1")
        try:
            yield db, srv, client
        finally:
            client.close()
            srv.stop()


def test_tcp_write_fetch_parity(tcp_node):
    db, srv, client = tcp_node
    ids = [b"a", b"b"]
    tags = [{b"__name__": b"a", b"k": b"v"}, {b"__name__": b"b"}]
    client.write_tagged_batch("default", ids, tags,
                              [T0 + 1 * SEC, T0 + 2 * SEC], [1.5, 2.5])
    out = client.fetch_tagged("default", [("eq", b"__name__", b"a")],
                              T0, T0 + 60 * SEC)
    assert list(out) == [b"a"]
    [(bs, payload)] = out[b"a"]
    ts, vs = payload
    assert list(map(int, ts)) == [T0 + 1 * SEC]
    assert list(vs) == [1.5]
    # and parity with the in-proc node
    direct = DatabaseNode(db, "n1").fetch_tagged(
        "default", [("eq", b"__name__", b"a")], T0, T0 + 60 * SEC)
    dts, dvs = direct[b"a"][0][1]
    assert list(map(int, dts)) == list(map(int, ts))


def test_tcp_blocks_metadata_and_blocks(tcp_node):
    db, srv, client = tcp_node
    client.write_tagged_batch("default", [b"s"], [{b"__name__": b"s"}],
                              [T0 + 1 * SEC], [7.0])
    shard = db._ns("default").shard_of(b"s").shard_id
    meta = client.fetch_blocks_metadata("default", shard, T0 - 10**12,
                                        T0 + 10**12)
    assert b"s" in meta
    tags, blocks = meta[b"s"]
    assert tags == {b"__name__": b"s"}
    bs = blocks[0][0]
    got = client.fetch_blocks("default", shard, {b"s": [bs]})
    ts, vs = got[b"s"][bs]
    assert list(vs) == [7.0]


def test_tcp_error_propagation(tcp_node):
    db, srv, client = tcp_node
    with pytest.raises(NodeError, match="unknown namespace"):
        client.fetch_tagged("nope", [], T0, T0 + 1)
    # connection survives an application error
    assert client.health()["ok"] is True


def test_tcp_peer_bootstrap_over_network():
    """ClusterStorageNode works with NodeClient transports — peer
    streaming over real sockets."""
    from m3_tpu.cluster.kv import MemStore
    from m3_tpu.cluster.placement import Instance
    from m3_tpu.cluster.service import PlacementService
    from m3_tpu.storage.cluster_node import ClusterStorageNode
    with tempfile.TemporaryDirectory() as td:
        db1 = Database(DatabaseOptions(path=f"{td}/1", num_shards=4))
        db1.create_namespace(NamespaceOptions(name="default"))
        db2 = Database(DatabaseOptions(path=f"{td}/2", num_shards=4))
        db2.create_namespace(NamespaceOptions(name="default"))
        db1.write_batch("default", [b"x"], [{b"__name__": b"x"}],
                        [T0 + SEC], [5.0])
        srv1 = NodeServer(DatabaseNode(db1, "n1")).start()
        try:
            store = MemStore()
            ps = PlacementService(store, key="_placement/m3db")
            ps.build_initial([Instance(id="n1", endpoint=srv1.endpoint)],
                             num_shards=4, replica_factor=1)
            ps.mark_all_available()
            node2 = ClusterStorageNode(
                db2, "n2", ps, {"n1": NodeClient(srv1.endpoint, "n1")},
                clock=lambda: T0 + 60 * SEC)
            # write enough series that n2 certainly receives some
            ids = [b"x%d" % i for i in range(32)]
            db1.write_batch("default", ids,
                            [{b"__name__": i} for i in ids],
                            [T0 + SEC] * 32, [float(i) for i in
                                              range(32)])
            ps.add_instances([Instance(id="n2", endpoint="e2")])
            assert node2.bootstrap_initializing() > 0
            from m3_tpu.storage.peers import payload_points
            from m3_tpu.utils.hash import shard_for
            owned = node2.owned_shards()
            assert owned
            checked = 0
            for i, sid in enumerate(ids):
                if shard_for(sid, 4) not in owned:
                    continue
                pts = []
                for _, p in db2.fetch_series("default", sid, T0,
                                             T0 + 60 * SEC):
                    t, v = payload_points(p)
                    pts += list(zip(map(int, t), v))
                assert pts == [(T0 + SEC, float(i))]
                checked += 1
            assert checked > 0
        finally:
            srv1.stop()


# --- service roles ----------------------------------------------------------


def test_dbnode_service_from_yaml(tmp_path):
    cfg_p = _write_cfg(tmp_path, f"""
        db:
          path: {tmp_path}/data
          instance_id: node-7
          num_shards: 8
          namespaces:
            - name: default
            - name: agg
    """)
    from m3_tpu.services import DBNodeService
    svc = DBNodeService(load_dbnode_config(cfg_p)).start()
    try:
        client = NodeClient(svc.endpoint)
        client.write_tagged_batch("agg", [b"m"], [{b"__name__": b"m"}],
                                  [T0], [1.0])
        assert client.health()["id"] == "node-7"
    finally:
        svc.stop()


def test_coordinator_service_from_yaml(tmp_path):
    import urllib.request
    cfg_p = _write_cfg(tmp_path, f"""
        coordinator:
          path: {tmp_path}/data
          num_shards: 4
          flush_interval: 1s
    """)
    from m3_tpu.services import (CoordinatorService,
                                 load_coordinator_config)
    svc = CoordinatorService(load_coordinator_config(cfg_p)).start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{svc.http_port}/api/v1/query_range"
                "?query=up&start=0&end=60&step=10") as r:
            assert r.status == 200
    finally:
        svc.stop()


# --- compile-cache placement + device-tier auto mode (process start-up)


def test_compile_cache_dir_from_environment_is_left_alone(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: JAX read it itself, the helper
    sets no directory in code."""
    import jax

    from m3_tpu.utils import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    monkeypatch.delenv("M3_NO_COMPILE_CACHE", raising=False)
    before = jax.config.jax_compilation_cache_dir
    calls = []
    real = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda key, value: (calls.append(key), real(key, value)))
    assert compile_cache.configure() == before
    assert "jax_compilation_cache_dir" not in calls
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_dir_defaults_to_checkout(monkeypatch):
    import pathlib

    import jax

    import m3_tpu
    from m3_tpu.utils import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("M3_NO_COMPILE_CACHE", raising=False)
    want = str(pathlib.Path(m3_tpu.__file__).resolve().parents[1]
               / ".jax_cache")
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.configure() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_device_serving_auto_asks_the_public_backend(monkeypatch,
                                                     tmp_path):
    """Auto mode is jax.default_backend() != "cpu", asked the public
    way: no private jax module is read."""
    import inspect

    import jax

    from m3_tpu.query.engine import Engine

    assert "jax._src" not in inspect.getsource(
        Engine._device_serving_active)
    db = Database(DatabaseOptions(path=str(tmp_path), num_shards=2,
                                  commit_log_enabled=False))
    try:
        assert jax.default_backend() == "cpu"
        assert Engine(db)._device_serving_active() is False
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert Engine(db)._device_serving_active() is True
        assert Engine(db, device_serving=False)\
            ._device_serving_active() is False
    finally:
        db.close()
