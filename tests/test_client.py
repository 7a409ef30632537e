"""Replicated client: routing, quorum, replica merge, topology changes.

Multi-node in one process with in-proc transports — the reference's
integration-test pattern (ref: src/dbnode/integration/,
fetch_tagged_quorum_test.go, cluster_add_one_node_test.go).
"""

import numpy as np
import pytest

from m3_tpu.client import DatabaseNode, NodeError, Session
from m3_tpu.client.session import ConsistencyError
from m3_tpu.cluster import Instance, MemStore, PlacementService
from m3_tpu.storage.database import Database, DatabaseOptions
from m3_tpu.storage.namespace import NamespaceOptions
from m3_tpu.topology import (
    DynamicTopology, ReadConsistencyLevel, StaticTopology,
    WriteConsistencyLevel, read_consistency_achieved,
    write_consistency_achieved,
)
from m3_tpu.topology.consistency import write_consistency_failed
from m3_tpu.utils import xtime

SEC = xtime.SECOND
START = 1_600_000_000 * SEC
NS = "default"


# ------------------------------------------------------------- consistency


class TestConsistencyMath:
    def test_write_levels(self):
        W = WriteConsistencyLevel
        assert write_consistency_achieved(W.ONE, 3, 1, 1)
        assert not write_consistency_achieved(W.MAJORITY, 3, 1, 3)
        assert write_consistency_achieved(W.MAJORITY, 3, 2, 2)
        assert not write_consistency_achieved(W.ALL, 3, 2, 3)
        assert write_consistency_achieved(W.ALL, 3, 3, 3)

    def test_write_failure_detection(self):
        W = WriteConsistencyLevel
        # 2 failures of 3 make MAJORITY impossible
        assert write_consistency_failed(W.MAJORITY, 3, 0, 2)
        assert not write_consistency_failed(W.MAJORITY, 3, 1, 2)
        assert write_consistency_failed(W.ALL, 3, 0, 1)

    def test_read_levels(self):
        R = ReadConsistencyLevel
        assert read_consistency_achieved(R.ONE, 3, 1, 1)
        assert not read_consistency_achieved(R.MAJORITY, 3, 2, 1)
        assert read_consistency_achieved(R.MAJORITY, 3, 2, 2)
        # unstrict levels succeed on any single success, even when the
        # other replicas never responded (ref ReadConsistencyAchieved:
        # numSuccess > 0) — availability under partial failure
        assert read_consistency_achieved(R.UNSTRICT_MAJORITY, 3, 2, 1)
        assert read_consistency_achieved(R.UNSTRICT_MAJORITY, 3, 1, 1)
        assert read_consistency_achieved(R.UNSTRICT_ALL, 3, 1, 1)
        assert not read_consistency_achieved(R.UNSTRICT_MAJORITY, 3, 3, 0)
        assert read_consistency_achieved(R.ALL, 3, 3, 3)
        assert not read_consistency_achieved(R.ALL, 3, 3, 2)


# ------------------------------------------------------------- test cluster


def make_cluster(tmp_path, n_nodes=3, rf=3, num_shards=8,
                 write_level=WriteConsistencyLevel.MAJORITY,
                 read_level=ReadConsistencyLevel.UNSTRICT_MAJORITY):
    store = MemStore()
    svc = PlacementService(store)
    insts = [Instance(f"node{i}", isolation_group=f"g{i}",
                      endpoint=f"127.0.0.1:{9000 + i}")
             for i in range(n_nodes)]
    svc.build_initial(insts, num_shards=num_shards, replica_factor=rf)
    svc.mark_all_available()
    dbs, nodes = {}, {}
    for i in range(n_nodes):
        db = Database(DatabaseOptions(path=str(tmp_path / f"node{i}"),
                                      num_shards=num_shards))
        db.create_namespace(NamespaceOptions(name=NS))
        dbs[f"node{i}"] = db
        nodes[f"node{i}"] = DatabaseNode(db, f"node{i}")
    topo = DynamicTopology(svc)
    sess = Session(topo, nodes, write_level=write_level,
                   read_level=read_level, flush_interval_s=0.002,
                   timeout_s=5.0)
    return store, svc, dbs, nodes, topo, sess


def write_points(sess, n_series=10, n_dp=5):
    for k in range(n_series):
        sid = b"cpu.util.host%d" % k
        tags = {b"__name__": b"cpu_util", b"host": b"h%d" % k}
        for j in range(n_dp):
            sess.write_tagged(NS, sid, tags,
                              START + j * 10 * SEC, float(k * 100 + j))


class TestReplicatedWrites:
    def test_writes_reach_all_replicas(self, tmp_path):
        # level ALL: a write returns once every replica has it (at
        # MAJORITY the third may still be in flight when the loop below
        # reads: it failed one whole run in two under six workers)
        store, svc, dbs, nodes, topo, sess = make_cluster(
            tmp_path, write_level=WriteConsistencyLevel.ALL)
        write_points(sess, n_series=6, n_dp=4)
        # RF=3 over 3 nodes: every node holds every series
        for name, db in dbs.items():
            res = db.fetch_tagged(
                NS, [("eq", b"__name__", b"cpu_util")], START,
                START + 3600 * SEC)
            assert len(res) == 6, name
        sess.close(); topo.close()

    def test_majority_survives_one_node_down(self, tmp_path):
        store, svc, dbs, nodes, topo, sess = make_cluster(tmp_path)
        nodes["node2"].set_down(True)
        write_points(sess, n_series=4, n_dp=3)
        up = [n for i, n in nodes.items() if i != "node2"]
        for node in up:
            res = node.fetch_tagged(
                NS, [("eq", b"__name__", b"cpu_util")], START,
                START + 3600 * SEC)
            assert len(res) == 4
        sess.close(); topo.close()

    def test_all_level_fails_with_node_down(self, tmp_path):
        store, svc, dbs, nodes, topo, sess = make_cluster(
            tmp_path, write_level=WriteConsistencyLevel.ALL)
        nodes["node1"].set_down(True)
        with pytest.raises(ConsistencyError):
            write_points(sess, n_series=1, n_dp=1)
        sess.close(); topo.close()

    def test_majority_fails_with_two_nodes_down(self, tmp_path):
        store, svc, dbs, nodes, topo, sess = make_cluster(tmp_path)
        nodes["node1"].set_down(True)
        nodes["node2"].set_down(True)
        with pytest.raises(ConsistencyError):
            write_points(sess, n_series=1, n_dp=1)
        sess.close(); topo.close()


class TestReplicatedReads:
    def test_fetch_merges_identical_replicas(self, tmp_path):
        store, svc, dbs, nodes, topo, sess = make_cluster(tmp_path)
        write_points(sess, n_series=3, n_dp=5)
        res = sess.fetch_tagged(
            NS, [("eq", b"__name__", b"cpu_util")], START,
            START + 3600 * SEC)
        assert len(res) == 3
        for sid, blocks in res.items():
            k = int(sid.decode().rsplit("host", 1)[1])
            pts = []
            for _bs, payload in blocks:
                ts, vs = payload
                pts.extend(zip(np.asarray(ts), np.asarray(vs)))
            assert [v for _, v in sorted(pts)] == [
                float(k * 100 + j) for j in range(5)]
        sess.close(); topo.close()

    def test_fetch_unions_diverged_replicas(self, tmp_path):
        """A replica that missed some writes: the merge must fill the
        holes from the other replicas (MultiReaderIterator semantics)."""
        store, svc, dbs, nodes, topo, sess = make_cluster(tmp_path)
        sid, tags = b"series.x", {b"__name__": b"sx"}
        sess.write_tagged(NS, sid, tags, START + 10 * SEC, 1.0)
        nodes["node0"].set_down(True)          # node0 misses point 2
        sess.write_tagged(NS, sid, tags, START + 20 * SEC, 2.0)
        nodes["node0"].set_down(False)
        nodes["node1"].set_down(True)          # node1 misses point 3
        sess.write_tagged(NS, sid, tags, START + 30 * SEC, 3.0)
        nodes["node1"].set_down(False)
        res = sess.fetch_tagged(NS, [("eq", b"__name__", b"sx")],
                                START, START + 3600 * SEC)
        (bs, payload), = res[sid]
        ts, vs = payload
        assert list(np.asarray(ts)) == [START + 10 * SEC, START + 20 * SEC,
                                        START + 30 * SEC]
        assert list(np.asarray(vs)) == [1.0, 2.0, 3.0]
        sess.close(); topo.close()

    def test_read_consistency_enforced(self, tmp_path):
        store, svc, dbs, nodes, topo, sess = make_cluster(
            tmp_path, read_level=ReadConsistencyLevel.ALL)
        write_points(sess, n_series=1, n_dp=1)
        nodes["node0"].set_down(True)
        with pytest.raises(ConsistencyError):
            sess.fetch_tagged(NS, [("eq", b"__name__", b"cpu_util")],
                              START, START + 3600 * SEC)
        sess.close(); topo.close()


class TestQuorumDuringTopologyChange:
    def test_initializing_holder_does_not_count_toward_quorum(self, tmp_path):
        """An INITIALIZING bootstrap target receives writes but its ack
        (or failure) must not affect consistency: ALL-level writes
        succeed with the initializing node down."""
        store, svc, dbs, nodes, topo, sess = make_cluster(
            tmp_path, n_nodes=3, rf=2, num_shards=8,
            write_level=WriteConsistencyLevel.ALL)
        db3 = Database(DatabaseOptions(path=str(tmp_path / "node3"),
                                       num_shards=8))
        db3.create_namespace(NamespaceOptions(name=NS))
        node3 = DatabaseNode(db3, "node3")
        nodes["node3"] = node3
        from m3_tpu.client.host_queue import HostQueue
        sess._queues["node3"] = HostQueue(node3, 128, 0.002)
        svc.add_instances([Instance("node3", isolation_group="g3")])
        import time as _t
        deadline = _t.time() + 2.0
        while topo.get().placement.instance("node3") is None:
            assert _t.time() < deadline
            _t.sleep(0.01)
        node3.set_down(True)   # bootstrap target dies
        write_points(sess, n_series=8, n_dp=2)   # must NOT raise
        sess.close(); topo.close()


class TestDynamicTopologyRouting:
    def test_new_node_receives_writes_after_placement_change(self, tmp_path):
        store, svc, dbs, nodes, topo, sess = make_cluster(
            tmp_path, n_nodes=3, rf=2, num_shards=8)
        # add a 4th node; writes must start flowing to it for the shards
        # it now owns (INITIALIZING targets receive live writes)
        db3 = Database(DatabaseOptions(path=str(tmp_path / "node3"),
                                       num_shards=8))
        db3.create_namespace(NamespaceOptions(name=NS))
        node3 = DatabaseNode(db3, "node3")
        nodes["node3"] = node3
        sess._queues["node3"] = __import__(
            "m3_tpu.client.host_queue", fromlist=["HostQueue"]
        ).HostQueue(node3, 128, 0.002)
        svc.add_instances([Instance("node3", isolation_group="g3",
                                    endpoint="127.0.0.1:9003")])
        # wait for the watch to deliver the new map
        deadline = __import__("time").time() + 2.0
        while topo.get().placement.instance("node3") is None:
            assert __import__("time").time() < deadline
            __import__("time").sleep(0.01)
        owned = [s.id for s in
                 topo.get().placement.instance("node3").shards]
        assert owned
        write_points(sess, n_series=20, n_dp=2)
        res = node3.fetch_tagged(NS, [("eq", b"__name__", b"cpu_util")],
                                 START, START + 3600 * SEC)
        # node3 sees exactly the series whose shard it owns
        from m3_tpu.utils.hash import shard_for
        expect = [b"cpu.util.host%d" % k for k in range(20)
                  if shard_for(b"cpu.util.host%d" % k, 8) in owned]
        assert sorted(res) == sorted(expect)
        assert expect, "test vacuous: no series landed on node3"
        sess.close(); topo.close()
