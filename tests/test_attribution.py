"""Workload attribution end to end: space-saving sketch bounds (zipf +
adversarial streams, merge correctness, bounded memory), tenant
propagation on the ``tc`` trace context, bounded-cardinality metric
families, OpenMetrics exemplars, and the acceptance surface — a
coordinator in front of a 3-node cluster under mixed per-tenant
traffic whose ``/debug/heavyhitters`` merged top-k matches exact
accounting within the documented sketch error bound, with
``m3_tenant_*`` queryable via PromQL out of ``_m3_internal``.
"""

import json
import random
import time
import urllib.error
import urllib.parse
import urllib.request

import numpy as np
import pytest

from m3_tpu import attribution
from m3_tpu.attribution import SpaceSaving, merge_dumps
from m3_tpu.client import DatabaseNode
from m3_tpu.client.tcp import NodeClient, NodeServer
from m3_tpu.query import remote_write
from m3_tpu.query.http import CoordinatorServer
from m3_tpu.storage import (
    Database, DatabaseOptions, NamespaceOptions, RetentionOptions,
)
from m3_tpu.utils import instrument, snappy, tracing, xtime

SEC = xtime.SECOND
BLOCK = 2 * xtime.HOUR
T0 = (1_600_000_000 * SEC // BLOCK) * BLOCK
NS = "default"


@pytest.fixture
def fresh_accounting():
    """Reset the process-global accountant around a test (counters are
    cumulative by design and are NOT reset — assertions on them use
    deltas or >=)."""
    acc = attribution.accountant()
    old_enabled = acc.enabled
    acc.reset()
    acc.configure(enabled=True)
    yield acc
    acc.reset()
    acc.configure(enabled=old_enabled)


@pytest.fixture
def sample_all():
    old = tracing.tracer().sample_1_in
    tracing.set_sampling(1)
    yield
    tracing.tracer().sample_1_in = old


# ------------------------------------------------- space-saving sketch


def _zipf_stream(n_offers, n_keys, seed=42, exponent=1.2):
    rng = random.Random(seed)
    weights = [1.0 / (i + 1) ** exponent for i in range(n_keys)]
    keys = [f"k{i}" for i in range(n_keys)]
    return rng.choices(keys, weights=weights, k=n_offers)


class TestSpaceSaving:
    def test_zipf_stream_within_error_bound(self):
        m = 64
        sk = SpaceSaving(m)
        exact: dict[str, int] = {}
        for key in _zipf_stream(20_000, 500):
            sk.offer(key)
            exact[key] = exact.get(key, 0) + 1
        n = sk.total
        assert n == 20_000
        bound = n / m
        for e in sk.top():
            true = exact.get(e["key"], 0)
            # count - error <= true <= count, error <= N/m
            assert e["count"] - e["error"] <= true <= e["count"]
            assert e["error"] <= bound
        # no false negatives among heavy hitters: every key with true
        # count > N/m is tracked
        tracked = {e["key"] for e in sk.top()}
        for key, cnt in exact.items():
            if cnt > bound:
                assert key in tracked, (key, cnt, bound)
        # the exact top-5 surface in the sketch top-10
        top5 = sorted(exact, key=exact.get, reverse=True)[:5]
        top10 = [e["key"] for e in sk.top(10)]
        assert set(top5) <= set(top10)

    def test_adversarial_all_distinct_keys_bounded(self):
        # worst case for space-saving: every key appears exactly once
        m = 32
        sk = SpaceSaving(m)
        for i in range(5_000):
            sk.offer(f"adv{i}")
            assert len(sk._counts) <= m  # bounded memory, always
        assert sk.total == 5_000
        for e in sk.top():
            # overestimate only, by at most N/m
            assert 1.0 <= e["count"] <= 1.0 + sk.total / m
            assert e["count"] - e["error"] <= 1.0

    def test_adversarial_rotating_then_heavy(self):
        # churn through distinct keys, then hammer one: the heavy key
        # must surface with a tight estimate despite inherited error
        m = 16
        sk = SpaceSaving(m)
        for i in range(1_000):
            sk.offer(f"noise{i}")
        for _ in range(500):
            sk.offer("whale")
        top = sk.top(1)[0]
        assert top["key"] == "whale"
        assert top["count"] - top["error"] <= 500 <= top["count"]
        assert top["error"] <= sk.total / m

    def test_weighted_offers_and_reset(self):
        sk = SpaceSaving(4)
        sk.offer("a", 10.0)
        sk.offer("b", 3.0)
        assert sk.total == 13.0
        assert sk.top(1)[0] == {"key": "a", "count": 10.0, "error": 0.0}
        sk.offer("a", 0.0)  # non-positive offers are ignored
        assert sk.total == 13.0
        sk.reset()
        assert sk.total == 0.0 and sk.top() == []

    def test_merge_matches_exact_within_summed_bound(self):
        # 3 simulated nodes, each sketching its own shard of a global
        # zipf stream — the merged view honors sum_i N_i / m
        m = 48
        sketches = [SpaceSaving(m) for _ in range(3)]
        exact: dict[str, int] = {}
        for i, key in enumerate(_zipf_stream(30_000, 400, seed=7)):
            sketches[i % 3].offer(key)
            exact[key] = exact.get(key, 0) + 1
        merged = merge_dumps([sk.dump() for sk in sketches])
        assert merged["total"] == 30_000
        bound = sum(sk.total / m for sk in sketches)
        by_key = {e["key"]: e for e in merged["entries"]}
        for key, e in by_key.items():
            true = exact.get(key, 0)
            # two-sided: a node that tracked the key over-counts by at
            # most its N_i/m; a node that evicted it under-reports by
            # at most the same — the summed bound absorbs both
            assert abs(e["count"] - true) <= bound, (key, e, true)
            assert e["error"] <= bound
        # global heavy hitters survive the merge
        top3 = sorted(exact, key=exact.get, reverse=True)[:3]
        merged_top = [e["key"] for e in merged["entries"][:10]]
        assert set(top3) <= set(merged_top)

    def test_merge_empty_and_capacity(self):
        assert merge_dumps([]) == {"total": 0.0, "capacity": 64,
                                   "entries": []}
        a, b = SpaceSaving(8), SpaceSaving(4)
        for i in range(20):
            a.offer(f"x{i}")
            b.offer(f"x{i}")
        merged = merge_dumps([a.dump(), b.dump()])
        assert merged["capacity"] == 8
        assert len(merged["entries"]) <= 8


# --------------------------------------- accountant + dump merge dedup


class TestAccountant:
    def test_write_read_query_accounting(self, fresh_accounting):
        acc = fresh_accounting
        acc.account_write("acme", samples=100, wire_bytes=512,
                          new_series=4)
        acc.account_write("acme", samples=50, wal_bytes=800)
        acc.account_read("acme", datapoints=1000, decoded_bytes=4096,
                         device_seconds=0.25)
        acc.account_query("acme", "sum(rate(cpu[5m]))", cost=1000.0)
        view = acc.tenants_view()
        t = view["tenants"]["acme"]
        assert t["samples"] == 150
        assert t["wire_bytes"] == 512
        assert t["wal_bytes"] == 800
        assert t["new_series"] == 4
        assert t["datapoints"] == 1000
        assert t["device_seconds"] == pytest.approx(0.25)
        assert t["queries"] == 1
        # sketches fed per-request, never per-sample
        assert acc.series_churn.top(1)[0]["key"] == "acme"
        assert acc.series_churn.top(1)[0]["count"] == 4
        qtop = acc.query_cost.top(1)[0]
        assert qtop["key"] == "acme|sum(rate(cpu[5m]))"
        assert qtop["count"] == 1000.0

    @pytest.mark.parametrize("n_samples", [8, 512])
    def test_write_path_accounts_once_a_batch(self, fresh_accounting,
                                              tmp_path, n_samples):
        """What keeps attribution cheap on the write path is a count,
        not a time: a batch is accounted in one call whatever it
        holds, every sample of it attributed."""
        acc = fresh_accounting
        calls = []
        real = acc.account_write
        acc.account_write = lambda tenant, **costs: (
            calls.append(costs), real(tenant, **costs))[1]
        db = Database(DatabaseOptions(path=str(tmp_path),
                                      commit_log_enabled=False))
        db.create_namespace(NamespaceOptions(name="default"))
        try:
            tags = [{b"__name__": b"m", b"i": b"%d" % i}
                    for i in range(n_samples)]
            now = time.time_ns()
            with tracing.tenant_scope("acme"):
                db.write_batch("default", [b"m|%d" % i
                                           for i in range(n_samples)],
                               tags, [now] * n_samples,
                               [1.0] * n_samples)
        finally:
            del acc.account_write
            db.close()
        assert len(calls) == 1
        t = acc.tenants_view()["tenants"]["acme"]
        assert t["samples"] == n_samples
        assert t["new_series"] == n_samples

    def test_tenant_cap_folds_overflow_to_other(self):
        acc = attribution.Accountant(tenant_cap=2)
        acc.account_write("t1", samples=1)
        acc.account_write("t2", samples=2)
        acc.account_write("t3", samples=3)  # over cap: folds
        acc.account_write("t4", samples=4)
        tenants = acc.tenants_view()["tenants"]
        assert set(tenants) == {"t1", "t2", "other"}
        assert tenants["other"]["samples"] == 7

    def test_sanitizer(self):
        assert attribution.safe_tenant(None) == "default"
        assert attribution.safe_tenant("") == "default"
        assert attribution.safe_tenant(b"acme") == "acme"
        assert attribution.safe_tenant("a b;c\nd") == "a_b_c_d"
        assert len(attribution.safe_tenant("x" * 200)) == 64

    def test_inflight_shares(self, fresh_accounting):
        acc = fresh_accounting
        acc.inflight_add("a", 300.0)
        acc.inflight_add("b", 100.0)
        infl = acc.tenants_view()["inflight"]
        assert infl["a"]["share"] == pytest.approx(0.75)
        assert infl["b"]["share"] == pytest.approx(0.25)
        acc.inflight_sub("a", 300.0)
        infl = acc.tenants_view()["inflight"]
        assert "a" not in infl
        assert infl["b"]["share"] == pytest.approx(1.0)

    def test_disabled_accounts_nothing(self):
        acc = attribution.Accountant()
        acc.configure(enabled=False)
        acc.account_write("t", samples=9)
        acc.account_query("t", "q", 5.0)
        acc.inflight_add("t", 1.0)
        assert acc.tenants_view()["tenants"] == {}
        assert acc.query_cost.total == 0.0

    def test_merge_dedups_by_source_id(self):
        a, b = attribution.Accountant(), attribution.Accountant()
        a.account_query("t1", "q1", 10.0)
        b.account_query("t2", "q2", 20.0)
        # node a's dump arrives twice (e.g. local + a peer sharing the
        # same process-global accountant): counted once
        merged = attribution.merge_attribution_dumps(
            [a.dump(), a.dump(), b.dump()])
        assert len(merged["sources"]) == 2
        qc = merged["sketches"]["query_cost"]
        assert qc["total"] == 30.0
        assert {e["key"]: e["count"] for e in qc["entries"]} == {
            "t1|q1": 10.0, "t2|q2": 20.0}
        assert qc["error_bound"] == pytest.approx(30.0 / qc["capacity"])


# -------------------------------------------------- tenant propagation


class TestTenantPropagation:
    def test_traceparent_tenant_suffix_roundtrip(self):
        hdr = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
        ctx = tracing.parse_traceparent(hdr + ";t=acme")
        assert ctx is not None and ctx.tenant == "acme"
        # bare W3C headers stay tenant-less (interop with external
        # tracers is unchanged)
        assert tracing.parse_traceparent(hdr).tenant is None
        assert tracing.TraceContext(1, 2).to_traceparent().count(";") == 0

    def test_activate_adopts_and_restores_tenant(self, sample_all):
        hdr = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01;t=globex"
        ctx = tracing.parse_traceparent(hdr)
        assert tracing.current_tenant() is None
        with tracing.activate(ctx):
            assert tracing.current_tenant() == "globex"
            # wire_context re-appends the suffix for the next hop
            assert tracing.wire_context().endswith(";t=globex")
        assert tracing.current_tenant() is None

    def test_unsampled_context_still_carries_tenant(self):
        # accounting is not sampled: an unsampled trace context must
        # still propagate its tenant baggage
        ctx = tracing.TraceContext(0xAB, 0xCD, sampled=False,
                                   tenant="acme")
        with tracing.activate(ctx):
            assert tracing.current_tenant() == "acme"
        assert tracing.current_tenant() is None

    def test_tenant_scope_nesting(self):
        with tracing.tenant_scope("outer"):
            assert tracing.current_tenant() == "outer"
            with tracing.tenant_scope("inner"):
                assert tracing.current_tenant() == "inner"
            with tracing.tenant_scope(None):  # no-op, keeps outer
                assert tracing.current_tenant() == "outer"
            assert tracing.current_tenant() == "outer"
        assert tracing.current_tenant() is None

    def test_current_tenant_default(self):
        assert attribution.current_tenant(default="ns1") == "ns1"
        with tracing.tenant_scope("t9"):
            assert attribution.current_tenant(default="ns1") == "t9"


# ------------------------------- bounded metric families (satellite 1)


class TestBoundedFamily:
    def test_fold_to_other_and_drop_counter(self):
        r = instrument.Registry()
        fam = r.bounded_counter("m3_bf_test_total", cap=2)
        fam.labels(tenant="a").inc(1)
        fam.labels(tenant="b").inc(2)
        fam.labels(tenant="c").inc(4)  # over cap: folds to "other"
        fam.labels(tenant="d").inc(8)
        samples = {(s.name, tuple(sorted(s.tags.items()))): s.value
                   for s in r.collect()}
        assert samples[("m3_bf_test_total", (("tenant", "a"),))] == 1
        assert samples[("m3_bf_test_total", (("tenant", "b"),))] == 2
        assert samples[
            ("m3_bf_test_total", (("tenant", "other"),))] == 12
        dropped = samples[("m3_instrument_dropped_labels_total",
                           (("metric", "m3_bf_test_total"),))]
        assert dropped == 2  # one per folded labels() resolution

    def test_known_labelsets_stay_exact_after_overflow(self):
        r = instrument.Registry()
        fam = r.bounded_counter("m3_bf_exact_total", cap=1)
        fam.labels(tenant="keep").inc(5)
        fam.labels(tenant="spill").inc(7)
        fam.labels(tenant="keep").inc(5)  # already tracked: exact
        samples = {tuple(sorted(s.tags.items())): s.value
                   for s in r.collect()
                   if s.name == "m3_bf_exact_total"}
        assert samples[(("tenant", "keep"),)] == 10
        assert samples[(("tenant", "other"),)] == 7

    def test_bounded_gauge_and_histogram(self):
        r = instrument.Registry()
        g = r.bounded_gauge("m3_bf_share", cap=2)
        g.labels(tenant="a").set(0.5)
        h = r.bounded_histogram("m3_bf_lat_seconds", cap=2)
        h.labels(tenant="a").observe(0.01)
        names = {s.name for s in r.collect()}
        assert "m3_bf_share" in names
        assert any(n.startswith("m3_bf_lat_seconds") for n in names)


# ------------------------------- OpenMetrics exemplars (satellite 2)


class TestExemplars:
    def test_exposition_gated_by_flag(self, sample_all):
        r = instrument.Registry()
        h = r.histogram("m3_ex_test_seconds")
        assert not instrument.exemplars_enabled()
        instrument.set_exemplars(True)
        try:
            with tracing.span(tracing.HTTP_REQUEST, route="ex"):
                ctx = tracing.current_context()
                h.observe(0.02)
            text = r.render_prometheus().decode()
            want = f'# {{trace_id="{ctx.trace_id:032x}"}} 0.02'
            bucket_lines = [ln for ln in text.splitlines()
                            if ln.startswith("m3_ex_test_seconds_bucket")]
            assert any(want in ln for ln in bucket_lines), bucket_lines
            # exemplar rides only the bucket the value landed in
            assert sum(1 for ln in bucket_lines if "trace_id" in ln) == 1
        finally:
            instrument.set_exemplars(False)
        # flag off: plain Prometheus exposition, no exemplar suffix
        assert "trace_id" not in r.render_prometheus().decode()

    def test_no_exemplar_outside_sampled_span(self, sample_all):
        r = instrument.Registry()
        h = r.histogram("m3_ex_bare_seconds")
        instrument.set_exemplars(True)
        try:
            h.observe(0.02)  # no active span: nothing to link to
            assert "trace_id" not in r.render_prometheus().decode()
        finally:
            instrument.set_exemplars(False)


# ----------------------------- acceptance: 3-node cluster, mixed load


def _post(port, path, body, headers=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=body,
        headers=headers or {}, method="POST")
    try:
        with urllib.request.urlopen(req) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _get(port, path, headers=None):
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}",
                                 headers=headers or {})
    try:
        with urllib.request.urlopen(req) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _write(port, tenant, metric, n_series, n_dp=20):
    total = 0
    for k in range(n_series):
        labels = {b"__name__": metric, b"host": b"h%d" % k,
                  b"dc": b"east"}
        samples = [((T0 + (j + 1) * 10 * SEC) // 1_000_000, float(j))
                   for j in range(n_dp)]
        payload = snappy.compress(
            remote_write.encode_write_request([(labels, samples)]))
        code, body = _post(port, "/api/v1/prom/remote/write", payload,
                           {"Content-Encoding": "snappy",
                            "M3-Tenant": tenant})
        assert code == 200, body
        total += n_dp
    return total


class TestClusterAcceptance:
    @pytest.fixture
    def cluster_srv(self, tmp_path, fresh_accounting):
        # coordinator db serves writes + queries; three dbnodes behind
        # real TCP transports are the attribution peers whose dumps
        # /debug/heavyhitters merges
        db = Database(DatabaseOptions(path=str(tmp_path / "coord"),
                                      num_shards=4,
                                      commit_log_enabled=False))
        db.create_namespace(NamespaceOptions(
            name=NS, retention=RetentionOptions(block_size=BLOCK)))
        db.create_namespace(NamespaceOptions(
            name="_m3_internal",
            retention=RetentionOptions(
                retention_period=24 * 3600 * 10**9,
                block_size=3600 * 10**9),
            writes_to_commit_log=False))
        db.bootstrap()
        node_dbs, servers, clients = [], [], []
        for i in range(3):
            ndb = Database(DatabaseOptions(
                path=str(tmp_path / f"node{i}"), num_shards=4,
                commit_log_enabled=False))
            ndb.create_namespace(NamespaceOptions(
                name=NS, retention=RetentionOptions(block_size=BLOCK)))
            node_dbs.append(ndb)
            srv = NodeServer(DatabaseNode(ndb, f"node{i}")).start()
            servers.append(srv)
            clients.append(NodeClient(srv.endpoint, f"node{i}"))
        srv = CoordinatorServer(db, port=0, trace_peers=clients).start()
        yield srv, db
        srv.stop()
        for c in clients:
            c.close()
        for s in servers:
            s.stop()
        for ndb in node_dbs:
            ndb.close()
        db.close()

    def test_heavyhitters_match_exact_within_bound(self, cluster_srv):
        srv, db = cluster_srv
        port = srv.port
        acc = attribution.accountant()

        # mixed per-tenant traffic: distinct series-churn + query load
        writes = {"acme": _write(port, "acme", b"cpu_acme", 8),
                  "globex": _write(port, "globex", b"cpu_globex", 3),
                  "initech": _write(port, "initech", b"cpu_initech", 1)}
        churn = {"acme": 8, "globex": 3, "initech": 1}
        qs = (f"/api/v1/query_range?query=cpu_acme"
              f"&start={T0 / 1e9}&end={(T0 + 300 * SEC) / 1e9}&step=10s")
        for _ in range(4):
            code, body = _get(port, qs, headers={"M3-Tenant": "acme"})
            assert code == 200, body
        code, body = _get(
            port,
            f"/api/v1/query_range?query=cpu_globex&start={T0 / 1e9}"
            f"&end={(T0 + 300 * SEC) / 1e9}&step=10s",
            headers={"M3-Tenant": "globex"})
        assert code == 200, body

        # exact per-tenant accounting at /debug/tenants
        code, body = _get(port, "/debug/tenants")
        assert code == 200, body
        tenants = body["data"]["tenants"]
        for t, n in writes.items():
            assert tenants[t]["samples"] == n, (t, tenants[t])
            assert tenants[t]["new_series"] == churn[t]
            assert tenants[t]["wire_bytes"] > 0
        assert tenants["acme"]["queries"] == 4
        assert tenants["acme"]["datapoints"] > 0
        assert tenants["globex"]["queries"] == 1

        # merged heavy hitters across the 3-node cluster
        code, body = _get(port, "/debug/heavyhitters")
        assert code == 200, body
        data = body["data"]
        assert set(data["peers"]) == {"node0", "node1", "node2"}
        assert all(v == "ok" for v in data["peers"].values())
        # in-process nodes share one accountant: dedup to one source
        assert data["sources"] == [acc.source_id]
        sc = data["sketches"]["series_churn"]
        assert sc["error_bound"] == pytest.approx(
            sc["total"] / sc["capacity"])
        by_key = {e["key"]: e for e in sc["entries"]}
        for t, n in churn.items():
            e = by_key[t]
            # acceptance: merged top-k matches exact accounting within
            # the documented bound (count - error <= exact <= count,
            # deviation <= error_bound)
            assert e["count"] - e["error"] <= n <= e["count"]
            assert abs(e["count"] - n) <= sc["error_bound"]
        assert sc["entries"][0]["key"] == "acme"  # top churn tenant
        qc = data["sketches"]["query_cost"]
        assert qc["entries"][0]["key"].startswith("acme|cpu_acme")
        lc = data["sketches"]["label_cardinality"]
        lc_keys = {e["key"] for e in lc["entries"]}
        assert {"host", "dc"} <= lc_keys  # __name__ excluded
        assert not any(k.startswith("__") for k in lc_keys)

    def test_tenant_counters_queryable_over_internal_ns(
            self, cluster_srv):
        srv, db = cluster_srv
        port = srv.port
        from m3_tpu.selfscrape import SelfScraper

        n_samples = _write(port, "acme", b"mem_acme", 2, n_dp=25)
        sc = SelfScraper(db.write_batch, namespace="_m3_internal",
                         interval_s=100, instance="coord-0",
                         role="coordinator")
        try:
            now = time.time_ns()
            sc.scrape_once(now_nanos=now - 30 * 10**9)
            sc.scrape_once(now_nanos=now - 15 * 10**9)
            assert sc.flush(10.0)
        finally:
            sc.stop(staleness=False)
        # the acceptance query: m3_tenant_* through PromQL over the
        # self-scraped _m3_internal namespace
        expr = urllib.parse.quote(
            'm3_tenant_samples_total{tenant="acme"}')
        code, body = _get(
            port,
            f"/api/v1/query_range?query={expr}&namespace=_m3_internal"
            f"&start={(now - 60 * 10**9) / 1e9}&end={now / 1e9}"
            f"&step=15")
        assert code == 200, body
        result = body["data"]["result"]
        assert result, "m3_tenant_samples_total not in _m3_internal"
        vals = [float(v) for _, v in result[0]["values"]]
        # cumulative counter: at least this test's samples (the global
        # registry carries earlier increments too)
        assert vals[-1] >= n_samples
        assert vals == sorted(vals)


if __name__ == "__main__":
    import sys
    sys.exit(pytest.main([__file__, "-v"]))
