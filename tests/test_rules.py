"""Rules engine: recording + alerting rules on the device query path
(m3_tpu/rules/).

Covers the acceptance seams:

- the ``for:`` state machine under fake clocks (pending flap resets,
  ``for: 0`` fires immediately, templating);
- restart/takeover resumes ``for:`` timers from KV without double-fire;
- recording-rule output written back through the real ingest seam and
  queried with PromQL;
- exactly-one-evaluator under leader failover (no eval gap > 2
  intervals, no double evaluation within half an interval);
- device-tier evaluation: steady-state rule queries re-hit the plan
  compile cache;
- notifier units: retry with deadline budget, Retry-After on 429,
  breaker fail-fast, payload shed, queue overflow drop-and-count;
- a 2-node e2e: wedged index compactor -> watchdog stall metric ->
  alert pending -> firing -> webhook delivered, with the alert state
  surviving a coordinator restart.
"""

import json
import math
import threading
import time
import urllib.error
import urllib.parse
import urllib.request
from email.message import Message
from http.server import BaseHTTPRequestHandler, HTTPServer

import pytest

from m3_tpu import observe
from m3_tpu.cluster.kv import MemStore
from m3_tpu.query import slowlog
from m3_tpu.query.engine import Engine
from m3_tpu.query.remote_write import series_id_from_labels
from m3_tpu.rules import (RulesEngine, STATE_FIRING, STATE_PENDING,
                          WebhookNotifier)
from m3_tpu.rules.engine import GroupEvaluator
from m3_tpu.services.config import (RuleDef, RuleGroupConfig, RulesConfig,
                                    bind)
from m3_tpu.storage.database import Database, DatabaseOptions
from m3_tpu.storage.namespace import NamespaceOptions, RetentionOptions
from m3_tpu.utils import instrument

SEC = 10**9
NS = "_m3_internal"


# --- harness ----------------------------------------------------------------


def _db(tmp_path):
    db = Database(DatabaseOptions(path=str(tmp_path / "db"), num_shards=4,
                                  commit_log_enabled=False))
    db.create_namespace(NamespaceOptions(
        name=NS,
        retention=RetentionOptions(retention_period=24 * 3600 * SEC,
                                   block_size=3600 * SEC),
        writes_to_commit_log=False))
    db.bootstrap()
    return db


def _write(db, name, tags, value, t_s):
    lbl = {b"__name__": name.encode()}
    for k, v in tags.items():
        lbl[k.encode()] = v.encode()
    db.write_batch(NS, [series_id_from_labels(lbl)], [lbl],
                   [int(t_s * 1e9)], [float(value)])


class FakeNotifier:
    """Captures enqueued alert batches; the real queue/transport is
    unit-tested separately."""

    def __init__(self):
        self.batches = []

    def enqueue(self, alerts):
        self.batches.append(list(alerts))
        return len(alerts)

    def close(self, timeout=0.0):
        pass

    def flat(self):
        return [a for b in self.batches for a in b]


def _group(rules, name="g", interval="1s"):
    return bind(RuleGroupConfig,
                {"name": name, "interval": interval, "rules": rules})


def _evaluator(db, group, store=None, instance="i0", notifier=None,
               engine=None, write_fn=None):
    return GroupEvaluator(
        group, store=store if store is not None else MemStore(),
        instance_id=instance,
        engine=engine if engine is not None
        else Engine(db, NS, device_serving=False),
        write_fn=write_fn if write_fn is not None else db.write_batch,
        namespace=NS, notifier=notifier)


# --- config binding ----------------------------------------------------------


def test_rule_config_binds_for_keyword_and_durations():
    g = _group([{"alert": "Hot", "expr": "x > 1", "for": "90s",
                 "labels": {"severity": "page"},
                 "annotations": {"summary": "hot"}}])
    r = g.rules[0]
    assert isinstance(r, RuleDef)
    assert r.for_ == 90 * SEC and r.name == "Hot"
    assert g.interval == SEC


def test_rule_config_rejects_invalid_rules():
    with pytest.raises(ValueError):  # both planes at once
        bind(RuleDef, {"record": "a", "alert": "b", "expr": "x"})
    with pytest.raises(ValueError):  # neither
        bind(RuleDef, {"expr": "x"})
    with pytest.raises(ValueError):  # recording rules have no for:
        bind(RuleDef, {"record": "a", "expr": "x", "for": "1m"})
    with pytest.raises(ValueError):  # empty expr
        bind(RuleDef, {"alert": "a"})
    with pytest.raises(ValueError):  # duplicate group names
        bind(RulesConfig, {"groups": [
            {"name": "g", "rules": [{"record": "a", "expr": "x"}]},
            {"name": "g", "rules": [{"record": "b", "expr": "y"}]}]})


# --- for: state machine (fake clocks) ----------------------------------------


def test_alert_pending_then_firing_with_for(tmp_path):
    db = _db(tmp_path)
    fn = FakeNotifier()
    ev = _evaluator(db, _group([{
        "alert": "Down", "expr": "up == 0", "for": "5s",
        "labels": {"severity": "page"},
        "annotations": {"summary": "{{ $labels.instance }} is down "
                                   "(value {{ $value }})"}}]),
        notifier=fn)
    try:
        t0 = time.time() - 30
        _write(db, "up", {"instance": "i0"}, 0.0, t0 - 1)

        ev.evaluate_once(t0)
        (alert,) = ev.alerts_json()
        assert alert["state"] == STATE_PENDING
        assert alert["labels"]["severity"] == "page"
        assert alert["annotations"]["summary"] == \
            "i0 is down (value 0.0)"
        assert not fn.flat()  # pending never notifies

        ev.evaluate_once(t0 + 2)  # still inside for: stays pending
        assert ev.alerts_json()[0]["state"] == STATE_PENDING

        ev.evaluate_once(t0 + 5.5)  # for elapsed: fires
        (alert,) = ev.alerts_json()
        assert alert["state"] == STATE_FIRING
        (fired,) = fn.flat()
        assert fired["status"] == "firing"
        assert fired["labels"]["alertname"] == "Down"
        assert fired["startsAt"] and fired["endsAt"] == ""

        # firing persists without re-notifying
        ev.evaluate_once(t0 + 7)
        assert len(fn.flat()) == 1

        # series recovers: resolved notification, alert gone
        _write(db, "up", {"instance": "i0"}, 1.0, t0 + 7.5)
        ev.evaluate_once(t0 + 8)
        assert ev.alerts_json() == []
        assert [a["status"] for a in fn.flat()] == ["firing", "resolved"]
        assert fn.flat()[1]["endsAt"] != ""
    finally:
        ev._leader.close()
        db.close()


def test_pending_flap_resets_instead_of_firing(tmp_path):
    db = _db(tmp_path)
    fn = FakeNotifier()
    ev = _evaluator(db, _group([{
        "alert": "Down", "expr": "up == 0", "for": "5s"}]), notifier=fn)
    try:
        t0 = time.time() - 60
        _write(db, "up", {"instance": "i0"}, 0.0, t0 - 1)
        ev.evaluate_once(t0)  # pending
        _write(db, "up", {"instance": "i0"}, 1.0, t0 + 1)
        ev.evaluate_once(t0 + 2)  # recovered: silently inactive
        assert ev.alerts_json() == []

        # down again PAST the original for window: the timer must
        # have reset — still pending, not firing
        _write(db, "up", {"instance": "i0"}, 0.0, t0 + 3)
        ev.evaluate_once(t0 + 6)
        assert ev.alerts_json()[0]["state"] == STATE_PENDING
        assert not fn.flat()

        ev.evaluate_once(t0 + 11.5)  # new timer elapsed: now it fires
        assert ev.alerts_json()[0]["state"] == STATE_FIRING
        assert len(fn.flat()) == 1
    finally:
        ev._leader.close()
        db.close()


def test_for_zero_fires_first_evaluation(tmp_path):
    db = _db(tmp_path)
    fn = FakeNotifier()
    ev = _evaluator(db, _group([{
        "alert": "Hot", "expr": "temp > 10"}]), notifier=fn)
    try:
        t0 = time.time() - 30
        _write(db, "temp", {"zone": "a"}, 50.0, t0 - 1)
        ev.evaluate_once(t0)
        assert ev.alerts_json()[0]["state"] == STATE_FIRING
        assert fn.flat()[0]["status"] == "firing"
    finally:
        ev._leader.close()
        db.close()


def test_alerts_synthetic_series_and_staleness(tmp_path):
    """ALERTS{alertstate=} is written each evaluation and the old
    state's series ends with a staleness marker on transition."""
    db = _db(tmp_path)
    ev = _evaluator(db, _group([{
        "alert": "Down", "expr": "up == 0", "for": "5s"}]))
    eng = Engine(db, NS, device_serving=False)
    try:
        t0 = time.time() - 30
        _write(db, "up", {"instance": "i0"}, 0.0, t0 - 1)
        ev.evaluate_once(t0)
        mat, _ = eng.query_instant_with_meta(
            'ALERTS{alertstate="pending"}', int(t0 * 1e9))
        vals = [float(r[0]) for r in mat.values
                if not math.isnan(float(r[0]))]
        assert vals == [1.0]

        ev.evaluate_once(t0 + 6)  # fires
        t = int((t0 + 6) * 1e9)
        mat, _ = eng.query_instant_with_meta(
            'ALERTS{alertstate="firing"}', t)
        vals = [float(r[0]) for r in mat.values
                if not math.isnan(float(r[0]))]
        assert vals == [1.0]
        # the pending series ended at the transition (NaN staleness
        # marker -> instant lookup sees no live pending series)
        mat, _ = eng.query_instant_with_meta(
            'ALERTS{alertstate="pending"}', t)
        vals = [float(r[0]) for r in mat.values
                if not math.isnan(float(r[0]))]
        assert vals == []
    finally:
        ev._leader.close()
        db.close()


# --- restart / KV persistence -------------------------------------------------


def test_restart_resumes_for_timer_from_kv(tmp_path):
    """A new evaluator (restart or takeover) continues the pending
    timer from the persisted active_at — it does NOT restart it."""
    db = _db(tmp_path)
    store = MemStore()
    rules = [{"alert": "Down", "expr": "up == 0", "for": "10s"}]
    t0 = time.time() - 60
    _write(db, "up", {"instance": "i0"}, 0.0, t0 - 1)

    a = _evaluator(db, _group(rules), store=store, instance="a")
    a.evaluate_once(t0)  # pending, active_at = t0, persisted
    a._leader.close()

    fn = FakeNotifier()
    b = _evaluator(db, _group(rules), store=store, instance="b",
                   notifier=fn)
    try:
        b._load_state()
        (alert,) = b.alerts_json()
        assert alert["state"] == STATE_PENDING

        b.evaluate_once(t0 + 6)  # 6s since the ORIGINAL active_at
        assert b.alerts_json()[0]["state"] == STATE_PENDING

        b.evaluate_once(t0 + 10.5)  # original timer elapsed: fires
        assert b.alerts_json()[0]["state"] == STATE_FIRING
        assert len(fn.flat()) == 1
    finally:
        b._leader.close()
        db.close()


def test_restart_does_not_refire_firing_alert(tmp_path):
    db = _db(tmp_path)
    store = MemStore()
    rules = [{"alert": "Down", "expr": "up == 0", "for": "1s"}]
    t0 = time.time() - 60
    _write(db, "up", {"instance": "i0"}, 0.0, t0 - 1)

    fn_a = FakeNotifier()
    a = _evaluator(db, _group(rules), store=store, instance="a",
                   notifier=fn_a)
    a.evaluate_once(t0)
    a.evaluate_once(t0 + 2)  # fires
    assert len(fn_a.flat()) == 1
    a._leader.close()

    fn_b = FakeNotifier()
    b = _evaluator(db, _group(rules), store=store, instance="b",
                   notifier=fn_b)
    try:
        b._load_state()
        b.evaluate_once(t0 + 4)
        b.evaluate_once(t0 + 6)
        assert b.alerts_json()[0]["state"] == STATE_FIRING
        assert fn_b.flat() == []  # already fired before the restart
    finally:
        b._leader.close()
        db.close()


# --- recording rules ----------------------------------------------------------


def test_recording_rule_output_queryable_with_promql(tmp_path):
    db = _db(tmp_path)
    ev = _evaluator(db, _group([{
        "record": "zone:temp:count",
        "expr": "count by (zone) (temp)",
        "labels": {"plane": "rules"}}]))
    eng = Engine(db, NS, device_serving=False)
    try:
        t0 = time.time() - 30
        for i in range(3):
            _write(db, "temp", {"zone": "a", "host": "h%d" % i},
                   20.0 + i, t0 - 1)
        _write(db, "temp", {"zone": "b", "host": "h9"}, 30.0, t0 - 1)

        rec0 = instrument.counter("m3_rules_recorded_samples_total").value
        ev.evaluate_once(t0)
        assert instrument.counter(
            "m3_rules_recorded_samples_total").value - rec0 == 2

        # recorded series selectable by name AND by the rule's extra
        # label, grouped output intact
        mat, _ = eng.query_instant_with_meta(
            'zone:temp:count{plane="rules"}', int(t0 * 1e9))
        got = {m[b"zone"].decode(): float(r[0])
               for m, r in zip(mat.labels, mat.values)}
        assert got == {"a": 3.0, "b": 1.0}

        # recorded series are rule inputs too (rule chaining)
        mat, _ = eng.query_instant_with_meta(
            'sum(zone:temp:count)', int(t0 * 1e9))
        assert [float(r[0]) for r in mat.values] == [4.0]
    finally:
        ev._leader.close()
        db.close()


def test_rule_queries_attributed_to_rules_tenant(tmp_path):
    """Evaluation queries stamp initiator rule:<group>/<name> and
    tenant _rules into the slow-query cost records."""
    db = _db(tmp_path)
    ev = _evaluator(db, _group([{
        "record": "t:c", "expr": "count(temp)"}], name="attr"))
    try:
        t0 = time.time() - 30
        _write(db, "temp", {"zone": "a"}, 1.0, t0 - 1)
        slowlog.log().clear()
        ev.evaluate_once(t0)
        rec = slowlog.log().records()[0]
        assert rec["initiator"] == "rule:attr/t:c"
        assert rec["tenant"] == "_rules"
        assert slowlog.current_initiator() == "http"  # scope restored
    finally:
        ev._leader.close()
        db.close()


# --- leader election ----------------------------------------------------------


def test_leader_failover_evaluates_exactly_once(tmp_path):
    """Two coordinators share one KV store: only the leaseholder
    evaluates; on failover the successor neither re-evaluates an
    interval the old leader covered (no double-fire / double-count)
    nor gaps longer than 2 intervals."""
    db = _db(tmp_path)
    store = MemStore()
    rules = [{"record": "t:c", "expr": "count(temp)"}]
    t0 = time.time() - 60
    _write(db, "temp", {"zone": "a"}, 1.0, t0 - 1)

    eval_log = []

    def logged_write(ns, ids, tags, times, values):
        eval_log.append(times[0] / 1e9)
        return db.write_batch(ns, ids, tags, times, values)

    a = _evaluator(db, _group(rules, interval="1s"), store=store,
                   instance="a", write_fn=logged_write)
    b = _evaluator(db, _group(rules, interval="1s"), store=store,
                   instance="b", write_fn=logged_write)
    try:
        assert a.tick(t0) is True          # a acquires and evaluates
        assert b.tick(t0 + 0.1) is False   # b is a follower
        assert a.is_leader() and not b.is_leader()
        assert len(eval_log) == 1

        a._leader.resign()                 # a dies / hands off

        # b takes over mid-interval: the KV last_eval guard skips the
        # interval a already covered
        assert b.tick(t0 + 0.3) is False
        assert b.is_leader()
        assert len(eval_log) == 1

        # next interval: b evaluates; total gap stays <= 2 intervals
        assert b.tick(t0 + 1.2) is True
        assert len(eval_log) == 2
        gap = eval_log[1] - eval_log[0]
        assert 0.5 <= gap <= 2.0, gap

        # a comes back as a follower: no split-brain double eval
        assert a.tick(t0 + 1.3) is False
    finally:
        a._leader.close()
        b._leader.close()
        db.close()


def test_handoff_writes_staleness_for_emitted_series(tmp_path):
    db = _db(tmp_path)
    store = MemStore()
    rules = [{"record": "t:c", "expr": "count(temp)"}]
    t0 = time.time() - 30
    _write(db, "temp", {"zone": "a"}, 1.0, t0 - 1)

    staleness = []

    def spy_write(ns, ids, tags, times, values):
        staleness.extend(v for v in values if math.isnan(v))
        return db.write_batch(ns, ids, tags, times, values)

    a = _evaluator(db, _group(rules), store=store, instance="a",
                   write_fn=spy_write)
    b = _evaluator(db, _group(rules), store=store, instance="b")
    try:
        assert a.tick(t0) is True
        a._leader.resign()
        assert b.tick(t0 + 1.2) is True    # b now holds the lease
        assert a.tick(t0 + 1.3) is False   # a notices it lost it
        assert staleness, "old leader must end its emitted series"
    finally:
        a._leader.close()
        b._leader.close()
        db.close()


# --- device tier / compile cache ----------------------------------------------


@pytest.mark.parametrize("n_recording,n_alerting", [(1, 0), (4, 3)])
def test_steady_state_evaluation_reuses_compile_cache(tmp_path,
                                                      n_recording,
                                                      n_alerting):
    """Rule expressions are fixed-shape instant queries: after the
    first evaluation compiles the fused plan, every subsequent tick
    must re-hit the plan compile cache (the device tier's contract
    for repeated dashboards — and rules are machine dashboards).  The
    second case is a rule load's shape (recording and alerting rules,
    each over its own job): at steady state at least nine evaluations
    in ten are hits, a count of work and not a time."""
    db = _db(tmp_path)
    t0 = time.time() - 600
    for i in range(4):
        for k in range(10):  # a rate() window needs >= 2 points
            _write(db, "reqs", {"job": "j%d" % i}, float(k * 5),
                   t0 - 300 + k * 30)
    rules = [{"record": "job:reqs:rate_%d" % i,
              "expr": "sum by (job) (rate(reqs[5m]))" if n_recording == 1
              else 'sum by (job) (rate(reqs{job="j%d"}[5m]))' % i}
             for i in range(n_recording)]
    rules += [{"alert": "HighRate%d" % i, "for": "1m",
               # a threshold the data never crosses: the query is paid
               # for, the alert plane stays inactive
               "expr": 'sum(rate(reqs{job="j%d"}[5m])) > 1e15' % (i % 4)}
              for i in range(n_alerting)]
    ev = _evaluator(db, _group(rules),
                    engine=Engine(db, NS, device_serving=True))
    hits = instrument.counter("m3_query_compile_cache_hits_total")
    misses = instrument.counter("m3_query_compile_cache_misses_total")
    try:
        ev.evaluate_once(t0)  # compile (cache miss) paid here
        h0, m0 = hits.value, misses.value
        for i in range(3):
            ev.evaluate_once(t0 + 1 + i)
        n_hits, n_misses = hits.value - h0, misses.value - m0
        assert n_hits >= 3 * len(rules)
        assert n_hits >= 0.9 * (n_hits + n_misses)
        if len(rules) == 1:
            assert n_misses == 0
    finally:
        ev._leader.close()
        db.close()


# --- notifier units -----------------------------------------------------------


def _http_error(code, headers=None):
    msg = Message()
    for k, v in (headers or {}).items():
        msg[k] = v
    return urllib.error.HTTPError("http://x", code, "err", msg, None)


def test_notifier_delivers_alertmanager_v4_payload():
    sent = []
    n = WebhookNotifier("http://x", transport=sent.append,
                        max_queue=8)
    try:
        n.enqueue([{"status": "firing", "labels": {"alertname": "A"},
                    "annotations": {}, "startsAt": "t", "endsAts": "",
                    "value": 1.0}])
        assert n.flush(5.0)
        (payload,) = sent
        doc = json.loads(payload)
        assert doc["version"] == "4"
        assert doc["alerts"][0]["labels"]["alertname"] == "A"
    finally:
        n.close()


def test_notifier_retries_with_backoff_then_succeeds():
    calls = []

    def flaky(payload):
        calls.append(payload)
        if len(calls) < 3:
            raise OSError("conn refused")

    sleeps = []
    n = WebhookNotifier("http://x", transport=flaky, max_retries=3,
                        sleep=sleeps.append)
    try:
        sent0 = instrument.counter("m3_rules_notifications_total").value
        n.enqueue([{"status": "firing", "labels": {}}])
        assert n.flush(5.0)
        assert len(calls) == 3
        assert len(sleeps) >= 2  # backed off between attempts
        assert instrument.counter(
            "m3_rules_notifications_total").value - sent0 == 1
    finally:
        n.close()


def test_notifier_honors_retry_after_on_429():
    calls = []

    def throttled(payload):
        calls.append(payload)
        if len(calls) == 1:
            raise _http_error(429, {"Retry-After": "1.5"})

    sleeps = []
    n = WebhookNotifier("http://x", transport=throttled,
                        sleep=sleeps.append)
    try:
        n.enqueue([{"status": "firing", "labels": {}}])
        assert n.flush(5.0)
        assert len(calls) == 2
        # the receiver's hint paced the retry (plus normal backoff)
        assert 1.5 in sleeps
    finally:
        n.close()


def test_notifier_breaker_fails_fast_once_tripped():
    def dead(payload):
        raise OSError("down")

    sleeps = []
    n = WebhookNotifier("http://x", transport=dead, max_retries=1,
                        sleep=sleeps.append,
                        breaker_kwargs={"consecutive_failures": 2,
                                        "open_timeout": 60.0})
    try:
        errs0 = instrument.counter(
            "m3_rules_notification_errors_total").value
        drop0 = instrument.counter(
            "m3_rules_notifications_dropped_total").value
        for _ in range(4):
            n.enqueue([{"status": "firing", "labels": {}}])
        assert n.flush(10.0)
        # every batch errored + was dropped; once the breaker opened
        # later batches failed fast (BreakerOpenError is
        # non-retryable, so attempts stop growing)
        assert instrument.counter(
            "m3_rules_notification_errors_total").value - errs0 == 4
        assert instrument.counter(
            "m3_rules_notifications_dropped_total").value - drop0 == 4
    finally:
        n.close()


def test_notifier_bounds_payload_and_sheds():
    sent = []
    n = WebhookNotifier("http://x", transport=sent.append,
                        max_batch=10, max_payload_bytes=1024)
    try:
        drop0 = instrument.counter(
            "m3_rules_notifications_dropped_total").value
        big = [{"status": "firing",
                "labels": {"alertname": "A%d" % i, "pad": "x" * 120}}
               for i in range(10)]
        n.enqueue(big)
        assert n.flush(5.0)
        assert sent, "a trimmed payload must still go out"
        assert all(len(p) <= 1024 for p in sent)
        assert instrument.counter(
            "m3_rules_notifications_dropped_total").value > drop0
    finally:
        n.close()


def test_notifier_queue_overflow_drops_and_counts():
    gate = threading.Event()

    def wedged(payload):
        gate.wait(timeout=30.0)

    n = WebhookNotifier("http://x", transport=wedged, max_queue=1)
    try:
        drop0 = instrument.counter(
            "m3_rules_notifications_dropped_total").value
        t0 = time.monotonic()
        for _ in range(8):  # wedged sender: queue fills, rest drop
            n.enqueue([{"status": "firing", "labels": {}}])
        # the producer side never blocked on the wedged receiver
        assert time.monotonic() - t0 < 1.0
        assert instrument.counter(
            "m3_rules_notifications_dropped_total").value > drop0
    finally:
        gate.set()
        n.close()


# --- 2-node e2e ----------------------------------------------------------------


class _WebhookReceiver:
    """Local Alertmanager stand-in capturing webhook POSTs."""

    def __init__(self):
        recv = self

        class Handler(BaseHTTPRequestHandler):
            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                recv.posts.append(json.loads(self.rfile.read(n)))
                self.send_response(200)
                self.end_headers()

            def log_message(self, *a):
                pass

        self.posts = []
        self.httpd = HTTPServer(("127.0.0.1", 0), Handler)
        self.port = self.httpd.server_address[1]
        self._thread = threading.Thread(
            target=self.httpd.serve_forever,
            daemon=True)  # lint: allow-unregistered-thread (test stub)
        self._thread.start()

    def alerts(self, status=None):
        out = [a for p in self.posts for a in p.get("alerts", [])]
        if status:
            out = [a for a in out if a.get("status") == status]
        return out

    def close(self):
        self.httpd.shutdown()
        self.httpd.server_close()


def _get_json(url, timeout=10.0):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return json.load(resp)


def _co_yml(tmp_path, hook_port):
    p = tmp_path / "co.yml"
    p.write_text(f"""
coordinator:
  path: {tmp_path}/data-co
  num_shards: 4
  instance_id: coord-rules
  self_scrape:
    enabled: true
    interval: 100ms
  observe:
    enabled: true
    watchdog_interval: 100ms
    watchdog_deadline: 1s
  rules:
    enabled: true
    election_ttl: 2s
    groups:
      - name: platform
        interval: 200ms
        rules:
          - record: stalled:watchdog:max
            expr: max(m3_watchdog_stalled_total)
          - alert: BackgroundJobStalled
            expr: m3_watchdog_stalled_total > 0
            for: 400ms
            labels:
              severity: page
            annotations:
              summary: "{{{{ $labels.job }}}} wedged"
    notify:
      url: http://127.0.0.1:{hook_port}/hook
      timeout: 2s
      deadline: 5s
""")
    return str(p)


def test_two_node_stall_alert_e2e_with_restart(tmp_path):
    """DB node + coordinator: a wedged index compactor flips the
    watchdog stall metric, the alert rides pending -> firing, exactly
    one firing webhook is delivered, and a coordinator restart
    resumes the firing state from KV without re-firing."""
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
    watchdog_interval: 100ms
    watchdog_deadline: 1s
""")
    hook = _WebhookReceiver()
    store = MemStore()  # shared across the restart, like a real etcd
    cfg_path = _co_yml(tmp_path, hook.port)
    svc_db = DBNodeService(load_dbnode_config(str(db_yml))).start()
    svc_co = CoordinatorService(load_coordinator_config(cfg_path),
                                kv_store=store).start()
    release = threading.Event()
    svc_co2 = None
    try:
        base = f"http://127.0.0.1:{svc_co.http_port}"

        # rules surface is live before any alert exists
        body = _get_json(f"{base}/api/v1/rules")
        groups = body["data"]["groups"]
        assert [g["name"] for g in groups] == ["platform"]
        assert "rules" in body  # legacy r2 ruleset key intact
        assert _get_json(f"{base}/api/v1/alerts")["data"]["alerts"] == []

        # -- wedge index compaction on the DB NODE --
        idx = svc_db.db._namespaces["default"].index
        idx.compact = lambda: release.wait(timeout=120.0)
        idx._compact_wake.set()
        idx._ensure_compactor()

        # stall metric -> _m3_internal -> rule fires -> webhook
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            if hook.alerts("firing"):
                break
            time.sleep(0.2)
        firing = hook.alerts("firing")
        assert firing, "firing webhook never arrived"
        assert firing[0]["labels"]["alertname"] == "BackgroundJobStalled"
        assert firing[0]["labels"]["severity"] == "page"
        assert "wedged" in firing[0]["annotations"]["summary"]

        # /api/v1/alerts agrees
        alerts = _get_json(f"{base}/api/v1/alerts")["data"]["alerts"]
        assert any(a["state"] == "firing" for a in alerts)

        # the recording rule's output is queryable over _m3_internal
        q = urllib.parse.urlencode({
            "query": "stalled:watchdog:max",
            "time": f"{time.time():.3f}",
            "namespace": NS,
        })
        body = _get_json(f"{base}/api/v1/query?{q}")
        res = body["data"]["result"]
        assert res and float(res[0]["value"][1]) >= 1.0

        n_firing_before = len(hook.alerts("firing"))

        # -- restart the coordinator (same KV store, same data dir) --
        svc_co.stop()
        svc_co2 = CoordinatorService(
            load_coordinator_config(cfg_path), kv_store=store).start()
        base = f"http://127.0.0.1:{svc_co2.http_port}"

        # firing state is back (loaded from KV), without a second
        # firing notification — fired_at survived the restart
        deadline = time.monotonic() + 60.0
        state = None
        while time.monotonic() < deadline:
            alerts = _get_json(f"{base}/api/v1/alerts")["data"]["alerts"]
            fir = [a for a in alerts if a["state"] == "firing"]
            if fir:
                state = fir[0]
                break
            time.sleep(0.2)
        assert state is not None, "firing alert lost across restart"
        time.sleep(1.0)  # a few more evaluation intervals
        assert len(hook.alerts("firing")) == n_firing_before, \
            "restart must not re-fire an already-firing alert"
    finally:
        release.set()
        if svc_co2 is not None:
            svc_co2.stop()
        else:
            svc_co.stop()
        svc_db.stop()
        hook.close()
        while observe.recorder() is not None or \
                observe.watchdog() is not None:
            observe.release()


if __name__ == "__main__":
    import sys
    sys.exit(pytest.main([__file__, "-v"]))
