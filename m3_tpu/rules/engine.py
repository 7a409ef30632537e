"""Recording + alerting rules engine: the platform acts on its own
telemetry.

The reference pairs M3 with Prometheus rule evaluation; here the loop
closes in-process: rule groups evaluate PromQL over the self-scraped
``_m3_internal`` namespace through the SAME fused device query tier
that serves user queries (``query/engine.Engine`` — fixed-shape
instant queries, so steady-state evaluation rides the plan compile
cache), write recording-rule output back through the self-scrape
write seam so recorded series are themselves queryable and retained,
and drive the full Prometheus alerting state machine
(inactive → pending → firing → resolved) with ``ALERTS{alertstate=}``
synthetic series.

Cluster semantics (ref: prometheus rule groups + m3aggregator's
leader/follower flush):

- **One evaluator per group.**  Every coordinator runs a per-group
  evaluation daemon, but only the holder of the group's KV lease
  (``cluster/election.LeaderService``, election id ``rules/<group>``)
  evaluates; followers campaign each tick and stand by.  On lease
  loss the old leader writes staleness markers for every series it
  emitted and drops its in-memory alert state.
- **Alert state lives in the KV store.**  ``for:`` timers
  (``active_at``) and fired-ness persist under
  ``_rules/state/<group>`` after every evaluation, so a coordinator
  restart or leader takeover RESUMES pending timers instead of
  resetting them, and never re-fires an already-firing alert.  A
  KV-persisted ``last_eval_wall`` guards takeover mid-interval:
  the new leader skips an evaluation the old one already covered.
- **Evaluation load is attributed.**  Queries run under tenant
  ``_rules`` and stamp ``initiator="rule:<group>/<name>"`` into the
  slow-query log, so rule-driven load is separable from user load in
  ``/debug/slowqueries`` and ``/debug/tenants``.
"""

from __future__ import annotations

import json
import math
import re
import threading
import time
from datetime import datetime, timezone

from m3_tpu.cluster.election import LeaderService
from m3_tpu.cluster.kv import ErrNotFound
from m3_tpu.query import slowlog
from m3_tpu.query.engine import Engine
from m3_tpu.utils import clock, instrument, tracing

_log = instrument.logger("rules")

RULES_TENANT = "_rules"
ALERTS_METRIC = b"ALERTS"

STATE_INACTIVE = "inactive"
STATE_PENDING = "pending"
STATE_FIRING = "firing"

# {{ $labels.foo }} / {{ $value }} — the subset of Prometheus template
# syntax alert annotations actually use in rule files
_TPL_RE = re.compile(
    r"\{\{\s*\$(?:labels\.([A-Za-z_][A-Za-z0-9_]*)|(value))\s*\}\}")


def _template(text, labels: dict, value: float) -> str:
    def sub(m):
        if m.group(2):
            return repr(float(value))
        return str(labels.get(m.group(1), ""))
    return _TPL_RE.sub(sub, str(text))


def _iso(wall_s: float) -> str:
    return datetime.fromtimestamp(wall_s, tz=timezone.utc).isoformat()


def _series_id(labels: dict) -> bytes:
    from m3_tpu.query.remote_write import series_id_from_labels
    return series_id_from_labels(labels)


class GroupEvaluator:
    """One rule group: an evaluation daemon + the group's leader
    election + its alert state machine.

    The thread loop only paces ticks; ``tick(now)`` / ``evaluate_once
    (now)`` take explicit wall-clock instants so tests drive the
    ``for:`` state machine with fake clocks."""

    def __init__(self, group, *, store, instance_id: str, engine: Engine,
                 write_fn, namespace: str, notifier=None,
                 election_ttl_s: float = 5.0, clock=time.time):
        self.group = group
        self._store = store
        self._engine = engine
        self._write = write_fn
        self.namespace = namespace
        self._notifier = notifier
        self._clock = clock
        self._interval_s = max(group.interval / 1e9, 0.01)
        self._leader = LeaderService(store, f"rules/{group.name}",
                                     instance_id,
                                     ttl_seconds=election_ttl_s)
        self._state_key = f"_rules/state/{group.name}"
        self._lock = threading.Lock()
        # alert key "<rule idx>:<labels fingerprint>" -> state dict
        self._alerts: dict[str, dict] = {}
        self._leading = False
        self._loaded = False
        self._last_eval = 0.0
        self._last_duration_s = 0.0
        self._rule_errors: dict[str, str] = {}
        # (name, sorted labels) -> (sid, byte tags): steady-state
        # evaluation repeats the same output series every tick
        self._sid_memo: dict[tuple, tuple[bytes, dict]] = {}
        # sid -> tags of every series this evaluator emitted since it
        # took leadership (staleness set for handoff, like selfscrape)
        self._seen: dict[bytes, dict] = {}
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._m_evals = instrument.counter("m3_rules_evaluations_total")
        self._m_failures = instrument.counter(
            "m3_rules_evaluation_failures_total")
        self._m_recorded = instrument.counter(
            "m3_rules_recorded_samples_total")
        self._m_fired = instrument.counter("m3_rules_alerts_fired_total")
        self._m_resolved = instrument.counter(
            "m3_rules_alerts_resolved_total")
        self._m_duration = instrument.histogram(
            "m3_rules_evaluation_seconds")
        self._g_last = instrument.gauge(
            "m3_rules_group_last_eval_timestamp", group=group.name)
        self._g_leader = instrument.gauge("m3_rules_leader",
                                          group=group.name)

    # -- daemon -----------------------------------------------------------

    def start(self) -> "GroupEvaluator":
        self._thread = threading.Thread(
            target=self._loop, daemon=True,
            name=f"rules-{self.group.name}")
        self._thread.start()
        return self

    def _loop(self) -> None:
        from m3_tpu import observe
        hb = observe.task_ledger().register_daemon(
            f"rules/{self.group.name}",
            interval_hint_s=self._interval_s)
        try:
            while not self._stop.wait(self._interval_s):
                hb.beat()
                try:
                    self.tick()
                except Exception as e:  # noqa: BLE001 — loop survives
                    self._m_failures.inc()
                    _log.error("rule group tick failed",
                               group=self.group.name, err=str(e)[:300])
        finally:
            hb.close()

    def stop(self, timeout: float = 5.0) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=timeout)
        with self._lock:
            if self._leading:
                self._write_staleness()
                self._leading = False
        self._g_leader.set(0.0)
        self._leader.close()

    # -- one tick ---------------------------------------------------------

    def tick(self, now: float | None = None) -> bool:
        """Campaign; evaluate if (and only if) this instance holds the
        group lease and the interval is due.  Returns True when an
        evaluation ran."""
        now = self._clock() if now is None else now
        lead = self._leader.campaign(block=False)
        self._g_leader.set(1.0 if lead else 0.0)
        with self._lock:
            if not lead:
                if self._leading:
                    # handoff: the next leader owns the state now —
                    # end our emitted series and drop local state
                    self._write_staleness()
                self._leading = False
                self._loaded = False
                return False
            if not self._loaded:
                self._load_state()
            self._leading = True
            if self._last_eval and \
                    now - self._last_eval < 0.5 * self._interval_s:
                # takeover mid-interval: the previous leader already
                # covered this interval (KV last_eval) — evaluating
                # again would double-count rates and double-fire
                return False
            self.evaluate_once(now)
            return True

    # -- state persistence ------------------------------------------------

    def _load_state(self) -> None:
        try:
            doc = self._store.get(self._state_key).json()
        except ErrNotFound:
            doc = {}
        except (ValueError, OSError):
            doc = {}
        self._alerts = dict(doc.get("alerts", {}))
        self._last_eval = float(doc.get("last_eval_wall", 0.0))
        self._loaded = True

    def _persist_state(self) -> None:
        self._store.set_json(self._state_key, {
            "last_eval_wall": self._last_eval,
            "alerts": self._alerts,
        })

    # -- evaluation -------------------------------------------------------

    def evaluate_once(self, now: float | None = None) -> None:
        """Evaluate every rule in the group at wall instant ``now``
        (callers hold no lock when using this directly in tests; the
        daemon path enters via ``tick`` which does)."""
        now = self._clock() if now is None else now
        t0 = time.perf_counter()
        t_nanos = int(now * 1e9)
        ids: list[bytes] = []
        tags: list[dict] = []
        values: list[float] = []
        notifications: list[dict] = []
        queried = self._query_rules(t_nanos)
        for idx, rule in enumerate(self.group.rules):
            try:
                mat, exc = queried[idx]
                if exc is not None:
                    raise exc
                if rule.record:
                    self._eval_recording(rule, mat, t_nanos,
                                         ids, tags, values)
                else:
                    self._eval_alerting(idx, rule, mat, now, t_nanos,
                                        ids, tags, values,
                                        notifications)
                self._rule_errors.pop(rule.name, None)
                self._m_evals.inc()
            except Exception as e:  # noqa: BLE001 — next rule still runs
                self._m_failures.inc()
                self._rule_errors[rule.name] = f"{type(e).__name__}: {e}"[:300]
                _log.warn("rule evaluation failed",
                          group=self.group.name, rule=rule.name,
                          err=str(e)[:300])
        if ids:
            try:
                self._write(self.namespace, ids, tags,
                            [t_nanos] * len(ids), values)
            except Exception as e:  # noqa: BLE001 — keep evaluating
                self._m_failures.inc()
                _log.warn("rule output write failed",
                          group=self.group.name, err=str(e)[:300])
        self._last_eval = now
        self._last_duration_s = time.perf_counter() - t0
        self._g_last.set(now)
        self._m_duration.observe(self._last_duration_s)
        try:
            self._persist_state()
        except Exception as e:  # noqa: BLE001 — KV down != eval down
            _log.warn("rule state persist failed",
                      group=self.group.name, err=str(e)[:300])
        if notifications and self._notifier is not None:
            self._notifier.enqueue(notifications)

    def _query_rules(self, t_nanos: int) -> list:
        """Run every rule's query for one tick; -> [(mat, exc)] in
        rule order, exactly one of the pair set.

        A rule group is the canonical shape-identical workload: every
        tick re-issues the same expressions over the same window, so
        with a serving batch scheduler installed the queries run
        concurrently inside ``serving.batch_scope()`` and coalesce
        into shared device dispatches (m3_tpu/serving/).  Without a
        scheduler they evaluate sequentially exactly as before —
        concurrency would buy nothing and reorder slowlog records for
        no benefit.  Per-rule error isolation is preserved either way:
        a failing query surfaces as its rule's exc, never aborts the
        tick."""
        from m3_tpu import serving

        def one(rule):
            try:
                with tracing.tenant_scope(RULES_TENANT), \
                        slowlog.initiator(
                            f"rule:{self.group.name}/{rule.name}"):
                    mat, _meta = self._engine.query_instant_with_meta(
                        rule.expr, t_nanos)
                return (mat, None)
            except Exception as e:  # noqa: BLE001 — next rule still runs
                return (None, e)

        rules = self.group.rules
        if serving.installed() is None or len(rules) < 2:
            return [one(r) for r in rules]

        def one_batched(rule):
            with serving.batch_scope():
                return one(rule)

        import concurrent.futures as cf
        with cf.ThreadPoolExecutor(
                max_workers=min(len(rules), 16),
                thread_name_prefix=f"rules-q-{self.group.name}") as pool:
            futs = [pool.submit(one_batched, r) for r in rules]
            # generous per-tick bound: one() already catches every
            # query-level error, so a hit here means a wedged engine
            return [f.result(timeout=600.0) for f in futs]

    def _eval_recording(self, rule, mat, t_nanos: int, ids, tags,
                        values) -> None:
        record = rule.record.encode()
        extra = tuple(sorted((str(k).encode(), str(v).encode())
                             for k, v in rule.labels.items()))
        n = 0
        for labels, row in zip(mat.labels, mat.values):
            v = float(row[0])
            if math.isnan(v):
                continue
            key = (record,
                   tuple(sorted((k, tv) for k, tv in labels.items()
                                if k != b"__name__")))
            memo = self._sid_memo.get(key)
            if memo is None:
                out = {b"__name__": record}
                for k, tv in labels.items():
                    if k != b"__name__":
                        out[k] = tv
                for k, tv in extra:
                    out[k] = tv
                memo = self._sid_memo[key] = (_series_id(out), out)
            ids.append(memo[0])
            tags.append(memo[1])
            values.append(v)
            self._seen.setdefault(memo[0], memo[1])
            n += 1
        if n:
            self._m_recorded.inc(n)

    def _eval_alerting(self, idx: int, rule, mat, now: float,
                       t_nanos: int, ids, tags, values,
                       notifications) -> None:
        for_s = rule.for_ / 1e9
        prefix = f"{idx}:"
        active: dict[str, tuple[dict, float]] = {}
        for labels, row in zip(mat.labels, mat.values):
            v = float(row[0])
            if math.isnan(v):
                continue
            lbl = {k.decode(): tv.decode() for k, tv in labels.items()
                   if k != b"__name__"}
            for k, tv in rule.labels.items():
                lbl[str(k)] = _template(tv, lbl, v)
            lbl["alertname"] = rule.alert
            fp = json.dumps(sorted(lbl.items()),
                            separators=(",", ":"))
            active[prefix + fp] = (lbl, v)

        for key, (lbl, v) in active.items():
            st = self._alerts.get(key)
            if st is None:
                st = self._alerts[key] = {
                    "state": STATE_PENDING, "active_at": now,
                    "fired_at": None, "labels": lbl,
                    "annotations": {}, "value": v,
                }
            st["value"] = v
            st["annotations"] = {
                str(k): _template(tv, lbl, v)
                for k, tv in rule.annotations.items()}
            if st["state"] == STATE_PENDING and \
                    now - st["active_at"] >= for_s:
                # pending long enough: FIRE (once — a restart reloads
                # fired_at from KV, so an already-firing alert never
                # re-enters this branch)
                self._emit_alert_sample(st["labels"], STATE_PENDING,
                                        t_nanos, ids, tags, values,
                                        stale=True)
                st["state"] = STATE_FIRING
                st["fired_at"] = now
                self._m_fired.inc()
                notifications.append({
                    "status": "firing", "labels": dict(lbl),
                    "annotations": dict(st["annotations"]),
                    "startsAt": _iso(st["active_at"]), "endsAt": "",
                    "value": v,
                })
            self._emit_alert_sample(st["labels"], st["state"], t_nanos,
                                    ids, tags, values)

        # series gone from the result vector: pending flaps reset to
        # inactive silently; firing alerts resolve (and notify)
        for key in [k for k in self._alerts
                    if k.startswith(prefix) and k not in active]:
            st = self._alerts.pop(key)
            self._emit_alert_sample(st["labels"], st["state"], t_nanos,
                                    ids, tags, values, stale=True)
            if st["state"] == STATE_FIRING:
                self._m_resolved.inc()
                notifications.append({
                    "status": "resolved", "labels": dict(st["labels"]),
                    "annotations": dict(st.get("annotations", {})),
                    "startsAt": _iso(st["active_at"]),
                    "endsAt": _iso(now),
                    "value": st.get("value", 0.0),
                })

    def _emit_alert_sample(self, lbl: dict, state: str, t_nanos: int,
                           ids, tags, values,
                           stale: bool = False) -> None:
        """One ``ALERTS{alertstate=...}`` sample (1.0, or a NaN
        staleness marker ending the series on a state transition)."""
        key = (ALERTS_METRIC, state,
               tuple(sorted(lbl.items())))
        memo = self._sid_memo.get(key)
        if memo is None:
            out = {b"__name__": ALERTS_METRIC,
                   b"alertstate": state.encode()}
            for k, v in lbl.items():
                out[str(k).encode()] = str(v).encode()
            memo = self._sid_memo[key] = (_series_id(out), out)
        ids.append(memo[0])
        tags.append(memo[1])
        values.append(float("nan") if stale else 1.0)
        if not stale:
            self._seen.setdefault(memo[0], memo[1])

    # -- handoff ----------------------------------------------------------

    def _write_staleness(self) -> None:
        """End every series this evaluator emitted (NaN staleness
        markers, the Prometheus convention) so the next leader's
        output doesn't continue ours seamlessly across a gap."""
        if not self._seen:
            self._alerts = {}
            return
        now = clock.now_nanos()
        sids = list(self._seen)
        try:
            self._write(self.namespace, sids,
                        [self._seen[s] for s in sids],
                        [now] * len(sids),
                        [float("nan")] * len(sids))
        except Exception as e:  # noqa: BLE001 — handoff is best-effort
            _log.warn("staleness write failed", group=self.group.name,
                      err=str(e)[:200])
        self._seen = {}
        self._alerts = {}

    # -- introspection (HTTP API) -----------------------------------------

    def is_leader(self) -> bool:
        return self._leader.is_leader()

    def alerts_json(self) -> list[dict]:
        with self._lock:
            alerts = [dict(st) for st in self._alerts.values()]
        return [{
            "labels": st["labels"],
            "annotations": st.get("annotations", {}),
            "state": st["state"],
            "activeAt": _iso(st["active_at"]),
            "value": repr(float(st.get("value", 0.0))),
        } for st in alerts]

    def to_json(self) -> dict:
        rules = []
        with self._lock:
            errors = dict(self._rule_errors)
            last_eval = self._last_eval
            duration = self._last_duration_s
            by_rule: dict[int, list[dict]] = {}
            for key, st in self._alerts.items():
                idx = int(key.split(":", 1)[0])
                by_rule.setdefault(idx, []).append({
                    "labels": st["labels"],
                    "annotations": st.get("annotations", {}),
                    "state": st["state"],
                    "activeAt": _iso(st["active_at"]),
                    "value": repr(float(st.get("value", 0.0))),
                })
        for idx, rule in enumerate(self.group.rules):
            err = errors.get(rule.name)
            entry = {
                "name": rule.name,
                "query": rule.expr,
                "labels": dict(rule.labels),
                "health": "err" if err else "ok",
                "lastError": err or "",
                "lastEvaluation": _iso(last_eval) if last_eval else "",
                "evaluationTime": duration,
            }
            if rule.record:
                entry["type"] = "recording"
            else:
                alerts = by_rule.get(idx, [])
                entry["type"] = "alerting"
                entry["duration"] = rule.for_ / 1e9
                entry["annotations"] = dict(rule.annotations)
                entry["alerts"] = alerts
                entry["state"] = (
                    STATE_FIRING if any(a["state"] == STATE_FIRING
                                        for a in alerts)
                    else STATE_PENDING if alerts else STATE_INACTIVE)
            rules.append(entry)
        return {
            "name": self.group.name,
            "interval": self._interval_s,
            "leader": self.is_leader(),
            "lastEvaluation": _iso(last_eval) if last_eval else "",
            "evaluationTime": duration,
            "rules": rules,
        }


class RulesEngine:
    """All configured rule groups over one shared query engine + one
    notification pipeline.  Built by ``CoordinatorService`` from
    ``RulesConfig``; also constructible directly in tests."""

    def __init__(self, db, store, cfg, instance_id: str, write_fn,
                 engine: Engine | None = None, notifier=None,
                 clock=time.time):
        self.cfg = cfg
        self.namespace = cfg.namespace
        self._engine = engine if engine is not None else Engine(
            db, cfg.namespace)
        self.notifier = notifier
        if self.notifier is None and cfg.notify.url:
            from m3_tpu.rules.notify import WebhookNotifier
            self.notifier = WebhookNotifier.from_config(cfg.notify)
        self.groups = [
            GroupEvaluator(g, store=store, instance_id=instance_id,
                           engine=self._engine, write_fn=write_fn,
                           namespace=cfg.namespace,
                           notifier=self.notifier,
                           election_ttl_s=cfg.election_ttl / 1e9,
                           clock=clock)
            for g in cfg.groups]

    def start(self) -> "RulesEngine":
        for g in self.groups:
            g.start()
        return self

    def stop(self, timeout: float = 5.0) -> None:
        for g in self.groups:
            g.stop(timeout=timeout)
        if self.notifier is not None:
            self.notifier.close(timeout=timeout)

    # -- HTTP API payloads -------------------------------------------------

    def groups_json(self) -> list[dict]:
        return [g.to_json() for g in self.groups]

    def alerts_json(self) -> list[dict]:
        out: list[dict] = []
        for g in self.groups:
            out.extend(g.alerts_json())
        return out
