"""Coordinator HTTP API (ref: src/query/api/v1/httpd/handler.go:136).

Routes (Prometheus-compatible envelope):
    POST /api/v1/prom/remote/write    snappy+protobuf remote write
    POST /api/v1/prom/remote/read     remote read (raw samples)
    POST /api/v1/influxdb/write       InfluxDB line protocol
    POST /api/v1/json/write           single-datapoint JSON write
    POST /search                      matcher tag search (index-only)
    GET/POST /api/v1/query_range      PromQL range query
    GET/POST /api/v1/query            PromQL instant query
    GET/POST /api/v1/m3ql             M3QL pipe-syntax query
    GET  /api/v1/labels               label names
    GET  /api/v1/label/<name>/values  label values
    GET  /api/v1/series               series matching matchers
    GET  /render, /metrics/find       Graphite render + find
    ...  /api/v1/rules[/<id>]         R2 rules CRUD (hot-reloaded)
    POST /api/v1/database/create, /api/v1/topic[/init],
         /api/v1/services/<svc>/placement[/init],
         /api/v1/services/m3db/namespace     cluster admin
    GET  /health, /metrics, /debug/dump      operational surfaces
    GET  /debug/profile, /debug/threads      sampling profiler + thread
                                             dump (pprof analog)
    GET  /debug/slowqueries                  per-query cost records
                                             (?min_seconds=, ?limit=)
    GET  /debug/traces                       finished spans; with
                                             ?trace_id= assembles the
                                             cross-node trace tree
    GET  /ctl                                operator console

Distributed tracing: a W3C ``traceparent`` request header joins this
request (and everything it fans out to — engine, session, remote
peers, device kernels) to the caller's trace; the response carries the
active context back in ``traceparent`` so callers can link logs.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import threading
import time
import urllib.parse
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np

from m3_tpu.cache import LRUCache
from m3_tpu import observe
from m3_tpu.client.session import ConsistencyError
from m3_tpu.query import remote_write
from m3_tpu.query.engine import Engine
from m3_tpu.query.promql import parse as promql_parse
from m3_tpu.storage.limits import (Deadline, QueryDeadlineExceeded,
                                   QueryLimitExceeded, QueryLimits)
from m3_tpu.storage.database import (ColdWriteError, Database,
                                     ResourceExhaustedError)
from m3_tpu.query import slowlog
from m3_tpu import attribution
from m3_tpu.resilience.admission import AdmissionRejected
from m3_tpu.utils import clock, instrument, native, snappy, tracing

# accepted remote-write request sizes in samples: the group-commit
# amortization upstream (m3_commitlog_group_batch_writes) only pays
# off if the edge actually sees batches — this histogram says so
_m_ingest_batch = instrument.histogram("m3_ingest_batch_samples")

_LABEL_VALUES_RE = re.compile(r"^/api/v1/label/([^/]+)/values$")
_PLACEMENT_RE = re.compile(
    r"^/api/v1/services/([a-zA-Z0-9_-]+)/placement(?:/init)?$")
_RULE_RE = re.compile(r"^/api/v1/rules/([A-Za-z0-9_.-]+)$")

# /debug/profile is single-flight across all handler threads (and all
# Handler instances sharing this process)
_PROFILE_LOCK = threading.Lock()


def _parse_time(s: str) -> int:
    """RFC3339 or unix seconds (float) -> nanos.  Raises ValueError on
    anything that cannot become an in-range int64 — callers turn that
    into a 400 at the API boundary instead of an int64 overflow
    mid-write."""
    try:
        t_ns = int(float(s) * 1e9)
    except ValueError:
        t = time.strptime(s.replace("Z", "+0000"), "%Y-%m-%dT%H:%M:%S%z")
        import calendar

        t_ns = calendar.timegm(t) * 1_000_000_000
    except OverflowError as e:  # float('1e999') -> inf
        raise ValueError(f"timestamp out of range: {s}") from e
    if not -(1 << 63) < t_ns < (1 << 63):
        # a nanosecond value passed where seconds belong lands here
        raise ValueError(f"timestamp out of range (unix seconds?): {s}")
    return t_ns


def _parse_step(s: str) -> int:
    try:
        return int(float(s) * 1e9)
    except ValueError:
        from m3_tpu.query.promql import parse_duration

        return parse_duration(s)


def _matrix_json(step_times, mat):
    result = []
    for labels, row in zip(mat.labels, mat.values):
        values = [
            [t / 1e9, repr(float(v))]
            for t, v in zip(step_times.tolist(), row.tolist())
            if not np.isnan(v)
        ]
        if values:
            result.append(
                {
                    "metric": {
                        k.decode(): v.decode() for k, v in labels.items()
                    },
                    "values": values,
                }
            )
    return {"resultType": "matrix", "result": result}


_REPLY_HEAD = b'{"status": "success", "data": '


def _matrix_reply(step_times, mat, warnings=None) -> tuple[bytes, str]:
    """A range query's success reply, ``{"status": "success", "data":
    <_matrix_json's document>[, "warnings": warnings]}``, as the bytes
    ``json.dumps`` gives it, and who rendered them: ``native``
    (native/json_wire.cc, in one call outside the interpreter lock) or
    ``python`` (``_matrix_json``: where the library cannot be built,
    or the values are not the [rows, steps] block the library reads)."""
    steps = np.ascontiguousarray(step_times, dtype=np.int64)
    values = np.ascontiguousarray(mat.values, dtype=np.float64)
    if values.shape == (len(mat.labels), len(steps)):
        metrics = [
            json.dumps({k.decode(): v.decode()
                        for k, v in labels.items()}).encode()
            for labels in mat.labels]
        tail = (b"}" if warnings is None else
                b', "warnings": %s}' % json.dumps(warnings).encode())
        try:
            return native.render_matrix_json_native(
                _REPLY_HEAD, steps, values, metrics, tail), "native"
        except (OSError, subprocess.CalledProcessError):
            pass  # no compiler here: utils/native.load keeps the failure
    body = {"status": "success", "data": _matrix_json(step_times, mat)}
    if warnings is not None:
        body["warnings"] = warnings
    return json.dumps(body).encode(), "python"


class _Handler(BaseHTTPRequestHandler):
    server_version = "m3tpu-coordinator/0.1"
    db: Database
    engine: Engine
    namespace: str
    dsw = None  # optional DownsamplerAndWriter (coordinator mode)
    kv_store = None  # optional control plane (admin placement/topic APIs)
    # degraded-mode query serving: server-wide limit defaults + the
    # per-query deadline ceiling the HTTP edge mints from
    default_limits: QueryLimits | None = None
    query_timeout_s: float = 30.0
    # span-export peers for /debug/traces assembly: objects exposing
    # trace_dump(trace_id) -> [span dicts] (NodeClient / RemoteStorage
    # / DatabaseNode all qualify)
    trace_peers: tuple = ()
    # optional resilience.AdmissionController guarding the write
    # routes: over-watermark ingest sheds with 429 + Retry-After
    # instead of blocking the writer inside the storage engine
    admission = None
    # graphite: device-lowering knob + per-namespace engine cache
    # (keeps fused compile caches warm across render requests)
    graphite_device: bool | None = None
    _graphite_engines: dict = {}

    def log_message(self, fmt, *args):  # quiet
        pass

    def _reply(self, code: int, body: dict | bytes,
               content_type="application/json", headers=None):
        payload = body if isinstance(body, bytes) else json.dumps(body).encode()
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(payload)))
        if self._trace_ctx is not None:
            self.send_header("traceparent",
                             self._trace_ctx.to_traceparent())
        for name, value in (headers or {}).items():
            self.send_header(name, value)
        self.end_headers()
        self.wfile.write(payload)

    def _error(self, code: int, msg: str, error_type: str = "bad_data",
               headers=None):
        self._reply(code, {"status": "error", "errorType": error_type,
                           "error": msg}, headers=headers)

    def _admit(self, samples: int = 0, nbytes: int = 0) -> bool:
        """Admission gate for the write routes: True admits; False
        means the edge shed — the 429 + ``Retry-After`` reply has
        already been sent.  An admitted request must pair with
        ``_release`` (success or failure) in internal-accounting mode."""
        if self.admission is None:
            # still track per-tenant inflight cost (observe-only
            # m3_admission_tenant_share) — the gate itself is absent
            attribution.inflight_add(self._tenant, samples + nbytes)
            return True
        try:
            self.admission.admit(samples=samples, nbytes=nbytes)
        except AdmissionRejected as e:
            self._shed_reply(e)
            return False
        attribution.inflight_add(self._tenant, samples + nbytes)
        return True

    def _release(self, samples: int = 0, nbytes: int = 0) -> None:
        attribution.inflight_sub(self._tenant, samples + nbytes)
        if self.admission is not None:
            self.admission.release(samples=samples, nbytes=nbytes)

    def _shed_reply(self, e) -> None:
        self._error(
            429, f"write shed: {e}", error_type="overloaded",
            headers={"Retry-After":
                     str(max(1, int(round(e.retry_after_s))))})

    def _params(self) -> dict:
        parsed = urllib.parse.urlparse(self.path)
        params = dict(urllib.parse.parse_qsl(parsed.query))
        if self.command == "POST" and self.headers.get(
            "Content-Type", ""
        ).startswith("application/x-www-form-urlencoded"):
            n = int(self.headers.get("Content-Length", 0))
            params.update(urllib.parse.parse_qsl(self.rfile.read(n).decode()))
        return params

    # --- routes ---

    def do_GET(self):
        try:
            self._route()
        except Exception as e:  # pragma: no cover - defensive edge
            self._error(500, f"{type(e).__name__}: {e}")

    do_POST = do_GET
    do_DELETE = do_GET

    _KNOWN_ROUTES = frozenset({
        "/health", "/metrics", "/debug/dump", "/debug/profile",
        "/debug/threads", "/debug/slowqueries", "/debug/traces",
        "/debug/tenants", "/debug/heavyhitters", "/debug/device",
        "/debug/tasks", "/debug/batching", "/ctl",
        "/api/v1/prom/remote/write", "/api/v1/prom/remote/read",
        "/api/v1/influxdb/write", "/api/v1/json/write", "/search",
        "/api/v1/query_range", "/api/v1/m3ql",
        "/api/v1/query", "/api/v1/labels", "/api/v1/series", "/render",
        "/metrics/find", "/api/v1/graphite/metrics/find",
        "/api/v1/services/m3db/namespace",
        "/api/v1/services/m3db/namespace/schema", "/api/v1/topic/init",
        "/api/v1/topic", "/api/v1/database/create", "/api/v1/rules",
        "/api/v1/alerts",
        "/api/v1/placement", "/api/v1/placement/add",
        "/api/v1/placement/remove", "/api/v1/placement/replace",
    })

    def _route_label(self, path: str) -> str:
        """Bounded-cardinality route label: the matched PATTERN, never
        raw user paths (label-name segments, 404 scans)."""
        if path != "/" and path.endswith("/"):
            path = path.rstrip("/")  # /ctl/ counts as /ctl
        if path in self._KNOWN_ROUTES:
            return path
        if _LABEL_VALUES_RE.match(path):
            return "/api/v1/label/:name/values"
        if _PLACEMENT_RE.match(path):
            return "/api/v1/services/:service/placement"
        if _RULE_RE.match(path):
            return "/api/v1/rules/:id"
        return "other"

    def _route(self):
        path = urllib.parse.urlparse(self.path).path
        route = self._route_label(path)
        t0 = time.perf_counter()
        # the front end's time goes into the record of the query this
        # request runs: drop what an earlier request left on this thread
        slowlog.take_last_record()
        front: dict = {}
        self._front_stamps = front
        # count on ENTRY: a client that saw this request's reply must
        # see it in a subsequent /metrics scrape (a finally-increment
        # races the next request on another server thread)
        instrument.counter("m3_http_requests_total", route=route).inc()
        # W3C trace-context extract: a caller-supplied traceparent
        # makes this request (and its whole fan-out) part of the
        # caller's trace — and forces sampling, since its spans are
        # children of the propagated context, never sampled roots
        ctx = tracing.parse_traceparent(self.headers.get("traceparent"))
        # workload attribution: explicit M3-Tenant header > tenant
        # propagated on the trace context > this server's namespace
        self._tenant = attribution.safe_tenant(
            self.headers.get(attribution.TENANT_HEADER)
            or (ctx.tenant if ctx is not None else None)
            or self.namespace)
        observed = False
        try:
            with tracing.activate(ctx), \
                    tracing.tenant_scope(self._tenant):
                with tracing.span(tracing.HTTP_REQUEST, route=route,
                                  method=self.command) as sp:
                    self._trace_ctx = (tracing.current_context()
                                       if sp is not None else None)
                    # the front end's own time: from the request's
                    # span opening to the last byte written, but for
                    # the engine call (_engine_call stops it)
                    self._front = tracing.phase("frontend",
                                                front).start()
                    try:
                        self._route_inner(path)
                    finally:
                        # observe INSIDE the span: exemplar capture
                        # reads the active trace at observe() time, so
                        # this is what links a latency bucket to its
                        # trace on /metrics
                        self._front.stop()
                        observed = True
                        instrument.histogram(
                            "m3_http_request_seconds").observe(
                                time.perf_counter() - t0)
                        rec = slowlog.take_last_record()
                        if rec is not None:
                            phases = rec["phases"]
                            phases["frontend_s"] = front["frontend_s"]
                            # a matrix's render, inside frontend_s
                            phases["render_s"] = front.get("render_s", 0.0)
                            # what the front end waited for (a full
                            # collection under the render) beside
                            # what the engine did
                            for k in tracing.WAIT_KEYS:
                                phases[k] += front.get(k, 0.0)
        finally:
            if not observed:  # traceparent/span machinery itself blew up
                instrument.histogram("m3_http_request_seconds").observe(
                    time.perf_counter() - t0)

    def _engine_call(self, run, *args, **kwargs):
        """Call into the query engine with the front end's stamp
        stopped: the engine's time is in its own record."""
        self._front.stop()
        try:
            return run(*args, **kwargs)
        finally:
            # a clocked query's front end is clocked from here on,
            # into the record's own `cpu` (its `frontend_s`: the
            # reply's render and write; the request's parse ran
            # before the engine decided)
            rec = slowlog.last_record()
            self._front.start(None if rec is None else rec.get("cpu"))

    def _reply_matrix(self, step_times, mat, warnings=None, headers=None):
        """Send a range query's matrix, rendered under a stamp of its
        own (``render_s``, inside ``frontend_s``; clocked where the
        query is); the query's record says who rendered it."""
        rec = slowlog.last_record() or {}
        with tracing.phase("render", self._front_stamps, rec.get("cpu")):
            payload, form = _matrix_reply(step_times, mat, warnings)
        # the share the native library wrote: all or nothing a reply
        rec["reply_native_pct"] = 100.0 * (form == "native")
        instrument.counter("m3_http_reply_render_total", form=form).inc()
        self._reply(200, payload, headers=headers)

    # set per-request in _route; the active context echoes back to the
    # caller in the response's traceparent header (see _reply)
    _trace_ctx = None
    # resolved per-request in _route (attribution)
    _tenant = None

    def _debug_profile(self):
        """Sampling CPU profile in collapsed-stacks text (pprof
        analog; feed to flamegraph.pl/speedscope).

        With the flight recorder enabled this NEVER blocks: the
        response is read straight out of the recorder's window ring —
        default merges every retained window, ``?seconds=S`` merges
        the newest windows covering S, ``?window=N`` returns one
        window, ``?diff=A,B`` returns B−A (what got hotter), and
        ``?list=1`` returns JSON window metadata.  With the recorder
        disabled the legacy on-demand capture runs inline (bounded
        duration, single-flight)."""
        from m3_tpu.utils import profile as _prof
        from m3_tpu.observe.recorder import render as _render_stacks
        p = self._params()
        rec = observe.recorder()
        if rec is not None:
            try:
                if "list" in p:
                    self._reply(200, {"status": "success", "data": {
                        "windows": [w.meta() for w in rec.windows()]}})
                    return
                if "diff" in p:
                    a, b = (int(x) for x in p["diff"].split(","))
                    d = rec.diff(a, b)
                    if d is None:
                        self._error(404, f"profile: window expired "
                                    f"(have {[w.seq for w in rec.windows()]})")
                        return
                    counts, _, _ = d
                elif "window" in p:
                    w = rec.window(int(p["window"]))
                    if w is None:
                        self._error(404, f"profile: window expired "
                                    f"(have {[w.seq for w in rec.windows()]})")
                        return
                    counts = w.counts
                else:
                    span = (float(p["seconds"]) if "seconds" in p
                            else None)
                    counts, _ = rec.merged(span)
            except ValueError as e:
                self._error(400, f"profile: {e}")
                return
            self._reply(200, _render_stacks(counts).encode(),
                        content_type="text/plain; charset=utf-8")
            return
        # Legacy path (recorder disabled): inline capture on this
        # handler thread, bounded duration.
        try:
            seconds = float(p.get("seconds", "5"))
            hz = int(p.get("hz", "100"))
        except ValueError as e:
            self._error(400, f"profile: {e}")
            return
        # single-flight: each concurrent profile walks every
        # thread's frames at up to 250 Hz — stacked samplers are a
        # cheap resource-exhaustion vector on the ops port
        if not _PROFILE_LOCK.acquire(blocking=False):
            self._error(429, "profile: a profile is already running")
            return
        try:
            text = _prof.sample(
                seconds, hz,
                include_idle=p.get("include_idle") in ("1", "true"))
        finally:
            _PROFILE_LOCK.release()
        self._reply(200, text.encode(),
                    content_type="text/plain; charset=utf-8")

    def _debug_device(self):
        """Device-memory ledger: live buffers by owner, per-kernel
        peak-HBM estimates, compile-cache inventory.  ``?evict=NAME``
        (or ``all``) drops a compile cache through its registered
        evictor."""
        led = observe.device_ledger()
        p = self._params()
        if "evict" in p:
            name = p["evict"]
            evicted = led.compile_cache_evict(
                None if name in ("all", "") else name)
            self._reply(200, {"status": "success",
                              "data": {"evicted": evicted}})
            return
        self._reply(200, {"status": "success", "data": led.view()})

    def _debug_tasks(self):
        """Live task inspector: in-flight queries (phase, tenant,
        trace id, elapsed, device tier) + background-daemon heartbeats
        with stall flags.  ``?cancel=TASK_ID`` cooperatively cancels a
        running query (it aborts at its next deadline checkpoint)."""
        led = observe.task_ledger()
        p = self._params()
        if "cancel" in p:
            try:
                task_id = int(p["cancel"])
            except ValueError as e:
                self._error(400, f"tasks: {e}")
                return
            if not led.cancel(task_id):
                self._error(404, f"tasks: no in-flight task {task_id}")
                return
            self._reply(200, {"status": "success",
                              "data": {"cancelled": task_id}})
            return
        self._reply(200, {"status": "success", "data": led.view()})

    def _debug_traces(self):
        """Span export + cross-node trace assembly.

        Without ``trace_id``: the local tracer's recent finished spans
        (newest last).  With ``trace_id``: collects spans for that
        trace from the local ring AND every configured trace peer (the
        storage replicas' span-export endpoints), then assembles one
        nested trace tree — the coordinator-side view of a distributed
        query (ref: the reference's jaeger UI role)."""
        p = self._params()
        trace_id = p.get("trace_id")
        try:
            limit = int(p.get("limit", "256"))
        except ValueError as e:
            self._error(400, f"traces: {e}")
            return
        if not trace_id:
            self._reply(200, {"status": "success", "data": {
                "spans": tracing.tracer().finished(limit=limit)}})
            return
        spans = tracing.tracer().export(trace_id=trace_id)
        peers = {}
        for peer in self.trace_peers:
            name = getattr(peer, "id", None) or getattr(
                peer, "name", None) or repr(peer)
            try:
                got = peer.trace_dump(trace_id)
                spans.extend(got)
                peers[str(name)] = len(got)
            except Exception as e:  # noqa: BLE001 — assembly stays partial
                peers[str(name)] = f"error: {type(e).__name__}: {e}"
        tree = tracing.assemble_trace(spans, trace_id)
        tree["peers"] = peers
        self._reply(200, {"status": "success", "data": tree})

    def _debug_tenants(self):
        """Exact per-tenant cost table + inflight admission shares for
        THIS process (write/read counters; the sketch view with
        cross-node merge is /debug/heavyhitters)."""
        self._reply(200, {"status": "success",
                          "data": attribution.accountant().tenants_view()})

    def _debug_heavyhitters(self):
        """Heavy-hitter sketches (expensive query fingerprints,
        series-churn tenants, label-cardinality offenders), merged
        across this process and every attribution peer — the
        coordinator-side top-k view.  Peer dumps de-duplicate by
        accountant source_id, so an in-process cluster (all nodes
        sharing one process-global accountant) is not double-counted."""
        dumps = [attribution.accountant().dump()]
        peers = {}
        for peer in self.trace_peers:
            name = getattr(peer, "id", None) or getattr(
                peer, "name", None) or repr(peer)
            dump_fn = getattr(peer, "attribution_dump", None)
            if dump_fn is None:
                continue
            try:
                got = dump_fn()
                if got:
                    dumps.append(got)
                peers[str(name)] = "ok"
            except Exception as e:  # noqa: BLE001 — view stays partial
                peers[str(name)] = f"error: {type(e).__name__}: {e}"
        merged = attribution.merge_attribution_dumps(dumps)
        merged["peers"] = peers
        self._reply(200, {"status": "success", "data": merged})

    def _fastpath(self):
        """Lazily construct the per-server columnar ingest fast path
        (None when the native toolchain is unavailable)."""
        state = self._fastpath_state
        if state[0] is None:
            try:
                from m3_tpu.coordinator.fastpath import PromIngestFastPath

                state[0] = PromIngestFastPath(self.db, self.namespace)
            except Exception:
                state[0] = False
        return state[0] or None

    def _influx_fastpath(self):
        """Lazily construct the columnar influx line-protocol fast path
        (None when the native toolchain is unavailable)."""
        state = self._influx_fastpath_state
        if state[0] is None:
            try:
                from m3_tpu.coordinator.fastpath import InfluxFastPath

                state[0] = InfluxFastPath(self.db, self.namespace)
            except Exception:
                state[0] = False
        return state[0] or None

    def _route_inner(self, path: str):
        if self.command == "DELETE" and not _RULE_RE.match(path):
            # DELETE is valid ONLY on /api/v1/rules/<id>; aliasing it
            # onto GET behavior elsewhere would fake success
            self._error(405, f"DELETE not supported on {path}")
            return
        if path == "/health":
            # readiness-aware: 503 while the database bootstrap is in
            # flight (body carries the phase + replay progress so
            # operators and the rolling-restart driver can watch
            # catch-up) or while a graceful shutdown is draining, so
            # LBs and health checkers don't route to a node that
            # cannot serve yet (the flags read lock-free — bootstrap
            # holds the db lock)
            if getattr(self.db, "bootstrap_in_flight", False):
                body = {"ok": False, "status": "bootstrapping"}
                body.update(
                    getattr(self.db, "bootstrap_progress", {}) or {})
                self._reply(503, body)
                return
            if getattr(self.db, "draining", False):
                self._reply(503, {"ok": False, "status": "draining"})
                return
            self._reply(200, {"ok": True, "uptime": "ok",
                              "bootstrapped": True})
            return
        if path in ("/ctl", "/ctl/"):
            self._ctl_ui()
            return
        if path == "/metrics":
            self._reply(200, instrument.registry().render_prometheus(),
                        content_type="text/plain; version=0.0.4")
            return
        if path == "/debug/profile":
            self._debug_profile()
            return
        if path == "/debug/device":
            self._debug_device()
            return
        if path == "/debug/tasks":
            self._debug_tasks()
            return
        if path == "/debug/batching":
            # cross-query megabatching scheduler snapshot (dispatch /
            # solo-fallback counters, open admission groups, memo)
            from m3_tpu import serving
            self._reply(200, {"status": "success",
                              "data": serving.stats()})
            return
        if path == "/debug/threads":
            from m3_tpu.utils import profile as _prof
            self._reply(200, _prof.thread_dump().encode(),
                        content_type="text/plain; charset=utf-8")
            return
        if path == "/debug/slowqueries":
            p = self._params()
            try:
                min_seconds = float(p.get("min_seconds", "0"))
                limit = int(p.get("limit", "0"))
            except ValueError as e:
                self._error(400, f"slowqueries: {e}")
                return
            self._reply(200, {"status": "success", "data": {
                "queries": slowlog.log().records(
                    min_seconds=min_seconds, limit=limit)}})
            return
        if path == "/debug/traces":
            self._debug_traces()
            return
        if path == "/debug/tenants":
            self._debug_tenants()
            return
        if path == "/debug/heavyhitters":
            self._debug_heavyhitters()
            return
        if path == "/debug/dump":
            extra = {"namespaces": {
                name: {"series": len(self.db._ns(name).index)}
                for name in self.db.namespaces()}}
            if self.kv_store is not None:
                try:
                    from m3_tpu.cluster.kv import ErrNotFound
                    from m3_tpu.cluster.service import PlacementService
                    try:
                        p, v = PlacementService(
                            self.kv_store, key="_placement/m3db").placement()
                        extra["placement"] = p.to_dict()
                    except ErrNotFound:
                        pass
                except Exception:  # noqa: BLE001 - dump must not fail
                    pass
            self._reply(200, instrument.debug_dump(extra))
            return
        if path == "/api/v1/prom/remote/write":
            self._remote_write()
            return
        if path == "/api/v1/prom/remote/read":
            self._remote_read()
            return
        if path == "/api/v1/influxdb/write":
            self._influx_write()
            return
        if path == "/api/v1/json/write":
            self._json_write()
            return
        if path == "/search":
            self._search()
            return
        if path == "/api/v1/query_range":
            self._query_range()
            return
        if path == "/api/v1/m3ql":
            self._m3ql()
            return
        if path == "/api/v1/query":
            self._query_instant()
            return
        if path == "/api/v1/labels":
            names = self.db._ns(self.namespace).index.label_names()
            self._reply(200, {"status": "success",
                              "data": [n.decode() for n in names]})
            return
        m = _LABEL_VALUES_RE.match(path)
        if m:
            vals = self.db._ns(self.namespace).index.label_values(
                m.group(1).encode()
            )
            self._reply(200, {"status": "success",
                              "data": [v.decode() for v in vals]})
            return
        if path == "/api/v1/series":
            self._series()
            return
        if path == "/render":
            self._graphite_render()
            return
        if path in ("/metrics/find", "/api/v1/graphite/metrics/find"):
            self._graphite_find()
            return
        if self._admin_route(path):
            return
        self._error(404, f"unknown route {path}")

    # -- admin APIs (ref: src/query/api/v1/handler/{database,namespace,
    #    placement,topic}/ — operators drive the cluster through the
    #    coordinator) ------------------------------------------------------

    def _json_body(self) -> dict:
        n = int(self.headers.get("Content-Length", 0))
        if not n:
            return {}
        try:
            return json.loads(self.rfile.read(n))
        except ValueError:
            return {}

    def _admin_route(self, path: str) -> bool:
        if path == "/api/v1/services/m3db/namespace":
            if self.command == "POST":
                self._namespace_create(self._json_body())
            else:
                self._namespace_list()
            return True
        if (path == "/api/v1/services/m3db/namespace/schema"
                and self.command == "POST"):
            self._namespace_schema(self._json_body())
            return True
        if path == "/api/v1/placement":
            self._placement_status()
            return True
        if (path in ("/api/v1/placement/add", "/api/v1/placement/remove",
                     "/api/v1/placement/replace")
                and self.command == "POST"):
            self._placement_migrate(path.rsplit("/", 1)[1],
                                    self._json_body())
            return True
        m = _PLACEMENT_RE.match(path)
        if m:
            svc = m.group(1)
            if self.command == "POST":
                self._placement_init(svc, self._json_body())
            else:
                self._placement_get(svc)
            return True
        if path == "/api/v1/topic/init" and self.command == "POST":
            self._topic_init(self._json_body())
            return True
        if path == "/api/v1/topic":
            self._topic_get()
            return True
        if path == "/api/v1/database/create" and self.command == "POST":
            self._database_create(self._json_body())
            return True
        if path == "/api/v1/rules":
            self._rules(self._json_body() if self.command == "POST" else None)
            return True
        if path == "/api/v1/alerts":
            # Prometheus /api/v1/alerts: active (pending|firing)
            # alerts from the rules engine, empty when none attached
            eng = self.rules_engine
            self._reply(200, {
                "status": "success",
                "data": {"alerts":
                         eng.alerts_json() if eng is not None else []},
            })
            return True
        m = _RULE_RE.match(path)
        if m and self.command == "DELETE":
            self._rule_delete(m.group(1))
            return True
        return False

    _CTL_HTML: bytes | None = None

    def _ctl_ui(self):
        """Operator console (ref: src/ctl/ui/ — the r2 React app; here
        one static page over the same coordinator APIs)."""
        cls = type(self)
        if cls._CTL_HTML is None:
            import pathlib
            page = (pathlib.Path(__file__).resolve().parent.parent
                    / "ctl" / "ui.html")
            cls._CTL_HTML = page.read_bytes()
        self._reply(200, cls._CTL_HTML, content_type="text/html")

    def _rules(self, body: dict | None):
        """R2-style rules CRUD (ref: src/ctl/service/r2/): GET the
        document, POST {mapping_rules, rollup_rules} to replace or
        {mapping_rule: {...}} / {rollup_rule: {...}} to upsert one.
        The coordinator's matcher follows the KV key, so edits apply
        live."""
        from m3_tpu.metrics.rules_codec import (RuleStore,
                                                ruleset_from_dict,
                                                ruleset_to_dict)
        if self.kv_store is None:
            self._error(501, "no KV store configured")
            return
        store = RuleStore(self.kv_store)
        if body is None:
            # one document, two rule planes: the legacy "rules" key is
            # the r2 mapping/rollup ruleset (its CRUD clients assert on
            # it); "data.groups" is the Prometheus-shaped view of the
            # recording/alerting rule groups when an engine is attached
            eng = self.rules_engine
            self._reply(200, {
                "status": "success",
                "rules": ruleset_to_dict(store.get()),
                "data": {"groups":
                         eng.groups_json() if eng is not None else []},
            })
            return
        if not any(k in body for k in ("mapping_rule", "rollup_rule",
                                       "mapping_rules", "rollup_rules")):
            # an empty/typo'd body must NOT silently wipe the ruleset
            self._error(400, "rule document requires mapping_rule(s) "
                             "or rollup_rule(s)")
            return
        try:
            if "mapping_rule" in body:
                # ids are server-generated on create, like the r2
                # service (ref: src/ctl/service/r2/store); callers may
                # still pass one to upsert a specific rule
                rule = body["mapping_rule"]
                if not isinstance(rule, dict):
                    raise TypeError("mapping_rule must be an object")
                rule.setdefault("id", "mr-" + uuid.uuid4().hex[:12])
                rs = ruleset_from_dict({"mapping_rules": [rule]})
                out = store.add_mapping_rule(rs.mapping_rules[0])
            elif "rollup_rule" in body:
                rule = body["rollup_rule"]
                if not isinstance(rule, dict):
                    raise TypeError("rollup_rule must be an object")
                rule.setdefault("id", "rr-" + uuid.uuid4().hex[:12])
                rs = ruleset_from_dict({"rollup_rules": [rule]})
                out = store.add_rollup_rule(rs.rollup_rules[0])
            else:
                out = store.set(ruleset_from_dict(body))
        except (KeyError, ValueError, TypeError) as e:
            self._error(400, f"bad rule document: {e}")
            return
        self._reply(200, {"status": "success",
                          "rules": ruleset_to_dict(out)})

    def _rule_delete(self, rule_id: str):
        from m3_tpu.metrics.rules_codec import RuleStore, ruleset_to_dict
        if self.kv_store is None:
            self._error(501, "no KV store configured")
            return
        try:
            out = RuleStore(self.kv_store).delete_rule(rule_id)
        except KeyError:
            self._error(404, f"no rule with id {rule_id!r}")
            return
        self._reply(200, {"status": "success",
                          "rules": ruleset_to_dict(out)})

    def _namespace_schema(self, body: dict):
        """Roll a structured namespace's schema forward (ref: the
        reference's AddSchema admin, src/query/api/v1/handler/
        namespace/schema.go + kvadmin SetSchema).  Body:
        {"name": ns, "fields": [{"num": 1, "type": "f64"}, ...]}."""
        from m3_tpu.ops.struct_codec import Field, FieldType, Schema
        name = body.get("name")
        if not name:
            self._error(400, "namespace name required")
            return
        try:
            fields = tuple(
                Field(int(f["num"]), FieldType[str(f["type"]).upper()])
                for f in body.get("fields", []))
            schema = Schema(fields)
        except (KeyError, ValueError, TypeError) as e:
            self._error(400, f"bad schema: {e}")
            return
        try:
            self.db.update_namespace_schema(name, schema)
        except KeyError as e:
            self._error(404, str(e))
            return
        self._reply(200, {"status": "success",
                          "fields": [{"num": f.num,
                                      "type": f.ftype.name.lower()}
                                     for f in fields]})

    def _namespace_create(self, body: dict):
        err = self._do_namespace_create(body)
        if err is not None:
            self._error(*err)
            return
        self._namespace_list()

    def _do_namespace_create(self, body: dict) -> tuple[int, str] | None:
        """Create without replying; returns (code, message) on error."""
        from m3_tpu.storage.namespace import (NamespaceOptions,
                                              RetentionOptions)
        name = body.get("name")
        if not name:
            return 400, "namespace name required"
        if name in self.db.namespaces():
            return 409, f"namespace {name} exists"
        ret = body.get("retention", {})
        self.db.create_namespace(NamespaceOptions(
            name=name,
            retention=RetentionOptions(
                retention_period=int(ret.get("retention_period",
                                             48 * 3600 * 10**9)),
                block_size=int(ret.get("block_size", 2 * 3600 * 10**9)),
            ),
            snapshot_enabled=bool(body.get("snapshot_enabled", True)),
            aggregated=bool(body.get("aggregated", False)),
            aggregation_resolution=int(body.get("aggregation_resolution", 0)),
        ))
        return None

    def _namespace_list(self):
        from m3_tpu.metrics.policy import format_duration
        out = {}
        for name in self.db.namespaces():
            o = self.db.namespace_options(name)
            out[name] = {
                "retention": {
                    "retention_period": o.retention.retention_period,
                    "block_size": o.retention.block_size,
                },
                "snapshot_enabled": o.snapshot_enabled,
                "aggregated": o.aggregated,
                "aggregation_resolution": o.aggregation_resolution,
                # operator-readable duration form of the same fields
                # (what the retention ladder validates against; "raw"
                # = unaggregated)
                "resolution": (format_duration(o.aggregation_resolution)
                               if o.aggregation_resolution else "raw"),
                "retention_str": format_duration(
                    o.retention.retention_period),
            }
        self._reply(200, {"status": "success", "namespaces": out})

    def _placement_svc(self, svc: str):
        from m3_tpu.cluster.service import PlacementService
        if self.kv_store is None:
            self._error(501, "no KV store configured")
            return None
        return PlacementService(self.kv_store, key=f"_placement/{svc}")

    @staticmethod
    def _placement_instances(body: dict) -> list:
        from m3_tpu.cluster.placement import Instance
        return [
            Instance(id=i["id"], endpoint=i.get("endpoint", ""),
                     isolation_group=i.get("isolation_group", ""),
                     zone=i.get("zone", ""),
                     weight=int(i.get("weight", 1)))
            for i in body.get("instances", [])
        ]

    def _placement_init(self, svc: str, body: dict):
        ps = self._placement_svc(svc)
        if ps is None:
            return
        instances = self._placement_instances(body)
        if not instances:
            self._error(400, "instances required")
            return
        ps.build_initial(instances,
                         num_shards=int(body.get("num_shards", 64)),
                         replica_factor=int(body.get("replication_factor",
                                                     body.get("replica_factor", 1))))
        ps.mark_all_available()
        self._placement_get(svc)

    def _placement_get(self, svc: str):
        from m3_tpu.cluster.kv import ErrNotFound
        ps = self._placement_svc(svc)
        if ps is None:
            return
        try:
            placement, version = ps.placement()
        except ErrNotFound:
            self._error(404, f"no placement for {svc}")
            return
        self._reply(200, {"status": "success", "version": version,
                          "placement": placement.to_dict()})

    # -- live migration (ref: src/query/api/v1/handler/placement/
    #    {add,delete,replace}.go — operators mutate the goal state
    #    through the coordinator; every dbnode's reconciler converges
    #    onto the CAS'd placement while traffic keeps flowing) -----------

    def _placement_status(self):
        """GET /api/v1/placement: the dbnode placement with per-shard
        migration state and a convergence summary — the operator's
        progress view while reconcilers stream bootstraps."""
        from m3_tpu.cluster.kv import ErrNotFound
        ps = self._placement_svc("m3db")
        if ps is None:
            return
        try:
            p, version = ps.placement()
        except ErrNotFound:
            self._error(404, "no placement for m3db")
            return
        shards: dict[str, list] = {}
        summary = {"initializing": 0, "available": 0, "leaving": 0}
        for inst in p.sorted_instances():
            for s in inst.shards:
                ent = {"instance": inst.id, "state": s.state.name}
                if s.source_id:
                    ent["source"] = s.source_id
                shards.setdefault(str(s.id), []).append(ent)
                k = s.state.name.lower()
                if k in summary:
                    summary[k] += 1
        converged = (summary["initializing"] == 0
                     and summary["leaving"] == 0)
        self._reply(200, {"status": "success", "version": version,
                          "converged": converged, "summary": summary,
                          "shards": shards, "placement": p.to_dict()})

    def _placement_migrate(self, op: str, body: dict):
        """POST /api/v1/placement/{add,remove,replace}: goal-state
        mutation.  Replies with the new placement status so the caller
        sees the INITIALIZING/LEAVING plan it just created."""
        from m3_tpu.cluster.kv import ErrNotFound
        ps = self._placement_svc("m3db")
        if ps is None:
            return
        try:
            if op == "add":
                insts = self._placement_instances(body)
                if not insts:
                    self._error(400, "instances required")
                    return
                ps.add_instances(insts)
            elif op == "remove":
                ids = [str(i) for i in body.get("instance_ids", [])]
                if not ids:
                    self._error(400, "instance_ids required")
                    return
                ps.remove_instances(ids)
            else:
                leaving = [str(i) for i in body.get("leaving", [])]
                insts = self._placement_instances(body)
                if not leaving or not insts:
                    self._error(400, "leaving and instances required")
                    return
                ps.replace_instances(leaving, insts)
        except ErrNotFound:
            self._error(404, "no placement for m3db")
            return
        except (KeyError, ValueError, TypeError) as e:
            self._error(400, f"placement {op}: {e}")
            return
        self._placement_status()

    def _topic_init(self, body: dict):
        from m3_tpu.msg import (ConsumerService, ConsumptionType, Topic,
                                TopicService)
        if self.kv_store is None:
            self._error(501, "no KV store configured")
            return
        name = body.get("name")
        if not name:
            self._error(400, "topic name required")
            return
        consumers = tuple(
            ConsumerService(c["service"],
                            ConsumptionType(c.get("type", "shared")))
            for c in body.get("consumer_services", []))
        ts = TopicService(self.kv_store)
        if ts.exists(name):
            self._error(409, f"topic {name} exists")
            return
        topic = ts.create(Topic(name, int(body.get("number_of_shards", 64)),
                                consumers))
        self._reply(200, {"status": "success", "topic": topic.to_dict()})

    def _topic_get(self):
        from m3_tpu.cluster.kv import ErrNotFound
        from m3_tpu.msg import TopicService
        if self.kv_store is None:
            self._error(501, "no KV store configured")
            return
        name = self._params().get("name", "")
        try:
            topic = TopicService(self.kv_store).get(name)
        except ErrNotFound:
            self._error(404, f"no topic {name}")
            return
        self._reply(200, {"status": "success", "topic": topic.to_dict()})

    def _database_create(self, body: dict):
        """Convenience: namespace + m3db placement in one call, ONE
        response (ref: api/v1/handler/database/create.go)."""
        ns_body = dict(body.get("namespace", {}))
        ns_body.setdefault("name", body.get("namespace_name", "default"))
        if ns_body["name"] not in self.db.namespaces():
            err = self._do_namespace_create(ns_body)
            if err is not None:
                self._error(*err)
                return
        if body.get("instances") and self.kv_store is not None:
            self._placement_init("m3db", body)
        else:
            self._namespace_list()

    # -- graphite (ref: graphite render/find handlers,
    #    src/query/api/v1/handler/graphite/) --------------------------------

    # graphite relative-time units (ref: src/query/graphite/graphite/
    # timespec.go:42 periods map — mon=30d, y=365d, case-insensitive)
    _GRAPHITE_UNITS = {
        "s": 1, "sec": 1, "seconds": 1,
        "m": 60, "min": 60, "mins": 60, "minute": 60, "minutes": 60,
        "h": 3600, "hr": 3600, "hour": 3600, "hours": 3600,
        "d": 86400, "day": 86400, "days": 86400,
        "w": 604800, "week": 604800, "weeks": 604800,
        "mon": 30 * 86400, "month": 30 * 86400, "months": 30 * 86400,
        "y": 365 * 86400, "year": 365 * 86400, "years": 365 * 86400,
    }
    _GRAPHITE_REL = re.compile(r"^-([0-9]+)([a-z]+)$", re.IGNORECASE)

    def _graphite_time(self, raw: str, now_s: float) -> int:
        """Graphite from/until: epoch seconds or relative -3min style."""
        raw = raw.strip()
        if raw in ("now", ""):
            return int(now_s * 1e9)
        m = self._GRAPHITE_REL.match(raw)
        if m:
            unit = self._GRAPHITE_UNITS.get(m.group(2).lower())
            if unit is None:
                raise ValueError(f"bad relative time unit '{m.group(2)}'")
            t_ns = int((now_s - int(m.group(1)) * unit) * 1e9)
        else:
            # same int64 boundary guard as _parse_time: reject here
            # with a 400, never overflow mid-render
            try:
                t_ns = int(float(raw) * 1e9)
            except OverflowError as e:
                raise ValueError(f"time out of range: {raw}") from e
        if not -(1 << 63) < t_ns < (1 << 63):
            raise ValueError(f"time out of range (unix seconds?): {raw}")
        return t_ns

    def _graphite_engine(self):
        # keyed by (db, namespace): bare _Handler subclasses share the
        # class-level cache dict, so the db identity must be in the key
        key = (id(self.db), self.namespace)
        eng = self._graphite_engines.get(key)
        if eng is None:
            from m3_tpu.query.graphite import GraphiteEngine
            eng = GraphiteEngine(self.db, self.namespace,
                                 device=self.graphite_device)
            self._graphite_engines[key] = eng
        return eng

    def _graphite_render(self):
        p = self._params()
        targets = p.get("target")
        if not targets:
            self._error(400, "missing target")
            return
        if isinstance(targets, str):
            targets = [targets]
        now = clock.now_s()
        try:
            start = self._graphite_time(p.get("from", "-1h"), now)
            end = self._graphite_time(p.get("until", "now"), now)
            # Grafana sends maxDataPoints; derive the step from it the
            # way the reference render handler does (ceil of range/
            # points, aligned up to the storage resolution).  An
            # explicit `step` (seconds) param remains as an extension.
            res_ns = 10 * 10**9
            if "step" in p:
                step = int(p["step"]) * 10**9
            else:
                mdp = int(p.get("maxDataPoints", "0") or 0)
                if mdp > 0 and end > start:
                    raw = -(-(end - start) // mdp)
                    step = max(-(-raw // res_ns) * res_ns, res_ns)
                else:
                    step = res_ns
        except ValueError as e:
            self._error(400, f"bad render params: {e}")
            return
        eng = self._graphite_engine()
        out = []
        try:
            for target in targets:
                sl = self._engine_call(eng.render, target, start,
                                       end, step)
                for name, row in zip(sl.names, sl.values):
                    out.append({
                        "target": name,
                        "datapoints": [
                            [None if np.isnan(v) else float(v),
                             int(t) // 10**9]
                            for t, v in zip(sl.step_times, row)],
                    })
        except (ValueError, KeyError, IndexError, TypeError) as e:
            # malformed targets / unknown function arguments are the
            # USER's error, not a server fault
            self._error(400, f"{type(e).__name__}: {e}")
            return
        self._reply(200, json.dumps(out).encode())

    def _graphite_find(self):
        p = self._params()
        q = p.get("query")
        if not q:
            self._error(400, "missing query")
            return
        eng = self._graphite_engine()
        out = [{"id": name, "text": name, "leaf": int(leaf),
                "expandable": int(not leaf), "allowChildren":
                int(not leaf)}
               for name, leaf in eng.find(q)]
        self._reply(200, json.dumps(out).encode())

    def _influx_write(self):
        """InfluxDB line-protocol write (ref: src/query/api/v1/handler/
        influxdb/write.go): measurement_field naming, tags -> labels,
        routed through downsample-and-write when configured."""
        from m3_tpu.coordinator.influx import LineError, parse_lines

        params = dict(
            urllib.parse.parse_qsl(urllib.parse.urlparse(self.path).query))
        n = int(self.headers.get("Content-Length", 0))
        body = self.rfile.read(n)
        if self.headers.get("Content-Encoding") == "gzip":
            import gzip
            import zlib

            try:
                body = gzip.decompress(body)
            except (OSError, EOFError, zlib.error) as e:
                self._error(400, f"gzip: {e}")
                return
        precision = params.get("precision", "ns")
        if self._influx_write_columnar(body, precision):
            return
        try:
            points = parse_lines(body, precision)
        except (LineError, UnicodeDecodeError) as e:
            self._error(400, f"line protocol: {e}")
            return
        if self._ingest_points(points):
            self._reply(200, {"status": "success"})

    def _influx_write_columnar(self, body: bytes, precision: str) -> bool:
        """Columnar influx tier: C++ line decode into the shared slot
        router + group-commit WAL, scalar reference parse only for
        lines the strict grammar defers (malformed ones counted, not
        rejected — single bad lines must not fail a batch).  Returns
        True when the request was fully handled (including error
        replies); False hands the request to the scalar tier."""
        from m3_tpu.coordinator.influx import (_PRECISION_NANOS,
                                               parse_lines_tolerant)

        fp = self._influx_fastpath()
        mult = _PRECISION_NANOS.get(precision)
        if fp is None or mult is None or not fp.eligible(self.dsw):
            return False
        if not self._admit(nbytes=len(body)):
            return True
        now = clock.now_nanos()
        n_malformed = 0
        try:
            n_fast, fb = fp.write(body, mult, now)
            if fb:
                # deferred lines: the scalar reference decides, with
                # the same `now` the columnar decode stamped
                deferred = b"\n".join(body[o:o + ln] for o, ln in fb)
                points, n_malformed = parse_lines_tolerant(
                    deferred, precision, now)
                if points:
                    self._ingest_points_inner(points)
                n_fast += len(points)
        except ColdWriteError as e:
            self._error(400, f"write: {e}")
            return True
        except AdmissionRejected as e:
            self._shed_reply(e)
            return True
        except ResourceExhaustedError as e:
            self._error(429, f"write: {e}")
            return True
        finally:
            self._release(nbytes=len(body))
        if n_malformed:
            instrument.counter("m3_ingest_protocol_malformed_total",
                               protocol="influx").inc(n_malformed)
        _m_ingest_batch.observe(n_fast)
        self._reply(200, {"status": "success"})
        return True

    def _ingest_points(self, points) -> bool:
        """[(labels, t_nanos, value)] -> downsample-and-write when
        configured, else direct storage writes (one contract shared by
        the influx and json write handlers).  Returns False after
        replying 400 for a cold-write-gate rejection (bad data) or 429
        for a transient series limit / admission shed (retryable) —
        never 500."""
        if not self._admit(samples=len(points)):
            return False
        try:
            self._ingest_points_inner(points)
        except AdmissionRejected as e:
            self._shed_reply(e)  # shed deeper in the stack (queue)
            return False
        except ResourceExhaustedError as e:
            self._error(429, f"write rejected: {e}")
            return False
        except ValueError as e:
            self._error(400, f"write rejected: {e}")
            return False
        finally:
            self._release(samples=len(points))
        return True

    def _ingest_points_inner(self, points):
        if self.dsw is not None:
            from m3_tpu.coordinator.downsample import MetricKind

            self.dsw.write_batch([
                (labels.get(b"__name__", b""),
                 {k: v for k, v in labels.items() if k != b"__name__"},
                 MetricKind.GAUGE, value, t_nanos)
                for labels, t_nanos, value in points
            ])
            return
        ids, tags, ts, vs = [], [], [], []
        for labels, t_nanos, value in points:
            ids.append(remote_write.series_id_from_labels(labels))
            tags.append(labels)
            ts.append(t_nanos)
            vs.append(value)
        if ids:
            self.db.write_batch(self.namespace, ids, tags, ts, vs)

    def _json_write(self):
        """Single-datapoint JSON write (ref: src/query/api/v1/handler/
        json/write.go WriteQuery: tags / timestamp / value)."""
        n = int(self.headers.get("Content-Length", 0))
        try:
            body = json.loads(self.rfile.read(n) or b"{}")
            tags_in = body["tags"]
            t_nanos = _parse_time(str(body["timestamp"]))
            value = float(body["value"])
            if not isinstance(tags_in, dict) or not tags_in:
                raise ValueError("tags must be a non-empty object")
        except (KeyError, ValueError, TypeError) as e:
            self._error(400, f"json write: {e}")
            return
        labels = {k.encode(): str(v).encode() for k, v in tags_in.items()}
        if self._ingest_points([(labels, t_nanos, value)]):
            self._reply(200, {"status": "success"})

    def _search(self):
        """Tag search (ref: src/query/api/v1/handler/search.go): POST
        {"start", "end", "matchers": [[kind, name, value], ...]} ->
        matching series tag sets, answered from the index."""
        n = int(self.headers.get("Content-Length", 0))
        try:
            body = json.loads(self.rfile.read(n) or b"{}")
            # absent bounds stay unbounded (query_ids accepts None);
            # inventing a sentinel would silently hide future data
            start = (_parse_time(str(body["start"]))
                     if "start" in body else None)
            end = _parse_time(str(body["end"])) if "end" in body else None
            matchers = [
                (str(k), str(name).encode(), str(val).encode())
                for k, name, val in body.get("matchers", [])
            ]
            if not matchers:
                raise ValueError("matchers required")
        except (KeyError, ValueError, TypeError) as e:
            self._error(400, f"search: {e}")
            return
        try:
            sids = self.db.query_ids(self.namespace, matchers, start, end)
        except (KeyError, ValueError, re.error) as e:
            # re.error: a malformed regex matcher is bad input, not a
            # server fault
            self._error(400, f"search: {e}")
            return
        idx = self.db._ns(self.namespace).index
        out = [
            {k.decode(): v.decode()
             for k, v in idx.tags_of(idx.ordinal(sid)).items()}
            for sid in sids
        ]
        self._reply(200, {"status": "success", "results": out})

    def _remote_write(self):
        n = int(self.headers.get("Content-Length", 0))
        body = self.rfile.read(n)
        attribution.account_write(self._tenant, wire_bytes=len(body))
        # admission runs BEFORE any parse/durability work: a shed
        # batch costs the writer one fast 429, and an accepted one is
        # exactly as durable as it always was
        if not self._admit(nbytes=len(body)):
            return
        try:
            self._remote_write_admitted(body)
        except AdmissionRejected as e:
            self._shed_reply(e)  # shed deeper in the stack (queue)
        finally:
            self._release(nbytes=len(body))

    def _remote_write_admitted(self, body: bytes):
        if self.headers.get("Content-Encoding", "snappy") == "snappy":
            try:
                body = snappy.decompress(body)
            except (ValueError, IndexError) as e:
                self._error(400, f"snappy: {e}")
                return
        if self.dsw is not None:
            # downsample-and-write: raw write + rule-driven aggregation
            # (ref: ingest/write.go:138 DownsamplerAndWriter).  Tiered:
            # (1) columnar C++ router fast path (no per-sample Python),
            # (2) fused parse + per-series memo, (3) reference path.
            from m3_tpu.coordinator.downsample import (prom_samples,
                                                       prom_samples_from_raw)
            fp = self._fastpath()
            try:
                if fp is not None and fp.eligible(self.dsw):
                    n_fast = fp.write(body)
                    if n_fast is not None:
                        _m_ingest_batch.observe(n_fast)
                        self._reply(200, {"status": "success"})
                        return
                batch = prom_samples_from_raw(body, self._series_memo)
                if batch is None:  # no native toolchain
                    batch = prom_samples(
                        remote_write.decode_write_request(body))
                _m_ingest_batch.observe(len(batch))
            except (ValueError, IndexError) as e:
                self._error(400, f"protobuf: {e}")
                return
            except ResourceExhaustedError as e:
                self._error(429, f"write: {e}")
                return
            try:
                self.dsw.write_batch(batch)
            except ColdWriteError as e:
                # out-of-retention/cold-write rejection is bad input, not
                # a server fault: a 500 here makes Prometheus retry the
                # same stale sample forever, wedging its WAL
                self._error(400, f"write: {e}")
                return
            except ResourceExhaustedError as e:
                # transient limit: 429 keeps the batch retryable (400
                # would make Prometheus drop samples that succeed a
                # second later)
                self._error(429, f"write: {e}")
                return
            self._reply(200, {"status": "success"})
            return
        # no downsampler: columnar straight through — per-SERIES Python
        # (sid + labels dict), per-sample stays numpy end to end
        try:
            ls, ss, off, blob, ts_ms, vals = (
                remote_write.decode_write_request_columnar(body))
        except (ValueError, IndexError) as e:
            self._error(400, f"protobuf: {e}")
            return
        _m_ingest_batch.observe(len(ts_ms))
        if len(ts_ms):
            counts = np.diff(np.asarray(ss, dtype=np.int64))
            nz = np.flatnonzero(counts)  # skip sampleless series: they
            uniq_ids, uniq_tags = [], []  # must not enter the index
            for s in nz.tolist():
                labels = remote_write.labels_from_offsets(
                    off, blob, int(ls[s]), int(ls[s + 1]))
                uniq_ids.append(
                    remote_write.series_id_from_labels(labels))
                uniq_tags.append(labels)
            uniq_idx = np.repeat(np.arange(len(nz), dtype=np.int64),
                                 counts[nz])
            try:
                self.db.write_columns(
                    self.namespace, uniq_ids, uniq_tags,
                    np.asarray(ts_ms, dtype=np.int64) * 1_000_000,
                    vals, uniq_idx)
            except ColdWriteError as e:
                self._error(400, f"write: {e}")
                return
            except ResourceExhaustedError as e:
                self._error(429, f"write: {e}")
                return
        self._reply(200, {"status": "success"})

    def _remote_read(self):
        """Prometheus remote read: raw (unconsolidated) samples per
        query, served through the namespace fan-out (ref: src/query/
        api/v1/handler/prometheus/remote/read.go)."""
        import numpy as np

        n = int(self.headers.get("Content-Length", 0))
        body = self.rfile.read(n)
        if self.headers.get("Content-Encoding", "snappy") == "snappy":
            try:
                body = snappy.decompress(body)
            except (ValueError, IndexError) as e:
                self._error(400, f"snappy: {e}")
                return
        try:
            queries = remote_write.decode_read_request(body)
        except (ValueError, IndexError) as e:
            self._error(400, f"protobuf: {e}")
            return
        results = []
        for start_ms, end_ms, matchers in queries:
            labels, times, values = self.engine._fetch_raw(
                matchers, start_ms * 1_000_000, end_ms * 1_000_000)
            series = []
            for i, ls in enumerate(labels):
                valid = ~np.isnan(values[i])
                samples = [(int(t) // 1_000_000, float(v))
                           for t, v in zip(times[i][valid], values[i][valid])]
                if samples:
                    series.append((ls, samples))
            results.append(series)
        payload = snappy.compress(
            remote_write.encode_read_response(results))
        self.send_response(200)
        self.send_header("Content-Type", "application/x-protobuf")
        self.send_header("Content-Encoding", "snappy")
        self.send_header("Content-Length", str(len(payload)))
        self.end_headers()
        self.wfile.write(payload)

    def _request_limits(self, p: dict) -> QueryLimits:
        """Mint this query's limits + deadline at the edge.  Server
        defaults, overridable per request via the reference's limit
        headers (M3-Limit-Max-Series / M3-Limit-Max-Docs /
        M3-Limit-Require-Exhaustive) and the Prometheus ``timeout`` /
        ``requireExhaustive`` params.  The deadline is minted HERE,
        once, and decremented across every layer below."""
        base = self.default_limits
        lim = QueryLimits() if base is None else QueryLimits(
            max_fetched_series=base.max_fetched_series,
            max_fetched_datapoints=base.max_fetched_datapoints,
            max_time_range_nanos=base.max_time_range_nanos,
            require_exhaustive=base.require_exhaustive)
        v = self.headers.get("M3-Limit-Max-Series")
        if v:
            lim.max_fetched_series = int(v)
        v = self.headers.get("M3-Limit-Max-Docs")
        if v:
            lim.max_fetched_datapoints = int(v)
        v = (self.headers.get("M3-Limit-Require-Exhaustive")
             or p.get("requireExhaustive"))
        if v is not None:
            lim.require_exhaustive = str(v).lower() in (
                "1", "true", "yes", "on")
        timeout_s = self.query_timeout_s
        if "timeout" in p:
            timeout_s = min(timeout_s, _parse_step(p["timeout"]) / 1e9)
        lim.deadline = Deadline.after(timeout_s)
        return lim

    def _degraded_reply(self, step_times, mat, meta, limits):
        """Shared 200-with-warnings vs 422 tail of the query routes:
        exhaustive results reply plain; degraded ones carry the
        Prometheus-style ``warnings`` field + ``M3-Results-Limited``
        header, or 422 under require-exhaustive."""
        if limits.require_exhaustive and not meta.exhaustive:
            self._error(422, "result not exhaustive: "
                        + ("; ".join(meta.warning_strings())
                           or "unknown degradation"),
                        error_type="query-limit-exceeded")
            return
        if meta.limited():
            self._reply_matrix(
                step_times, mat, warnings=meta.warning_strings(),
                headers={"M3-Results-Limited":
                         meta.header_value() or "true"})
            return
        self._reply_matrix(step_times, mat)

    def _engine_for(self, p):
        """Resolve the engine for a query request.  A ``namespace``
        param targets a non-default namespace — notably
        ``_m3_internal`` (self-monitoring), which is non-aggregated
        and therefore invisible to the default engine's fan-out.
        Returns None after replying 400 for an unknown namespace."""
        ns = p.get("namespace")
        if not ns or ns == self.namespace:
            return self.engine
        if ns not in self.db.namespaces():
            self._error(400, f"unknown namespace {ns!r}")
            return None
        cache = type(self)._ns_engines  # per-server, engines are cheap
        eng = cache.get(ns)
        if eng is None:
            eng = cache[ns] = Engine(self.db, ns)
        return eng

    def _range_query(self, run, with_meta: bool = False):
        """Shared query_range-shaped param handling: run(query, start,
        end, step) -> (step_times, Matrix); with_meta runners take a
        ``limits=`` kwarg and also return a ResultMeta.  A string
        ``run`` names a method looked up on the namespace-resolved
        engine (the ``namespace`` request param)."""
        p = self._params()
        for req in ("query", "start", "end", "step"):
            if req not in p:
                self._error(400, f"missing parameter {req}")
                return
        if isinstance(run, str):
            eng = self._engine_for(p)
            if eng is None:
                return
            run = getattr(eng, run)
        try:
            start = _parse_time(p["start"])
            end = _parse_time(p["end"])
            step = _parse_step(p["step"])
            if step <= 0 or end < start:
                raise ValueError("bad time range/step")
            # HTTP-edge queries are batch-eligible: with a serving
            # scheduler installed, shape-identical concurrent queries
            # share one device dispatch (m3_tpu/serving/)
            from m3_tpu import serving
            if with_meta:
                limits = self._request_limits(p)
                with serving.batch_scope():
                    step_times, mat, meta = self._engine_call(
                        run, p["query"], start, end, step,
                        limits=limits)
            else:
                with serving.batch_scope():
                    step_times, mat = self._engine_call(
                        run, p["query"], start, end, step)
        except QueryLimitExceeded as e:
            self._error(422, str(e), error_type="query-limit-exceeded")
            return
        except QueryDeadlineExceeded as e:
            self._error(504, str(e), error_type="timeout")
            return
        except ConsistencyError as e:
            # strict read levels fail CLEANLY on a degraded cluster:
            # the request was fine, a dependency wasn't (never a 500)
            self._error(424, str(e), error_type="consistency")
            return
        except observe.QueryCancelled as e:
            # operator cancel via /debug/tasks — nginx's 499 ("client
            # closed request"): the request was killed, not failed
            self._error(499, str(e), error_type="cancelled")
            return
        except (ValueError, KeyError) as e:
            self._error(400, str(e))
            return
        if with_meta:
            self._degraded_reply(step_times, mat, meta, limits)
            return
        self._reply_matrix(step_times, mat)

    def _query_range(self):
        self._range_query("query_range_with_meta", with_meta=True)

    def _m3ql(self):
        """M3QL pipe queries over the same matrix JSON shape
        (ref: parser/m3ql riding the query API)."""
        from m3_tpu.query.m3ql import M3QLEngine
        self._range_query(M3QLEngine(self.db, self.namespace).query)

    def _query_instant(self):
        p = self._params()
        if "query" not in p:
            self._error(400, "missing parameter query")
            return
        eng = self._engine_for(p)
        if eng is None:
            return
        try:
            t = _parse_time(p.get("time", str(clock.now_s())))
            limits = self._request_limits(p)
            from m3_tpu import serving
            with serving.batch_scope():
                mat, meta = self._engine_call(
                    eng.query_instant_with_meta, p["query"], t,
                    limits=limits)
        except QueryLimitExceeded as e:
            self._error(422, str(e), error_type="query-limit-exceeded")
            return
        except QueryDeadlineExceeded as e:
            self._error(504, str(e), error_type="timeout")
            return
        except ConsistencyError as e:
            self._error(424, str(e), error_type="consistency")
            return
        except observe.QueryCancelled as e:
            self._error(499, str(e), error_type="cancelled")
            return
        except (ValueError, KeyError) as e:
            self._error(400, str(e))
            return
        if limits.require_exhaustive and not meta.exhaustive:
            self._error(422, "result not exhaustive: "
                        + ("; ".join(meta.warning_strings())
                           or "unknown degradation"),
                        error_type="query-limit-exceeded")
            return
        result = []
        for labels, row in zip(mat.labels, mat.values):
            if not np.isnan(row[0]):
                result.append({
                    "metric": {k.decode(): v.decode() for k, v in labels.items()},
                    "value": [t / 1e9, repr(float(row[0]))],
                })
        body = {"status": "success",
                "data": {"resultType": "vector", "result": result}}
        headers = None
        if meta.limited():
            body["warnings"] = meta.warning_strings()
            headers = {"M3-Results-Limited": meta.header_value() or "true"}
        self._reply(200, body, headers=headers)

    def _series(self):
        p = self._params()
        sel = p.get("match[]", p.get("match", ""))
        if not sel:
            self._error(400, "missing match[]")
            return
        try:
            ast = promql_parse(sel)
        except ValueError as e:
            self._error(400, str(e))
            return
        ns = p.get("namespace", self.namespace)
        if ns not in self.db.namespaces():
            self._error(400, f"unknown namespace {ns!r}")
            return
        ids = self.db.query_ids(ns, ast.matchers)
        n = self.db._ns(ns)
        data = [
            {k.decode(): v.decode()
             for k, v in n.index.tags_of(n.index.ordinal(sid)).items()}
            for sid in ids
        ]
        self._reply(200, {"status": "success", "data": data})


class CoordinatorServer:
    """Embedded coordinator: HTTP API over a Database."""

    def __init__(self, db: Database, namespace: str = "default",
                 host: str = "127.0.0.1", port: int = 7201,
                 downsampler_writer=None, kv_store=None,
                 query_limits: QueryLimits | None = None,
                 query_timeout_s: float = 30.0,
                 engine: Engine | None = None,
                 trace_peers=None, admission=None, planner=None,
                 graphite_device: bool | None = None):
        # device serving: Engine auto-detects the backend; operators can
        # force either tier (M3_DEVICE_SERVING=1/0) — e.g. pin the host
        # tier on a shared accelerator, or force-enable in a soak test
        dev_env = os.environ.get("M3_DEVICE_SERVING")
        if dev_env is None:
            device_serving = None
        elif dev_env.lower() in ("1", "true", "yes", "on"):
            device_serving = True
        elif dev_env.lower() in ("0", "false", "no", "off"):
            device_serving = False
        else:  # fail loud: a typo must not silently pin a tier
            raise ValueError(
                f"M3_DEVICE_SERVING={dev_env!r}: use 1/0 (or true/false)")
        # multi-chip serving: M3_SERVING_MESH=<n> spreads the device
        # tier over an n-device series mesh (shard_map pipelines)
        serving_mesh = None
        mesh_env = os.environ.get("M3_SERVING_MESH")
        if mesh_env:
            n_shards = int(mesh_env)
            if n_shards > 1:
                from m3_tpu.parallel.mesh import make_mesh
                serving_mesh = make_mesh(n_series_shards=n_shards,
                                         n_window_shards=1)
        handler = type("BoundHandler", (_Handler,), {
            "db": db,
            # an injected engine (e.g. a FanoutEngine over remote
            # peers, or one over SessionStorage) overrides the default
            # `planner` (retention.QueryPlanner) rides into the default
            # engine so ladder deployments get resolution-aware reads
            # without re-deriving the device-serving env handling above
            "engine": engine if engine is not None else Engine(
                db, namespace, device_serving=device_serving,
                serving_mesh=serving_mesh, planner=planner),
            "namespace": namespace,
            "dsw": downsampler_writer, "kv_store": kv_store,
            "default_limits": query_limits,
            "query_timeout_s": query_timeout_s,
            "trace_peers": tuple(trace_peers or ()),
            "admission": admission,
            # per-server parsed-series memo for the remote-write fast
            # path — a bounded LRU (thread-safe) so unbounded label
            # churn evicts cold series instead of wiping the memo
            "_series_memo": LRUCache("series_memo", capacity=1_000_000),
            "_fastpath_state": [None],
            "_influx_fastpath_state": [None],
            # lazily-built per-namespace engines for ?namespace=
            # requests (e.g. the _m3_internal self-monitoring ns)
            "_ns_engines": {},
            # attached post-construction by CoordinatorService when
            # recording/alerting rules are configured
            "rules_engine": None,
            # graphite device lowering: explicit knob wins, else the
            # server-wide device-serving resolution above; cached
            # engines keep the fused compile caches warm across
            # requests (a fresh engine per render would recompile)
            "graphite_device": (graphite_device
                                if graphite_device is not None
                                else device_serving),
            "_graphite_engines": {},
        })
        self.httpd = ThreadingHTTPServer((host, port), handler)
        self.port = self.httpd.server_address[1]
        self._thread: threading.Thread | None = None

    def attach_rules_engine(self, engine) -> None:
        """Expose a ``rules.RulesEngine`` on /api/v1/rules and
        /api/v1/alerts (called by CoordinatorService after both the
        server and the engine exist)."""
        self.httpd.RequestHandlerClass.rules_engine = engine

    def start(self) -> "CoordinatorServer":
        self._thread = threading.Thread(target=self.httpd.serve_forever, daemon=True)  # lint: allow-unregistered-thread (accept loop blocks in socket)
        self._thread.start()
        return self

    def stop(self) -> None:
        if self._thread:  # shutdown() blocks unless serve_forever runs
            self.httpd.shutdown()
            self._thread.join(timeout=5.0)
        self.httpd.server_close()
