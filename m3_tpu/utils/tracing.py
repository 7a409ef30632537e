"""Lightweight distributed tracing: spans, tracepoints, sampling,
cross-process context propagation.

Parity target: src/dbnode/tracepoint/tracepoint.go:32 (the stable
tracepoint-name catalog threaded through the read/write paths) and
src/x/opentracing/ (tracer setup).  The reference attaches OpenTracing
spans to RPC-scoped contexts; here a span is a context-manager around
the same hot-path seams, parented through a thread-local stack, with:

  - deterministic sampling (1-in-N by operation) so the hot write path
    does not pay per-sample span cost
  - a bounded ring of finished spans exposed via the debug dump
    (`/debug/dump` -> "traces"), the zipkin-lite this image can serve
    with zero egress
  - span tags + per-span duration on a monotonic clock (one wall
    reading places the span, ``perf_counter_ns`` measures it, so a
    stepped wall clock cannot bend a duration); errors mark the span
  - Dapper-style cross-process propagation (Sigelman et al., 2010):
    a `TraceContext` rides the W3C ``traceparent`` header at the HTTP
    edge and a context field in the node-RPC / remote-query / m3msg
    wire frames, so a query fanning out coordinator -> storage
    replicas -> device kernels shares one trace_id.  ``activate()``
    adopts a remote or handed-off parent on the current thread — the
    explicit handoff for worker-thread pools (host queues, session
    fan-out executors).

The tracepoint catalog mirrors the reference's naming scheme
(`component.Method`) so a reader can map traces across systems.  The
observability lint (tools/lint_robustness.py) enforces that every
``tracing.span("...")`` string literal in the production tree comes
from this catalog.

``phase(name, sink)`` is the query path's one cost clock: a phase is
stamped once on ``perf_counter_ns`` and that stamp feeds the query's
cost record (``sink``), a child span (when the request is sampled or
carries a ``traceparent``) and a ``jax.profiler.TraceAnnotation``
named ``m3:<phase>`` that lands in a device trace when a profiler
session is open.  What a phase waited for is clocked where the wait
happens (``wait(name)``: the database lock; ``charge``: the chip, by
kernel telemetry's own stamps; ``watch_collector``: the interpreter's
full collections) and added to the sink of the phase it interrupted;
what it worked is read from the thread's CPU clock on one query in
``COST_CLOCK_1_IN`` (``phase(.., cpu=)``).
"""

from __future__ import annotations

import contextlib
import gc
import os
import threading
import time
from collections import deque
from typing import NamedTuple

from jax.profiler import TraceAnnotation

from m3_tpu.utils import instrument

# ---------------------------------------------------------------- catalog
# Stable tracepoint names (ref: dbnode/tracepoint/tracepoint.go:32 — the
# catalog exists so span names never drift between emit and analysis).

DB_WRITE_BATCH = "db.WriteBatch"
DB_FETCH_TAGGED = "db.FetchTagged"
ENGINE_QUERY_RANGE = "engine.QueryRange"
MSG_PUBLISH = "msg.Publish"
MSG_CONSUME = "msg.Consume"
REMOTE_FETCH = "remote.Fetch"
REMOTE_SERVE = "remote.Serve"
HTTP_REQUEST = "http.Request"
NODE_SERVE = "node.Serve"
SESSION_FETCH = "session.FetchTagged"
SESSION_FETCH_HOST = "session.FetchHost"
HOSTQ_WRITE_BATCH = "client.HostQueueWriteBatch"
DEVICE_KERNEL = "device.Kernel"
# the query path's phases (``phase()`` below): one span per stamped
# phase of a query's cost record, keyed by the phase's short name
ENGINE_PARSE = "engine.Parse"
ENGINE_GATHER = "engine.Gather"
ENGINE_OPEN_READ = "engine.OpenRead"
ENGINE_PACK = "engine.Pack"
ENGINE_PLAN = "engine.Plan"
ENGINE_DECODE = "engine.Decode"
ENGINE_MERGE = "engine.Merge"
ENGINE_DEVICE = "engine.Device"
DEVICE_H2D = "device.HostToDevice"
DEVICE_D2H = "device.DeviceToHost"
HTTP_FRONTEND = "http.Frontend"
HTTP_RENDER = "http.Render"
PHASE_SPANS = {
    "parse": ENGINE_PARSE, "fetch": ENGINE_GATHER,
    "open_read": ENGINE_OPEN_READ, "pack": ENGINE_PACK,
    "plan": ENGINE_PLAN,
    "decode": ENGINE_DECODE, "merge": ENGINE_MERGE,
    "device": ENGINE_DEVICE, "h2d": DEVICE_H2D, "d2h": DEVICE_D2H,
    "frontend": HTTP_FRONTEND, "render": HTTP_RENDER,
}


# --------------------------------------------------------------- context

class TraceContext(NamedTuple):
    """The cross-boundary identity of an active span: what rides wire
    frames and worker-pool handoffs (the role of the reference's
    RPC-scoped opentracing.SpanContext)."""

    trace_id: int
    span_id: int
    sampled: bool = True
    # workload-attribution baggage: the originating tenant, so fan-out
    # RPC work on dbnodes is attributed to the tenant that caused it
    # (rides the wire as a ";t=<tenant>" suffix on the tc field; the
    # bare traceparent header stays spec-clean)
    tenant: str | None = None

    def to_traceparent(self) -> str:
        """W3C trace-context header value (version 00)."""
        flags = "01" if self.sampled else "00"
        return f"00-{self.trace_id:032x}-{self.span_id:016x}-{flags}"


def parse_traceparent(value) -> TraceContext | None:
    """Parse a W3C ``traceparent`` header (or wire field).  Returns
    None for anything malformed — propagation is best-effort and a bad
    header must never fail the request it rides on.  A ``;t=<tenant>``
    suffix (this platform's attribution baggage on the RPC ``tc``
    field) is split off and carried on the returned context."""
    if not value:
        return None
    if isinstance(value, (bytes, bytearray)):
        try:
            value = bytes(value).decode("ascii")
        except UnicodeDecodeError:
            return None
    value, _, baggage = value.strip().partition(";")
    tenant = baggage[2:] if baggage.startswith("t=") else None
    parts = value.split("-")
    if len(parts) != 4:
        return None
    version, tid, sid, flags = parts
    if len(version) != 2 or len(tid) != 32 or len(sid) != 16:
        return None
    try:
        trace_id = int(tid, 16)
        span_id = int(sid, 16)
        sampled = bool(int(flags, 16) & 1)
    except ValueError:
        return None
    if version == "ff" or trace_id == 0 or span_id == 0:
        return None  # per spec: invalid version / all-zero ids
    return TraceContext(trace_id, span_id, sampled, tenant or None)


class Span:
    __slots__ = ("name", "trace_id", "span_id", "parent_id", "start",
                 "duration", "tags", "error", "_t0_ns")

    def __init__(self, name: str, trace_id: int, span_id: int,
                 parent_id: int | None, tags: dict):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.start = time.time()  # places the span; never subtracted
        self._t0_ns = time.perf_counter_ns()
        self.duration = 0.0
        self.tags = tags
        self.error = ""

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "trace_id": f"{self.trace_id:032x}",
            "span_id": f"{self.span_id:016x}",
            "parent_id": f"{self.parent_id:016x}" if self.parent_id else None,
            "start": self.start,
            "duration_ms": round(self.duration * 1e3, 3),
            "tags": {k: str(v) for k, v in self.tags.items()},
            "error": self.error or None,
        }


class Tracer:
    """Sampled span recorder with a bounded finished-span ring."""

    def __init__(self, sample_1_in: int = 100, max_spans: int = 2048):
        self.sample_1_in = max(1, int(sample_1_in))
        self._ring: deque[Span] = deque(maxlen=max_spans)
        self._tls = threading.local()
        self._counts: dict[str, int] = {}
        self._lock = threading.Lock()
        self._next_id = 1
        # ids must not collide ACROSS processes in a cluster (every
        # node contributes spans to one assembled trace), so the
        # sequential counter rides on a per-process random base
        self._id_base = int.from_bytes(os.urandom(4), "big")

    # -- internals --

    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def _sampled(self, name: str, root: bool) -> bool:
        if not root:
            return True  # children follow their root's decision
        with self._lock:
            n = self._counts.get(name, 0)
            self._counts[name] = n + 1
        return n % self.sample_1_in == 0

    def _new_id(self) -> int:
        with self._lock:
            self._next_id += 1
            return (self._id_base << 32) | (self._next_id & 0xFFFFFFFF)

    def _new_trace_id(self) -> int:
        return int.from_bytes(os.urandom(16), "big") or 1

    # -- public --

    def span(self, name: str, **tags):
        """Context manager; no-ops (cheaply) when unsampled."""
        return _SpanCtx(self, name, tags)

    def current(self) -> TraceContext | None:
        """The context of the innermost live sampled span on this
        thread (what a wire injection or worker handoff should carry);
        None when nothing sampled is active."""
        for s in reversed(self._stack()):
            if s is None:
                continue
            if isinstance(s, TraceContext):
                return s
            return TraceContext(s.trace_id, s.span_id, True)
        return None

    def activate(self, ctx: TraceContext | None):
        """Adopt a remote/handed-off parent context on this thread.

        Spans opened inside the ``with`` block parent to ``ctx`` and
        inherit its trace_id — the explicit handoff for worker-thread
        pools and the extract side of wire propagation.  ``ctx=None``
        (nothing propagated) is a no-op: spans root normally under
        local sampling.  An unsampled context suppresses local spans,
        honoring the upstream decision."""
        return _ActivateCtx(self, ctx)

    def finished(self, limit: int = 0) -> list[dict]:
        """Last `limit` finished spans (0 = all).  Snapshot the Span
        refs under the lock, serialize outside it — record() on hot
        paths must never wait on a debug dump."""
        with self._lock:
            spans = list(self._ring)[-limit:] if limit else list(self._ring)
        return [s.to_dict() for s in spans]

    def export(self, trace_id: str | None = None,
               limit: int = 0) -> list[dict]:
        """Finished spans, optionally filtered to one trace — the
        per-node span-export surface."""
        spans = self.finished(limit=limit)
        if trace_id:
            want = trace_id.lower().lstrip("0") or "0"
            spans = [s for s in spans
                     if s["trace_id"].lstrip("0") == want]
        return spans

    def record(self, span: Span) -> None:
        with self._lock:
            self._ring.append(span)


class _SpanCtx:
    __slots__ = ("_tracer", "_name", "_tags", "_span")

    def __init__(self, tracer: Tracer, name: str, tags: dict):
        self._tracer = tracer
        self._name = name
        self._tags = tags
        self._span: Span | None = None

    def __enter__(self) -> Span | None:
        st = self._tracer._stack()
        root = not st
        if not self._tracer._sampled(self._name, root):
            st.append(None)  # unsampled marker keeps parenting honest
            return None
        parent = next((s for s in reversed(st) if s is not None), None)
        if parent is None and not root:
            # unsampled root: children stay unsampled
            st.append(None)
            return None
        span = Span(
            self._name,
            trace_id=(parent.trace_id if parent
                      else self._tracer._new_trace_id()),
            span_id=self._tracer._new_id(),
            parent_id=parent.span_id if parent else None,
            tags=self._tags,
        )
        st.append(span)
        self._span = span
        return span

    def __exit__(self, exc_type, exc, _tb) -> bool:
        st = self._tracer._stack()
        if st:
            st.pop()
        if self._span is not None:
            self._span.duration = (
                time.perf_counter_ns() - self._span._t0_ns) / 1e9
            if exc is not None:
                self._span.error = f"{type(exc).__name__}: {exc}"
            self._tracer.record(self._span)
        return False


class _ActivateCtx:
    __slots__ = ("_tracer", "_ctx", "_pushed", "_tenant_pushed",
                 "_prev_tenant")

    def __init__(self, tracer: Tracer, ctx: TraceContext | None):
        self._tracer = tracer
        self._ctx = ctx
        self._pushed = False
        self._tenant_pushed = False
        self._prev_tenant = None

    def __enter__(self):
        if self._ctx is not None:
            st = self._tracer._stack()
            # an unsampled upstream decision suppresses local children
            st.append(self._ctx if self._ctx.sampled else None)
            self._pushed = True
            tenant = getattr(self._ctx, "tenant", None)
            if tenant:
                # adopt propagated attribution baggage even for
                # unsampled contexts: accounting is not sampled
                self._prev_tenant = current_tenant()
                _TENANT_TLS.tenant = tenant
                self._tenant_pushed = True
        return self._ctx

    def __exit__(self, exc_type, exc, _tb) -> bool:
        if self._pushed:
            st = self._tracer._stack()
            if st:
                st.pop()
        if self._tenant_pushed:
            _TENANT_TLS.tenant = self._prev_tenant
        return False


# ------------------------------------------------- attribution baggage
# Thread-local tenant for workload attribution (m3_tpu.attribution).
# Deliberately separate from the span stack: accounting must work even
# when the request's trace is unsampled.

_TENANT_TLS = threading.local()


def current_tenant() -> str | None:
    """The tenant attributed to work on this thread, or None."""
    return getattr(_TENANT_TLS, "tenant", None)


class _TenantScope:
    __slots__ = ("_tenant", "_prev")

    def __init__(self, tenant: str | None):
        self._tenant = tenant
        self._prev = None

    def __enter__(self):
        self._prev = current_tenant()
        if self._tenant:
            _TENANT_TLS.tenant = self._tenant
        return self._tenant

    def __exit__(self, exc_type, exc, _tb) -> bool:
        _TENANT_TLS.tenant = self._prev
        return False


def tenant_scope(tenant: str | None):
    """Attribute work in the ``with`` block to ``tenant`` (None keeps
    the current attribution — the scope is then a no-op)."""
    return _TenantScope(tenant)


# ------------------------------------------------------------- assembly

def assemble_trace(spans: list[dict], trace_id: str) -> dict:
    """Collected span dicts (local ring + peer exports) -> one nested
    trace tree keyed by trace_id: the coordinator/tools view of a
    cross-node query (ref: the reference's jaeger UI role).

    Spans whose parent is missing from the collected set (ring
    eviction, an unreachable peer) surface under "orphans" rather than
    disappearing — partial traces must stay diagnosable."""
    want = trace_id.lower().lstrip("0") or "0"
    by_id: dict[str, dict] = {}
    mine: list[dict] = []
    for s in spans:
        if str(s.get("trace_id", "")).lstrip("0") != want:
            continue
        if s["span_id"] in by_id:
            continue  # same span collected from several sources
            # (local ring + a peer export of the same process)
        s = dict(s)
        s["children"] = []
        by_id[s["span_id"]] = s
        mine.append(s)
    roots, orphans = [], []
    for s in mine:
        pid = s.get("parent_id")
        if pid is None:
            roots.append(s)
        elif pid in by_id:
            by_id[pid]["children"].append(s)
        else:
            orphans.append(s)
    for s in mine:
        s["children"].sort(key=lambda c: c.get("start", 0.0))
    roots.sort(key=lambda c: c.get("start", 0.0))
    orphans.sort(key=lambda c: c.get("start", 0.0))
    return {"trace_id": trace_id, "span_count": len(mine),
            "roots": roots, "orphans": orphans}


_GLOBAL = Tracer()


def tracer() -> Tracer:
    return _GLOBAL


def span(name: str, **tags):
    """Module-level convenience: ``with tracing.span(DB_WRITE_BATCH):``"""
    return _GLOBAL.span(name, **tags)


def current_context() -> TraceContext | None:
    """The active span's cross-boundary context on this thread."""
    return _GLOBAL.current()


def activate(ctx: TraceContext | None):
    """Module-level convenience for Tracer.activate."""
    return _GLOBAL.activate(ctx)


def wire_context() -> str | None:
    """Inject side of wire propagation: the current context as a
    traceparent string for a frame field / HTTP header, or None when
    nothing sampled is active (unsampled work propagates nothing — the
    downstream process makes its own root sampling decision).  When a
    tenant is active (attribution baggage) it rides as a ``;t=``
    suffix so fan-out work downstream is attributed correctly."""
    ctx = _GLOBAL.current()
    if ctx is None:
        return None
    tp = ctx.to_traceparent()
    tenant = current_tenant()
    return f"{tp};t={tenant}" if tenant else tp


# One query in sixteen reads the thread's CPU clock beside the wall
# clock (``Engine.query_range_with_meta`` decides; a live span forces
# it).  A constant, not an option: ``trace_sample_1_in`` stays the one
# knob.  Why sixteen (the builder of the refused PR 40, on the chip's
# machine, gVisor): ``time.thread_time_ns`` cost 6.2 us a read alone and
# 19.4 us beside three busy threads, is read under the interpreter
# lock, so every client pays for every client's reads, and moves in
# steps of 10 ms; read at every stamp of every query it put
# dash-sealed's median panel +3.7 to +4.6%, by one query in eight +0.6
# to +1.6%.  So the clock gives sums and means over a window's clocked
# records, never one record's value.
COST_CLOCK_1_IN = 16

# what a phase may have waited for, by the key its sink is charged
# under; every slow-query record carries each, 0.0 where nothing waited
WAIT_KEYS = ("db_lock_wait_s", "device_wait_s", "gc_pause_s")


class _CostState(threading.local):
    # the sink of the innermost open phase of this thread: where its
    # waits are charged; None outside any query
    sink = None


_STATE = _CostState()


def charge(key: str, seconds: float) -> None:
    """Add `seconds` under `key` (one of ``WAIT_KEYS``) to the sink of
    the calling thread's innermost open phase; a thread outside any
    query has none and nothing is kept."""
    sink = _STATE.sink
    if sink is not None:
        sink[key] = sink.get(key, 0.0) + seconds


@contextlib.contextmanager
def sink_scope(sink: dict):
    """``with sink_scope(d):`` waits on this thread are charged to `d`
    where no phase is open (the engine's self time)."""
    outer, _STATE.sink = _STATE.sink, sink
    try:
        yield
    finally:
        _STATE.sink = outer


class _Phase:
    """One stamped phase; ``start``/``stop`` for a phase that does not
    fit a ``with`` block (the HTTP front end's two halves)."""

    __slots__ = ("_name", "_sink", "_cpu", "_span", "_live", "_ann",
                 "_t0_ns", "_c0_ns", "_outer")

    def __init__(self, name: str, sink: dict, cpu: dict | None):
        self._name = name
        self._sink = sink
        self._cpu = cpu
        self._t0_ns = None

    def start(self, cpu: dict | None = None) -> "_Phase":
        """`cpu`: clock the phase into it from this start on (the
        front end learns only from the engine call whether its query
        is a clocked one)."""
        if cpu is not None:
            self._cpu = cpu
        self._span = _GLOBAL.span(PHASE_SPANS[self._name])
        self._live = self._span.__enter__()
        self._ann = TraceAnnotation("m3:" + self._name)
        self._ann.__enter__()
        self._outer = _STATE.sink
        _STATE.sink = self._sink
        self._t0_ns = time.perf_counter_ns()
        if self._cpu is not None:
            # inside the wall stamps, so that wall - CPU is no less
            # than nought by more than the clock's step
            self._c0_ns = time.thread_time_ns()
        return self

    def stop(self, exc_type=None, exc=None, tb=None) -> None:
        if self._t0_ns is None:
            return
        key = self._name + "_s"
        cpu = self._cpu
        if cpu is not None:
            cpu_s = (time.thread_time_ns() - self._c0_ns) / 1e9
            cpu[key] = cpu.get(key, 0.0) + cpu_s
        seconds = (time.perf_counter_ns() - self._t0_ns) / 1e9
        self._t0_ns = None
        self._sink[key] = self._sink.get(key, 0.0) + seconds
        _STATE.sink = self._outer
        if cpu is not None and self._live is not None:
            self._live.tags["cpu_ms"] = round(cpu_s * 1e3, 3)
            self._live.tags["wait_ms"] = round(
                (seconds - cpu_s) * 1e3, 3)
        self._ann.__exit__(exc_type, exc, tb)
        self._span.__exit__(exc_type, exc, tb)

    __enter__ = start

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.stop(exc_type, exc, tb)
        return False


def phase(name: str, sink: dict, cpu: dict | None = None) -> _Phase:
    """Stamp one phase of a query: ``with tracing.phase("pack", d):``
    adds the block's seconds to ``d["pack_s"]``, opens the catalog
    span ``PHASE_SPANS[name]`` under the active trace and writes an
    ``m3:<name>`` annotation into an open profiler session.  Phases
    may nest (``h2d`` inside ``device``); the sink keeps each whole.
    While the phase is open, what its thread waits for (``wait``,
    ``charge``) is added to ``d`` under ``WAIT_KEYS``.  With `cpu` (a
    clocked query's, one in ``COST_CLOCK_1_IN``) the thread's CPU
    seconds of the block are added to ``cpu["pack_s"]`` and a live
    span is tagged ``cpu_ms`` / ``wait_ms``; without it the phase
    reads no CPU clock."""
    return _Phase(name, sink, cpu)


class _Wait:
    """One blocking acquisition, on the wall clock."""

    __slots__ = ("_name", "_ann", "t0_ns", "t1_ns")

    def __init__(self, name: str):
        self._name = name

    def __enter__(self) -> "_Wait":
        self._ann = TraceAnnotation("m3:wait:" + self._name)
        self._ann.__enter__()
        self.t0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.t1_ns = time.perf_counter_ns()
        self._ann.__exit__(exc_type, exc, tb)
        seconds = (self.t1_ns - self.t0_ns) / 1e9
        charge(self._name + "_wait_s", seconds)
        instrument.counter("m3_wait_seconds_total",
                           on=self._name).inc(seconds)
        instrument.counter("m3_waits_total", on=self._name).inc()
        return False


def wait(name: str) -> _Wait:
    """Clock a wait where it happens: ``with tracing.wait("db_lock"):``
    around a blocking acquisition stamps ``perf_counter_ns`` on both
    sides (``t0_ns``, ``t1_ns``), charges the seconds as
    ``<name>_wait_s`` to the phase the calling thread is in, counts
    them in ``m3_wait_seconds_total{on=<name>}`` /
    ``m3_waits_total{on=<name>}`` whoever the thread is, and writes an
    ``m3:wait:<name>`` annotation, so that the wait lies on a device
    trace's clock.  For the contended path only: try the acquisition
    without blocking first, and an uncontended one reads no clock."""
    return _Wait(name)


def tag_current(**tags) -> None:
    """Tag the calling thread's innermost span, if it is live."""
    st = _GLOBAL._stack()
    if st and isinstance(st[-1], Span):
        st[-1].tags.update(tags)


class _CollectorWatch:
    """The ``gc.callbacks`` entry.  The interpreter runs one collection
    at a time, start and stop on the thread whose allocation set it
    off, wherever that thread was: the callback takes no lock the
    interrupted code may hold, so its counters are made before it is
    installed and not looked up in the registry from inside it."""

    def __init__(self):
        self._seconds = instrument.counter("m3_gc_pause_seconds_total")
        self._count = instrument.counter("m3_gc_collections_total")
        self._ann = None
        self._t0_ns = 0

    def __call__(self, when: str, info: dict) -> None:
        if info["generation"] != 2:
            return
        if when == "start":
            self._ann = TraceAnnotation("m3:gc")
            self._ann.__enter__()
            self._t0_ns = time.perf_counter_ns()
        elif self._ann is not None:
            seconds = (time.perf_counter_ns() - self._t0_ns) / 1e9
            self._ann.__exit__(None, None, None)
            self._ann = None
            charge("gc_pause_s", seconds)
            self._seconds.inc(seconds)
            self._count.inc()


_COLLECTOR_WATCH = _CollectorWatch()


def watch_collector() -> None:
    """Clock the interpreter's full collections (generation 2: every
    thread stands still for one): an ``m3:gc`` annotation from start
    to stop, ``m3_gc_pause_seconds_total`` / ``m3_gc_collections_total``
    and the pause charged as ``gc_pause_s`` to the phase it
    interrupted on the thread it ran on.  One ``gc.callbacks`` entry a
    process, installed where a service starts."""
    if _COLLECTOR_WATCH not in gc.callbacks:
        gc.callbacks.append(_COLLECTOR_WATCH)


def set_sampling(sample_1_in: int) -> None:
    """Hot-reloadable sampling rate (1 = trace everything)."""
    _GLOBAL.sample_1_in = max(1, int(sample_1_in))


def traced(name: str):
    """Decorator form for method-boundary tracepoints."""
    import functools

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with _GLOBAL.span(name):
                return fn(*args, **kwargs)

        return wrapper

    return deco
