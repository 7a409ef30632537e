"""Slow-query log: per-query cost records in a bounded ring.

Monarch-style per-query cost accounting (Adams et al., VLDB 2020):
every query the engine serves leaves one cost record — expression,
phase timings (see ``phases`` in docs/observability.md: parse, fetch,
pack, decode, merge, device with its h2d and d2h, the engine's self
time, and the HTTP front end's, which sum to the request), series and
datapoints touched, device-vs-host serving, the limits/warnings its
ResultMeta accumulated, and its trace_id so a slow entry links
straight to the distributed trace.  Queries that ran (partly) through
the fused whole-query device pipeline additionally carry a
``device_tier`` dict — ``compile_cache`` ("hit"/"miss"),
``compile_s``, ``device_nodes`` vs ``host_nodes`` (how much of the
op-tree ran on device vs fell back to the host evaluator), and
``transfer_bytes`` (the single device→host result copy), and
``host_splits`` ({reason: count} wherever the plan compiler declined,
the same slugs as ``m3_query_host_split_total``) — so a slow fused
query can be attributed to an XLA recompile vs a genuinely expensive
tree without re-running it.  Records land in a bounded ring
(`/debug/slowqueries` serves it newest-first); queries slower than the
``M3_SLOW_QUERY_SECONDS`` threshold additionally emit a structured
warn log and bump ``m3_slow_queries_total`` — the grep-able breadcrumb
for incident response.

The ring keeps EVERY query, not just slow ones: "why is this dashboard
suddenly slow" usually needs the fast-query baseline next to the slow
outlier.  Filtering happens at read time (``records(min_seconds=...)``).
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from collections import deque

from m3_tpu.utils import instrument

_log = instrument.logger("query.slowlog")

# a benchmark window is read back from the ring after it closes: 235
# panels today, and a faster device program only makes more
DEFAULT_CAPACITY = 2048
DEFAULT_THRESHOLD_S = 1.0
DEFAULT_INITIATOR = "http"

# thread-local query initiator: "http" (user-facing edge, the
# default) vs "rule:<group>/<name>" (the rules engine's evaluation
# loop) — so /debug/slowqueries can tell rule-driven load from user
# load without parsing expressions
_tl = threading.local()


def current_initiator() -> str:
    return getattr(_tl, "initiator", DEFAULT_INITIATOR)


@contextlib.contextmanager
def initiator(name: str):
    """Scope the calling thread's query initiator; the engine stamps
    it onto every cost record cut inside the scope."""
    prev = getattr(_tl, "initiator", None)
    _tl.initiator = name
    try:
        yield
    finally:
        if prev is None:
            _tl.initiator = DEFAULT_INITIATOR
        else:
            _tl.initiator = prev


def last_record() -> dict | None:
    """The record the calling thread cut last and has not taken."""
    return getattr(_tl, "last_record", None)


def take_last_record() -> dict | None:
    """The record the calling thread cut last, handed out once: the
    HTTP front end adds its own phase (``frontend_s``) to the record
    of the query it just served."""
    rec = last_record()
    _tl.last_record = None
    return rec


def _threshold_s() -> float:
    """Hot-reloadable via env: operators tune it without a restart."""
    raw = os.environ.get("M3_SLOW_QUERY_SECONDS", "")
    try:
        return float(raw) if raw else DEFAULT_THRESHOLD_S
    except ValueError:
        return DEFAULT_THRESHOLD_S


class SlowQueryLog:
    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self._lock = threading.Lock()
        self._ring: deque[dict] = deque(maxlen=capacity)

    def record(self, rec: dict) -> None:
        rec.setdefault("ts", time.time())
        with self._lock:
            self._ring.append(rec)
        _tl.last_record = rec
        total = rec.get("total_s", 0.0)
        if total >= _threshold_s():
            instrument.counter("m3_slow_queries_total").inc()
            extra = {}
            tier = rec.get("device_tier")
            if isinstance(tier, dict):
                extra = {
                    "compile_cache": tier.get("compile_cache"),
                    "compile_s": tier.get("compile_s"),
                    "device_nodes": tier.get("device_nodes"),
                    "host_nodes": tier.get("host_nodes"),
                    "transfer_bytes": tier.get("transfer_bytes"),
                }
                if tier.get("host_splits"):
                    # where the plan compiler declined: {reason: n},
                    # same slugs as m3_query_host_split_total
                    extra["host_splits"] = tier["host_splits"]
            _log.warn("slow query", expr=rec.get("expr"),
                      tenant=rec.get("tenant"),
                      total_s=total, series=rec.get("series"),
                      datapoints=rec.get("datapoints"),
                      device_serving=rec.get("device_serving"),
                      trace_id=rec.get("trace_id"),
                      error=rec.get("error"), **extra)

    def records(self, min_seconds: float = 0.0,
                limit: int = 0) -> list[dict]:
        """Newest-first cost records at or above ``min_seconds``."""
        with self._lock:
            recs = list(self._ring)
        recs.reverse()
        if min_seconds > 0.0:
            recs = [r for r in recs
                    if r.get("total_s", 0.0) >= min_seconds]
        return recs[:limit] if limit else recs

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()


_GLOBAL = SlowQueryLog()


def log() -> SlowQueryLog:
    return _GLOBAL
