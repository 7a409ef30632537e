"""Continuous-batching query scheduler: admission window, plan-keyed
groups, one device dispatch per group.

Protocol (leaderless token claim — no scheduler thread):

  * Every eligible query thread calls ``submit()`` with its fully
    lowered fused inputs (the static plan tuple plus the traced
    leaves/params/steps pytrees run_sym just built).  The plan tuple
    is the group key: equal plans guarantee shape-identical pytrees,
    so stacking is always well-formed and the batched program is
    shared via the jit cache exactly like the solo one.
  * Threads in a group wait on one process-wide condition in short
    slices, re-running the engine's cooperative-cancel / deadline
    checkpoint each slice (``_check_deadline("batch window")``), so a
    cancelled or expired query aborts its wait promptly — it is
    masked out of the demux, never out of the dispatch.
  * When the group fills (``max_queries`` or the lane/byte budget) or
    its window expires, the first thread to notice claims the
    dispatch token, removes the group from the admission map (new
    arrivals start a fresh group), stacks the entries along a leading
    query axis padded to a power of two, and runs
    ``device_expr_pipeline_batched`` once.  Results and errors are
    delivered to every entry; waiters that already abandoned (cancel
    / deadline) simply never read theirs.
  * A group of one means the window bought nothing: ``submit``
    returns None and the caller proceeds on today's solo path
    (``m3_query_batch_solo_total{reason="no_partner"}``).

Attribution: the batched kernel call runs under the reserved
``BATCH_TENANT`` scope so kernel telemetry does not bill the whole
dispatch to whichever tenant's thread claimed the token; the
scheduler then splits the measured device seconds across the real
entries by lane share (identical plans -> equal lanes -> equal
split) and accounts each slice to its query's tenant.

The scheduler also hosts the cross-query fetch memo: two batched
queries over the same (namespace, selector, window) share one
gather + pack instead of packing the same blocks twice.  Entries
live for a few admission windows at most, so the memo can never
serve a meaningfully stale storage snapshot, and the map is bounded
(expired-first eviction at the cap).
"""

from __future__ import annotations

import contextlib
import threading
import time

import numpy as np

from m3_tpu.attribution import BATCH_TENANT  # noqa: F401 — re-export
from m3_tpu.utils import instrument, tracing

_log = instrument.logger("serving.batch")

# pow2 floor for the stacked query axis (a 2-query batch compiles the
# q_pad=2 program; padding replicates entry 0 and is never demuxed)
_Q_FLOOR = 2

# hard safety cap a waiter adds on top of the admission window before
# abandoning a dispatch that never delivered (token holder died in a
# way that skipped the delivery except) — the query then reruns solo
_WAIT_CAP_S = 60.0

# wait-slice granularity: cancel/deadline latency for batched queries
_SLICE_S = 0.01

_tl = threading.local()

_INSTALL_LOCK = threading.Lock()
_SCHED: "BatchScheduler | None" = None


def in_batch_scope() -> bool:
    return bool(getattr(_tl, "batching", False))


@contextlib.contextmanager
def batch_scope():
    """Mark the calling thread's queries as batchable.  Entered by the
    HTTP query handlers and the rules engine's evaluation workers;
    everything outside the scope keeps solo dispatch untouched."""
    prev = getattr(_tl, "batching", False)
    _tl.batching = True
    try:
        yield
    finally:
        _tl.batching = prev


def installed() -> "BatchScheduler | None":
    return _SCHED


def install(sched: "BatchScheduler | None") -> None:
    global _SCHED
    with _INSTALL_LOCK:
        _SCHED = sched


def uninstall() -> None:
    install(None)


def configure(cfg) -> "BatchScheduler | None":
    """Install a scheduler from a services.config.QueryBatchingConfig
    (or any object with the same fields); disabled config uninstalls.
    Returns the installed scheduler (None when disabled)."""
    if cfg is None or not getattr(cfg, "enabled", False):
        uninstall()
        return None
    sched = BatchScheduler(
        window_s=cfg.window / 1e9,
        max_queries=cfg.max_queries,
        max_lanes=cfg.max_lanes,
        max_bytes=cfg.max_bytes)
    install(sched)
    return sched


def _active() -> "BatchScheduler | None":
    sched = _SCHED
    if sched is None or not in_batch_scope():
        return None
    return sched


def count_solo(reason: str) -> None:
    """Count a batch-eligible query that served solo.  Only counted
    when a scheduler is installed and the thread is in batch scope —
    otherwise every ordinary query would show up as a fallback."""
    sched = _active()
    if sched is None:
        return
    instrument.bounded_counter(
        "m3_query_batch_solo_total", cap=16).labels(reason=reason).inc()
    with sched._lock:
        sched._solo[reason] = sched._solo.get(reason, 0) + 1


def try_batched_dispatch(engine, plan_t, leaves, params, steps_pad,
                         nbytes: int, n_bufs: int):
    """run_sym's batching seam: returns the per-query
    (out, aux, errs, info) demux slice when this query served through
    a shared dispatch, or None when it should proceed solo.
    Cooperative-cancel and deadline exceptions raised while waiting
    propagate; anything else (device error, lost token holder) falls
    back to solo so batching can never fail a query the solo path
    would have answered."""
    sched = _active()
    if sched is None:
        return None
    from m3_tpu import observe
    from m3_tpu.storage.limits import QueryDeadlineExceeded
    try:
        return sched.submit(engine, plan_t, leaves, params, steps_pad,
                            nbytes, n_bufs)
    except (observe.QueryCancelled, QueryDeadlineExceeded):
        raise
    except Exception as exc:  # noqa: BLE001 — solo path still answers
        _log.warn("batched dispatch failed, serving solo",
                  err=f"{type(exc).__name__}: {exc}"[:200])
        count_solo("error")
        return None


def shared_fetch_memo_get(engine, key):
    """Cross-query gather/pack memo lookup (engine._gather_cached):
    active only inside batch scope with a scheduler installed."""
    sched = _active()
    if sched is None:
        return None
    return sched.memo_get((engine.ns, id(engine.db)) + key)


def shared_fetch_memo_put(engine, key, ent) -> None:
    sched = _active()
    if sched is None:
        return
    sched.memo_put((engine.ns, id(engine.db)) + key, ent)


def shared_fetch_memo_abort(engine, key) -> None:
    """Release a single-flight reservation whose gather raised."""
    sched = _active()
    if sched is None:
        return
    sched.memo_abort((engine.ns, id(engine.db)) + key)


def stats() -> dict:
    """Installed-scheduler snapshot for /debug/batching."""
    sched = _SCHED
    if sched is None:
        return {"installed": False}
    return sched.snapshot()


class _Entry:
    __slots__ = ("engine", "leaves", "params", "steps", "nbytes",
                 "n_bufs", "tenant", "enqueued", "result", "error",
                 "done", "abandoned")

    def __init__(self, engine, leaves, params, steps, nbytes, n_bufs,
                 tenant):
        self.engine = engine
        self.leaves = leaves
        self.params = params
        self.steps = steps
        self.nbytes = nbytes
        self.n_bufs = n_bufs
        self.tenant = tenant
        self.enqueued = time.monotonic()
        self.result = None
        self.error = None
        self.done = False
        self.abandoned = False


class _Group:
    __slots__ = ("plan_t", "entries", "deadline", "full",
                 "dispatching", "active")

    def __init__(self, plan_t, deadline: float):
        self.plan_t = plan_t
        self.entries: list[_Entry] = []
        self.deadline = deadline  # admission-window end (monotonic)
        self.full = False
        self.dispatching = False
        self.active = 0  # threads still waiting on this group


class BatchScheduler:
    """One per process, installed via serving.install()/configure()."""

    def __init__(self, window_s: float = 0.002, max_queries: int = 64,
                 max_lanes: int = 16384,
                 max_bytes: int = 256 * 1024 * 1024,
                 memo_cap: int = 256):
        self.window_s = float(window_s)
        self.max_queries = int(max_queries)
        self.max_lanes = int(max_lanes)
        self.max_bytes = int(max_bytes)
        self.enabled = True
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._groups: dict = {}  # plan_t -> _Group (open for admission)
        self._solo: dict[str, int] = {}
        self._dispatches = 0
        self._queries = 0
        self._lanes = 0
        self._last_batch = 0
        # cross-query fetch memo (see module docstring); bounded, TTL
        # a few admission windows — floor keeps the default 2ms window
        # usable for queries that take longer than the window to plan
        self._memo: dict = {}
        self._memo_cap = int(memo_cap)
        self._memo_ttl = max(self.window_s * 4.0, 0.25)
        self._memo_hits = 0

    # ---------------- admission + dispatch ----------------

    def submit(self, engine, plan_t, leaves, params, steps_pad,
               nbytes: int, n_bufs: int):
        """Offer one lowered query to the batcher.  Returns the demux
        slice (out_np, aux_np, errs_np, info) or None for solo."""
        if not self.enabled:
            return None
        lanes = sum(int(lf["valid"].shape[0]) for lf in leaves)
        # budget pre-checks: if even a 2-batch would exceed a budget
        # there is no partner worth waiting for
        if 2 * lanes > self.max_lanes:
            count_solo("lane_budget")
            return None
        if 2 * nbytes > self.max_bytes:
            count_solo("bytes_budget")
            return None
        limits = getattr(engine._qrange_local, "limits", None)
        deadline = getattr(limits, "deadline", None)
        if deadline is not None and deadline.remaining() < (
                4.0 * self.window_s):
            # not enough budget left to sit out an admission window
            count_solo("deadline")
            return None
        from m3_tpu import attribution
        entry = _Entry(engine, leaves, params, steps_pad, nbytes,
                       n_bufs,
                       attribution.current_tenant(
                           attribution.DEFAULT_TENANT))
        with self._cv:
            group = self._groups.get(plan_t)
            if group is not None and (group.dispatching or group.full):
                # sealed or already claimed: a fresh group replaces it
                # in the admission map (the old one's members hold
                # their own reference and clean up by identity)
                group = None
            if group is not None:
                n = len(group.entries)
                if ((n + 1) * lanes > self.max_lanes
                        or (n + 1) * entry.nbytes > self.max_bytes):
                    # joining would blow the budget: seal the group
                    # for dispatch and start a fresh one with us
                    group.full = True
                    self._cv.notify_all()
                    group = None
            if group is None:
                group = _Group(plan_t,
                               time.monotonic() + self.window_s)
                self._groups[plan_t] = group
            group.entries.append(entry)
            group.active += 1
            if len(group.entries) >= self.max_queries:
                group.full = True
                self._cv.notify_all()
        try:
            return self._wait_and_serve(engine, group, entry, lanes)
        finally:
            with self._cv:
                group.active -= 1
                if not entry.done:
                    entry.abandoned = True
                if (group.active == 0
                        and self._groups.get(group.plan_t) is group):
                    # every member left before anyone claimed the
                    # token (all cancelled/expired): drop the group so
                    # a later arrival never joins a dead window
                    del self._groups[group.plan_t]

    def _wait_and_serve(self, engine, group, entry, lanes):
        hard_cap = time.monotonic() + self.window_s + _WAIT_CAP_S
        with self._cv:
            while True:
                if entry.done:
                    break
                now = time.monotonic()
                if not group.dispatching and (group.full
                                              or now >= group.deadline):
                    # claim the dispatch token; close admission so new
                    # arrivals start a fresh group
                    group.dispatching = True
                    if self._groups.get(group.plan_t) is group:
                        del self._groups[group.plan_t]
                    break
                if now >= hard_cap:
                    raise RuntimeError(
                        "batch dispatch never delivered "
                        f"(waited {self.window_s + _WAIT_CAP_S:.0f}s)")
                self._cv.wait(min(_SLICE_S, max(
                    group.deadline - now, 0.0) or _SLICE_S))
                # cooperative cancel / deadline checkpoint: a
                # cancelled query leaves the window here — masked out
                # of the demux, not out of the dispatch
                engine._check_deadline("batch window")
        if entry.done:
            return self._consume(entry)
        return self._dispatch(group, entry, lanes)

    def _consume(self, entry):
        if entry.error is not None:
            raise entry.error
        return entry.result

    def _dispatch(self, group, my_entry, lanes):
        """Token holder: stack, run the batched program once, deliver
        every entry's slice, return our own."""
        entries = group.entries
        if len(entries) == 1:
            count_solo("no_partner")
            my_entry.done = True
            return None
        try:
            self._dispatch_inner(group, entries, lanes)
        except Exception as exc:  # noqa: BLE001 — deliver, then let
            # every member (us included) fall back to its solo path
            with self._cv:
                for e in entries:
                    if not e.done:
                        e.error = exc
                        e.done = True
                self._cv.notify_all()
        return self._consume(my_entry)

    def _dispatch_inner(self, group, entries, lanes):
        from m3_tpu import attribution, observe
        from m3_tpu.models import query_pipeline as qp
        from m3_tpu.observe.devmem import nbytes_of
        from m3_tpu.ops import kernel_telemetry
        from m3_tpu.query import plan as qplan

        q = len(entries)
        q_pad = 1 << max(q - 1, _Q_FLOOR - 1).bit_length()
        # pad the query axis by replicating entry 0: the padding
        # slices recompute a real query and are never demuxed
        idx = list(range(q)) + [0] * (q_pad - q)
        leaves = tuple(
            {k: np.stack([entries[i].leaves[li][k] for i in idx])
             for k in lf}
            for li, lf in enumerate(entries[0].leaves))
        params = tuple(
            tuple(np.stack([np.asarray(entries[i].params[pi][j])
                            for i in idx])
                  for j in range(len(p)))
            for pi, p in enumerate(entries[0].params))
        steps = np.stack([entries[i].steps for i in idx])

        plan_t = group.plan_t
        hit = qplan._note_fingerprint((plan_t, ("batch", q_pad)),
                                      bucket=f"batch{q_pad}")
        ker = kernel_telemetry.kernels().get(
            "device_expr_pipeline_batched")
        before = ker.stats() if ker is not None else {}
        stacked = nbytes_of(leaves) + nbytes_of(params) + steps.nbytes
        n_bufs = len(leaves) + len(params) + 1
        t0 = time.perf_counter()
        # the shared dispatch runs under the reserved batch tenant so
        # kernel telemetry's per-call billing skips it; the per-tenant
        # split happens below on the measured elapsed time
        with tracing.tenant_scope(BATCH_TENANT), \
                observe.device_ledger().borrow(
                    "query_batch", stacked, count=n_bufs):
            out, aux, errs = qp.device_expr_pipeline_batched(
                plan_t, leaves, params, steps)
        out_np = np.asarray(out)
        aux_np = tuple(np.asarray(a) for a in aux)
        errs_np = [np.asarray(e) for e in errs]
        elapsed = time.perf_counter() - t0

        after = ker.stats() if ker is not None else {}
        compiled = (after.get("compiles", 0)
                    > before.get("compiles", 0))
        compile_s = (after.get("compile_s", 0.0)
                     - before.get("compile_s", 0.0))
        # identical plans -> identical lane counts -> equal split of
        # the shared device time across the real entries
        share = elapsed / q
        if attribution.enabled():
            for e in entries:
                attribution.account_read(e.tenant, device_seconds=share)

        instrument.counter("m3_query_batch_dispatches_total").inc()
        instrument.counter("m3_query_batch_queries_total").inc(q)
        instrument.counter("m3_query_batch_lanes_total").inc(lanes * q)
        now = time.monotonic()
        win = instrument.histogram("m3_query_batch_window_seconds")
        with self._lock:
            self._dispatches += 1
            self._queries += q
            self._lanes += lanes * q
            self._last_batch = q

        info_base = {
            "batch_size": q,
            "q_pad": q_pad,
            "compile_cache_hit": bool(hit and not compiled),
            "compiled": compiled,
            "compile_s": compile_s,
            "device_s": elapsed,
            "device_s_share": share,
            # blocked for the chip inside it, by kernel telemetry's
            # stamps (a compiling call counts none)
            "device_wait_s": (after.get("wait_s", 0.0)
                              - before.get("wait_s", 0.0)),
        }
        with self._cv:
            for qi, e in enumerate(entries):
                win.observe(max(now - e.enqueued - elapsed, 0.0))
                if e.done:
                    continue
                e.result = (
                    out_np[qi],
                    tuple(a[qi] for a in aux_np),
                    [err[qi] for err in errs_np],
                    dict(info_base,
                         waited_s=max(now - e.enqueued - elapsed, 0.0)))
                e.done = True
            self._cv.notify_all()

    # ---------------- cross-query fetch memo ----------------

    def memo_get(self, key):
        """Single-flight lookup: a miss RESERVES the key, so when a
        whole fleet of batched queries arrives at the same selector at
        once, exactly one thread walks the index and packs — the rest
        block (bounded) on its reservation and adopt the entry.
        Without this the fleet races: everyone misses simultaneously,
        everyone re-gathers, and the admission window expires before
        the stragglers reach the batch seam."""
        now = time.monotonic()
        with self._lock:
            ent = self._memo.get(key)
            if ent is not None and "ent" in ent:
                if now - ent["ts"] <= self._memo_ttl:
                    self._memo_hits += 1
                    return ent["ent"]
                del self._memo[key]
                ent = None
            if ent is None:
                # reserve: this caller computes, memo_put fulfills
                self._memo[key] = {"event": threading.Event(),
                                   "ts": now}
                return None
            ev = ent["event"]
        # someone else is computing this key: wait off-lock, bounded
        # by the same horizon a batch member would wait for admission
        ev.wait(min(max(self.window_s * 4.0, 0.25), 2.0))
        with self._lock:
            ent = self._memo.get(key)
            if (ent is not None and "ent" in ent
                    and time.monotonic() - ent["ts"] <= self._memo_ttl):
                self._memo_hits += 1
                return ent["ent"]
            # the computer died or timed out: take over the reservation
            self._memo[key] = {"event": threading.Event(),
                               "ts": time.monotonic()}
            return None

    def memo_put(self, key, ent) -> None:
        now = time.monotonic()
        ev = None
        with self._lock:
            cur = self._memo.get(key)
            if cur is not None and "event" in cur:
                ev = cur["event"]
            elif cur is None and len(self._memo) >= self._memo_cap:
                expired = [k for k, v in self._memo.items()
                           if now - v["ts"] > self._memo_ttl]
                for k in expired:
                    del self._memo[k]
                if len(self._memo) >= self._memo_cap:
                    return  # full of live entries: don't evict them
            self._memo[key] = {"ent": ent, "ts": now}
        if ev is not None:
            ev.set()  # wake the single-flight waiters

    def memo_abort(self, key) -> None:
        """Drop this caller's reservation (the gather raised): waiters
        stop blocking and the next miss re-reserves."""
        ev = None
        with self._lock:
            cur = self._memo.get(key)
            if cur is not None and "event" in cur:
                ev = cur["event"]
                del self._memo[key]
        if ev is not None:
            ev.set()

    # ---------------- observability ----------------

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "installed": True,
                "enabled": self.enabled,
                "window_s": self.window_s,
                "max_queries": self.max_queries,
                "max_lanes": self.max_lanes,
                "max_bytes": self.max_bytes,
                "dispatches": self._dispatches,
                "batched_queries": self._queries,
                "batched_lanes": self._lanes,
                "last_batch_size": self._last_batch,
                "solo": dict(self._solo),
                "groups_open": len(self._groups),
                "fetch_memo_entries": len(self._memo),
                "fetch_memo_hits": self._memo_hits,
            }
