"""Flush management: leader/follower coordination + flush-times state.

The reference elects one leader per shard-set via etcd; the leader
flushes expired windows on schedule and persists flush times to KV,
while followers shadow-consume so a takeover is warm
(ref: src/aggregator/aggregator/flush_mgr.go,
leader_flush_mgr.go:134 Prepare, follower_flush_mgr.go,
flush_times_mgr.go, election_mgr.go:250).

Here the same contract rides the framework's KV + LeaderService
(m3_tpu/cluster/{kv,election}.py): the leader calls
``Aggregator.flush_before`` and records the cutoff; followers discard
up to the recorded cutoff (keeping device state bounded and
transformation state warm) without emitting.  On takeover the new
leader first discards everything the old leader recorded as flushed.

Delivery contract: emit happens BEFORE the cutoff is persisted, so a
leader crash between the two re-emits those windows on takeover —
at-least-once across crashes (never silent loss), exactly once under
clean failover.  The reference makes the same trade: its flush handler
hands metrics to an at-least-once transport (m3msg) and downstream
writes are idempotent upserts keyed by (id, timestamp).
"""

from __future__ import annotations

import threading

from m3_tpu.aggregator.aggregator import AggregatedMetric, Aggregator
from m3_tpu.cluster.election import LeaderService
from m3_tpu.cluster.kv import ErrNotFound, MemStore
from m3_tpu.utils import instrument
from m3_tpu.utils.clock import now_nanos

_log = instrument.logger("aggregator.flush")


class FlushTimesManager:
    """Last flushed cutoff per shard-set, persisted in KV
    (ref: aggregator/flush_times_mgr.go)."""

    def __init__(self, store: MemStore, shard_set_id: str):
        self._store = store
        self._key = f"_flush_times/{shard_set_id}"

    def get(self) -> int:
        try:
            val = self._store.get(self._key)
        except ErrNotFound:
            return -(1 << 62)
        return val.json()["cutoff_nanos"]

    def set(self, cutoff_nanos: int) -> None:
        self._store.set_json(self._key, {"cutoff_nanos": cutoff_nanos})


class FlushManager:
    """Drives one aggregator instance's flushes (ref: flush_mgr.go)."""

    def __init__(self, aggregator: Aggregator, handler,
                 store: MemStore, shard_set_id: str, instance_id: str,
                 buffer_past_nanos: int = 0,
                 election_ttl_seconds: float = 5.0):
        self.aggregator = aggregator
        self.handler = handler
        self.instance_id = instance_id
        self.shard_set_id = shard_set_id
        self.flush_times = FlushTimesManager(store, shard_set_id)
        self.election = LeaderService(
            store, f"agg-flush/{shard_set_id}", instance_id,
            ttl_seconds=election_ttl_seconds)
        self.buffer_past = buffer_past_nanos
        self._discarded_to = -(1 << 62)
        self._pending: list[AggregatedMetric] = []  # emit retry buffer
        self._flush_lock = threading.Lock()  # background loop vs manual
        self.n_handler_errors = 0
        self.n_loop_errors = 0
        self._m_windows = instrument.counter(
            "m3_aggregator_flush_windows_total")
        self._m_errors = instrument.counter(
            "m3_aggregator_handler_errors_total")
        self._m_leader = instrument.gauge(
            "m3_aggregator_is_leader", instance=instance_id)
        self._m_transitions = instrument.counter(
            "m3_election_transitions_total", instance=instance_id)
        # how late windows are when they finally emit, relative to
        # their window END — the aggregation-side half of ingest lag
        self._m_lateness = instrument.histogram(
            "m3_aggregator_flush_lateness_seconds")
        self._was_leader = False
        self._thread: threading.Thread | None = None
        self._stop = threading.Event()

    @property
    def is_leader(self) -> bool:
        return self.election.is_leader()

    @property
    def pending_emits(self) -> int:
        return len(self._pending)

    def campaign(self, block: bool = False, timeout: float | None = None):
        return self.election.campaign(block=block, timeout=timeout)

    def resign(self) -> None:
        self.election.resign()

    def flush_once(self, now_nanos: int) -> list[AggregatedMetric]:
        """One flush pass. Leader emits; follower shadow-discards.
        Serialized: the background loop and manual calls must not
        interleave consume/retry-buffer/cutoff updates."""
        with self._flush_lock:
            return self._flush_once_locked(now_nanos)

    def _flush_once_locked(self, now_nanos: int) -> list[AggregatedMetric]:
        leader = self.is_leader
        self._m_leader.set(1.0 if leader else 0.0)
        if leader != self._was_leader:
            self._m_transitions.inc()
            _log.info("leadership change", leader=leader)
            self._was_leader = leader
        last = self.flush_times.get()
        if not leader:  # the SAME read the gauge/transition log saw
            # follower: drop windows the leader already emitted
            # (discard pass: nothing may leave the process, including
            # remote forwarded writes — the leader sent those)
            if last > self._discarded_to:
                self.aggregator.flush_before(last, discard=True)
                self._discarded_to = last
            return []
        # leader: first discard anything a previous leader emitted
        if last > self._discarded_to:
            self.aggregator.flush_before(last, discard=True)
            self._discarded_to = last
        cutoff = now_nanos - self.buffer_past
        if cutoff <= last and not self._pending:
            return []
        out = (self.aggregator.flush_before(cutoff)
               if cutoff > last else [])
        # consumed windows survive a failing handler in the retry
        # buffer: the cutoff is only persisted once the emit lands, so
        # neither a handler error nor a crash silently loses windows
        out = self._pending + out
        if out:
            try:
                self.handler.handle(out)
            except Exception as exc:  # noqa: BLE001 — ref counts flush errors
                self.n_handler_errors += 1
                self._m_errors.inc()
                _log.error("flush handler failed", error=exc,
                           pending=len(out))
                self._pending = out
                return []
        self._pending = []
        self.flush_times.set(cutoff)
        self._discarded_to = cutoff
        self._m_windows.inc(len(out))
        if out:
            # one observation per pass (the oldest window) bounds the
            # cost; retries naturally surface as growing lateness
            self._m_lateness.observe(
                (now_nanos - min(m.time_nanos for m in out)) / 1e9)
        return out

    # -- background loop -----------------------------------------------------

    def open(self, interval_seconds: float,
             clock=now_nanos) -> None:
        def loop():
            from m3_tpu import observe
            hb = observe.task_ledger().register_daemon(
                "aggregator_flush", interval_hint_s=interval_seconds)
            while not self._stop.wait(interval_seconds):
                hb.beat()
                try:
                    # continuous candidacy (the reference's election
                    # manager campaigns in a loop): after a resign or a
                    # leader crash, some follower's next tick acquires
                    # the lapsed lease — an operator /resign yields
                    # leadership without halting flushes forever
                    if not self.is_leader:
                        self.election.campaign(block=False)
                    self.flush_once(clock())
                except Exception:  # noqa: BLE001 — keep the loop alive
                    self.n_loop_errors += 1  # ref logs + counts these
            hb.close()
        self._thread = threading.Thread(target=loop, daemon=True)
        self._thread.start()

    def close(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
        self.election.resign()
