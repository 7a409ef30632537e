"""Self-scrape: the platform ingests its own telemetry.

M3 at Uber is famously monitored by itself — operators graph M3's
health out of M3.  This loop periodically samples the in-process
metrics registry (``utils/instrument.Registry.collect()``), converts
every sample into the platform's own series shape (``__name__`` +
metric tags + ``instance``/``role``), and writes the batch through the
real ingest path into a dedicated internal namespace — so
``rate(m3_insert_queue_failed_writes_total[5m])`` is answerable by the
platform's own ``query_range``.

Contracts:

- **Counters stay cumulative.**  Samples carry the raw monotonic
  counter value; a process restart resets to zero, which PromQL
  ``rate()``/``increase()`` already treat as a counter reset.  On
  clean shutdown a **staleness marker** (NaN sample, the Prometheus
  convention) is written for every series this scraper ever emitted,
  so dashboards show the series ending instead of a flat last value.
- **Histograms ride as buckets.**  ``collect()`` flattens histograms
  into cumulative ``_bucket{le=...}`` / ``_sum`` / ``_count`` series,
  so ``histogram_quantile`` over the scraped data works unchanged.
- **Self-scrape can never stall user writes.**  The scrape cycle
  enqueues its batch into a BOUNDED queue drained by one writer
  thread; when ingest is stalled the queue fills and whole cycles are
  dropped-and-counted (``m3_selfscrape_dropped_total``) instead of
  blocking.  The scrape thread never touches the database lock.
- The scrape loop emits its own cycle metrics
  (``m3_selfscrape_duration_seconds``, ``m3_selfscrape_samples_total``)
  which the NEXT cycle scrapes — self-monitoring includes the monitor.
"""

from __future__ import annotations

import queue
import threading
import time

from m3_tpu.utils import clock, instrument

DEFAULT_NAMESPACE = "_m3_internal"

_log = instrument.logger("selfscrape")


def _series_id_from_labels(labels: dict) -> bytes:
    # late import: selfscrape sits below query in the layer order, but
    # the canonical series-id codec lives with the remote-write path
    from m3_tpu.query.remote_write import series_id_from_labels

    return series_id_from_labels(labels)


class SelfScraper:
    """Background loop: registry collect -> encode -> bounded queue ->
    ingest write.

    ``write_fn(ns, ids, tags, times, values)`` is the ingest entry
    point — ``Database.write_batch``, ``InsertQueue.write_batch_async``
    or ``Session.write_tagged_batch`` all satisfy it, so the scraped
    data rides whatever ingest path the deployment already uses.
    """

    def __init__(self, write_fn, namespace: str = DEFAULT_NAMESPACE,
                 interval_s: float = 10.0, instance: str = "",
                 role: str = "", registry=None,
                 max_pending_batches: int = 4):
        self._write = write_fn
        self.namespace = namespace
        self.interval = interval_s
        self._registry = registry or instrument.registry()
        self._base: dict[bytes, bytes] = {}
        if instance:
            self._base[b"instance"] = instance.encode()
        if role:
            self._base[b"role"] = role.encode()
        # (name, sorted-tags) -> (sid, labels): steady-state scrapes
        # repeat the same series every cycle, so id encoding collapses
        # into one dict hit (same memo idea as the ingest fast path)
        self._sid_memo: dict[tuple, tuple[bytes, dict]] = {}
        # sid -> labels of every series ever enqueued (staleness set)
        self._seen: dict[bytes, dict] = {}
        self._q: queue.Queue = queue.Queue(
            maxsize=max(1, max_pending_batches))
        self._stop = threading.Event()
        self._writer_stop = threading.Event()
        self._m_duration = self._registry.histogram(
            "m3_selfscrape_duration_seconds")
        self._m_samples = self._registry.counter(
            "m3_selfscrape_samples_total")
        self._m_dropped = self._registry.counter(
            "m3_selfscrape_dropped_total")
        self._m_cycles = self._registry.counter(
            "m3_selfscrape_cycles_total")
        self._m_errors = self._registry.counter(
            "m3_selfscrape_write_errors_total")
        self._registry.gauge_fn("m3_selfscrape_queue_depth",
                                self._q.qsize)
        self._thread: threading.Thread | None = None
        self._writer = threading.Thread(target=self._writer_loop,
                                        daemon=True,
                                        name="selfscrape-writer")
        self._writer.start()

    # -- one scrape cycle ------------------------------------------------

    def scrape_once(self, now_nanos: int | None = None) -> int:
        """Sample the registry and enqueue one write batch.  Returns
        the sample count enqueued (0 when the cycle was dropped under
        backpressure).  Never blocks on ingest."""
        t0 = time.perf_counter()
        now = clock.now_nanos() if now_nanos is None else int(now_nanos)
        self._m_cycles.inc()
        ids: list[bytes] = []
        tags: list[dict] = []
        values: list[float] = []
        for s in self._registry.collect():
            key = (s.name, tuple(sorted(s.tags.items())))
            memo = self._sid_memo.get(key)
            if memo is None:
                labels = {b"__name__": s.name.encode()}
                for k, v in s.tags.items():
                    labels[k.encode()] = str(v).encode()
                labels.update(self._base)
                memo = self._sid_memo[key] = (
                    _series_id_from_labels(labels), labels)
            ids.append(memo[0])
            tags.append(memo[1])
            values.append(float(s.value))
        n = len(ids)
        enqueued = 0
        try:
            self._q.put_nowait((ids, tags, [now] * n, values))
            enqueued = n
            self._m_samples.inc(n)
            for sid, labels in zip(ids, tags):
                self._seen.setdefault(sid, labels)
        except queue.Full:
            # drop-and-count: ingest is stalled/overloaded and the
            # bounded queue is the backpressure valve — losing a
            # telemetry cycle is always better than wedging a scrape
            # thread or competing with user writes
            self._m_dropped.inc(n)
        self._m_duration.observe(time.perf_counter() - t0)
        return enqueued

    # -- writer side -----------------------------------------------------

    def _writer_loop(self) -> None:
        from m3_tpu import observe
        hb = observe.task_ledger().register_daemon(
            "selfscrape_writer", interval_hint_s=0.25)
        try:
            self._writer_loop_inner(hb)
        finally:
            hb.close()

    def _writer_loop_inner(self, hb) -> None:
        while True:
            try:
                batch = self._q.get(timeout=0.25)
            except queue.Empty:
                hb.beat()
                if self._writer_stop.is_set():
                    return
                continue
            hb.beat()
            try:
                self._write(self.namespace, *batch)
            except Exception as e:  # noqa: BLE001 - loop must survive
                self._m_errors.inc()
                _log.warn("self-scrape write failed", err=str(e),
                          samples=len(batch[0]))
            finally:
                self._q.task_done()

    def flush(self, timeout: float = 5.0) -> bool:
        """Wait (bounded) until everything enqueued so far has been
        handed to the ingest path; True when fully drained."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self._q.unfinished_tasks == 0:
                return True
            time.sleep(0.01)
        return self._q.unfinished_tasks == 0

    # -- lifecycle -------------------------------------------------------

    def start(self) -> "SelfScraper":
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="selfscrape")
        self._thread.start()
        return self

    def _loop(self) -> None:
        from m3_tpu import observe
        hb = observe.task_ledger().register_daemon(
            "selfscrape", interval_hint_s=self.interval)
        while not self._stop.wait(self.interval):
            hb.beat()
            try:
                self.scrape_once()
            except Exception as e:  # noqa: BLE001 - loop must survive
                self._m_errors.inc()
                _log.error("self-scrape cycle failed", err=str(e))
        hb.close()

    def stop(self, staleness: bool = True, timeout: float = 5.0) -> None:
        """Stop scraping; on clean shutdown write one NaN staleness
        marker per emitted series (Prometheus staleness convention) so
        readers see the series END at shutdown rather than persist."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=timeout)
        if staleness and self._seen:
            now = clock.now_nanos()
            sids = list(self._seen)
            batch = (sids, [self._seen[s] for s in sids],
                     [now] * len(sids), [float("nan")] * len(sids))
            try:
                self._q.put_nowait(batch)
            except queue.Full:
                self._m_dropped.inc(len(sids))
        self.flush(timeout=timeout)
        self._writer_stop.set()
        self._writer.join(timeout=timeout)
