"""Device-memory ledger — per-owner accounting of live device buffers.

jax gives a single process-wide HBM number at best; when the query
megabatch, the decoded-block device bridge, the aggregator pools, and
the encode scratch all share one chip, "HBM is 80% full" is not
actionable.  This ledger threads a tiny accounting call through every
device-upload seam so ``/debug/device`` can answer *whose* bytes are
resident:

  - ``borrow(owner, nbytes)`` — scoped: bytes live for the duration
    of a ``with`` block (query megabatch upload around a fused call,
    encode scratch around a pack kernel).
  - ``track(owner, arrays)`` — lifetime-tracked: bytes live until the
    arrays are garbage collected (DecodedBlockCache device bridge);
    uses ``weakref.finalize`` and degrades to a scoped count when an
    object is not weakref-able.
  - ``register(owner, nbytes)`` — a resizable handle for long-lived
    pools (aggregator elem state) that call ``set(nbytes, count)`` on
    every grow.

Alongside buffers the ledger keeps per-kernel peak-HBM estimates
(max over invocations of arg bytes + result bytes, fed by
``ops/kernel_telemetry``) and a compile-cache inventory (fingerprint,
shape bucket, hits, last-used) with manual eviction — the
``/debug/device`` JSON and the ``m3_device_*`` /
``m3_compile_cache_entries`` gauges all read from here.

Owner names are short literal strings chosen at the call site
("query_megabatch", "decoded_block_bridge", "aggregator_pool",
"encode_scratch", ...) — the label domain is bounded by construction.
"""

from __future__ import annotations

import threading
import time
import weakref
from contextlib import contextmanager
from typing import Callable, Dict, Iterable, Optional

from ..utils import instrument

log = instrument.logger("observe.devmem")


def nbytes_of(arrays: Iterable) -> int:
    """Total nbytes across array-likes, walking nested tuple/list/dict
    containers — the same pytree shape kernel_telemetry._arg_volume
    counts, so per-owner upload bytes reconcile with the per-kernel
    transfer counters.  Ignores things without nbytes."""
    total = 0
    stack = list(arrays)
    while stack:
        a = stack.pop()
        if isinstance(a, (tuple, list)):
            stack.extend(a)
            continue
        if isinstance(a, dict):
            stack.extend(a.values())
            continue
        n = getattr(a, "nbytes", None)
        if n is not None:
            total += int(n)
    return total


class PoolHandle:
    """Resizable accounting handle for a long-lived device pool."""

    __slots__ = ("_ledger", "owner", "nbytes", "count", "_closed")

    def __init__(self, ledger: "DeviceMemLedger", owner: str,
                 nbytes: int, count: int):
        self._ledger = ledger
        self.owner = owner
        self.nbytes = int(nbytes)
        self.count = int(count)
        self._closed = False

    def set(self, nbytes: int, count: int = 1) -> None:
        nbytes, count = int(nbytes), int(count)
        d_bytes, d_count = nbytes - self.nbytes, count - self.count
        self.nbytes, self.count = nbytes, count
        self._ledger._adjust(self.owner, d_bytes, d_count,
                             upload=max(0, d_bytes))

    def close(self) -> None:
        if not self._closed:
            self._closed = True
            self._ledger._adjust(self.owner, -self.nbytes, -self.count)


class DeviceMemLedger:
    """Per-owner live device-buffer accounting + kernel peaks +
    compile-cache inventory."""

    def __init__(self):
        # re-entrant: track()'s weakref finalizers call _adjust, and a
        # GC pass can fire one on the thread that is inside _adjust
        self._lock = threading.RLock()
        self._bytes: Dict[str, int] = {}
        self._counts: Dict[str, int] = {}
        self._kernel_peaks: Dict[str, int] = {}
        # compile caches: cache name -> {fingerprint -> entry dict}
        self._cc: Dict[str, Dict[str, dict]] = {}
        self._cc_evictors: Dict[str, Callable[[], int]] = {}
        self._upload_total = instrument.bounded_counter(
            "m3_device_upload_bytes_total", cap=32)
        self._peak_gauge = instrument.bounded_gauge(
            "m3_kernel_peak_hbm_bytes", cap=64)
        instrument.gauge_fn("m3_device_buffer_bytes_all", self.total_bytes)
        instrument.gauge_fn("m3_compile_cache_entries",
                            lambda: float(sum(len(v)
                                              for v in self._cc.values())))

    # -- buffer accounting -----------------------------------------

    def _adjust(self, owner: str, d_bytes: int, d_count: int,
                upload: int = 0) -> None:
        with self._lock:
            if owner not in self._bytes:
                self._bytes[owner] = 0
                self._counts[owner] = 0
                # First sighting of an owner: mint its gauges.  The
                # owner set is small and literal, so this is bounded.
                instrument.gauge_fn(
                    "m3_device_buffer_bytes",
                    lambda o=owner: float(self._bytes.get(o, 0)),
                    owner=owner)
                instrument.gauge_fn(
                    "m3_device_buffers",
                    lambda o=owner: float(self._counts.get(o, 0)),
                    owner=owner)
            self._bytes[owner] = max(0, self._bytes[owner] + d_bytes)
            self._counts[owner] = max(0, self._counts[owner] + d_count)
        if upload > 0:
            self._upload_total.labels(owner=owner).inc(upload)

    @contextmanager
    def borrow(self, owner: str, nbytes: int, count: int = 1):
        """Scoped accounting: bytes live for the duration of the
        ``with`` block (device call argument uploads, scratch)."""
        nbytes, count = int(nbytes), int(count)
        self._adjust(owner, nbytes, count, upload=nbytes)
        try:
            yield
        finally:
            self._adjust(owner, -nbytes, -count)

    def track(self, owner: str, arrays: Iterable) -> int:
        """Lifetime accounting: bytes live until the arrays are
        collected.  Returns the nbytes tracked."""
        arrays = list(arrays)
        total = 0
        for a in arrays:
            n = getattr(a, "nbytes", None)
            if n is None:
                continue
            n = int(n)
            try:
                weakref.finalize(a, self._adjust, owner, -n, -1)
            except TypeError:
                # Not weakref-able (e.g. a committed numpy scalar):
                # count the upload but not residency.
                self._upload_total.labels(owner=owner).inc(n)
                continue
            total += n
            self._adjust(owner, n, 1, upload=n)
        return total

    def register(self, owner: str, nbytes: int = 0,
                 count: int = 0) -> PoolHandle:
        """Resizable handle for a long-lived pool; call ``set`` on
        every grow/shrink, ``close`` on teardown."""
        h = PoolHandle(self, owner, 0, 0)
        if nbytes or count:
            h.set(nbytes, count)
        return h

    def total_bytes(self) -> float:
        with self._lock:
            return float(sum(self._bytes.values()))

    # -- kernel peaks ----------------------------------------------

    def note_kernel(self, kernel: str, arg_bytes: int,
                    result_bytes: int = 0) -> None:
        """Fed by ops/kernel_telemetry per invocation: the working-set
        estimate for one call is args + results resident together."""
        est = int(arg_bytes) + int(result_bytes)
        with self._lock:
            prev = self._kernel_peaks.get(kernel, 0)
            if est <= prev:
                return
            self._kernel_peaks[kernel] = est
        self._peak_gauge.labels(kernel=kernel).set(est)

    # -- compile-cache inventory -----------------------------------

    def compile_cache_note(self, cache: str, fingerprint: str,
                           bucket: str = "", hit: bool = False) -> None:
        """One compile-cache lookup: keeps (fingerprint, shape bucket,
        hits, last-used) per cache for the /debug/device inventory."""
        with self._lock:
            entries = self._cc.setdefault(cache, {})
            e = entries.get(fingerprint)
            if e is None:
                e = entries[fingerprint] = {
                    "fingerprint": fingerprint, "bucket": bucket,
                    "hits": 0, "compiles": 0, "last_used": 0.0,
                }
            if hit:
                e["hits"] += 1
            else:
                e["compiles"] += 1
            if bucket:
                e["bucket"] = bucket
            e["last_used"] = time.time()

    def compile_cache_register_evictor(self, cache: str,
                                       fn: Callable[[], int]) -> None:
        """``fn`` drops the real memoized state (jit cache / seen-set)
        and returns how many entries it evicted."""
        with self._lock:
            self._cc_evictors[cache] = fn

    def compile_cache_evict(self, cache: Optional[str] = None) -> dict:
        """Evict one cache (or all): clears the inventory and invokes
        the registered evictor so the underlying jit/seen state goes
        too.  Returns {cache: evicted_count}."""
        with self._lock:
            names = [cache] if cache else list(
                set(self._cc) | set(self._cc_evictors))
            evictors = {n: self._cc_evictors.get(n) for n in names}
            dropped = {n: len(self._cc.pop(n, {})) for n in names}
        out = {}
        for name in names:
            n = dropped.get(name, 0)
            fn = evictors.get(name)
            if fn is not None:
                try:
                    n = max(n, int(fn() or 0))
                except Exception as exc:  # noqa: BLE001
                    log.warn("compile-cache evictor failed",
                             cache=name, error=str(exc))
            out[name] = n
            log.info("compile cache evicted", cache=name, entries=n)
        return out

    # -- views -----------------------------------------------------

    def view(self) -> dict:
        """JSON-ready snapshot for /debug/device."""
        with self._lock:
            owners = sorted(self._bytes)
            buffers = [{
                "owner": o,
                "bytes": self._bytes[o],
                "buffers": self._counts[o],
            } for o in owners]
            kernels = [{
                "kernel": k,
                "peak_hbm_bytes": v,
            } for k, v in sorted(self._kernel_peaks.items(),
                                 key=lambda kv: -kv[1])]
            caches = {}
            for name, entries in self._cc.items():
                rows = sorted(entries.values(),
                              key=lambda e: -e["last_used"])
                caches[name] = [{
                    **e, "last_used": round(e["last_used"], 3),
                } for e in rows[:256]]
        return {
            "total_bytes": sum(b["bytes"] for b in buffers),
            "buffers": buffers,
            "kernel_peaks": kernels,
            "compile_caches": caches,
        }

    def reset(self) -> None:
        """Test hook: forget everything (weakref finalizers from old
        tracks will no-op against the floor-at-zero accounting)."""
        with self._lock:
            self._bytes.clear()
            self._counts.clear()
            self._kernel_peaks.clear()
            self._cc.clear()
            self._cc_evictors.clear()
