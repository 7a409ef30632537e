"""Device kernel telemetry: per-kernel compile/execute accounting.

The ROADMAP north-star is the device serving path, yet the jitted
kernels in ``models/`` were black boxes: a p99 regression could not be
attributed to XLA recompiles (new static-arg combinations) vs slow
execution vs growing payloads.  ``instrument_kernel(name)`` wraps a
jitted entry point and records, per kernel:

- ``m3_kernel_compiles_total{kernel}`` — XLA compilations (detected as
  a jit cache-size delta across the call; every new static-arg shape
  pays one)
- ``m3_kernel_compile_seconds{kernel}`` — wall time of compiling calls
- ``m3_kernel_execute_seconds{kernel}`` — wall time of cache-hit
  calls, fenced with ``jax.block_until_ready`` so async dispatch does
  not make every kernel look free
- ``m3_kernel_invocations_total{kernel}``,
  ``m3_kernel_elements_total{kernel}``,
  ``m3_kernel_bytes_total{kernel}`` — call rate and input volume
- ``m3_kernel_result_bytes_total{kernel}`` — device->host result
  volume (the transfer the fused path pays to bring answers back)
- ``m3_kernel_hbm_peak_bytes{kernel}`` (``hbm_peak_bytes`` in the
  stats) — the compiler's own account of the program's peak in device
  memory: arguments, result and temporaries of the compiled executable
  (``memory_analysis()``), the largest of the kernel's compilations.
  Read once a compile, where the compiling call is detected, from the
  executable that call left in jit's cache; never on a cache-hit call
- ``m3_kernel_queued_ahead_total{kernel}`` and the process-wide gauge
  ``m3_device_inflight`` — the device's queue, counted where calls
  are dispatched: one device runs one program at a time, so a call
  that finds N instrumented calls in flight waits for N runs before
  its own.  ``execute_s`` cannot tell that wait from running;
  ``queued_ahead / invocations`` is the mean depth a call met, and
  ``dispatch_s`` (the jitted call's return) against ``wait_s`` (from
  there to ready) splits the host's part from the device's; a
  cache-hit call's wait is also charged to the calling query
  (``tracing.charge``: its record's ``device_wait_s``).

and opens a ``device.Kernel`` span (tagged ``queued_ahead``) so device
time shows up inside distributed query traces (the Monarch-style cost
attribution the slow-query log consumes), and an
``m3:kernel:<name>`` annotation so it shows up in a profiler trace.

Two contract details worth their weight:

- the wrapper is a class with ``__getattr__`` delegation, so jit
  internals the codebase relies on (``_cache_size`` / ``_clear_cache``
  / ``lower``) keep working on the wrapped name;
- a call whose arguments are jax Tracers (the kernel re-entered under
  ``shard_map`` or an outer jit) goes straight to the raw function:
  timing an abstract trace would both crash ``block_until_ready`` and
  record nonsense.
"""

from __future__ import annotations

import threading
import time

import jax

from m3_tpu.utils import instrument, tracing

_metrics = instrument.registry()

# name -> InstrumentedKernel, for bench/debug snapshots
_KERNELS: dict[str, "InstrumentedKernel"] = {}
_KERNELS_LOCK = threading.Lock()

# instrumented calls dispatched and not yet ready, over all kernels:
# one device, one queue
_INFLIGHT_LOCK = threading.Lock()
_inflight = 0


def _inflight_add(delta: int) -> int:
    """-> the count in flight before this change."""
    global _inflight
    with _INFLIGHT_LOCK:
        before = _inflight
        _inflight = before + delta
        # under the lock: a late write of an older count would leave
        # an idle device reading busy
        _metrics.gauge("m3_device_inflight").set(_inflight)
    return before


def _is_traced(args, kwargs) -> bool:
    return any(isinstance(a, jax.core.Tracer) for a in args) or any(
        isinstance(v, jax.core.Tracer) for v in kwargs.values())


def _hbm_peak_bytes(fn, args, kwargs) -> int:
    """The peak device memory of the executable that `fn(*args,
    **kwargs)` has just compiled, by the compiler's own account; 0
    where the backend gives none.  `lower().compile()` of a signature
    jit has compiled hands back the cached executable (0.6 ms on
    XLA:CPU), it does not compile again."""
    try:
        mem = fn.lower(*args, **kwargs).compile().memory_analysis()
        return int(getattr(mem, "peak_memory_in_bytes", 0) or (
            mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes))
    except Exception:  # noqa: BLE001 - telemetry is best-effort
        return 0


def _arg_volume(args, kwargs):
    """(elements, bytes) across array-like inputs, walking nested
    tuple/list/dict pytrees — the fused whole-query pipeline passes
    its leaves/params as nested containers, and the volume counters
    must reflect the real host->device upload, not just the flat
    args."""
    elements = 0
    nbytes = 0
    stack = list(args) + list(kwargs.values())
    while stack:
        a = stack.pop()
        if isinstance(a, (tuple, list)):
            stack.extend(a)
            continue
        if isinstance(a, dict):
            stack.extend(a.values())
            continue
        size = getattr(a, "size", None)
        if isinstance(size, int):
            elements += size
            nb = getattr(a, "nbytes", None)
            if isinstance(nb, int):
                nbytes += nb
    return elements, nbytes


class InstrumentedKernel:
    """Telemetry wrapper around one jitted kernel entry point."""

    def __init__(self, fn, name: str):
        self.__dict__["_fn"] = fn
        self.__dict__["name"] = name
        self.__dict__["_lock"] = threading.Lock()
        self.__dict__["_stats"] = {
            "invocations": 0, "compiles": 0,
            "compile_s": 0.0, "execute_s": 0.0,
            "dispatch_s": 0.0, "wait_s": 0.0, "queued_ahead": 0,
            "elements": 0, "bytes": 0, "result_bytes": 0,
            "hbm_peak_bytes": 0,
        }
        try:
            self.__dict__["__wrapped__"] = fn
            self.__dict__["__doc__"] = fn.__doc__
        except AttributeError:
            pass
        with _KERNELS_LOCK:
            _KERNELS[name] = self

    def __call__(self, *args, **kwargs):
        fn = self.__dict__["_fn"]
        if _is_traced(args, kwargs):
            return fn(*args, **kwargs)
        name = self.__dict__["name"]
        try:
            before = fn._cache_size()
        except (AttributeError, TypeError):
            before = None
        queued_ahead = _inflight_add(1)
        try:
            with tracing.span(tracing.DEVICE_KERNEL, kernel=name,
                              queued_ahead=queued_ahead), \
                    tracing.TraceAnnotation("m3:kernel:" + name):
                t0 = time.perf_counter_ns()
                out = fn(*args, **kwargs)
                t_dispatched = time.perf_counter_ns()
                out = jax.block_until_ready(out)
                t_ready = time.perf_counter_ns()
        finally:
            _inflight_add(-1)
        elapsed = (t_ready - t0) / 1e9
        dispatch_s = (t_dispatched - t0) / 1e9
        compiled = False
        if before is not None:
            try:
                compiled = fn._cache_size() > before
            except (AttributeError, TypeError):
                compiled = False
        elements, nbytes = _arg_volume(args, kwargs)
        _, result_bytes = _arg_volume((out,), {})
        hbm_peak = _hbm_peak_bytes(fn, args, kwargs) if compiled else 0
        st = self.__dict__["_stats"]
        with self.__dict__["_lock"]:
            st["invocations"] += 1
            st["elements"] += elements
            st["bytes"] += nbytes
            st["result_bytes"] += result_bytes
            st["queued_ahead"] += queued_ahead
            if compiled:
                st["compiles"] += 1
                st["compile_s"] += elapsed
                hbm_peak = max(st["hbm_peak_bytes"], hbm_peak)
                st["hbm_peak_bytes"] = hbm_peak
            else:
                st["execute_s"] += elapsed
                st["dispatch_s"] += dispatch_s
                st["wait_s"] += elapsed - dispatch_s
        _metrics.counter("m3_kernel_invocations_total", kernel=name).inc()
        _metrics.counter("m3_kernel_queued_ahead_total",
                         kernel=name).inc(queued_ahead)
        _metrics.counter("m3_kernel_elements_total",
                         kernel=name).inc(elements)
        _metrics.counter("m3_kernel_bytes_total", kernel=name).inc(nbytes)
        _metrics.counter("m3_kernel_result_bytes_total",
                         kernel=name).inc(result_bytes)
        # device-memory ledger: arg + result bytes resident together
        # is this call's working-set estimate; the ledger keeps the
        # per-kernel max as its peak-HBM figure (lazy import — ops/
        # must stay importable standalone)
        try:
            from m3_tpu import observe

            observe.device_ledger().note_kernel(name, nbytes,
                                                result_bytes)
        except Exception:  # noqa: BLE001 - telemetry is best-effort
            pass
        if compiled:
            _metrics.counter("m3_kernel_compiles_total", kernel=name).inc()
            _metrics.gauge("m3_kernel_hbm_peak_bytes",
                           kernel=name).set(hbm_peak)
            _metrics.histogram("m3_kernel_compile_seconds",
                               kernel=name).observe(elapsed)
        else:
            _metrics.histogram("m3_kernel_execute_seconds",
                               kernel=name).observe(elapsed)
            # workload attribution: device execute seconds credited to
            # the tenant whose query ran this kernel (lazy import —
            # ops/ must stay importable without the full package)
            try:
                from m3_tpu import attribution

                tenant = attribution.current_tenant()
                # a cross-query batched dispatch runs under the
                # reserved batch scope: the scheduler hands each entry
                # the call's device seconds and its wait, so charging
                # the token holder here would double-count
                if tenant != attribution.BATCH_TENANT:
                    # blocked for the chip, by the two stamps above:
                    # the calling query's device_wait_s
                    tracing.charge("device_wait_s",
                                   elapsed - dispatch_s)
                    if attribution.enabled():
                        attribution.account_read(
                            tenant, device_seconds=elapsed)
            except Exception:  # noqa: BLE001 - telemetry is best-effort
                pass
        return out

    def __getattr__(self, attr):
        # jit internals (_cache_size / _clear_cache / lower / ...)
        return getattr(self.__dict__["_fn"], attr)

    def stats(self) -> dict:
        with self.__dict__["_lock"]:
            return dict(self.__dict__["_stats"])

    def reset(self) -> None:
        """Zero the counts and seconds; `hbm_peak_bytes` describes the
        executables compiled so far, which a reset does not drop."""
        st = self.__dict__["_stats"]
        with self.__dict__["_lock"]:
            for k in st:
                if k != "hbm_peak_bytes":
                    st[k] = 0 if isinstance(st[k], int) else 0.0


def instrument_kernel(name: str):
    """Decorator: apply ABOVE the jit decorator so the wrapper sees
    the jitted callable (and its compile cache)."""

    def deco(fn):
        return InstrumentedKernel(fn, name)

    return deco


def kernels() -> dict[str, InstrumentedKernel]:
    with _KERNELS_LOCK:
        return dict(_KERNELS)


def snapshot() -> dict[str, dict]:
    """{kernel: stats()} — read before and after a window by
    benchmark/traffic_kinds/query_closed_loop.py (its delta is what
    benchmark/readers/kernel_telemetry.py reads) and by chip_smoke.py's
    phase lines."""
    with _KERNELS_LOCK:
        items = list(_KERNELS.items())
    return {name: k.stats() for name, k in items}


def reset() -> None:
    with _KERNELS_LOCK:
        items = list(_KERNELS.values())
    for k in items:
        k.reset()
