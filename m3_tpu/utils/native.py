"""ctypes loader for the native (C++) runtime pieces.

Builds on demand with g++ and caches the shared object next to the
source.  The reference is pure Go with no cgo (SURVEY.md §2.4); in this
framework the native layer plays the role Go's compiled runtime plays
there — scalar wire codecs and host-side hot loops — while the device
math lives in JAX/XLA.
"""

from __future__ import annotations

import ctypes
import os
import pathlib
import subprocess
import threading

import numpy as np

_ROOT = pathlib.Path(__file__).resolve().parent.parent.parent
_NATIVE_DIR = _ROOT / "native"
_LIB_CACHE: dict[str, ctypes.CDLL] = {}  # lint: allow-unbounded-cache (one entry per native lib)


def load(name: str) -> ctypes.CDLL:
    """Load native/<name>.cc as a shared library, compiling if stale.
    A failed compile is cached and re-raised — without this, every
    caller with a fallback path would re-run the (slow, doomed) g++
    invocation per request."""
    cached = _LIB_CACHE.get(name)
    if cached is not None:
        if isinstance(cached, Exception):
            raise cached
        return cached
    src = _NATIVE_DIR / f"{name}.cc"
    so = _NATIVE_DIR / f"lib{name}.so"
    try:
        if not so.exists() or so.stat().st_mtime < src.stat().st_mtime:
            # built under a name of this thread's own and moved into
            # place whole: server threads and test workers that find
            # the library missing at once each load a finished file
            tmp = so.with_name(
                f"lib{name}.{os.getpid()}.{threading.get_ident()}.so")
            subprocess.run(
                # no -march=native: the object is cached beside the
                # source and a copied tree may run on another CPU
                ["g++", "-O2", "-shared", "-fPIC",
                 "-pthread", "-o", str(tmp), str(src)],
                check=True,
                capture_output=True,
            )
            os.replace(tmp, so)
        lib = ctypes.CDLL(str(so))
    except Exception as exc:
        _LIB_CACHE[name] = exc
        raise
    _LIB_CACHE[name] = lib
    return lib


def blob_offsets(streams: list[bytes]) -> tuple[bytes, np.ndarray]:
    """Concatenate streams + int64 offset table (the marshalling shape
    every native batch entry point takes).  Callers running a count
    pass and a decode pass back-to-back should compute this once and
    pass it to both via ``packed=``— the join is hundreds of MB at
    fan-out scale."""
    blob = b"".join(streams)
    offsets = np.zeros(len(streams) + 1, dtype=np.int64)
    np.cumsum([len(s) for s in streams], out=offsets[1:])
    return blob, offsets


def m3tsz_ref():
    """Typed handle to the scalar C++ M3TSZ decoder."""
    lib = load("m3tsz_ref")
    lib.m3tsz_decode_downsample.restype = ctypes.c_int64
    lib.m3tsz_decode_downsample.argtypes = [
        ctypes.c_char_p,
        np.ctypeslib.ndpointer(np.int64),
        ctypes.c_int64,
        ctypes.c_int64,
        ctypes.c_int,
        ctypes.c_int,
        np.ctypeslib.ndpointer(np.float64),
    ]
    lib.m3tsz_decode_one.restype = ctypes.c_int
    lib.m3tsz_decode_one.argtypes = [
        ctypes.c_char_p,
        ctypes.c_int64,
        ctypes.c_int64,
        np.ctypeslib.ndpointer(np.int64),
        np.ctypeslib.ndpointer(np.float64),
        ctypes.c_int,
    ]
    return lib


def decode_one_native(stream: bytes, max_dp: int, unit_nanos: int = 1_000_000_000):
    """Decode one stream with the C++ decoder (test/bench helper)."""
    lib = m3tsz_ref()
    t = np.zeros(max_dp, dtype=np.int64)
    v = np.zeros(max_dp, dtype=np.float64)
    n = lib.m3tsz_decode_one(stream, len(stream), unit_nanos, t, v, max_dp)
    if n < 0:
        raise ValueError("unsupported construct in stream")
    return t[:n], v[:n]


def decode_downsample_native(
    streams: list[bytes], max_dp: int, window: int, unit_nanos: int = 1_000_000_000
):
    """Single-core scalar decode + windowed mean — the CPU baseline."""
    lib = m3tsz_ref()
    blob, offsets = blob_offsets(streams)
    out = np.zeros((len(streams), max_dp // window), dtype=np.float64)
    total = lib.m3tsz_decode_downsample(
        blob, offsets, len(streams), unit_nanos, max_dp, window, out
    )
    return out, int(total)


def count_batch_native(
    streams: list[bytes], unit_nanos: int = 1_000_000_000,
    n_threads: int = 0, packed: tuple[bytes, np.ndarray] | None = None,
) -> np.ndarray:
    """Threaded count-only decode pass: datapoints per stream, -1 for
    streams with constructs the C++ decoder cannot handle.  Lets batch
    readers size the decode grid exactly (a stream's dp count is not
    recoverable from its byte length)."""
    lib = load("m3tsz_ref")
    fn = lib.m3tsz_count_batch
    if not getattr(fn, "_typed", False):
        fn.restype = None
        fn.argtypes = [
            ctypes.c_char_p,
            np.ctypeslib.ndpointer(np.int64),
            ctypes.c_int64,
            ctypes.c_int64,
            ctypes.c_int,
            np.ctypeslib.ndpointer(np.int64),
        ]
        fn._typed = True
    L = len(streams)
    blob, offsets = packed if packed is not None else blob_offsets(streams)
    counts = np.zeros(L, dtype=np.int64)
    fn(blob, offsets, L, unit_nanos, n_threads, counts)
    return counts


def decode_batch_native(
    streams: list[bytes], max_dp: int, unit_nanos: int = 1_000_000_000,
    n_threads: int = 0,
):
    """Threaded raw batch decode (the CPU serving path for fan-out
    reads).  Returns (ts [L, max_dp] i64, vs [L, max_dp] f64,
    counts [L] i64) — counts[i] < 0 marks a stream the C++ decoder
    cannot handle (annotations / unit changes); callers patch those
    lanes with the Python scalar oracle."""
    lib = load("m3tsz_ref")
    fn = lib.m3tsz_decode_batch
    if not getattr(fn, "_typed", False):
        fn.restype = None
        fn.argtypes = [
            ctypes.c_char_p,
            np.ctypeslib.ndpointer(np.int64),
            ctypes.c_int64,
            ctypes.c_int64,
            ctypes.c_int,
            ctypes.c_int,
            np.ctypeslib.ndpointer(np.int64),
            np.ctypeslib.ndpointer(np.float64),
            np.ctypeslib.ndpointer(np.int64),
        ]
        fn._typed = True
    L = len(streams)
    blob, offsets = blob_offsets(streams)
    ts = np.zeros((L, max_dp), dtype=np.int64)
    vs = np.zeros((L, max_dp), dtype=np.float64)
    counts = np.zeros(L, dtype=np.int64)
    fn(blob, offsets, L, unit_nanos, max_dp, n_threads, ts, vs, counts)
    return ts, vs, counts


def encode_batch_native(
    timestamps: np.ndarray, values: np.ndarray, starts: np.ndarray,
    stride: int = 4096,
) -> list[bytes]:
    """Single-core scalar M3TSZ encode — the CPU baseline + oracle.

    timestamps: [L, T] int64, values: [L, T] float64, starts: [L] int64.
    """
    lib = load("m3tsz_ref")
    lib.m3tsz_encode_batch.restype = ctypes.c_int64
    lib.m3tsz_encode_batch.argtypes = [
        np.ctypeslib.ndpointer(np.int64),
        np.ctypeslib.ndpointer(np.float64),
        ctypes.c_int64,
        ctypes.c_int64,
        np.ctypeslib.ndpointer(np.int64),
        np.ctypeslib.ndpointer(np.uint8),
        ctypes.c_int64,
        np.ctypeslib.ndpointer(np.int64),
    ]
    ts = np.ascontiguousarray(timestamps, dtype=np.int64)
    vs = np.ascontiguousarray(values, dtype=np.float64)
    st = np.ascontiguousarray(starts, dtype=np.int64)
    L, T = ts.shape
    out = np.zeros(L * stride, dtype=np.uint8)
    nbytes = np.zeros(L, dtype=np.int64)
    total = lib.m3tsz_encode_batch(ts, vs, L, T, st, out, stride, nbytes)
    if total < 0:
        raise ValueError(f"series exceeds stride {stride} bytes")
    return [out[l * stride:l * stride + nbytes[l]].tobytes()
            for l in range(L)]


def encode_columnar_native(
    bounds: np.ndarray, times: np.ndarray, values: np.ndarray,
    starts: np.ndarray, n_threads: int = 0,
) -> list[bytes]:
    """Threaded ragged M3TSZ encode straight from lane-sorted columnar
    data (the shard seal layout): lane l encodes slice
    [bounds[l], bounds[l+1]) of times/values.  The CPU serving path for
    block seals — byte-exact vs the batched device encoder (both are
    oracle-locked)."""
    lib = load("m3tsz_ref")
    fn = lib.m3tsz_encode_columnar
    if not getattr(fn, "_typed", False):
        i64p = np.ctypeslib.ndpointer(np.int64)
        fn.restype = ctypes.c_int64
        fn.argtypes = [i64p, i64p, np.ctypeslib.ndpointer(np.float64),
                       ctypes.c_int64, i64p,
                       np.ctypeslib.ndpointer(np.uint8),
                       ctypes.c_int64, ctypes.c_int, i64p]
        fn._typed = True
    bounds = np.ascontiguousarray(bounds, dtype=np.int64)
    ts = np.ascontiguousarray(times, dtype=np.int64)
    vs = np.ascontiguousarray(values, dtype=np.float64)
    st = np.ascontiguousarray(starts, dtype=np.int64)
    L = len(bounds) - 1
    max_count = int(np.diff(bounds).max(initial=0))
    # worst-case record ~15 bytes (same bound as the batch encoder)
    stride = 64 + 15 * max_count
    for _ in range(3):
        out = np.zeros(L * stride, dtype=np.uint8)
        nbytes = np.zeros(L, dtype=np.int64)
        total = int(fn(bounds, ts, vs, L, st, out, stride, n_threads,
                       nbytes))
        if total >= 0:
            return [out[l * stride:l * stride + nbytes[l]].tobytes()
                    for l in range(L)]
        stride *= 2
    raise ValueError("series exceeds encoder stride bound")


def prepare_value_fields_native(
    values: np.ndarray, n_valid: np.ndarray, n_threads: int = 0
):
    """Threaded C++ value-grammar pass (native/m3tsz_prepare.cc) —
    the production host half of the hybrid batch encoder.  Returns
    (ctl_bits, ctl_n, pay_bits, pay_n), each [L, T], identical to
    m3_tpu.ops.m3tsz_encode.prepare_value_fields (numpy reference)."""
    lib = load("m3tsz_prepare")
    lib.m3tsz_prepare_value_fields.restype = None
    lib.m3tsz_prepare_value_fields.argtypes = [
        np.ctypeslib.ndpointer(np.float64),
        np.ctypeslib.ndpointer(np.int32),
        ctypes.c_int64,
        ctypes.c_int64,
        ctypes.c_int,
        np.ctypeslib.ndpointer(np.uint64),
        np.ctypeslib.ndpointer(np.int32),
        np.ctypeslib.ndpointer(np.uint64),
        np.ctypeslib.ndpointer(np.int32),
    ]
    vs = np.ascontiguousarray(values, dtype=np.float64)
    nv = np.ascontiguousarray(n_valid, dtype=np.int32)
    L, T = vs.shape
    ctl_bits = np.zeros((L, T), dtype=np.uint64)
    ctl_n = np.zeros((L, T), dtype=np.int32)
    pay_bits = np.zeros((L, T), dtype=np.uint64)
    pay_n = np.zeros((L, T), dtype=np.int32)
    lib.m3tsz_prepare_value_fields(
        vs, nv, L, T, n_threads, ctl_bits, ctl_n, pay_bits, pay_n
    )
    return ctl_bits, ctl_n, pay_bits, pay_n


def extrapolated_rate_native(
    times: np.ndarray, values: np.ndarray, step_times: np.ndarray,
    range_nanos: int, is_counter: bool, is_rate: bool, n_threads: int = 0,
) -> np.ndarray:
    """Single-pass windowed rate/increase/delta over a packed batch
    (native/temporal.cc) — semantics locked to
    m3_tpu.ops.consolidate.extrapolated_rate (the numpy reference)."""
    lib = load("temporal")
    fn = lib.prom_extrapolated_rate
    if not getattr(fn, "_typed", False):
        fn.restype = None
        fn.argtypes = [
            np.ctypeslib.ndpointer(np.int64),
            np.ctypeslib.ndpointer(np.float64),
            ctypes.c_int64,
            ctypes.c_int64,
            np.ctypeslib.ndpointer(np.int64),
            ctypes.c_int64,
            ctypes.c_int64,
            ctypes.c_int,
            ctypes.c_int,
            ctypes.c_int,
            np.ctypeslib.ndpointer(np.float64),
        ]
        fn._typed = True
    ts = np.ascontiguousarray(times, dtype=np.int64)
    vs = np.ascontiguousarray(values, dtype=np.float64)
    st = np.ascontiguousarray(step_times, dtype=np.int64)
    L, N = ts.shape
    out = np.empty((L, len(st)), dtype=np.float64)
    fn(ts, vs, L, N, st, len(st), range_nanos,
       int(is_counter), int(is_rate), n_threads, out)
    return out


def decode_merged_native(
    streams: list[bytes], row_dst: np.ndarray, row_cap: np.ndarray,
    out_t: np.ndarray, out_v: np.ndarray,
    unit_nanos: int = 1_000_000_000, n_threads: int = 0,
    packed: tuple[bytes, np.ndarray] | None = None,
):
    """Fused decode+merge (native/m3tsz_ref.cc m3tsz_decode_merged):
    decode stream m directly at flat offset row_dst[m] of out_t/out_v.
    Returns (row_n, row_first, row_last, row_sorted)."""
    lib = load("m3tsz_ref")
    fn = lib.m3tsz_decode_merged
    if not getattr(fn, "_typed", False):
        i64p = np.ctypeslib.ndpointer(np.int64)
        fn.restype = None
        fn.argtypes = [
            ctypes.c_char_p, i64p, ctypes.c_int64, ctypes.c_int64,
            i64p, i64p, ctypes.c_int,
            i64p, np.ctypeslib.ndpointer(np.float64),
            i64p, i64p, i64p, np.ctypeslib.ndpointer(np.uint8),
        ]
        fn._typed = True
    M = len(streams)
    blob, offsets = packed if packed is not None else blob_offsets(streams)
    row_n = np.zeros(M, dtype=np.int64)
    row_first = np.zeros(M, dtype=np.int64)
    row_last = np.zeros(M, dtype=np.int64)
    row_sorted = np.zeros(M, dtype=np.uint8)
    fn(blob, offsets, M, unit_nanos,
       np.ascontiguousarray(row_dst, dtype=np.int64),
       np.ascontiguousarray(row_cap, dtype=np.int64),
       n_threads, out_t, out_v, row_n, row_first, row_last, row_sorted)
    return row_n, row_first, row_last, row_sorted


def pad_lane_tails_native(out_t: np.ndarray, out_v: np.ndarray,
                          lane_counts: np.ndarray) -> None:
    lib = load("m3tsz_ref")
    fn = lib.pad_lane_tails
    if not getattr(fn, "_typed", False):
        i64p = np.ctypeslib.ndpointer(np.int64)
        fn.restype = None
        fn.argtypes = [i64p, np.ctypeslib.ndpointer(np.float64), i64p,
                       ctypes.c_int64, ctypes.c_int64]
        fn._typed = True
    n_lanes, n_cap = out_t.shape
    fn(out_t, out_v,
       np.ascontiguousarray(lane_counts, dtype=np.int64),
       n_lanes, n_cap)


_WINDOW_OPS = {"avg_over_time": 0, "sum_over_time": 1,
               "min_over_time": 2, "max_over_time": 3,
               "count_over_time": 4, "stddev_over_time": 5,
               "stdvar_over_time": 6, "present_over_time": 7}


def window_reduce_native(
    times: np.ndarray, values: np.ndarray, step_times: np.ndarray,
    range_nanos: int, reducer: str, n_threads: int = 0,
) -> np.ndarray:
    """Single-pass windowed *_over_time reductions (native/temporal.cc)
    — semantics locked to consolidate.window_reduce's numpy reference."""
    lib = load("temporal")
    fn = lib.prom_window_reduce
    if not getattr(fn, "_typed", False):
        i64p = np.ctypeslib.ndpointer(np.int64)
        f64p = np.ctypeslib.ndpointer(np.float64)
        fn.restype = None
        fn.argtypes = [i64p, f64p, ctypes.c_int64, ctypes.c_int64,
                       i64p, ctypes.c_int64, ctypes.c_int64,
                       ctypes.c_int, ctypes.c_int, f64p]
        fn._typed = True
    ts = np.ascontiguousarray(times, dtype=np.int64)
    vs = np.ascontiguousarray(values, dtype=np.float64)
    st = np.ascontiguousarray(step_times, dtype=np.int64)
    L, N = ts.shape
    out = np.empty((L, len(st)), dtype=np.float64)
    fn(ts, vs, L, N, st, len(st), range_nanos,
       _WINDOW_OPS[reducer], n_threads, out)
    return out


def window_holt_winters_native(
    times: np.ndarray, values: np.ndarray, step_times: np.ndarray,
    range_nanos: int, sf: float, tf: float, n_threads: int = 0,
) -> np.ndarray:
    """Single-pass holt_winters (native/temporal.cc) — semantics locked
    to consolidate.window_holt_winters's numpy reference."""
    lib = load("temporal")
    fn = lib.prom_window_holt_winters
    if not getattr(fn, "_typed", False):
        i64p = np.ctypeslib.ndpointer(np.int64)
        f64p = np.ctypeslib.ndpointer(np.float64)
        fn.restype = None
        fn.argtypes = [i64p, f64p, ctypes.c_int64, ctypes.c_int64,
                       i64p, ctypes.c_int64, ctypes.c_int64,
                       ctypes.c_double, ctypes.c_double, ctypes.c_int,
                       f64p]
        fn._typed = True
    ts = np.ascontiguousarray(times, dtype=np.int64)
    vs = np.ascontiguousarray(values, dtype=np.float64)
    st = np.ascontiguousarray(step_times, dtype=np.int64)
    L, N = ts.shape
    out = np.empty((L, len(st)), dtype=np.float64)
    fn(ts, vs, L, N, st, len(st), range_nanos, float(sf), float(tf),
       n_threads, out)
    return out


def window_quantile_native(
    times: np.ndarray, values: np.ndarray, step_times: np.ndarray,
    range_nanos: int, phi: float, n_threads: int = 0,
) -> np.ndarray:
    """Single-pass quantile_over_time (native/temporal.cc) — numpy
    nanquantile 'linear' semantics; caller handles out-of-range phi."""
    lib = load("temporal")
    fn = lib.prom_window_quantile
    if not getattr(fn, "_typed", False):
        i64p = np.ctypeslib.ndpointer(np.int64)
        f64p = np.ctypeslib.ndpointer(np.float64)
        fn.restype = None
        fn.argtypes = [i64p, f64p, ctypes.c_int64, ctypes.c_int64,
                       i64p, ctypes.c_int64, ctypes.c_int64,
                       ctypes.c_double, ctypes.c_int, f64p]
        fn._typed = True
    ts = np.ascontiguousarray(times, dtype=np.int64)
    vs = np.ascontiguousarray(values, dtype=np.float64)
    st = np.ascontiguousarray(step_times, dtype=np.int64)
    L, N = ts.shape
    out = np.empty((L, len(st)), dtype=np.float64)
    fn(ts, vs, L, N, st, len(st), range_nanos, float(phi), n_threads,
       out)
    return out


def merge_grids_native(
    slots: np.ndarray, ts: np.ndarray, vs: np.ndarray,
    counts: np.ndarray, n_lanes: int,
    t_min_excl: int, t_max_incl: int, n_threads: int = 0,
):
    """Native two-pass grid merge (native/temporal.cc): per-row window
    clamp + per-lane totals, then threaded row copies into the packed
    [n_lanes, N] batch.  Contract (verified by the caller): each row's
    first counts[m] timestamps ascend, same-lane rows appear in
    ascending time order."""
    lib = load("temporal")
    fa, fb = lib.merge_grids_pass_a, lib.merge_grids_pass_b
    if not getattr(fa, "_typed", False):
        i64p = np.ctypeslib.ndpointer(np.int64)
        fa.restype = ctypes.c_int64
        fa.argtypes = [i64p, ctypes.c_int64, ctypes.c_int64, i64p, i64p,
                       ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
                       i64p, i64p, i64p]
        fb.restype = None
        fb.argtypes = [i64p, np.ctypeslib.ndpointer(np.float64),
                       ctypes.c_int64, ctypes.c_int64, i64p, i64p, i64p,
                       i64p, ctypes.c_int64, ctypes.c_int64,
                       ctypes.c_int,
                       i64p, np.ctypeslib.ndpointer(np.float64)]
        fa._typed = True
    ts = np.ascontiguousarray(ts, dtype=np.int64)
    vs = np.ascontiguousarray(vs, dtype=np.float64)
    slots = np.ascontiguousarray(slots, dtype=np.int64)
    counts = np.ascontiguousarray(counts, dtype=np.int64)
    M, T = ts.shape
    row_lo = np.empty(M, dtype=np.int64)
    row_cnt = np.empty(M, dtype=np.int64)
    lane_counts = np.empty(n_lanes, dtype=np.int64)
    n = int(fa(ts, M, T, counts, slots, n_lanes, t_min_excl, t_max_incl,
               row_lo, row_cnt, lane_counts))
    out_t = np.empty((n_lanes, n), dtype=np.int64)
    out_v = np.empty((n_lanes, n), dtype=np.float64)
    fb(ts, vs, M, T, slots, row_lo, row_cnt, lane_counts, n_lanes, n,
       n_threads, out_t, out_v)
    return out_t, out_v, lane_counts


def decode_write_request_native(data: bytes):
    """Prometheus WriteRequest -> columnar arrays via the C++ parser
    (native/prom_wire.cc) — the ingest hot loop's escape hatch from
    Python varint walking.

    Returns (label_start i64[S+1], sample_start i64[S+1],
    label_off i64[L,4] (name_off,name_len,val_off,val_len),
    blob bytes, ts_ms i64[N], values f64[N]).
    Raises ValueError on malformed input."""
    lib = load("prom_wire")
    fn = lib.prom_decode_write_request
    if not getattr(fn, "_typed", False):
        fn.restype = ctypes.c_int
        fn.argtypes = [
            ctypes.c_char_p, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            np.ctypeslib.ndpointer(np.int64),
            np.ctypeslib.ndpointer(np.int64),
            np.ctypeslib.ndpointer(np.int64),
            np.ctypeslib.ndpointer(np.uint8),
            np.ctypeslib.ndpointer(np.int64),
            np.ctypeslib.ndpointer(np.float64),
            np.ctypeslib.ndpointer(np.int64),
        ]
        fn._typed = True
    n = len(data)
    # capacity bounds from the wire grammar: a series costs >= 2 bytes,
    # a label >= 4, a sample >= 4 (tag+varint ts, value may be absent);
    # blob <= payload bytes.  One pass almost always fits; double on -2.
    cap_series = n // 2 + 4
    cap_labels = n // 4 + 4
    cap_blob = n + 16
    cap_samples = n // 4 + 4
    for _ in range(3):
        label_start = np.empty(cap_series + 1, dtype=np.int64)
        sample_start = np.empty(cap_series + 1, dtype=np.int64)
        label_off = np.empty(4 * cap_labels, dtype=np.int64)
        blob = np.empty(cap_blob, dtype=np.uint8)
        ts_ms = np.empty(cap_samples, dtype=np.int64)
        values = np.empty(cap_samples, dtype=np.float64)
        counts = np.zeros(4, dtype=np.int64)
        rc = fn(data, n, cap_series, cap_labels, cap_blob, cap_samples,
                label_start, sample_start, label_off, blob, ts_ms,
                values, counts)
        if rc == 0:
            ns, nl, nb, nsmp = (int(c) for c in counts)
            return (label_start[:ns + 1], sample_start[:ns + 1],
                    label_off[:4 * nl].reshape(nl, 4),
                    blob[:nb].tobytes(), ts_ms[:nsmp], values[:nsmp])
        if rc == -1:
            raise ValueError("malformed WriteRequest protobuf")
        cap_series *= 2
        cap_labels *= 2
        cap_blob *= 2
        cap_samples *= 2
    raise ValueError("WriteRequest exceeds parser capacity bounds")


def _text_decode_fn(name: str, lib):
    """Shared ctypes signature for the text_wire decoders (carbon and
    influx differ only by one leading scalar)."""
    fn = getattr(lib, name)
    if not getattr(fn, "_typed", False):
        i64p = np.ctypeslib.ndpointer(np.int64)
        head = [ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64]
        if name == "influx_decode_lines":
            head.append(ctypes.c_int64)  # precision multiplier
        fn.restype = ctypes.c_int
        fn.argtypes = head + [
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            i64p, i64p, i64p,
            np.ctypeslib.ndpointer(np.uint8),
            i64p, np.ctypeslib.ndpointer(np.float64),
            i64p, i64p,
        ]
        fn._typed = True
    return fn


def _decode_text_lines(name: str, data: bytes, head_args):
    """Capacity-retry driver shared by both text decoders.

    Returns (label_start, sample_start, label_off [L,4], blob bytes,
    ts_ns i64[N], values f64[N], fallback_ranges [(off, len), ...]) —
    fallback ranges are line slices the strict columnar grammar
    deferred to the scalar reference parser."""
    lib = load("text_wire")
    fn = _text_decode_fn(name, lib)
    n = len(data)
    n_lines = data.count(b"\n") + data.count(b"\r") + 1
    # carbon: ~2x path bytes + 8 bytes of __gN__/__name__ framing per
    # component; influx re-emits the tag set once per numeric field.
    # Start generous and double on -2 (same convention as prom_wire).
    cap_series = n // 4 + 8
    cap_labels = n // 2 + 8
    cap_blob = 4 * n + 256
    fb_off = np.empty(2 * n_lines, dtype=np.int64)
    for _ in range(6):
        label_start = np.empty(cap_series + 1, dtype=np.int64)
        sample_start = np.empty(cap_series + 1, dtype=np.int64)
        label_off = np.empty(4 * cap_labels, dtype=np.int64)
        blob = np.empty(cap_blob, dtype=np.uint8)
        ts_ns = np.empty(cap_series, dtype=np.int64)
        values = np.empty(cap_series, dtype=np.float64)
        counts = np.zeros(5, dtype=np.int64)
        rc = fn(data, n, *head_args, cap_series, cap_labels, cap_blob,
                label_start, sample_start, label_off, blob, ts_ns,
                values, fb_off, counts)
        if rc == 0:
            ns, nl, nb, nsmp, nfb = (int(c) for c in counts)
            fb = [(int(fb_off[2 * i]), int(fb_off[2 * i + 1]))
                  for i in range(nfb)]
            return (label_start[:ns + 1], sample_start[:ns + 1],
                    label_off[:4 * nl].reshape(nl, 4),
                    blob[:nb].tobytes(), ts_ns[:nsmp], values[:nsmp], fb)
        cap_series *= 2
        cap_labels *= 2
        cap_blob *= 2
    raise ValueError(f"{name}: payload exceeds decoder capacity bounds")


def decode_carbon_native(data: bytes, now_nanos: int):
    """Carbon plaintext lines -> columnar arrays (native/text_wire.cc):
    __g0__..__gN__ component tags + __name__ per line, `-1`/`N`
    timestamps resolved to ``now_nanos``.  See _decode_text_lines for
    the return shape."""
    return _decode_text_lines("carbon_decode_lines", data, (now_nanos,))


def decode_influx_native(data: bytes, mult: int, now_nanos: int):
    """InfluxDB line protocol -> columnar arrays (native/text_wire.cc):
    one series row per numeric field, tags + __name__ =
    <measurement>_<field>; ``mult`` is the precision->nanos multiplier.
    See _decode_text_lines for the return shape."""
    return _decode_text_lines("influx_decode_lines", data,
                              (now_nanos, mult))


def render_matrix_json_native(
    head: bytes, step_times: np.ndarray, values: np.ndarray,
    metrics: list[bytes], tail: bytes,
) -> bytes:
    """``head`` + the JSON of a query_range matrix + ``tail``
    (native/json_wire.cc): ``step_times`` int64[S] nanos, ``values``
    float64[R, S] with NaN for no sample, ``metrics`` each row's
    ``"metric"`` object already rendered.  The bytes are json.dumps'
    of query/http.py ``_matrix_json``'s document."""
    lib = load("json_wire")
    fn = lib.matrix_json_render
    if not getattr(fn, "_typed", False):
        i64p = np.ctypeslib.ndpointer(np.int64, flags="C")
        fn.restype = ctypes.c_int64
        fn.argtypes = [
            ctypes.c_char_p, ctypes.c_int64,
            i64p, ctypes.c_int64,
            np.ctypeslib.ndpointer(np.float64, flags="C"), ctypes.c_int64,
            ctypes.c_char_p, i64p,
            ctypes.c_char_p, ctypes.c_int64,
            np.ctypeslib.ndpointer(np.uint8), ctypes.c_int64,
        ]
        fn._typed = True
    n_rows, n_steps = values.shape
    blob, offsets = blob_offsets(metrics)
    # the library's own worst case: 56 bytes a point, 34 a row
    out = np.empty(len(head) + len(tail) + len(blob) + 64
                   + n_rows * (34 + 56 * n_steps), dtype=np.uint8)
    n = fn(head, len(head), step_times, n_steps, values, n_rows,
           blob, offsets, tail, len(tail), out, len(out))
    if n < 0:
        raise ValueError("matrix reply exceeds the render's buffer bound")
    return out[:n].tobytes()
