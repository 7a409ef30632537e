#!/usr/bin/env python3
"""Serve from the chip: one coordinator process on a TPU, proven per phase.

Drives the system's main path once through the entry points a user
calls — `deploy/config/coordinator.yml` -> `CoordinatorService` ->
`POST /api/v1/prom/remote/write` -> the service's own tick/flush ->
`GET /api/v1/query_range` — at the size of BASELINE.json config 4
("m3query fan-out read: 50k-series PromQL rate() over 6h"): 50,000
counter series (labels `__name__`, `job` x32, `host`) at 10 s cadence in
2 h blocks.  Everything runs in THIS process: it is the only one that
touches JAX, so it owns the chip.

stdout carries one JSON object per phase (also written to
`chiprun_out/chip_smoke_phases.jsonl`); the LAST line is exactly
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}`
and is printed only when every phase passed on a TPU.  Any failed
assertion, HTTP status, native build, or a non-TPU platform exits
non-zero; nothing is caught to let the run continue.

    python chip_smoke.py                  # one chip, as the driver runs it
    python chip_smoke.py --chips 4        # sharded-mesh path only (4 chips)
    JAX_PLATFORMS=cpu M3_DEVICE_SERVING=1 \\
      python chip_smoke.py --series 500 --hours 2   # rehearsal; ends not-ok
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import pathlib
import shutil
import sys
import time
import urllib.parse

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent

SERIES = 50_000          # BASELINE.json config 4; never cut on the chip
# span of whole flushed blocks.  Config 4 reads 6 h; the default is cut
# to 4 because the fused program's pow2 buckets at 6 h (150,000 streams
# -> 262,144 rows, 2,160 samples -> 4,096 per lane) need 18.8 GB by
# memory_analysis() against the chip's 16 GB (tests/test_tpu_aot_compile)
HOURS = 4
JOBS = 32
CADENCE_S = 10
STEP_S = 60
BLOCK_S = 2 * 3600       # RetentionOptions.block_size default (2 h)
BUFFER_PAST_S = 600      # RetentionOptions.buffer_past default (10 min)
RTOL = 1e-9              # tests/test_device_query_fusion.py's loosest
LIVE_TAIL_S = 1200       # newest samples written past the sealed blocks
# a sealed block's slice goes in requests of 25 series x 720 samples
# (~18,000 samples from one keep-alive client: the shape the write
# path's group commit is tuned for, storage/commitlog.py); the live
# tail in requests of 1,000 series, because every request leaves one
# chunk in each shard's open buffer and a mutable-buffer read scans all
# of a buffer's chunks per series (storage/buffer.py read_lane)
RNG_SERIES = 25
BLOCK_SERIES_PER_REQUEST = 25
LIVE_SERIES_PER_REQUEST = 1000
METRIC = "http_requests_total"

# what jax reports for a v5e chip; an unknown kind is an error
KNOWN_DEVICE_KINDS = ("TPU v5 lite", "TPU v5e")

QUERIES = (
    ("a", f"rate({METRIC}[5m])"),
    ("b", f"sum by (job)(rate({METRIC}[5m]))"),
    ("c", f"topk(5, sum by (host)(rate({METRIC}[5m])))"),
)

_phase_log = None


def emit(phase: str, **fields) -> None:
    line = json.dumps({"phase": phase, **fields}, sort_keys=False)
    print(line, flush=True)
    if _phase_log is not None:
        _phase_log.write(line + "\n")
        _phase_log.flush()


def fail(msg: str) -> "NoReturn":
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


# --------------------------------------------------------------- data

def series_labels(i: int) -> dict[bytes, bytes]:
    """Every job runs on every host: series i = (job i%32, host i//32)."""
    return {b"__name__": METRIC.encode(),
            b"job": b"job-%02d" % (i % JOBS),
            b"host": b"host-%05d" % (i // JOBS)}


def _uvarint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _len_delim(field: int, body: bytes) -> bytes:
    return bytes([(field << 3) | 2]) + _uvarint(len(body)) + body


def label_bytes(i: int) -> bytes:
    """TimeSeries.labels (field 1) for series i, sorted by name."""
    labels = series_labels(i)
    return b"".join(
        _len_delim(1, _len_delim(1, k) + _len_delim(2, labels[k]))
        for k in sorted(labels))


def sample_bytes(ts_ms, values):
    """Vectorized TimeSeries.samples (field 2): [S, T] float64 values at
    shared timestamps ts_ms [T] -> uint8 [S, T*18].  A Sample is
    `09 <f64 LE> 10 <varint ts_ms>`; ms timestamps of this century take
    a 6-byte varint, so every sample is 18 bytes on the wire."""
    assert int(ts_ms.min()) >= 1 << 35 and int(ts_ms.max()) < 1 << 42
    S, T = values.shape
    out = np.empty((S, T, 18), dtype=np.uint8)
    out[:, :, 0] = 0x12
    out[:, :, 1] = 16
    out[:, :, 2] = 0x09
    out[:, :, 3:11] = np.ascontiguousarray(
        values, dtype="<f8").view(np.uint8).reshape(S, T, 8)
    out[:, :, 11] = 0x10
    t = ts_ms.astype(np.uint64)
    for k in range(6):
        byte = ((t >> np.uint64(7 * k)) & np.uint64(0x7F)).astype(np.uint8)
        if k < 5:
            byte |= 0x80
        out[:, :, 12 + k] = byte[None, :]
    return out.reshape(S, T * 18)


def snappy_literal(data: bytes) -> bytes:
    """Spec-valid snappy block stream made of literal elements only —
    the load generator's side of the wire, not the system under test
    (the repo's own `snappy.compress` is a pure-Python matcher)."""
    out = bytearray(_uvarint(len(data)))
    for lo in range(0, len(data), 65536):
        chunk = data[lo:lo + 65536]
        out.append(61 << 2)                      # literal, 2-byte length
        out += (len(chunk) - 1).to_bytes(2, "little")
        out += chunk
    return bytes(out)


class Workload:
    """Deterministic counters from --seed: each run of RNG_SERIES series
    draws its increments from default_rng([seed, run]), so any (series
    range, time slice) is regenerated on demand instead of holding 10^8
    samples."""

    def __init__(self, seed: int, n_series: int, hours: int, now_s: int):
        self.seed, self.n_series = seed, n_series
        t_last = now_s - now_s % CADENCE_S
        # newest block the service's tick can seal right now
        self.seal_end = ((now_s - BUFFER_PAST_S) // BLOCK_S) * BLOCK_S
        self.t0 = self.seal_end - (hours // 2) * BLOCK_S
        # whole blocks [t0, seal_end), then a scrape gap, then a live
        # tail ending at now: the run's size does not depend on how far
        # into its 2 h block the wall clock happens to be
        live_from = max(self.seal_end, t_last - LIVE_TAIL_S + CADENCE_S)
        self.ts_s = np.concatenate([
            np.arange(self.t0, self.seal_end, CADENCE_S, dtype=np.int64),
            np.arange(live_from, t_last + 1, CADENCE_S, dtype=np.int64)])
        self.n_sealed_blocks = hours // 2
        self._labels = {}

    def values(self, lo: int, hi: int):
        """float64 [hi - lo, len(ts_s)]; lo is a multiple of RNG_SERIES."""
        parts = []
        for run in range(lo // RNG_SERIES, -(-hi // RNG_SERIES)):
            rng = np.random.default_rng([self.seed, run])
            inc = rng.integers(0, 100, size=(RNG_SERIES, len(self.ts_s)))
            parts.append(np.cumsum(inc, axis=1))
        return np.concatenate(parts)[:hi - lo].astype(np.float64)

    def requests(self):
        """(lo, hi, c0, c1): series range x column range of ts_s, one
        2 h block's slice at a time, oldest block first."""
        edges = np.flatnonzero(np.diff(self.ts_s // BLOCK_S)) + 1
        bounds = [0, *edges.tolist(), len(self.ts_s)]
        out = []
        for c0, c1 in zip(bounds[:-1], bounds[1:]):
            per = (BLOCK_SERIES_PER_REQUEST
                   if self.ts_s[c0] < self.seal_end
                   else LIVE_SERIES_PER_REQUEST)
            out += [(lo, min(lo + per, self.n_series), c0, c1)
                    for lo in range(0, self.n_series, per)]
        return out

    def body(self, lo: int, hi: int, c0: int, c1: int) -> tuple[bytes, int]:
        vals = self.values(lo, hi)[:, c0:c1]
        samples = sample_bytes(self.ts_s[c0:c1] * 1000, vals)
        parts = []
        for row, i in enumerate(range(lo, hi)):
            lb = self._labels.get(i)
            if lb is None:
                lb = self._labels[i] = label_bytes(i)
            parts.append(_len_delim(1, lb + samples[row].tobytes()))
        return snappy_literal(b"".join(parts)), vals.size


# --------------------------------------------------------------- http

class Client:
    """Keep-alive loopback client."""

    def __init__(self, port: int):
        self.conn = http.client.HTTPConnection("127.0.0.1", port,
                                               timeout=900)

    def request(self, method: str, path: str, body=None, headers=None):
        self.conn.request(method, path, body, headers or {})
        resp = self.conn.getresponse()
        return resp.status, resp.read()

    def get_json(self, path: str, **params):
        qs = urllib.parse.urlencode(params)
        status, body = self.request("GET", f"{path}?{qs}" if qs else path)
        if status != 200:
            fail(f"GET {path} {params} -> HTTP {status}: {body[:300]!r}")
        return json.loads(body)


# ------------------------------------------------------------- phases

def phase_start(args, out_dir):
    t0 = time.perf_counter()
    # every native library is rebuilt from the tracked sources on the
    # machine that runs this; a failed build/load raises right here
    for so in (ROOT / "native").glob("lib*.so"):
        so.unlink()

    import jax

    from m3_tpu.utils import compile_cache, native
    cache_dir = compile_cache.configure()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    on_tpu = dev.platform == "tpu"
    if on_tpu and dev.device_kind not in KNOWN_DEVICE_KINDS:
        fail(f"unknown device_kind {dev.device_kind!r}")
    if on_tpu and device["count"] != args.chips:
        fail(f"--chips {args.chips} but JAX sees {device['count']} devices")
    if not on_tpu and args.series >= SERIES:
        # the full size is only ever run on the chip; a cut size on
        # another platform is a rehearsal and still ends not-ok
        fail(f"no TPU: jax.devices()[0].platform is {dev.platform!r}")

    built = {}
    for src in sorted((ROOT / "native").glob("*.cc")):
        t = time.perf_counter()
        native.load(src.stem)
        built[src.stem] = round(time.perf_counter() - t, 2)

    data_dir = out_dir / "data"
    shutil.rmtree(data_dir, ignore_errors=True)
    os.environ["M3TPU_DATA"] = str(data_dir)
    os.environ["M3TPU_COORDINATOR_PORT"] = "0"
    os.environ["M3TPU_CARBON_PORT"] = "-1"
    if args.chips > 1:
        os.environ["M3_SERVING_MESH"] = str(args.chips)
    # one overlay on the shipped config: the background mediator is off
    # and the seal phase drives the same Database.tick()/flush() it
    # would.  A mediator tick in the middle of a minutes-long BACKFILL
    # seals half-written past blocks, and every later cold write then
    # re-seals through a pure-Python decode (storage/shard.py unseal) —
    # an artifact of compressing 6 h of arrivals into minutes, not of
    # the deployment.
    overlay = out_dir / "smoke_overlay.yml"
    overlay.write_text("coordinator:\n  tick_every: 0\n")

    from m3_tpu.services.config import load_coordinator_config
    from m3_tpu.services.run import CoordinatorService
    cfg = load_coordinator_config(
        str(ROOT / "deploy" / "config" / "coordinator.yml"), str(overlay))
    svc = CoordinatorService(cfg).start()
    emit("start", **device, jax=jax.__version__,
         compile_cache_dir=cache_dir,
         compile_cache_files_at_start=(
             len(os.listdir(cache_dir))
             if cache_dir and os.path.isdir(cache_dir) else 0),
         native_build_s=built,
         http_port=svc.http_port, num_shards=cfg.num_shards,
         config_overlay={"tick_every": 0},
         serving_mesh=os.environ.get("M3_SERVING_MESH"),
         seconds=round(time.perf_counter() - t0, 2))
    return svc, device, on_tpu


def phase_ingest(args, client, name="ingest"):
    wl = Workload(args.seed, args.series, args.hours, int(time.time()))
    tasks = wl.requests()
    headers = {"Content-Encoding": "snappy",
               "Content-Type": "application/x-protobuf"}
    t0 = time.perf_counter()
    sent = acked = nbytes = 0
    for task in tasks:
        body, n = wl.body(*task)
        status, resp = client.request(
            "POST", "/api/v1/prom/remote/write", body, headers)
        sent += n
        nbytes += len(body)
        if not 200 <= status < 300:
            fail(f"remote write -> HTTP {status}: {resp[:200]!r}")
        acked += n
    seconds = time.perf_counter() - t0
    fields = dict(
        series=args.series, cadence_s=CADENCE_S,
        points_per_series=len(wl.ts_s),
        sealable_blocks=wl.n_sealed_blocks,
        live_tail_points=int((wl.ts_s >= wl.seal_end).sum()),
        requests=len(tasks),
        samples_sent=sent, samples_acked=acked, body_bytes=nbytes,
        first_ts=int(wl.ts_s[0]), last_ts=int(wl.ts_s[-1]),
        seconds=round(seconds, 2))
    if args.hours != 6:
        # BASELINE.json config 4 reads 6 h; see the note at HOURS
        fields["reduced"] = {"hours": args.hours}
    emit(name, **fields)
    assert acked == sent == args.series * len(wl.ts_s)
    return wl, acked


def _encode_counters():
    from m3_tpu.utils import instrument
    return {k: instrument.counter(
        f"m3_encode_compile_cache_{k}_total").value
        for k in ("hits", "misses")}


def phase_seal(svc, wl, on_tpu, name="seal"):
    before = _encode_counters()
    t0 = time.perf_counter()
    sealed = svc.db.tick()
    t_tick = time.perf_counter() - t0
    flushed = svc.db.flush()
    seconds = time.perf_counter() - t0
    after = _encode_counters()
    ns = svc.cfg.unagg_namespace
    blocks = sorted({bs // 10**9 for bs in flushed.get(ns, [])})
    want = [wl.t0 + k * BLOCK_S for k in range(wl.n_sealed_blocks)]
    emit(name, blocks_flushed=len(blocks),
         shard_blocks_flushed=len(flushed.get(ns, [])),
         shard_blocks_sealed=len(sealed.get(ns, [])),
         block_starts=blocks, tick_s=round(t_tick, 2),
         seconds=round(seconds, 2),
         encode_compile_cache_hits=after["hits"] - before["hits"],
         encode_compile_cache_misses=after["misses"] - before["misses"],
         device_encode=("asserted" if on_tpu else
                        "not asserted: the CPU backend seals through "
                        "the native encoder"))
    assert blocks[:len(want)] == want, (blocks, want)
    if on_tpu:
        # only encode_batched (the device half's caller) moves these
        rose = sum(after.values()) - sum(before.values())
        assert rose > 0, "seal did not go through the device encode"


def _kernel_delta(before, after):
    out = {}
    for name, st in after.items():
        b = before.get(name, {})
        calls = st["invocations"] - b.get("invocations", 0)
        if calls:
            out[name] = {
                "calls": calls,
                "compiles": st["compiles"] - b.get("compiles", 0),
                "compile_s": round(
                    st["compile_s"] - b.get("compile_s", 0.0), 3),
                "execute_s": round(
                    st["execute_s"] - b.get("execute_s", 0.0), 3)}
    return out


def http_query_range(client, expr, start_s, end_s):
    """-> (seconds, {label tuple: (timestamps f64[], values f64[])},
    newest slow-query record, kernels that ran)."""
    from m3_tpu.ops import kernel_telemetry
    before = kernel_telemetry.snapshot()
    t0 = time.perf_counter()
    doc = client.get_json("/api/v1/query_range", query=expr,
                          start=start_s, end=end_s, step=STEP_S)
    seconds = time.perf_counter() - t0
    kernels = _kernel_delta(before, kernel_telemetry.snapshot())
    assert doc["status"] == "success", doc
    rows = {}
    for s in doc["data"]["result"]:
        key = tuple(sorted(s["metric"].items()))
        assert key not in rows, f"duplicate series {key}"
        rows[key] = (np.array([t for t, _ in s["values"]], dtype=np.float64),
                     np.array([float(v) for _, v in s["values"]]))
    rec = client.get_json("/debug/slowqueries",
                          limit=1)["data"]["queries"][0]
    assert rec["expr"] == expr, (rec["expr"], expr)
    return seconds, rows, rec, kernels


def compare_with_host(host_engine, expr, start_s, end_s, rows):
    """Same label sets, same NaN positions, values within RTOL of the
    host tier evaluated on the same database.  -> max relative error."""
    t0 = time.perf_counter()
    step_times, mat = host_engine.query_range(
        expr, start_s * 10**9, end_s * 10**9, STEP_S * 10**9)
    host_s = time.perf_counter() - t0
    ts = np.asarray(step_times, dtype=np.float64) / 1e9
    host_rows = {}
    for labels, row in zip(mat.labels, np.asarray(mat.values)):
        keep = ~np.isnan(row)
        if keep.any():
            key = tuple(sorted((k.decode(), v.decode())
                               for k, v in labels.items()))
            host_rows[key] = (ts[keep], row[keep])
    assert set(rows) == set(host_rows), (
        f"{expr}: label sets differ: served {len(rows)} series, host "
        f"{len(host_rows)}")
    worst = 0.0
    for key, (t_d, v_d) in rows.items():
        t_h, v_h = host_rows[key]
        assert np.array_equal(t_d, t_h), f"{expr}: NaN positions differ"
        err = np.abs(v_d - v_h) / np.maximum(np.abs(v_h), 1e-300)
        err = np.where(v_d == v_h, 0.0, err)
        worst = max(worst, float(err.max(initial=0.0)))
    return worst, host_s


def check_device_record(label, rec, kernels, warm: bool,
                        need_kernel: bool = True):
    """The proof that the device tier served this query, from the
    service's own evidence: the slow-query record and the kernel
    telemetry delta around the request."""
    assert rec.get("error") is None, rec
    assert "device_tier_error" not in rec, rec["device_tier_error"]
    assert rec["device_serving"] is True, (
        f"query {label} was host-served: {rec}")
    if need_kernel:
        assert any(k.startswith("device_") for k in kernels), (
            f"query {label}: no device kernel ran: {kernels}")
    tier = rec.get("device_tier")
    if tier is not None:      # whole-query fusion (query/plan.py)
        assert tier["host_nodes"] == 0, tier
        if warm:
            assert tier["compile_cache"] == "hit", tier
    if warm:
        assert all(k["compiles"] == 0 for k in kernels.values()), kernels


def phase_query(svc, wl, client, acked, on_tpu):
    from m3_tpu.query.engine import Engine
    start_s = wl.t0 + 600
    end_s = wl.seal_end - STEP_S
    served = {}
    for label, expr in QUERIES:
        for run in ("cold", "warm"):
            seconds, rows, rec, kernels = http_query_range(
                client, expr, start_s, end_s)
            if on_tpu or os.environ.get("M3_DEVICE_SERVING") == "1":
                check_device_record(label, rec, kernels, run == "warm")
            tier = rec.get("device_tier") or {}
            emit("query", query=label, expr=expr, run=run,
                 range_s=[start_s, end_s], step_s=STEP_S,
                 series=len(rows), seconds=round(seconds, 3),
                 device_serving=rec["device_serving"],
                 kernels=kernels, device_tier=tier or None,
                 compile_cache=tier.get("compile_cache"),
                 record_phases=rec["phases"],
                 datapoints=rec["datapoints"])
            served[label] = rows
    # host tier on the same database, same process — AFTER the device
    # runs, so its decoded-block cache fills cannot change what they do
    host = Engine(svc.db, svc.cfg.unagg_namespace, device_serving=False)
    for label, expr in QUERIES:
        worst, host_s = compare_with_host(host, expr, start_s, end_s,
                                          served[label])
        emit("query_check", query=label, series=len(served[label]),
             max_rel_err=worst, rtol=RTOL, host_seconds=round(host_s, 2))
        assert worst <= RTOL, f"{expr}: max rel err {worst} > {RTOL}"
    # read-back guarantee: every acknowledged sample is served, flushed
    # blocks and live buffer alike.  One instant query per job: a
    # fleet-wide window over the mutable buffers does not fit the
    # service's 30 s query budget (see phase_live)
    span = int(wl.ts_s[-1] - wl.ts_s[0]) + CADENCE_S
    at = int(wl.ts_s[-1])
    t0 = time.perf_counter()
    n_series = n_samples = 0
    for j in range(JOBS):
        expr = f'count_over_time({METRIC}{{job="job-{j:02d}"}}[{span}s])'
        _, rows, _, _ = http_query_range(client, expr, at, at)
        n_series += len(rows)
        n_samples += int(sum(v[-1] for _, v in rows.values()))
    emit("read_back", series_counted=n_series, samples_counted=n_samples,
         samples_acked=acked, window_s=span, queries=JOBS,
         seconds=round(time.perf_counter() - t0, 2))
    assert n_series == wl.n_series, (n_series, wl.n_series)
    assert n_samples == acked, (n_samples, acked)
    return host


def _host_split_reasons() -> dict[str, float]:
    """m3_query_host_split_total by reason, from the process registry."""
    from m3_tpu.utils import instrument
    return {m.tags.get("reason", ""): m.value
            for m in instrument.registry().collect()
            if m.name == "m3_query_host_split_total"}


def phase_live(wl, client, host):
    """Finding, not assertion: a dashboard range that ends at *now*
    reaches the mutable buffer.  Scoped to one job's series: the same
    query over all 50,000 spends longer in the mutable-buffer gather
    than the service's 30 s query budget allows (HTTP 504)."""
    expr = f'sum by (job)(rate({METRIC}{{job="job-00"}}[5m]))'
    end_s = int(wl.ts_s[-1])
    start_s = wl.seal_end - 1800      # last sealed half hour .. now
    before = _host_split_reasons()
    seconds, rows, rec, kernels = http_query_range(client, expr,
                                                   start_s, end_s)
    after = _host_split_reasons()
    worst, _ = compare_with_host(host, expr, start_s, end_s, rows)
    emit("live", expr=expr, range_s=[start_s, end_s],
         seconds=round(seconds, 3), series=len(rows),
         served_by=("device" if rec["device_serving"] else "host"),
         kernels=kernels, device_tier=rec.get("device_tier"),
         device_tier_error=rec.get("device_tier_error"),
         host_split_total={k: v - before.get(k, 0.0)
                           for k, v in after.items()
                           if v != before.get(k, 0.0)},
         record_phases=rec["phases"], max_rel_err=worst)
    assert worst <= RTOL


def phase_mesh(svc, wl, client, n_chips):
    """--chips N: queries (b) and (a) served over HTTP through the
    N x 1 series mesh the service built from M3_SERVING_MESH, against
    the single-device tier evaluated in this same process."""
    import jax

    from m3_tpu.query.engine import Engine
    eng = svc.coordinator.http.httpd.RequestHandlerClass.engine
    assert eng._serving_shards() == n_chips, eng.serving_mesh
    start_s = wl.t0 + 600
    end_s = wl.seal_end - STEP_S
    single = Engine(svc.db, svc.cfg.unagg_namespace, device_serving=True)
    for label, expr in (QUERIES[1], QUERIES[0]):
        seconds, rows, rec, kernels = http_query_range(
            client, expr, start_s, end_s)
        # the per-node shard_map pipelines are not instrument_kernel
        # entry points: the query's record carries the shard count (the
        # engine's last_fetch_stats are the serving thread's own)
        check_device_record(label, rec, kernels, warm=False,
                            need_kernel=False)
        served = {"device_serving": rec["device_serving"],
                  "n_shards": rec.get("n_shards"), "fn": rec.get("fn"),
                  "device_s": rec["phases"]["device_s"]}
        assert served.get("device_serving") is True, served
        assert served.get("n_shards") == n_chips, served
        per_device = [
            {"id": d.id, **{k: (d.memory_stats() or {}).get(k)
                            for k in ("bytes_in_use", "peak_bytes_in_use")}}
            for d in jax.devices()]
        worst, single_s = compare_with_host(single, expr, start_s,
                                            end_s, rows)
        stats = single.last_fetch_stats or {}
        assert stats.get("device_serving") is True, stats
        emit("mesh_query", query=label, expr=expr, n_shards=n_chips,
             series=len(rows), seconds=round(seconds, 3),
             served_stats=served,
             per_device_memory=per_device,
             single_device_seconds=round(single_s, 3),
             max_rel_err_vs_single_device=worst, rtol=RTOL)
        assert worst <= RTOL


def emit_stop(stop_seconds: float) -> None:
    import jax

    from m3_tpu.ops import kernel_telemetry
    mem = jax.devices()[0].memory_stats() or {}
    emit("stop", seconds=round(stop_seconds, 2),
         peak_bytes_in_use=mem.get("peak_bytes_in_use"),
         bytes_limit=mem.get("bytes_limit"),
         kernels={k: {f: (round(v, 3) if isinstance(v, float) else v)
                      for f, v in st.items()
                      if f in ("invocations", "compiles", "compile_s",
                               "execute_s")}
                  for k, st in kernel_telemetry.snapshot().items()
                  if st["invocations"]})


def main() -> int:
    global _phase_log
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = only the sharded-mesh queries, on 4 chips")
    ap.add_argument("--series", type=int, default=SERIES,
                    help="rehearsal only: fewer series than 50,000")
    ap.add_argument("--hours", type=int, default=HOURS, choices=(2, 4, 6),
                    help="span of whole flushed 2 h blocks (a live "
                         "buffer up to now is written on top)")
    ap.add_argument("--out", default=str(ROOT / ".chip_smoke_out"),
                    help="scratch dir: database files (removed at the "
                         "end), config overlay")
    args = ap.parse_args()
    if not __debug__:
        fail("run without -O: the phase checks are assert statements")

    out_dir = pathlib.Path(args.out).resolve()
    out_dir.mkdir(parents=True, exist_ok=True)
    # the chip tool brings back chiprun_out/ (and only a few MiB of it):
    # the phase lines go there, the database does not
    log_dir = ROOT / "chiprun_out"
    log_dir.mkdir(exist_ok=True)
    _phase_log = open(log_dir / "chip_smoke_phases.jsonl", "w")
    t_all = time.perf_counter()
    svc, device, on_tpu = phase_start(args, out_dir)
    try:
        client = Client(svc.http_port)
        if args.chips > 1:
            wl, _ = phase_ingest(args, client, name="setup_ingest")
            phase_seal(svc, wl, on_tpu, name="setup_seal")
            phase_mesh(svc, wl, client, args.chips)
        else:
            wl, acked = phase_ingest(args, client)
            phase_seal(svc, wl, on_tpu)
            host = phase_query(svc, wl, client, acked, on_tpu)
            phase_live(wl, client, host)
    finally:
        t_stop = time.perf_counter()
        svc.stop()
        stop_seconds = time.perf_counter() - t_stop
        shutil.rmtree(out_dir / "data", ignore_errors=True)
    emit_stop(stop_seconds)
    emit("done", seconds=round(time.perf_counter() - t_all, 2))
    print(json.dumps({"ok": on_tpu, "device": device}), flush=True)
    return 0 if on_tpu else 1


if __name__ == "__main__":
    sys.exit(main())
