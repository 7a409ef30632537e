"""Carbon (Graphite) line-protocol ingest.

(ref: src/cmd/services/m3coordinator/ingest/carbon/ingest.go:270
Handle — TCP line protocol ``path value timestamp\\n``; parser
src/metrics/carbon/parser.go; graphite path components become
``__g0__``..``__gN__`` tags so the path is queryable per component,
ref: src/query/graphite/storage/m3_wrapper.go GraphiteTagName.)
"""

from __future__ import annotations

import math
import socket
import socketserver
import threading

from m3_tpu.aggregator import MetricKind
from m3_tpu.utils import clock, instrument

SECOND = 1_000_000_000


def graphite_tags(path: bytes) -> dict[bytes, bytes]:
    """``foo.bar.baz`` -> {__g0__: foo, __g1__: bar, __g2__: baz}."""
    return {b"__g%d__" % i: part
            for i, part in enumerate(path.split(b"."))}


def parse_line(line: bytes, now_nanos: int | None = None):
    """``path value timestamp`` -> (name, tags, kind, value, t_nanos).

    Matches the reference parser's tolerance (carbon/parser.go): any
    run of spaces/tabs separates fields; value may be float or NaN;
    timestamp is unix seconds (fractional allowed).  ``-1`` and ``N``
    timestamps mean server time (carbon writers commonly send -1;
    graphite's own plaintext receiver takes N), resolved against
    ``now_nanos`` when given."""
    parts = line.split()
    if len(parts) != 3:
        raise ValueError(f"carbon: expected 3 fields, got {len(parts)}")
    path, raw_v, raw_t = parts
    if not path:
        raise ValueError("carbon: empty path")
    value = float(raw_v)
    if raw_t in (b"N", b"n"):
        t_nanos = now_nanos if now_nanos is not None else clock.now_nanos()
    else:
        tsec = float(raw_t)
        if tsec == -1.0:
            t_nanos = now_nanos if now_nanos is not None else clock.now_nanos()
        else:
            t_nanos = int(tsec * SECOND)
    return (path, graphite_tags(path), MetricKind.GAUGE, value, t_nanos)


class CarbonIngester:
    """Parses carbon traffic and feeds the downsampler-and-writer.

    When a ``CarbonFastPath`` is attached (coordinator wiring) and
    eligible, whole batches decode columnar in C++ and ride the shared
    slot router + group-commit WAL; lines the strict columnar grammar
    defers — and any batch hitting an ineligible window — go through
    this scalar loop, which stays the semantic reference.  Malformed
    lines are counted, never raised, in both paths."""

    def __init__(self, writer, batch_size: int = 1024, fastpath=None):
        self._writer = writer
        self._batch_size = batch_size
        self._fastpath = fastpath
        self.n_malformed = 0
        self.n_ingested = 0
        self._m_malformed = instrument.counter(
            "m3_ingest_protocol_malformed_total", protocol="carbon")

    def ingest_lines(self, data: bytes) -> None:
        fp = self._fastpath
        if fp is not None and fp.eligible(self._writer):
            now = clock.now_nanos()
            try:
                n, fb = fp.write(data, now)
            except Exception:  # noqa: BLE001 - scalar path must serve
                instrument.counter(
                    "m3_ingest_protocol_fastpath_errors_total",
                    protocol="carbon").inc()
            else:
                self.n_ingested += n
                for off, ln in fb:
                    self._ingest_scalar(data[off:off + ln], now)
                return
        self._ingest_scalar(data, None)

    def _ingest_scalar(self, data: bytes, now_nanos: int | None) -> None:
        batch = []
        for line in data.splitlines():  # lint: allow-per-sample-loop (scalar reference + columnar fallback slices)
            line = line.strip()
            if not line:
                continue
            try:
                sample = parse_line(line, now_nanos)
            except ValueError:
                self.n_malformed += 1
                self._m_malformed.inc()
                continue
            if math.isnan(sample[3]):
                self.n_malformed += 1  # ref drops NaN carbon values
                self._m_malformed.inc()
                continue
            batch.append(sample)
            if len(batch) >= self._batch_size:
                self._writer.write_batch(batch)
                self.n_ingested += len(batch)
                batch = []
        if batch:
            self._writer.write_batch(batch)
            self.n_ingested += len(batch)


MAX_LINE_BYTES = 4096  # bound per-connection buffering (ref: the
# reference parser bounds line length; a newline-free stream must not
# grow the buffer without limit)


class _CarbonHandler(socketserver.StreamRequestHandler):
    def handle(self):
        buf = b""
        overflowing = False
        while True:
            try:
                chunk = self.request.recv(65536)
            except OSError:
                break
            if not chunk:
                break
            buf += chunk
            # feed complete lines; keep any partial tail
            nl = buf.rfind(b"\n")
            if nl >= 0:
                if overflowing:  # discard the tail of an over-long line
                    overflowing = False
                    first = buf.index(b"\n")
                    buf = buf[first + 1:]
                    nl = buf.rfind(b"\n")
                if nl >= 0:
                    self.server.ingester.ingest_lines(buf[:nl + 1])
                    buf = buf[nl + 1:]
            if len(buf) > MAX_LINE_BYTES:
                self.server.ingester.n_malformed += 1
                buf = b""
                overflowing = True  # skip until the next newline
        if buf.strip() and not overflowing:
            self.server.ingester.ingest_lines(buf + b"\n")


class CarbonServer(socketserver.ThreadingTCPServer):
    """TCP listener speaking the carbon line protocol
    (ref: ingest/carbon/ingest.go server wiring)."""

    allow_reuse_address = True
    daemon_threads = True

    def __init__(self, writer, host: str = "127.0.0.1", port: int = 0,
                 batch_size: int = 1024, fastpath=None):
        super().__init__((host, port), _CarbonHandler)
        self.ingester = CarbonIngester(writer, batch_size=batch_size,
                                       fastpath=fastpath)
        self.port = self.server_address[1]
        self._thread: threading.Thread | None = None

    def start(self) -> "CarbonServer":
        self._thread = threading.Thread(target=self.serve_forever,  # lint: allow-unregistered-thread (accept loop blocks in socket)
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        if self._thread:  # shutdown() blocks unless serve_forever runs
            self.shutdown()
            self._thread.join(timeout=2.0)
        self.server_close()


def send_lines(host: str, port: int, lines: bytes) -> None:
    """Tiny client used by tests and the load generator."""
    with socket.create_connection((host, port), timeout=5.0) as s:
        s.sendall(lines)
