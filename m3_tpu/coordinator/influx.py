"""InfluxDB line-protocol ingest.

Parity target: src/query/api/v1/handler/influxdb/write.go — the
coordinator accepts InfluxDB line protocol and maps it onto tagged
writes: measurement + field key become the metric name
(``<measurement>_<field>``, the reference's promRewriter naming), tags
become labels, each numeric field becomes one sample.

Line grammar (https's public line-protocol spec, first-principles
implementation):

    measurement[,tag=val...] field=value[,field2=value2...] [timestamp]

with backslash escaping of ',', ' ', '=' in identifiers, string field
values in double quotes (skipped — only numeric fields become
samples), `i`/`u` suffixes for integer fields, and booleans mapped to
0/1.  Timestamps honor the `precision` query parameter (ns default).
"""

from __future__ import annotations

import re
from m3_tpu.utils import clock

_PRECISION_NANOS = {
    "ns": 1, "n": 1,
    "us": 1_000, "u": 1_000,
    "ms": 1_000_000,
    "s": 1_000_000_000,
}


class LineError(ValueError):
    pass


def _split_unescaped(s: str, sep: str) -> list[str]:
    """Split on sep outside backslash escapes (identifiers only)."""
    out, cur, i = [], [], 0
    while i < len(s):
        c = s[i]
        if c == "\\" and i + 1 < len(s):
            cur.append(s[i : i + 2])
            i += 2
            continue
        if c == sep:
            out.append("".join(cur))
            cur = []
        else:
            cur.append(c)
        i += 1
    out.append("".join(cur))
    return out


def _partition_unescaped(s: str, sep: str) -> tuple[str, str] | None:
    """(left, right) at the FIRST unescaped sep, or None.  Only the key
    side is scanned, so quotes in the value side stay intact."""
    i = 0
    while i < len(s):
        if s[i] == "\\" and i + 1 < len(s):
            i += 2
            continue
        if s[i] == sep:
            return s[:i], s[i + 1 :]
        i += 1
    return None


def _unescape(s: str) -> str:
    out, i = [], 0
    while i < len(s):
        if s[i] == "\\" and i + 1 < len(s) and s[i + 1] in ",= \\":
            out.append(s[i + 1])
            i += 2
        else:
            out.append(s[i])
            i += 1
    return "".join(out)


def _split_fields_section(line: str) -> tuple[str, str, str]:
    """-> (series part, fields part, timestamp part); spaces inside
    quoted field-string values do not delimit."""
    parts, cur, in_quote, i = [], [], False, 0
    while i < len(line):
        c = line[i]
        if c == '"' and (i == 0 or line[i - 1] != "\\"):
            in_quote = not in_quote
            cur.append(c)
        elif c == "\\" and i + 1 < len(line) and not in_quote:
            cur.append(line[i : i + 2])
            i += 1
        elif c == " " and not in_quote and len(parts) < 2:
            parts.append("".join(cur))
            cur = []
        else:
            cur.append(c)
        i += 1
    parts.append("".join(cur))
    if len(parts) < 2:
        raise LineError(f"missing fields section: {line!r}")
    series, fields = parts[0], parts[1]
    stamp = parts[2].strip() if len(parts) > 2 else ""
    return series, fields, stamp


def _split_fields(s: str) -> list[str]:
    """Split the fields section on ',' outside double-quoted string
    values (a quoted value may contain ',' and escaped '\"')."""
    out, cur, in_quote, i = [], [], False, 0
    while i < len(s):
        c = s[i]
        if c == '"' and (i == 0 or s[i - 1] != "\\"):
            in_quote = not in_quote
            cur.append(c)
        elif c == "\\" and i + 1 < len(s) and not in_quote:
            cur.append(s[i : i + 2])
            i += 1
        elif c == "," and not in_quote:
            out.append("".join(cur))
            cur = []
        else:
            cur.append(c)
        i += 1
    out.append("".join(cur))
    return out


_INT_BODY = re.compile(r"[+-]?[0-9]+\Z")


def _field_value(raw: str) -> float | None:
    """Numeric value of a field, or None for strings (not ingestible).

    Integer fields carry an ``i`` (signed) or ``u`` (unsigned) suffix
    and must be plain decimal digits — ``1e3i`` or ``2.5u`` is a
    malformed field, not a float that happens to end in a suffix
    letter.  Plain float fields accept the full scientific-notation
    grammar via float().  Keeping the accepted integer language to
    strict digits holds the scalar and columnar decoders bit-identical
    (Python's int() alone would also take underscores the columnar
    C parser rejects)."""
    if not raw:
        raise LineError("empty field value")
    if raw[0] == '"':
        return None
    low = raw.lower()
    if low in ("t", "true"):
        return 1.0
    if low in ("f", "false"):
        return 0.0
    if raw[-1] in "iu":
        body = raw[:-1]
        if not _INT_BODY.match(body):
            raise LineError(f"bad integer field {raw!r}")
        return float(int(body))
    return float(raw)


def parse_lines(
    payload: bytes, precision: str = "ns", now_nanos: int | None = None
) -> list[tuple[dict[bytes, bytes], int, float]]:
    """-> [(labels, t_nanos, value)]; one entry per numeric field.

    Labels: tags plus ``__name__ = <measurement>_<field>`` (the
    reference's influxdb promRewriter naming, with '.'->'_'
    sanitization).
    """
    mult = _PRECISION_NANOS.get(precision)
    if mult is None:
        raise LineError(f"unknown precision {precision!r}")
    out: list[tuple[dict[bytes, bytes], int, float]] = []
    for lineno, raw_line in enumerate(payload.decode("utf-8").splitlines(), 1):  # lint: allow-per-sample-loop (strict scalar reference)
        line = raw_line.strip()
        if not line or line.startswith("#"):
            continue
        try:
            out.extend(_parse_one(line, mult, now_nanos))
        except LineError as e:
            raise LineError(f"line {lineno}: {e}") from None
        except (ValueError, IndexError) as e:
            raise LineError(f"line {lineno}: {e}") from None
    return out


def _parse_one(line: str, mult: int, now_nanos: int | None
               ) -> list[tuple[dict[bytes, bytes], int, float]]:
    """One non-blank, non-comment line -> its numeric-field samples.
    Raises LineError/ValueError/IndexError on malformed input."""
    series, fields, stamp = _split_fields_section(line)
    series_parts = _split_unescaped(series, ",")
    measurement = _sanitize(_unescape(series_parts[0]))
    if not measurement:
        raise LineError("empty measurement")
    tags: dict[bytes, bytes] = {}
    for part in series_parts[1:]:
        kv = _partition_unescaped(part, "=")
        if kv is None or not kv[0] or not kv[1]:
            raise LineError(f"bad tag {part!r}")
        k, v = kv
        tags[_sanitize(_unescape(k)).encode()] = _unescape(v).encode()
    if stamp:
        t_nanos = int(stamp) * mult
    elif now_nanos is not None:
        t_nanos = now_nanos
    else:
        t_nanos = clock.now_nanos()
    out: list[tuple[dict[bytes, bytes], int, float]] = []
    n_fields = 0
    for part in _split_fields(fields):
        kv = _partition_unescaped(part, "=")
        if kv is None or not kv[0]:
            raise LineError(f"bad field {part!r}")
        k, v = kv
        val = _field_value(v)
        n_fields += 1
        if val is None:
            continue  # string fields are not samples
        labels = dict(tags)
        labels[b"__name__"] = (
            f"{measurement}_{_sanitize(_unescape(k))}".encode())
        out.append((labels, t_nanos, val))
    if n_fields == 0:
        raise LineError("no fields")
    return out


def parse_lines_tolerant(
    payload: bytes, precision: str = "ns", now_nanos: int | None = None
) -> tuple[list[tuple[dict[bytes, bytes], int, float]], int]:
    """Per-line-tolerant variant: -> (samples, n_malformed).  A bad
    line inside an otherwise-good batch is counted and skipped instead
    of failing the whole payload — the scalar reference the columnar
    decoder's fallback slices run through."""
    mult = _PRECISION_NANOS.get(precision)
    if mult is None:
        raise LineError(f"unknown precision {precision!r}")
    out: list[tuple[dict[bytes, bytes], int, float]] = []
    n_malformed = 0
    for raw_line in payload.splitlines():  # lint: allow-per-sample-loop (columnar fallback slices)
        try:
            line = raw_line.decode("utf-8").strip()
        except UnicodeDecodeError:
            n_malformed += 1
            continue
        if not line or line.startswith("#"):
            continue
        try:
            out.extend(_parse_one(line, mult, now_nanos))
        except (LineError, ValueError, IndexError, OverflowError):
            n_malformed += 1
    return out, n_malformed


def _sanitize(name: str) -> str:
    """Prometheus-safe metric-name characters (the reference rewrites
    unsupported runes to '_')."""
    return "".join(
        c if c.isalnum() or c in "_:" else "_" for c in name
    )
