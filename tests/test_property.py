"""Property-based test tier (r3 verdict missing #2).

Mirrors the reference's gopter suites with hypothesis:
  - randomized M3TSZ roundtrip incl. annotations, time-unit changes and
    int<->float mode flips, plus corrupted/truncated streams erroring
    cleanly (ref: src/dbnode/encoding/proto/corruption_prop_test.go,
    src/dbnode/encoding/m3tsz/ roundtrip tests)
  - commit-log WAL model test: random batch/rotate sequences with
    crash damage (truncation / bit flips) must replay a prefix of the
    acknowledged records and never raise or invent data (ref:
    src/dbnode/persist/fs/commitlog/read_write_prop_test.go)
  - mutable-vs-sealed index query equivalence over the full matcher
    grammar (ref: src/m3ninx/search/proptest/)
"""

import math
import struct
import tempfile

import numpy as np
import pytest
from hypothesis import HealthCheck, example, given, settings
from hypothesis import strategies as st

from m3_tpu.ops import m3tsz_scalar as tsz
from m3_tpu.storage.commitlog import CommitLog
from m3_tpu.storage.index import TagIndex
from m3_tpu.utils import xtime

SEC = xtime.SECOND
START = 1_600_000_000 * SEC

_PROP_SETTINGS = dict(
    deadline=None,  # shared single-core host: wall-clock is noisy
    suppress_health_check=[HealthCheck.too_slow],
)

# ---------------------------------------------------------------------------
# M3TSZ codec roundtrip
# ---------------------------------------------------------------------------

_UNITS = (xtime.Unit.SECOND, xtime.Unit.MILLISECOND,
          xtime.Unit.MICROSECOND, xtime.Unit.NANOSECOND)


@st.composite
def _series(draw):
    """(start, [(t, v, annotation, unit)]) with deltas that are
    multiples of the datapoint's unit (the codec's granularity
    contract, like the reference's) — including zero and negative
    deltas, int-looking and arbitrary float values, NaN/Inf, and
    occasional annotation / unit changes."""
    n = draw(st.integers(min_value=1, max_value=40))
    start = START + draw(st.integers(0, 10**6)) * SEC
    unit = draw(st.sampled_from(_UNITS))
    t = start
    dps = []
    for _ in range(n):
        if draw(st.integers(0, 9)) == 0:
            unit = draw(st.sampled_from(_UNITS))
        step = draw(st.one_of(
            st.integers(1, 120),          # forward
            st.integers(0, 0),            # duplicate timestamp
            st.integers(-30, -1),         # backwards
        ))
        t = t + step * unit.nanos
        # magnitudes stay under 2^53: beyond it the int-mode pipeline's
        # float64 diff arithmetic rounds — in the reference identically
        # (encoder.go:161) — and at +/-2^63 the asymmetric overflow
        # guard (m3tsz.go:80 `v < maxInt`, no minInt check) clamps via
        # float->int64 conversion in BOTH implementations.  Those are
        # documented shared envelopes, not roundtrip properties; NaN,
        # +/-Inf, -0.0, subnormals and huge floats (float mode) stay in.
        v = draw(st.one_of(
            st.integers(-10**6, 10**6).map(float),   # int-mode friendly
            st.floats(allow_nan=True, allow_infinity=True, width=64,
                      allow_subnormal=True).filter(
                lambda x: not math.isfinite(x) or abs(x) < 2.0**53
                or x > 1e19),  # negative huge ints hit the same clamp
                               # (the quick-path guard passes all negatives)
            st.sampled_from([-0.0, 0.0, 1.5, -1.5, 1e300, 5e-324]),
        ))
        ann = draw(st.one_of(
            st.just(b""),
            st.binary(min_size=1, max_size=12),
        ))
        dps.append((t, v, ann, unit))
    return start, dps


def _same_value(a: float, b: float, int_optimized: bool) -> bool:
    pa = struct.pack("<d", a)
    pb = struct.pack("<d", b)
    if pa == pb:
        return True
    if np.isnan(a) and np.isnan(b):
        return True  # payload bits may normalize through int-mode math
    if int_optimized:
        if a == b:
            # -0.0 -> +0.0 in int-optimized mode is reference-parity
            return True
        # int-mode diffs are computed in float64 BY THE REFERENCE TOO
        # (encoder.go:161 `valDiff = enc.intVal - val`), so integral
        # values whose successive diffs exceed 2^53 round identically
        # there — a documented shared precision envelope, not a bug
        if abs(a) >= 2.0**53 and a == int(a):
            return abs(a - b) <= abs(a) * 1e-12
        # knife-edge snapping: values within one ulp of an integer are
        # deliberately snapped by convertToIntFloat in the reference
        # ("potential for a small accuracy loss", m3tsz.go:72-77);
        # accept exactly what the codec's own conversion yields
        snapped, mult, is_float = tsz.convert_to_int_float(a, 0)
        if not is_float and tsz.convert_from_int_float(snapped, mult) == b:
            return True
    return False


@settings(max_examples=300, **_PROP_SETTINGS)
@given(series=_series(), int_optimized=st.booleans())
def test_m3tsz_roundtrip_prop(series, int_optimized):
    start, dps = series
    enc = tsz.Encoder(start, int_optimized=int_optimized,
                      default_unit=dps[0][3])
    for t, v, ann, unit in dps:
        enc.encode(t, v, annotation=ann, unit=unit)
    blob = enc.finalize()
    assert blob, "finalize of a non-empty stream must produce bytes"
    dec = tsz.Decoder(blob, int_optimized=int_optimized,
                      default_unit=dps[0][3])
    out = list(dec)
    assert len(out) == len(dps)
    # int-mode diffs >= 2^53 round in float64 — in the REFERENCE too
    # (encoder.go:161 computes `valDiff = enc.intVal - val` in float64
    # and keeps the unrounded val as state, so encoder and decoder
    # drift by <= ulp(diff) per event and the drift persists).  Track
    # the accumulated rounding budget; values must stay within it.
    taint = 0.0
    prev = None
    for (t, v, _ann, _u), dp in zip(dps, out):
        assert dp.t_nanos == t, (dp.t_nanos, t)
        if (int_optimized and prev is not None
                and math.isfinite(v) and math.isfinite(prev)
                and abs(v - prev) >= 2.0**53):
            taint += math.ulp(max(abs(v), abs(prev)))
        if np.isnan(v):
            assert np.isnan(dp.value), (v, dp.value)
        elif taint and math.isfinite(v):
            assert abs(dp.value - v) <= 64 * taint, (v, dp.value, taint)
        else:
            assert _same_value(v, dp.value, int_optimized), (v, dp.value)
        prev = v


@settings(max_examples=300, **_PROP_SETTINGS)
@given(
    series=_series(),
    damage=st.one_of(
        st.tuples(st.just("truncate"), st.floats(0, 1)),
        st.tuples(st.just("flip"), st.floats(0, 1), st.integers(0, 7)),
        st.tuples(st.just("garbage"), st.binary(min_size=1, max_size=64)),
    ),
)
def test_m3tsz_corruption_errors_cleanly_prop(series, damage):
    """Any truncation/bit-flip/garbage either decodes to SOME list
    (possibly short) or raises EOFError/ValueError — never a crash,
    hang, or foreign exception (ref: corruption_prop_test.go)."""
    start, dps = series
    enc = tsz.Encoder(start, default_unit=dps[0][3])
    for t, v, ann, unit in dps:
        enc.encode(t, v, annotation=ann, unit=unit)
    blob = bytearray(enc.finalize())
    if damage[0] == "truncate":
        blob = blob[: int(damage[1] * len(blob))]
    elif damage[0] == "flip":
        blob[int(damage[1] * (len(blob) - 1))] ^= 1 << damage[2]
    else:
        blob = bytearray(damage[1])
    try:
        out = tsz.decode_series(bytes(blob))
        assert isinstance(out, tuple) and len(out) == 2
    except (EOFError, ValueError):
        pass  # the sanctioned failure mode


# ---------------------------------------------------------------------------
# Commit-log WAL model test
# ---------------------------------------------------------------------------

_ids = st.binary(min_size=1, max_size=16)
_tags = st.dictionaries(
    st.binary(min_size=1, max_size=8), st.binary(min_size=0, max_size=8),
    max_size=3)
_record = st.tuples(
    _ids,
    st.integers(min_value=0, max_value=2**50),
    st.floats(allow_nan=True, allow_infinity=True, width=64),
    _tags)
_batch = st.lists(_record, min_size=1, max_size=6)


def _record_key(sid, t, v, tags):
    return (sid, t, struct.pack("<d", v), tuple(sorted(tags.items())))


@settings(max_examples=150, **_PROP_SETTINGS)
@example(  # two batches inside one write-behind window: one chunk, cut
    ops=[("write", [(b"\x00", 0, 0.0, {})]),
         ("write", [(b"\x00", 0, 0.0, {})] * 3)],
    damage=("truncate", 0.5))
@given(
    ops=st.lists(
        st.one_of(_batch.map(lambda b: ("write", b)),
                  st.just(("rotate", None))),
        min_size=1, max_size=12),
    damage=st.one_of(
        st.just(("none",)),
        st.tuples(st.just("truncate"), st.floats(0, 1)),
        st.tuples(st.just("flip"), st.floats(0, 1), st.integers(0, 7)),
    ),
)
def test_wal_model_prop(ops, damage):
    """Model: FIFO chunks, one per burst the writer drained (group
    commit: write_batch calls that queue up within the write-behind
    window share ONE chunk, so the chunk and not the batch is what a
    torn tail takes or leaves; a batch is never split between chunks).
    The model used to say "one chunk per write_batch", which the
    write-behind window made stale: two quick batches are one chunk,
    and a cut through it rightly loses both (the recorded example
    below).  Where the chunks ended is read from the undamaged file and
    held against the ops.  After flush + crash damage to the live file,
    replay must yield a per-damage-consistent PREFIX of the
    acknowledged records: nothing invented, order kept, and every chunk
    wholly before the damage point intact.  Exact float
    bits (incl. NaN) roundtrip.  Tags are stored once per (sid, file)
    (write-side dedup) and rehydrated on replay, so within a file a
    sid's tags are FIRST-WRITER-WINS — the model below mirrors that
    (the db layer's sids are derived from tags, making them immutable
    per sid in practice).
    (ref: src/dbnode/persist/fs/commitlog/read_write_prop_test.go)"""
    with tempfile.TemporaryDirectory(prefix="m3_walprop_") as td:
        log = CommitLog(td, rotate_bytes=1 << 30)
        written = []          # acknowledged records w/ EXPECTED tags
        model_first: dict = {}    # per-file: sid -> first tags seen
        for op, arg in ops:
            if op == "write":
                log.write_batch([r[0] for r in arg], [r[1] for r in arg],
                                [r[2] for r in arg], [r[3] for r in arg])
                for sid, t, v, tags in arg:
                    if tags and sid not in model_first:
                        model_first[sid] = tags
                    written.append((sid, t, v,
                                    model_first.get(sid, {})))
            else:
                log.rotate()
                model_first = {}
        log.flush()
        log.close()

        # index of the first record living in the live file
        live_batches = [len(arg) for _, arg in _live_ops(ops)]
        first_live = len(written) - sum(live_batches)

        import pathlib
        # numeric index order, NOT lexicographic: with >= 10 files a
        # string sort puts commitlog-9 after commitlog-10 and the test
        # would damage a rotated file instead of the live one
        from m3_tpu.storage.commitlog import _by_index
        live = max(pathlib.Path(td).glob("commitlog-*.db"), key=_by_index)
        data = bytearray(live.read_bytes())
        # the live file's chunks as written: (bytes, records) each, the
        # log's last ones.  They fill the file, and each holds a run of
        # whole batches
        live_chunks, n = [], 0
        for c in reversed(list(CommitLog.replay_chunks(td))):
            if n == sum(live_batches):
                break
            live_chunks.insert(0, (c.nbytes, len(c.times)))
            n += len(c.times)
        assert sum(size for size, _ in live_chunks) == len(data)
        ends = set(np.cumsum(live_batches).tolist())
        assert set(np.cumsum([k for _, k in live_chunks]).tolist()) <= ends, \
            "a batch was split between chunks"

        def before(at):
            """Records of the live chunks that end at or before `at`."""
            pos = kept = 0
            for size, k in live_chunks:
                if pos + size > at:
                    break
                pos, kept = pos + size, kept + k
            return kept

        guaranteed = len(written)  # lower bound on surviving records
        if damage[0] == "truncate" and data:
            cut = int(damage[1] * len(data))
            data = data[:cut]
            guaranteed = first_live + before(cut)
            live.write_bytes(bytes(data))
        elif damage[0] == "flip" and data:
            at = int(damage[1] * (len(data) - 1))
            data[at] ^= 1 << damage[2]
            guaranteed = first_live + before(at)
            live.write_bytes(bytes(data))

        replayed = [(sid, t, v, tg) for sid, t, v, tg, _, _ns in
                    CommitLog.replay(td)]
        want = [_record_key(*r) for r in written]
        got = [_record_key(*r) for r in replayed]
        # prefix property: nothing invented, nothing reordered
        assert got == want[: len(got)], "replay is not a prefix"
        # durability floor: chunks wholly before the damage survive
        assert len(got) >= guaranteed, (len(got), guaranteed)
        if damage[0] == "none":
            assert len(got) == len(want)


def _live_ops(ops):
    """The write ops after the last rotate — the ones whose chunks are
    in the live WAL file, in order."""
    out = []
    for op, arg in ops:
        if op == "rotate":
            out = []
        else:
            out.append((op, arg))
    return out


# ---------------------------------------------------------------------------
# Index: mutable vs sealed-segment equivalence
# ---------------------------------------------------------------------------

_keys = st.sampled_from([b"app", b"dc", b"host", b"tier"])
_vals = st.sampled_from([b"a", b"b", b"ab", b"abc", b"zz", b""])
_series_tags = st.dictionaries(_keys, _vals, min_size=0, max_size=3)
_patterns = st.sampled_from([rb"a.*", rb".*b", rb"a|zz", rb"", rb".*",
                             rb"ab?c?", rb"nomatch", rb"ab.*", rb"abc",
                             rb"zz", rb"ab[cd]?", rb"(?i)AB.*"])
_matcher = st.one_of(
    st.tuples(st.sampled_from(["eq", "neq"]), _keys, _vals),
    st.tuples(st.sampled_from(["re", "nre"]), _keys, _patterns),
)


@settings(max_examples=200, **_PROP_SETTINGS)
@given(
    tag_sets=st.lists(_series_tags, min_size=1, max_size=25),
    term=st.tuples(_keys, _vals),
    rx=st.tuples(_keys, _patterns),
    conj=st.lists(_matcher, min_size=1, max_size=3),
)
def test_index_mutable_vs_sealed_equivalence_prop(tag_sets, term, rx, conj):
    """The same inserts answer every query identically from the mutable
    tail and from sealed frozen segments — the reference's mem-vs-FST
    equivalence property (src/m3ninx/search/proptest/)."""
    mut = TagIndex(seal_threshold=1 << 30)
    sealed = TagIndex(seal_threshold=1 << 30)
    # interleave seals so SEVERAL frozen segments exist (exercises the
    # segment merge/union path, not just one big freeze)
    for i, tags in enumerate(tag_sets):
        sid = b"s%04d" % i
        mut.insert(sid, tags)
        sealed.insert(sid, tags)
        if i % 7 == 6:
            sealed.seal()
    sealed.seal()

    assert np.array_equal(mut.query_term(*term), sealed.query_term(*term))
    assert np.array_equal(mut.query_regexp(*rx), sealed.query_regexp(*rx))
    assert np.array_equal(mut.query_field(term[0]),
                          sealed.query_field(term[0]))
    assert np.array_equal(mut.query_conjunction(conj),
                          sealed.query_conjunction(conj))


@settings(max_examples=60, **_PROP_SETTINGS)
@given(tag_sets=st.lists(_series_tags, min_size=1, max_size=15),
       conj=st.lists(_matcher, min_size=1, max_size=2))
def test_index_persist_reload_equivalence_prop(tag_sets, conj):
    """Sealed + persisted + mmap-reloaded index answers conjunctions
    identically to the in-memory mutable one."""
    mut = TagIndex(seal_threshold=1 << 30)
    disk = TagIndex(seal_threshold=1 << 30)
    for i, tags in enumerate(tag_sets):
        sid = b"s%04d" % i
        mut.insert(sid, tags)
        disk.insert(sid, tags)
    disk.seal()
    with tempfile.TemporaryDirectory(prefix="m3_idxprop_") as td:
        disk.persist(td)
        loaded = TagIndex()
        loaded.load(td)
        assert len(loaded) == len(mut)
        assert np.array_equal(mut.query_conjunction(conj),
                              loaded.query_conjunction(conj))


if __name__ == "__main__":
    import sys
    sys.exit(pytest.main([__file__, "-q"]))


# ---------------------------------------------------------------------------
# Prometheus WriteRequest: native C++ parser vs pure-Python walker
# ---------------------------------------------------------------------------

_label_bytes = st.binary(min_size=0, max_size=12)
_prom_series = st.tuples(
    st.dictionaries(_label_bytes, _label_bytes, min_size=0, max_size=5),
    st.lists(st.tuples(st.integers(-2**62, 2**62),
                       st.floats(allow_nan=True, allow_infinity=True,
                                 width=64)),
             min_size=0, max_size=4))


@settings(max_examples=200, **_PROP_SETTINGS)
@given(series=st.lists(_prom_series, min_size=0, max_size=12),
       damage=st.one_of(
           st.none(),
           st.tuples(st.floats(0, 1)),
           st.tuples(st.floats(0, 1), st.integers(0, 7))))
def test_prom_wire_native_matches_python_prop(series, damage):
    """decode_write_request's two implementations (native/prom_wire.cc
    and the pure-Python walker) must agree on every well-formed payload
    — NaN bits, negative timestamps, empty labels/samples — and fail
    identically-cleanly on damaged ones."""
    from m3_tpu.query import remote_write as rw

    body = bytearray(rw.encode_write_request(series))
    if damage is not None and body:
        if len(damage) == 1:
            body = body[: int(damage[0] * len(body))]
        else:
            body[int(damage[0] * (len(body) - 1))] ^= 1 << damage[1]
    body = bytes(body)

    def run(fn):
        try:
            out = fn(body)
        except (ValueError, IndexError):
            return "error"
        # normalize NaN for comparison
        return [(labels, [(t, struct.pack("<d", v)) for t, v in samples])
                for labels, samples in out]

    native = run(rw.decode_write_request)
    # non-vacuity: the native parser must actually be in play, else
    # this compares the Python walker with itself
    assert rw._NATIVE_OK is True, "native prom_wire parser not loaded"
    py = run(rw._decode_write_request_py)
    if native == "error" or py == "error":
        # both sides must refuse (clean, typed error) — a payload one
        # side accepts and the other rejects is a divergence
        assert native == py == "error", (native == "error", py == "error")
    else:
        assert native == py


def test_prom_wire_adversarial_payload_parity():
    """Hand-built payloads the generator cannot produce (review r4):
    over-long varints and wrong-wire-typed label fields must behave
    IDENTICALLY in the native parser and the Python fallback."""
    from m3_tpu.query import remote_write as rw

    def both(body):
        outs = []
        for fn in (rw.decode_write_request, rw._decode_write_request_py):
            try:
                outs.append(fn(body))
            except (ValueError, IndexError):
                outs.append("error")
        assert rw._NATIVE_OK is True
        assert outs[0] == outs[1], (body.hex(), outs)
        return outs[0]

    def ts_msg(inner):  # wrap as WriteRequest{timeseries{inner}}
        return bytes([0x0A, len(inner)]) + inner

    # 11-byte varint timestamp: both must reject
    sample = bytes([0x10]) + b"\x80" * 10 + b"\x01"
    assert both(ts_msg(bytes([0x12, len(sample)]) + sample)) == "error"
    # 10-byte varint (max legal): both accept, identical 64-bit value
    sample = bytes([0x10]) + b"\xff" * 9 + b"\x01"
    out = both(ts_msg(bytes([0x12, len(sample)]) + sample))
    assert out != "error" and out[0][1][0][0] == -1  # 2^64-1 as int64
    # varint-typed field 1 inside a Label: skipped, not taken as name
    label = bytes([0x08, 0x05])
    out = both(ts_msg(bytes([0x0A, len(label)]) + label))
    assert out == [({b"": b""}, [])]
    # unknown field in TimeSeries: skipped by both
    unknown = bytes([0x18, 0x07])
    assert both(ts_msg(unknown)) == [({}, [])]
