"""Device-resident PromQL read pipeline: decode -> merge -> rate in ONE
jitted program.

The host-side serving tier (native C++; ops/consolidate.py +
ops/m3tsz_decode.py) answers fan-out reads on CPU deployments.  On an
accelerator deployment the same pipeline should never leave HBM: this
module fuses the batched M3TSZ decoder, the per-slot block merge, and
the windowed extrapolated-rate kernel into one jit so the
[streams, samples] intermediate lives only on device and only the
[series, steps] result crosses back (the pipeline the bench legs'
"TPU projection" describes; ref: the reference's per-series chain
src/query/ts/m3db/encoded_step_iterator_generic.go:120 + functions/
temporal/rate.go, here batched across all series).

Semantics parity: every stage is asserted against the host reference
(merge_grids / extrapolated_rate numpy) in
tests/test_query_pipeline_device.py; precision notes follow the decode
kernel's contract (integer state exact on all backends, f64 emission
exact on CPU, ~1 ulp on emulated-f64 accelerators).

Two per-node entry points, `device_temporal_pipeline` and
`device_grouped_pipeline`; given a `mesh` either runs the same program
under `shard_map` over the series axis — streams of a slot must be
placed on one shard (slots are data-parallel), and grouped aggregates
(`sum by (..)(rate(...))`) reduce with one collective over ICI.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from m3_tpu.ops import m3tsz_decode
from m3_tpu.ops.bitstream import I32
from m3_tpu.ops.histo_quantile import bucket_quantile
from m3_tpu.ops.kernel_telemetry import instrument_kernel
from m3_tpu.ops.lane_topk import masked_topk
from m3_tpu.parallel.mesh import SERIES_AXIS, shard_map
from m3_tpu.utils import xtime

_INF = jnp.iinfo(jnp.int64).max
# lanes merged per round: the round's temporaries then stay on chip
# (65,536 lanes on a v5e: 68 ms at 512, 80 at 2,048, 255 in one round,
# PERF.md PR 26) and off the program's HBM peak.  In the "rotate" form
# (merge_form) they are the row padded to [lanes, n_cap] and its rotated
# copies; in the "window" form the row's window [lanes, 2 x n_dp] and
# its rotated copies, and the lane itself is only the loop's carry
_MERGE_LANES = 512

# rows' widths a lane from which _merge_device rotates a row inside a
# window of two rows' width ("window") and under which it rotates it
# over the lane's ("rotate"): merge_form.  Set from a sweep of the merge
# alone (v5e, [512, n_cap] from rows of 768 cells filled to 713-720,
# host clock around a call, some 1.3 ms of it the call's own; rotate
# against window): 1.55 / 1.52 ms at 1,536 (2 rows a lane), 1.59 / 1.58
# at 1,920, 1.58 / 1.60 at 2,048, 2.47 / 2.01 at 3,072 (4 rows), 6.31 /
# 3.01 at 6,144 (8), 108.4 / 10.2 at 15,872 (22); the fleet's 25 chunks
# of 512 lanes at 1,536: 14.6 / 14.7.  Under three rows a lane the forms
# cost the same (the rotated widths are the same) and the accepted
# cells keep the program they had; the first width measured at which
# the window wins is four rows (PERF.md PR 46)
_WINDOW_MIN_ROWS = 4


def lane_chunks(n_lanes: int) -> int:
    """Rounds in which _merge_device and _rate_device go through
    `n_lanes` lanes, _MERGE_LANES at a time.  A function of the static
    lane bucket alone, so the engine can put it on a query's record
    without asking the program."""
    return -(-n_lanes // _MERGE_LANES)


def decode_refills(n_dp: int, n_words: int) -> int:
    """Refills of the decode scan's per-row word window in one
    _decode_merge of rows `n_words` wide and `n_dp` samples deep (and
    the step that tells a truncated stream); 0 where a row is no longer
    than the window.  Like lane_chunks a function of the static buckets
    alone."""
    return m3tsz_decode.decode_refills(n_dp + 1, n_words)


def merge_form(n_cap: int, n_dp: int | None) -> str:
    """How _merge_device rotates a row of `n_dp` cells (None: a row as
    wide as the lane) to its offset in a lane of `n_cap`: "rotate" over
    the lane's width, or "window" inside two rows' width where the lane
    is _WINDOW_MIN_ROWS rows wide or more.  Like window_form a function
    of the static buckets alone, so the engine can count which form
    served a call without asking the program."""
    if n_dp is None or n_cap < _WINDOW_MIN_ROWS * n_dp:
        return "rotate"
    return "window"


def _merge_device(ts, vs, valid, slots, n_lanes: int, n_cap: int,
                  order=None):
    """Compact per-(series, block) decode grids into the packed
    [n_lanes, n_cap] batch on device, a whole row at a time.

    Contract (the engine's emission order, same as the host merge):
    rows grouped by slot, slots ascending, ascending block time within
    a slot, timestamps ascending within a row, and a row's valid cells
    its first `count` cells (decode_batched emits a prefix; _tier_cut
    keeps a prefix of it).  So a row lands as one contiguous run at
    (slot, samples of the slot's earlier rows): round k moves the k-th
    row of every lane at once and rotates it to its offset with a
    log-step select.  The TPU compiler runs an element-indexed scatter
    one cell at a time (71 ns a cell); here nothing is indexed by cell.
    Lanes go _MERGE_LANES at a time (the last chunk overlaps its
    neighbour rather than pad).  Cells past a lane's n_cap budget DROP,
    never spill into the next lane (callers surface the overflow via
    counts).

    What is rotated is read off the static shapes (merge_form).
    "rotate": the row padded to the lane's width, by the offset; a
    round's temporaries are [_MERGE_LANES, n_cap] whatever the fan-out,
    and stay on chip while the lane is a few rows wide.  "window" (a
    lane many rows wide: a long range's 22 blocks): the offset is
    q * T + r with T the rows' width, the row is rotated by r inside a
    window of [_MERGE_LANES, 2T], and the window's halves are laid on
    blocks q and q + 1 of the lane, kept as [blocks, _MERGE_LANES, T],
    in the one pass that merges them in under the same mask: the
    halves broadcast inside it, no [_MERGE_LANES, n_cap] array is built
    for the row, and a round moves the lane once instead of once a bit
    of the offset.  The block past the lane's end is never written.

    `order` [M], where rows were laid end to end from two sources (the
    decoded streams, then the rows that arrived as arrays), is the
    permutation that puts them into the contract's order; the rows
    stay where they are and are reached through it.
    """
    M, T = ts.shape
    B = min(n_lanes, _MERGE_LANES)
    row_counts = valid.sum(axis=1, dtype=I32)  # [M]
    if order is not None:
        slots, row_counts = slots[order], row_counts[order]
    first = jnp.searchsorted(slots, jnp.arange(n_lanes + 1), side="left",
                             method="scan_unrolled")  # lane -> first row
    # trailing empty rows (jit padding parked on the last lane) must not
    # lengthen the loop
    used = jnp.max(jnp.where(row_counts > 0, jnp.arange(1, M + 1), 0))
    n_rows = jnp.minimum(first[1:], used) - first[:-1]  # [n_lanes]

    def rotated(x, row, by, width, reach):
        # rows `row` of x at `width` cells, rotated right by `by` < reach
        x = jnp.pad(x[:, :width].at[row].get(mode="promise_in_bounds"),
                    ((0, 0), (0, max(width - T, 0))))
        for b in range((reach - 1).bit_length()):
            x = jnp.where((by >> b & 1)[:, None] == 1,
                          jnp.roll(x, 1 << b, axis=1), x)
        return x

    if merge_form(n_cap, T) == "window":
        n_blk = -(-n_cap // T)
        shape = (n_blk, B, T)  # a chunk's lanes, in blocks of T cells
        blk = jnp.arange(n_blk, dtype=I32)[:, None, None]
        col = blk * T + jnp.arange(T, dtype=I32)

        def per_lane(v):
            return v[None, :, None]

        def place(x, row, off):
            q, r = jnp.divmod(off, T)
            w = rotated(x, row, r, 2 * T, T)
            return jnp.where(blk == per_lane(q), w[None, :, :T],
                             w[None, :, T:])

        def lanes_of(out):  # the blocks end to end
            return jnp.concatenate(list(out), axis=1)[:, :n_cap]
    else:
        shape = (B, n_cap)
        col = jnp.arange(n_cap, dtype=I32)

        def per_lane(v):
            return v[:, None]

        def place(x, row, off):
            return rotated(x, row, off, n_cap, n_cap)

        def lanes_of(out):
            return out

    def chunk(c, outs):
        lo = jnp.minimum(c * B, n_lanes - B)
        first_c = jax.lax.dynamic_slice_in_dim(first, lo, B)
        n_rows_c = jax.lax.dynamic_slice_in_dim(n_rows, lo, B)

        def body(k, carry):
            out_t, out_v, counts = carry
            row = jnp.minimum(first_c + k, M - 1)
            cnt = jnp.where(k < n_rows_c, row_counts[row], 0)
            if order is not None:
                row = order[row]
            off = jnp.minimum(counts, n_cap)
            take = (col >= per_lane(off)) & (col < per_lane(off + cnt))
            return (jnp.where(take, place(ts, row, off), out_t),
                    jnp.where(take, place(vs, row, off), out_v),
                    counts + cnt)

        out_t, out_v, counts = jax.lax.fori_loop(
            0, jnp.max(n_rows_c), body, (
                jnp.full(shape, _INF, dtype=jnp.int64),
                jnp.full(shape, jnp.nan, dtype=vs.dtype),
                jnp.zeros((B,), I32)))
        return tuple(jax.lax.dynamic_update_slice_in_dim(o, d, lo, 0)
                     for o, d in zip(outs, (lanes_of(out_t),
                                            lanes_of(out_v), counts)))

    return jax.lax.fori_loop(0, lane_chunks(n_lanes), chunk, (
        jnp.empty((n_lanes, n_cap), jnp.int64),
        jnp.empty((n_lanes, n_cap), vs.dtype),
        jnp.empty((n_lanes,), I32)))


# steps a block of the band: the windows of so many consecutive steps
# are searched inside one span of a lane (band_width).  Set from sweeps
# of the rate stage alone (v5e, host clock around a call, regular lanes
# 10 s apart of which a seventh started late, [5m]; full width against
# blocks of 16 / 32 / 64 / 128 steps): 97.9 against 149.0 / 99.8 / 79.5
# ms at the fleet's [12,544, 1,536] x 256 in 25 chunks (spans of 384,
# 384, 640), 4.94 against 6.97 / 4.97 / 4.27 at one chunk of it, 19.4
# against - / 18.5 / 14.0 at [2,048, 2,048] x 256 in four, 136.7 against
# 115.8 / 114.6 / 111.0 / 119.5 at the two-day panel's [512, 15,872] x
# 1,344 (bounds alone; spans of 384, 640, 1,024, 1,792).  A block costs
# one coarse count and one selection of its span's tiles whatever its
# steps, so halving the blocks halves both (0.8 ms a fleet chunk at 32).
# The picks inside the spans earn their place at 64 and not at 32: with
# the bounds alone in the band the fleet reads 94.9 / 93.9 at 32 / 64,
# its one chunk 4.54 and [2,048, 2,048] 17.8 at 64 (PERF.md PR 48)
_BAND_STEPS = 64
# samples past a block's steps that a span holds for the windows' range
# (at a 10 s cadence a range of some ten minutes; the fleet's stage
# reads 78.8 ms without them and 79.5 with), and the multiple a span's
# width is rounded up to (the chip's 128 lanes)
_BAND_SLACK = 64
_BAND_TILE = 128


def band_width(n_cap: int, n_steps: int) -> int | None:
    """Samples in the span of a lane in which the windowed stage looks
    for the windows of one block of _BAND_STEPS steps, or None where it
    searches the whole lane: what a block's steps reach of a lane that
    covers the steps evenly, _BAND_SLACK more for the range, rounded up
    to _BAND_TILE; None where that is not under half the lane (the
    band would cost what it saves).  A function of the static buckets
    alone, as window_form is: no query's range or step keys a
    program."""
    reach = _BAND_STEPS * n_cap // max(n_steps, 1) + _BAND_SLACK
    width = (-(-reach // _BAND_TILE) + 1) * _BAND_TILE
    return width if 2 * width <= n_cap and n_cap % _BAND_TILE == 0 else None


def _count_at_or_before(times, bounds):
    """For every lane of times [L, N] the count of its samples at or
    before each of bounds ([Q], or a lane's own [L, Q]): one fused
    compare-and-sum over [L, Q, N]."""
    search = functools.partial(jnp.searchsorted, side="right",
                               method="compare_all")
    return jax.vmap(search, in_axes=(0, None if bounds.ndim == 1 else 0))(
        times, bounds)


def _spans_device(xs, tile, n_tiles: int):
    """Of every [L, N] array of `xs` the `n_tiles` tiles of _BAND_TILE
    cells from tile[l, b] on, as [L x B, n_tiles x _BAND_TILE]: a
    span a (lane, block), each lane's at its own offset.  Tiles are
    SELECTED (_select_at over a lane's N / _BAND_TILE tiles, a tile the
    element), not gathered: this chip's compiler turns a gather of
    contiguous spans into a loop of one dynamic slice a span, a
    microsecond each (PERF.md PR 48), as it runs an element-indexed
    gather an element at a time."""
    L, B = tile.shape
    tiled = tuple(x.reshape(L, -1, _BAND_TILE) for x in xs)
    at = (tile[:, :, None] + jnp.arange(n_tiles, dtype=tile.dtype)).reshape(
        L, B * n_tiles)
    (spans,) = _select_at(tiled, (at,))
    return tuple(x.reshape(L * B, n_tiles * _BAND_TILE) for x in spans)


def _windows_device(xs, steps, range_nanos, pick: bool = False,
                    band: bool = True):
    """The windowed stage's search, for every caller: per-(lane, step)
    index bounds of the [t - range, t] INCLUSIVE window (the -1ns
    exclusive-start trick mirroring consolidate._range_left) over the
    lanes xs[0] = times [L, N], and with `pick` every array of `xs` at
    the windows' ends (clip(left), clip(right - 1): _select_at).  ->
    (starts_excl, left, right, picks or None, lane chunks served at
    the full width: 0 or 1).

    A bound is the count of samples at or before it, by compare-and-sum
    (a binary search would be log2(N) dependent element gathers, which
    this chip runs an element at a time).  Lanes and steps are both
    sorted, so the windows of _BAND_STEPS consecutive steps end inside
    one short run of a lane.  THE BAND: the full-width count finds, a
    (lane, block of steps), only where the block's earliest window
    opens and its latest closes; the span of band_width(N, S) samples
    from there is fetched (_spans_device), and the steps' bounds, and
    the picks, are made inside it: `left` / `right` are the span's
    offset plus the count in the span, the same integers, and a pick
    is the same element.  Cells compared: N x S / _BAND_STEPS +
    width x S a lane where the full width has N x S.

    Proven on every call: the band holds a (lane, block) iff the
    block's latest window closes inside the span that starts at the
    tile its earliest opens in (and the lane ascends, as _decode_merge
    flags it must): the coarse search has both numbers.  Where any lane of
    the batch does not fit, a burst or a lane scraped ten times as
    often or an hour's range, the whole batch takes the full-width
    body under `lax.cond`: the parent's answer, bit for bit.  _INF
    padding, duplicates, a lane that ended before a block and a lane
    that began inside one are ordinary cases of the band; a span is a
    lane's own, so a lane that started late shares a chunk with lanes
    hundreds of samples ahead of it.  Where band_width is None, or
    without `band` (under `vmap`, where a `cond` on a batched predicate
    is a select that runs both bodies), the full width is the only
    body.

    In a trace the counts stand under `bounds`, the spans' fetch under
    `span`, the picks under `take`, each in the caller's scope
    (`m3.temporal/bounds`, ...)."""
    times = xs[0]
    L, N = times.shape
    S = steps.shape[0]
    starts_excl = steps - range_nanos - 1

    def ends(left, right):
        return (jnp.clip(left, 0, N - 1), jnp.clip(right - 1, 0, N - 1))

    def full_width():
        with jax.named_scope("bounds"):
            left = _count_at_or_before(times, starts_excl)
            right = _count_at_or_before(times, steps)
        if not pick:
            return left, right, None
        with jax.named_scope("take"):
            return left, right, _select_at(xs, ends(left, right))

    width = band_width(N, S) if band else None
    if width is None:
        return (starts_excl, *full_width(), jnp.ones((), I32))
    sb, n_tiles = _BAND_STEPS, width // _BAND_TILE
    n_blk = -(-S // sb)
    with jax.named_scope("bounds"):
        # a block's steps in whatever order they came: its earliest
        # window's start and its latest's end
        blocks = jnp.pad(steps, (0, n_blk * sb - S),
                         mode="edge").reshape(n_blk, sb)
        coarse = _count_at_or_before(times, jnp.concatenate(
            [blocks.min(axis=1) - range_nanos - 1, blocks.max(axis=1)]))
        # the span: whole tiles from the one the earliest window opens in
        tile = jnp.clip(coarse[:, :n_blk] // _BAND_TILE, 0,
                        N // _BAND_TILE - n_tiles)
        fits = (jnp.all(coarse[:, n_blk:] <= tile * _BAND_TILE + width)
                & jnp.all(times[:, 1:] >= times[:, :-1]))

    def banded():
        with jax.named_scope("span"):
            spans = _spans_device(xs, tile, n_tiles)
        off = (tile * _BAND_TILE).reshape(L * n_blk, 1)
        with jax.named_scope("bounds"):
            at = jnp.broadcast_to(blocks, (L, n_blk, sb)).reshape(
                L * n_blk, sb)
            left = off + _count_at_or_before(spans[0],
                                             at - range_nanos - 1)
            right = off + _count_at_or_before(spans[0], at)
        picks = None
        if pick:
            with jax.named_scope("take"):
                picks = _select_at(spans, tuple(
                    jnp.clip(i - off, 0, width - 1)
                    for i in ends(left, right)))
        return jax.tree.map(
            lambda x: x.reshape(L, n_blk * sb)[:, :S], (left, right, picks))

    left, right, picks = jax.lax.cond(fits, banded, full_width)
    return starts_excl, left, right, picks, (~fits).astype(I32)


def _window_bounds_device(times, steps, range_nanos, band: bool = True):
    """Per-(lane, step) index bounds of the [t - range, t] INCLUSIVE
    window — the one definition every windowed function shares
    (_windows_device: searched in a band of the lane where its static
    shape allows and the lanes fit, at the full width otherwise; the
    same integers either way).  -> (starts_excl, left, right, lane
    chunks served at the full width: 0 or 1)."""
    starts_excl, left, right, _, full = _windows_device(
        (times,), steps, range_nanos, band=band)
    return starts_excl, left, right, full


# samples a lane up to which _take_at_device selects and above which it
# gathers: the selection's cost grows with them and the gather's does
# not.  Set from a sweep at 256 steps (v5e, [512, n] x 256, both ends
# of three arrays: 2.01 ms at 1,536, 2.33 at 1,920, 7.07 at 6,144, 21.5
# at 18,432 against the gathers' 16.3-16.6 at each; they meet near
# 14,000.  PERF.md PR 33).  Both grow with the steps alike, so the
# crossing moves little: at the two-day panel's 1,344 steps the
# selection takes 9.4 ms at 1,536, 50.4 at 6,144, 101.0 at 12,288, 140.5
# at 15,872 and 150.6 at 18,432 against the gathers' 85.7-92.0 at each;
# they meet near 11,000, and at the constant the selection is 10%
# dearer (PERF.md PR 45, the cell dash-2d runs the gathers' side).
# Since PR 48 the selection up to the constant runs inside a band's
# spans where the lanes fit them (_windows_device): the sweeps above
# are the full width's, which is what a lane that does not fit still
# runs.  Past the constant the band serves the bounds alone and the
# gathers stay: dash-2d's check holds its records to them
_SELECT_MAX_N = 12288


def window_form(n_cap: int) -> str:
    """How _rate_device reads a window's first and last sample at
    `n_cap` samples a lane: "select" or "gather" (_take_at_device).  A
    function of the static bucket alone, so the engine can count which
    form served a call without asking the program."""
    return "select" if n_cap <= _SELECT_MAX_N else "gather"


@jax.named_scope("take")
def _take_at_device(xs, idxs):
    """For each [L, S] index of `idxs` (cells in [0, N)), x[l, idx[l, s]]
    of every [L, N] array of `xs`: `take_along_axis`, the element
    itself, whatever its bits (NaN, +-inf, _INF padding).  In a trace
    its operations stand under `m3.temporal/take`, in either form.

    The TPU compiler runs an element-indexed gather one element at a
    time, 10 ns each whatever N.  Up to _SELECT_MAX_N samples a lane
    the read is a selection instead (_select_at)."""
    if window_form(xs[0].shape[1]) == "gather":
        return [tuple(jnp.take_along_axis(x, idx, axis=1) for x in xs)
                for idx in idxs]
    return _select_at(xs, idxs)


def _select_at(xs, idxs):
    """_take_at_device's reads as a selection: ONE reduction over the
    sample axis for all of `idxs` and `xs`, whose combiner only
    selects, never adds or compares values, under the one-hot masks
    `arange(N) == idx`.  Masks and broadcasts fuse into the reduction;
    no [L, S, N] array exists.  Exactly one cell a (lane, step) is
    flagged under each index, so whatever order the partial results
    meet in, the flagged one survives.  The lanes may be a band's
    spans, [lanes x blocks, width], and the steps a block's; an element
    may be a whole tile of cells, xs [L, N, T] (_spans_device)."""
    L, N, *tile = xs[0].shape
    S, k, n = idxs[0].shape[1], len(xs), len(idxs)
    cell = jnp.arange(N, dtype=I32)
    wide = tuple(jnp.broadcast_to(x[:, None], (L, S, N, *tile)) for x in xs)

    def pick(a, b):
        # an index's flag, and under it one accumulator an x
        out = [hit_a | hit_b for hit_a, hit_b in zip(a[:n], b[:n])]
        for i, hit in enumerate(b[:n]):
            at = slice(n + i * k, n + (i + 1) * k)
            out += [jnp.where(hit, y, x) for x, y in zip(a[at], b[at])]
        return tuple(out)

    got = jax.lax.reduce(
        tuple(jnp.broadcast_to(
            (cell == idx[:, :, None]).reshape(L, S, N, *(1,) * len(tile)),
            wide[0].shape) for idx in idxs) + wide * n,
        (jnp.asarray(False),) * n + tuple(
            jnp.zeros((), x.dtype) for x in xs) * n,
        pick, (2,))
    return [got[n + i * k:n + (i + 1) * k] for i in range(n)]


# samples a lane up to which _prefix_sum_device is ONE reduce_window and
# above which it is a log-step scan: the TPU compiler splits a window
# into pieces of 128 and its time to compile grows steeply with their
# count past 32 (compiled for a described v5e, [512, n] float64: 1.4 s
# at 4,095, 38.9 at 8,191, 114.8 at 12,287, 168.5 at 15,871; on the
# chip's machine the two-day panel's first request gave up after 60 s).
# At [512, 15,871] on a v5e the scan compiles in 4.9 s and runs in
# 2.85 ms; the same window in blocks of 1,024 in 10.7 (PERF.md PR 45)
_PREFIX_MAX_N = 4096


def _prefix_sum_device(x):
    """Inclusive prefix sum of x [L, n] along a lane.

    Up to _PREFIX_MAX_N it is jnp.cumsum's own lowering, spelled out:
    its rule emits this reduce_window from a cached function that drops
    the caller's scope.  Here the lowered operation is named under
    m3.temporal; the TPU compiler still splits so wide a window in two
    pieces that carry no name (PERF.md, PR 34), so a chip's trace shows
    it scoped only inside a chunk loop.  A longer lane is scanned in
    log2(n) steps of shifted adds (the sums meet in another order than
    a running sum's: exact on counters' integer-valued resets)."""
    n = x.shape[1]
    if n > _PREFIX_MAX_N:
        return jax.lax.associative_scan(jnp.add, x, axis=1)
    return jax.lax.reduce_window(
        x, x.dtype.type(0), jax.lax.add, (1, n), (1, 1),
        ((0, 0), (n - 1, 0)))


def _rate_device(times, values, steps, range_nanos,
                 is_counter: bool, is_rate: bool, band: bool = True):
    """Windowed extrapolated rate on device — the jnp port of
    consolidate.extrapolated_rate (upstream Prometheus semantics:
    >=2 samples, counter-reset prefix sums, 1.1x-avg-spacing
    extrapolation caps, counter zero floor).  Lanes go in even chunks
    of at most _MERGE_LANES (the last overlaps its neighbour where
    they do not divide): the stage's [lanes, n_cap] and [lanes, S]
    temporaries, and what the compiler re-lays of a lane batch for the
    prefix sums, are a chunk's and not the fan-out's.  -> (the rates,
    the lane chunks whose windows were searched at the full width and
    not in a band: _windows_device)."""
    L = values.shape[0]
    n_chunks = lane_chunks(L)
    B = min(L, -(-L // (8 * n_chunks)) * 8)
    rate = functools.partial(_rate_lanes, steps=steps,
                             range_nanos=range_nanos,
                             is_counter=is_counter, is_rate=is_rate,
                             band=band)
    if n_chunks == 1:
        return rate(times, values)

    def chunk(c, carry):
        out, full = carry
        lo = jnp.minimum(c * B, L - B)
        rates, at_full = rate(jax.lax.dynamic_slice_in_dim(times, lo, B),
                              jax.lax.dynamic_slice_in_dim(values, lo, B))
        return (jax.lax.dynamic_update_slice_in_dim(out, rates, lo, 0),
                full + at_full)

    return jax.lax.fori_loop(0, n_chunks, chunk, (
        jnp.empty((L, steps.shape[0]), values.dtype), jnp.zeros((), I32)))


def _rate_lanes(times, values, steps, range_nanos,
                is_counter: bool, is_rate: bool, band: bool):
    """_rate_device of one chunk of lanes."""
    L, N = values.shape
    ends = (times, values)
    if is_counter and N > 1:
        prev = values[:, :-1]
        curr = values[:, 1:]
        resets = jnp.where(curr < prev, prev, 0.0)
        ends += (jnp.concatenate(
            [jnp.zeros((L, 1), values.dtype), _prefix_sum_device(resets)],
            axis=1),)
    # the windows' first and last samples: up to _SELECT_MAX_N a lane
    # selected where the bounds were counted (in the band's spans, or
    # over the lane), past it gathered from the lane by the bounds
    select = window_form(N) == "select"
    starts_excl, left, right, picks, full = _windows_device(
        ends if select else ends[:1], steps, range_nanos, pick=select,
        band=band)
    if not select:
        picks = _take_at_device(ends, (jnp.clip(left, 0, N - 1),
                                       jnp.clip(right - 1, 0, N - 1)))
    (t_first, v_first, *cum_first), (t_last, v_last, *cum_last) = picks
    has2 = (right - left) >= 2
    if cum_first:
        corr = jnp.where(has2, cum_last[0] - cum_first[0], 0.0)
    else:
        corr = jnp.zeros_like(v_last)

    result = v_last - v_first + corr
    sampled = (t_last - t_first).astype(values.dtype)
    n_samples = (right - left).astype(values.dtype)
    avg_dur = jnp.where(has2, sampled / jnp.maximum(n_samples - 1, 1),
                        0.0)
    dur_start = (t_first - starts_excl[None, :]).astype(values.dtype)
    dur_end = (steps[None, :] - t_last).astype(values.dtype)
    threshold = avg_dur * 1.1
    if is_counter:
        dur_to_zero = jnp.where(
            (result > 0) & (v_first >= 0),
            sampled * v_first / jnp.where(result > 0, result, 1.0),
            jnp.inf)
        dur_start = jnp.minimum(dur_start, dur_to_zero)
    extrap_start = jnp.where(dur_start < threshold, dur_start,
                             avg_dur / 2)
    extrap_end = jnp.where(dur_end < threshold, dur_end, avg_dur / 2)
    interval = sampled + extrap_start + extrap_end
    out = result * (interval / jnp.maximum(sampled, 1.0))
    if is_rate:
        out = out / (range_nanos / 1e9)
    return jnp.where(has2 & (sampled > 0), out, jnp.nan), full


def _tier_cut(ts, valid, slots, tiers, n_lanes: int, n_tiers: int):
    """Cross-namespace stitch on device: tier rank r contributes only
    samples strictly OLDER than the earliest sample any finer tier
    (rank < r) holds for the same slot — the jnp form of the engine's
    vectorized host stitch (finest-first cut cascade, per-slot minimum
    scatters).  `tiers` are dense ranks (0 = finest); the loop unrolls
    over the static tier count (1-3 in practice)."""
    cut = jnp.full((n_lanes,), _INF, dtype=jnp.int64)
    for t in range(n_tiers):
        in_tier = (tiers == t)[:, None]
        keep = valid & (ts < cut[slots][:, None]) & in_tier
        valid = jnp.where(in_tier, keep, valid)
        row_min = jnp.where(keep, ts, _INF).min(axis=1)
        row_min = jnp.where(in_tier[:, 0], row_min, _INF)
        tier_min = jax.ops.segment_min(row_min, slots,
                                       num_segments=n_lanes,
                                       indices_are_sorted=True)
        cut = jnp.minimum(cut, tier_min)
    return valid


def _decode_merge(words, nbits, slots, n_lanes: int, n_cap: int,
                  n_dp: int | None, unit_nanos: int,
                  tiers=None, n_tiers: int = 1, open_rows=None):
    """Shared front half of every device serving pipeline: batched
    decode at stream width, the cross-namespace tier cut (multi-tier
    fan-outs), row-wise merge into lanes, and the full error contract
    (per-stream decode errors, truncation at n_dp, lane overflow past
    n_cap, unsorted merged lanes).

    `open_rows` = (times i64[R, T], values f64[R, T], counts i32[R],
    slots i64[R], order i64[M + R]): rows that reach the engine as
    arrays (open buffers, blocks already decoded), at the decoded rows'
    width T, a row's samples its first `count` cells.  They are laid
    after the decoded rows and merged with them by the same
    _merge_device; `order` puts the M + R rows into its contract's
    order (grouped by slot, block-ascending: a lane's open rows after
    its sealed ones), so a merged lane stays time-ascending and the
    overflow and unsorted flags hold for these rows too.  The error
    comes back as bool[M + R] then.  No tier cut reads them: the
    engine declines array rows in a multi-tier fan-out.

    Multi-tier merge ordering contract: within a slot, rows arrive
    coarsest tier first (the cut guarantees coarse samples all precede
    the finest tier's earliest sample, so the merged lane stays
    time-ascending — violations trip the unsorted flag)."""
    T = n_cap if n_dp is None else n_dp
    with jax.named_scope("m3.decode"):
        ts, vs, valid, _count, error = m3tsz_decode.decode_batched(
            words, nbits, T, int_optimized=True, unit_nanos=unit_nanos,
            flag_truncation=True)
    with jax.named_scope("m3.merge"):
        if n_tiers > 1 and tiers is not None:
            valid = _tier_cut(ts, valid, slots, tiers, n_lanes, n_tiers)
            # the merge moves a row's FIRST `count` cells: a cut that
            # keeps anything but a prefix (a coarse row out of time
            # order) must fall back, not land the wrong cells
            error = error | jnp.any(valid[:, 1:] & ~valid[:, :-1], axis=1)
        order = None
        if open_rows is not None:
            with jax.named_scope("m3.open"):
                o_ts, o_vs, o_counts, o_slots, order = open_rows
                o_valid = (jnp.arange(T, dtype=I32)[None, :]
                           < o_counts[:, None])
                ts = jnp.concatenate([ts, o_ts])
                vs = jnp.concatenate([vs, o_vs.astype(vs.dtype)])
                valid = jnp.concatenate([valid, o_valid])
                slots = jnp.concatenate([slots, o_slots])
                error = jnp.concatenate(
                    [error, jnp.zeros(o_slots.shape, error.dtype)])
        times, values, counts = _merge_device(ts, vs, valid, slots,
                                              n_lanes, n_cap, order)
        error = error | (counts > n_cap)[slots]
        unsorted = jnp.any(jnp.diff(times, axis=1) < 0, axis=1)
        error = error | unsorted[slots]
    return times, values, error


_MINMAX_BLOCK = 32


def _minmax_device(values, left, right, is_max: bool):
    """Windowed min/max_over_time on device: max/min have no prefix-sum
    form, so windows decompose over a two-level range-max structure —
    per-block prefix/suffix cummax + a sparse (doubling) table over
    block maxima — with the single-block case answered by a direct
    masked reduction over that one 32-sample block.  Memory is ~3x the
    values buffer plus a [L, log2(N/B) * N/B] table (vs the O(N log N)
    full sparse table a textbook RMQ would allocate per lane).

    Host contract (_masked_minmax): NaN samples are absent; a window
    with zero present samples -> NaN; ±Inf samples are legal values.
    min runs as max over negated values."""
    L, N = values.shape
    B = _MINMAX_BLOCK
    w = ~jnp.isnan(values)
    zero = jnp.zeros((L, 1), values.dtype)
    ccnt = jnp.concatenate([zero, jnp.cumsum(w, axis=1)], axis=1)
    n = (jnp.take_along_axis(ccnt, right, axis=1)
         - jnp.take_along_axis(ccnt, left, axis=1))
    vm = jnp.where(w, values, -jnp.inf if is_max else jnp.inf)
    if not is_max:
        vm = -vm
    n2 = -(-N // B) * B
    vmp = jnp.pad(vm, ((0, 0), (0, n2 - N)),
                  constant_values=-jnp.inf)
    nb = n2 // B
    v3 = vmp.reshape(L, nb, B)
    pref = jax.lax.cummax(v3, axis=2).reshape(L, n2)
    suff = jnp.flip(jax.lax.cummax(jnp.flip(v3, 2), axis=2),
                    2).reshape(L, n2)
    block_max = v3.max(axis=2)  # [L, nb]
    tables = [block_max]
    k = 1
    while (1 << k) <= nb:
        prev = tables[-1]
        idx = jnp.minimum(jnp.arange(nb) + (1 << (k - 1)), nb - 1)
        tables.append(jnp.maximum(prev, prev[:, idx]))
        k += 1
    n_lvl = len(tables)
    table = jnp.stack(tables, axis=1).reshape(L, n_lvl * nb)
    l_i = jnp.clip(left, 0, N - 1)
    r_i = jnp.clip(right - 1, 0, N - 1)
    bl, jl = l_i // B, l_i % B
    br, jr = r_i // B, r_i % B
    S = left.shape[1]
    # same-block window: direct masked reduction over block bl
    blk = jnp.take_along_axis(
        v3, jnp.broadcast_to(bl[:, :, None], (L, S, B)), axis=1)
    jj = jnp.arange(B)
    intra = jnp.where(
        (jj >= jl[:, :, None]) & (jj <= jr[:, :, None]), blk,
        -jnp.inf).max(-1)
    # cross-block: suffix of the first block + sparse-table mid-range +
    # prefix of the last block
    a = jnp.take_along_axis(suff, l_i, axis=1)
    c = jnp.take_along_axis(pref, r_i, axis=1)
    x, y = bl + 1, br - 1
    mlen = y - x + 1
    k_lvl = jnp.clip(
        jnp.floor(jnp.log2(jnp.maximum(mlen, 1).astype(
            values.dtype))).astype(l_i.dtype), 0, n_lvl - 1)
    pow2 = jnp.left_shift(jnp.ones_like(k_lvl), k_lvl)
    p1 = jnp.clip(x, 0, nb - 1)
    p2 = jnp.clip(y - pow2 + 1, 0, nb - 1)
    mid = jnp.where(
        mlen > 0,
        jnp.maximum(jnp.take_along_axis(table, k_lvl * nb + p1, axis=1),
                    jnp.take_along_axis(table, k_lvl * nb + p2, axis=1)),
        -jnp.inf)
    cross = jnp.maximum(jnp.maximum(a, c), mid)
    wmax = jnp.where(bl == br, intra, cross)
    if not is_max:
        wmax = -wmax
    return jnp.where(n > 0, wmax, jnp.nan)


def _lift_tables(block, combine):
    """Binary-lifting table over per-block summaries (tuple of
    [L, nb] component arrays): level k holds the combine of 2^k
    consecutive blocks starting at j.  Edge entries whose window would
    overrun are built from clamped indices — shape-keeping only, never
    taken by _lift_mid's greedy decomposition (it only uses segments
    that fit).  Returns the levels stacked per component as
    [L, n_lvl * nb] for one-gather lookups."""
    L, nb = block[0].shape
    tables = [block]
    k = 1
    while (1 << k) <= nb:
        prev = tables[-1]
        idx = jnp.minimum(jnp.arange(nb) + (1 << (k - 1)), nb - 1)
        tables.append(combine(prev, tuple(t[:, idx] for t in prev)))
        k += 1
    n_lvl = len(tables)
    tab = tuple(
        jnp.stack([tables[j][c] for j in range(n_lvl)],
                  axis=1).reshape(L, n_lvl * nb)
        for c in range(len(block)))
    return tab, n_lvl


def _lift_mid(acc, tab, n_lvl, nb, bl, br, combine, ident):
    """Combine the blocks STRICTLY BETWEEN bl and br onto `acc` via a
    greedy binary decomposition — one table segment per set bit of the
    length, positions advancing left to right so the segment order is
    correct for non-commutative combiners (affine composition).
    Untaken levels substitute the combiner's identity element."""
    pos = bl + 1
    remaining = jnp.maximum(br - bl - 1, 0)
    for k in range(n_lvl - 1, -1, -1):
        take = remaining >= (1 << k)
        p = jnp.clip(pos, 0, nb - 1)
        seg = tuple(jnp.where(take,
                              jnp.take_along_axis(t, k * nb + p, axis=1),
                              i)
                    for t, i in zip(tab, ident))
        acc = combine(acc, seg)
        pos = jnp.where(take, pos + (1 << k), pos)
        remaining = jnp.where(take, remaining - (1 << k), remaining)
    return acc


def _wf_merge(a, b):
    """Chan/Welford parallel-variance merge of two (n, mean, M2)
    summaries — numerically stable (no E[x^2] term, so 1e9-scale
    counters don't cancel), associative, and exact-identity against the
    empty state (0, 0, 0): the n_a*n_b cross term vanishes when either
    side is empty.  This is the combiner every level of the range
    structure below uses."""
    na, ma, sa = a
    nb, mb, sb = b
    n = na + nb
    nn = jnp.maximum(n, 1.0)
    d = mb - ma
    mean = ma + d * (nb / nn)
    m2 = sa + sb + d * d * (na * nb / nn)
    return n, mean, m2


def _stdvar_device(values, left, right, is_stddev: bool):
    """Windowed stddev/stdvar_over_time on device.  Variance has no
    per-window prefix-sum form that survives f64 (E[x^2]-E[x]^2
    cancels at counter magnitudes), but Welford summaries MERGE stably
    (Chan's parallel algorithm) — so windows decompose over the same
    two-level structure as _minmax_device, with (n, mean, M2) states in
    place of maxima: per-block prefix/suffix Welford scans + a
    binary-lifting table of DISJOINT power-of-two block-range
    summaries (variance merge is not idempotent, so the overlapping
    sparse-table trick is out; the mid-range instead greedily takes
    non-overlapping segments, one per set bit of its length).
    Same-block windows answer with a direct masked two-pass over that
    one 32-sample block.

    Host contract (consolidate._stdvar): population variance
    M2 / max(n, 1); NaN samples absent; window with zero samples at
    all -> NaN; nonempty-but-all-NaN window -> 0.0."""
    L, N = values.shape
    B = _MINMAX_BLOCK
    m = ~jnp.isnan(values)
    x = jnp.where(m, values, 0.0)
    nf = m.astype(values.dtype)
    n2 = -(-N // B) * B
    pad = ((0, 0), (0, n2 - N))
    xe = jnp.pad(x, pad)
    ne = jnp.pad(nf, pad)
    nb = n2 // B
    x3 = xe.reshape(L, nb, B)
    n3 = ne.reshape(L, nb, B)
    z3 = jnp.zeros_like(x3)
    elems = (n3, x3, z3)  # per-element states: (present, value, 0)
    pref = jax.lax.associative_scan(_wf_merge, elems, axis=2)
    suff = jax.lax.associative_scan(_wf_merge, elems, axis=2,
                                    reverse=True)
    block = tuple(t[:, :, -1] for t in pref)  # [L, nb] totals
    tab, n_lvl = _lift_tables(block, _wf_merge)
    l_i = jnp.clip(left, 0, N - 1)
    r_i = jnp.clip(right - 1, 0, N - 1)
    bl, jl = l_i // B, l_i % B
    br, jr = r_i // B, r_i % B
    S = left.shape[1]
    # same-block window: direct masked two-pass over block bl
    gidx = jnp.broadcast_to(bl[:, :, None], (L, S, B))
    blk_x = jnp.take_along_axis(x3, gidx, axis=1)
    blk_n = jnp.take_along_axis(n3, gidx, axis=1)
    jj = jnp.arange(B)
    in_w = ((jj >= jl[:, :, None]) & (jj <= jr[:, :, None])) * blk_n
    cnt_i = in_w.sum(-1)
    mean_i = (blk_x * in_w).sum(-1) / jnp.maximum(cnt_i, 1.0)
    dev = (blk_x - mean_i[:, :, None]) * in_w
    m2_i = (dev * dev).sum(-1)
    # cross-block: suffix of first block + greedy mid-segments + prefix
    # of last block
    st = tuple(jnp.take_along_axis(t.reshape(L, n2), l_i, axis=1)
               for t in suff)
    en = tuple(jnp.take_along_axis(t.reshape(L, n2), r_i, axis=1)
               for t in pref)
    acc = _lift_mid(st, tab, n_lvl, nb, bl, br, _wf_merge,
                    (0.0, 0.0, 0.0))  # identity = the empty summary
    acc = _wf_merge(acc, en)
    cnt_x, _, m2_x = acc
    same = bl == br
    cnt = jnp.where(same, cnt_i, cnt_x)
    m2 = jnp.where(same, m2_i, m2_x)
    var = m2 / jnp.maximum(cnt, 1.0)
    if is_stddev:
        var = jnp.sqrt(jnp.maximum(var, 0.0))
    return jnp.where(right > left, var, jnp.nan)


def _changes_device(values, left, right, resets_only: bool):
    """changes()/resets() on device: adjacent-pair event counts per
    window via a prefix sum over pair flags (pair (i, i+1) counted when
    left <= i and i+1 < right) — the jnp mirror of the host
    consolidate.window_changes/_pair_window_count.  Counts are
    integers: exact on every backend."""
    L, N = values.shape
    prev, curr = values[:, :-1], values[:, 1:]
    flags = jnp.where(curr < prev, 1.0, 0.0) if resets_only else \
        jnp.where(curr != prev, 1.0, 0.0)
    flags = jnp.where(jnp.isnan(prev) | jnp.isnan(curr), 0.0, flags)
    zero = jnp.zeros((L, 1), values.dtype)
    cum = jnp.concatenate([zero, jnp.cumsum(flags, axis=1)], axis=1)
    hi = jnp.clip(right - 1, 0, N - 1)
    lo = jnp.clip(left, 0, N - 1)
    out = (jnp.take_along_axis(cum, hi, axis=1)
           - jnp.take_along_axis(cum, lo, axis=1))
    return jnp.where(right > left, out, jnp.nan)


def _linreg_device(times, values, steps, range_nanos, left, right):
    """Per-window least-squares fit on device — the jnp mirror of the
    host consolidate.window_linreg (same origin shift, same closed-form
    step-time recentring of the moment sums, so the two tiers agree to
    f64 associativity).  Returns (slope, intercept_at_step, n)."""
    L, N = values.shape
    vz = jnp.nan_to_num(values)
    ok = (~jnp.isnan(values)).astype(values.dtype)
    origin = steps[0] - range_nanos
    tsec = (jnp.where(times == _INF, origin, times)
            - origin).astype(values.dtype) / 1e9

    zero = jnp.zeros((L, 1), values.dtype)

    def wsum(x):
        cum = jnp.concatenate([zero, jnp.cumsum(x, axis=1)], axis=1)
        return (jnp.take_along_axis(cum, right, axis=1)
                - jnp.take_along_axis(cum, left, axis=1))

    n = wsum(ok)
    sv = wsum(vz * ok)
    st = wsum(tsec * ok)
    stv = wsum(tsec * vz * ok)
    stt = wsum(tsec * tsec * ok)
    step_sec = (steps - origin).astype(values.dtype)[None, :] / 1e9
    st_ = st - n * step_sec
    stv_ = stv - step_sec * sv
    stt_ = stt - 2 * step_sec * st + n * step_sec * step_sec
    denom = n * stt_ - st_ * st_
    slope = (n * stv_ - st_ * sv) / denom
    intercept = sv / jnp.maximum(n, 1) - slope * (st_ / jnp.maximum(n, 1))
    valid = (n >= 2) & (jnp.abs(denom) > 1e-30)
    return (jnp.where(valid, slope, jnp.nan),
            jnp.where(valid, intercept, jnp.nan), n)


def _reduce_device(times, values, steps, range_nanos, left, right,
                   reducer: str):
    """Windowed *_over_time reductions on device via NaN-masked prefix
    sums over the merged [L, N] batch (windows are contiguous index
    ranges once lanes are time-sorted).  Semantics mirror the host
    consolidate.window_reduce / step_consolidate exactly: [t-range, t]
    inclusive windows, NaN samples excluded from the mask, empty window
    (no samples at all) -> NaN, nonempty-but-all-NaN windows follow the
    host's masked arithmetic (sum/avg -> 0.0, count -> 0, present ->
    NaN, min/max -> NaN, stddev/stdvar -> 0.0).  min/max route through
    the two-level range-max structure (_minmax_device); stddev/stdvar
    through the mergeable-Welford analog (_stdvar_device)."""
    if reducer in ("min_over_time", "max_over_time"):
        return _minmax_device(values, left, right,
                              reducer == "max_over_time")
    if reducer in ("stddev_over_time", "stdvar_over_time"):
        return _stdvar_device(values, left, right,
                              reducer == "stddev_over_time")
    if reducer in ("changes", "resets"):
        return _changes_device(values, left, right,
                               reducer == "resets")
    if reducer == "deriv":
        slope, _, _ = _linreg_device(times, values, steps, range_nanos,
                                     left, right)
        return slope
    L, N = values.shape
    empty = right == left
    if reducer == "last_over_time":
        picked = jnp.take_along_axis(
            values, jnp.clip(right - 1, 0, N - 1), axis=1)
        return jnp.where(empty, jnp.nan, picked)
    w = ~jnp.isnan(values)
    v0 = jnp.where(w, values, 0.0)
    zero = jnp.zeros((L, 1), values.dtype)
    csum = jnp.concatenate([zero, jnp.cumsum(v0, axis=1)], axis=1)
    ccnt = jnp.concatenate([zero, jnp.cumsum(w, axis=1)], axis=1)
    s = (jnp.take_along_axis(csum, right, axis=1)
         - jnp.take_along_axis(csum, left, axis=1))
    n = (jnp.take_along_axis(ccnt, right, axis=1)
         - jnp.take_along_axis(ccnt, left, axis=1))
    if reducer == "sum_over_time":
        out = s
    elif reducer == "avg_over_time":
        out = s / jnp.maximum(n, 1.0)
    elif reducer == "count_over_time":
        out = n
    elif reducer == "present_over_time":
        out = jnp.where(n > 0, 1.0, jnp.nan)
    else:
        raise ValueError(f"no device form for {reducer}")
    return jnp.where(empty, jnp.nan, out)


def _aff_combine(a, b):
    """Compose two affine maps on (level, trend) states — `a` applied
    FIRST (earlier samples), then `b`: (M, v) with M row-major 2x2 as
    (m00, m01, m10, m11, v0, v1); composed = (Mb·Ma, Mb·va + vb).
    Identity (1,0,0,1,0,0) is the absent-sample element, so NaN holes
    compose away exactly."""
    a00, a01, a10, a11, av0, av1 = a
    b00, b01, b10, b11, bv0, bv1 = b
    return (b00 * a00 + b01 * a10, b00 * a01 + b01 * a11,
            b10 * a00 + b11 * a10, b10 * a01 + b11 * a11,
            b00 * av0 + b01 * av1 + bv0,
            b10 * av0 + b11 * av1 + bv1)


def _holt_winters_device(values, left, right, sf: float, tf: float):
    """holt_winters (double exponential smoothing) on device.  The
    upstream recurrence is affine in the (level, trend) state:

        level' = (1-sf)*level + (1-sf)*trend + sf*x
        trend' = -sf*tf*level + (1-sf*tf)*trend + sf*tf*x

    and affine maps compose associatively — so per-window evaluation
    decomposes over the same two-level structure as the Welford
    variance (_stdvar_device): per-block prefix/suffix map scans + a
    binary-lifting table of disjoint power-of-two block compositions.
    The window's initial state u0 = (x_first, x_second - x_first) is
    built from the first two PRESENT samples (rank lookups on the
    presence prefix count), and the composed map is queried over
    [idx_first + 1, right) — rebasing at the first sample instead of
    inverting its map keeps every factor's spectral radius <= 1 (A's
    inverse would grow as 1/(1-sf) per step and explode over long
    windows).  Same-block windows run the recurrence directly (32
    masked steps), exactly like the host loop.

    sf/tf are STATIC (compile keys): dashboards use fixed smoothing
    factors, and static factors let the per-element map constants fold
    into the program.  Host contract (consolidate.window_holt_winters):
    windows with < 2 present samples -> NaN."""
    L, N = values.shape
    B = _MINMAX_BLOCK
    m = ~jnp.isnan(values)
    x = jnp.where(m, values, 0.0)
    mf = m.astype(values.dtype)
    zero = jnp.zeros((L, 1), values.dtype)
    ccnt = jnp.concatenate([zero, jnp.cumsum(mf, axis=1)], axis=1)
    cnt = (jnp.take_along_axis(ccnt, right, axis=1)
           - jnp.take_along_axis(ccnt, left, axis=1))
    valid = cnt >= 2
    # index of the window's rank-1 / rank-2 present samples
    base_rank = jnp.take_along_axis(ccnt, left, axis=1)
    inner = ccnt[:, 1:]

    def _rank_idx(cc_row, r_row):
        return jnp.searchsorted(cc_row, r_row, side="left")

    idx1 = jax.vmap(_rank_idx)(inner, base_rank + 1.0)
    idx2 = jax.vmap(_rank_idx)(inner, base_rank + 2.0)
    idx1c = jnp.clip(idx1, 0, N - 1)
    idx2c = jnp.clip(idx2, 0, N - 1)
    x0 = jnp.take_along_axis(x, idx1c, axis=1)
    x1 = jnp.take_along_axis(x, idx2c, axis=1)
    u0 = (x0, x1 - x0)
    # per-element affine maps (identity where absent)
    a00, a01 = 1.0 - sf, 1.0 - sf
    a10, a11 = -sf * tf, 1.0 - sf * tf
    n2 = -(-N // B) * B
    pad = ((0, 0), (0, n2 - N))
    xe = jnp.pad(x, pad)
    me = jnp.pad(mf, pad)
    nb = n2 // B
    me3 = me.reshape(L, nb, B)
    xe3 = xe.reshape(L, nb, B)
    one = jnp.ones_like(me3)
    elems = (one + me3 * (a00 - 1.0), me3 * a01,
             me3 * a10, one + me3 * (a11 - 1.0),
             me3 * xe3 * sf, me3 * xe3 * (sf * tf))
    pref = jax.lax.associative_scan(_aff_combine, elems, axis=2)
    # reverse scans hand the combiner (later-accumulated, earlier)
    # operands — harmless for the commutative Welford/max merges, but
    # affine composition is NON-commutative: flip the arguments so the
    # suffix at i is still f_{B-1} ∘ ... ∘ f_i (apply f_i first)
    suff = jax.lax.associative_scan(
        lambda a, b: _aff_combine(b, a), elems, axis=2, reverse=True)
    block = tuple(t[:, :, -1] for t in pref)
    tab, n_lvl = _lift_tables(block, _aff_combine)
    # query range [q_lo, right): the composed map G applied to u0
    q_lo = jnp.clip(idx1 + 1, 0, N - 1)
    r_i = jnp.clip(right - 1, 0, N - 1)
    bl, jl = q_lo // B, q_lo % B
    br, jr = r_i // B, r_i % B
    ident = (1.0, 0.0, 0.0, 1.0, 0.0, 0.0)  # the identity affine map
    st = tuple(jnp.take_along_axis(t.reshape(L, n2), q_lo, axis=1)
               for t in suff)
    en = tuple(jnp.take_along_axis(t.reshape(L, n2), r_i, axis=1)
               for t in pref)
    acc = _lift_mid(st, tab, n_lvl, nb, bl, br, _aff_combine, ident)
    acc = _aff_combine(acc, en)
    g00, g01, _, _, gv0, _ = acc
    lvl_x = g00 * u0[0] + g01 * u0[1] + gv0
    # same-block window [q_lo .. r_i]: run the recurrence directly over
    # the gathered 32-sample block (the host loop, unrolled + masked)
    S = left.shape[1]
    gidx = jnp.broadcast_to(bl[:, :, None], (L, S, B))
    blk_x = jnp.take_along_axis(xe3, gidx, axis=1)
    blk_m = jnp.take_along_axis(me3, gidx, axis=1)
    jj = jnp.arange(B)
    act = ((jj >= jl[:, :, None]) & (jj <= jr[:, :, None])
           & (blk_m > 0))
    level, trend = u0
    for j in range(B):
        aj = act[:, :, j]
        xj = blk_x[:, :, j]
        nl = sf * xj + (1.0 - sf) * (level + trend)
        nt = tf * (nl - level) + (1.0 - tf) * trend
        level = jnp.where(aj, nl, level)
        trend = jnp.where(aj, nt, trend)
    lvl = jnp.where(bl == br, level, lvl_x)
    return jnp.where(valid, lvl, jnp.nan)


def _quantile_window_device(values, left, right, phi):
    """quantile_over_time on device by direct window materialization:
    gather each (lane, step) window's samples into a [L, S, N] grid,
    sort the window axis (absent/NaN keyed +inf past the present
    prefix), and interpolate at h = phi * (n - 1) — upstream promql
    quantile semantics, the jnp mirror of consolidate.window_quantile.

    Order statistics have no range-decomposable summary, so unlike the
    other reducers this costs O(L*S*N) memory — the ENGINE gates
    eligibility by that product and falls back to the host native
    kernel for large fan-outs; phi is traced (dashboards sweep
    quantiles; the shape, not the value, keys the jit cache).  Windows
    can never exceed the lane's N samples, so the gather is exact by
    construction."""
    L, N = values.shape
    idxw = left[:, :, None] + jnp.arange(N)[None, None, :]
    inw = idxw < right[:, :, None]
    v = jnp.take_along_axis(values[:, None, :],
                            jnp.clip(idxw, 0, N - 1), axis=2)
    pres = inw & ~jnp.isnan(v)
    vs = jnp.sort(jnp.where(pres, v, jnp.inf), axis=2)
    n = pres.sum(axis=2).astype(values.dtype)
    h = phi * jnp.maximum(n - 1.0, 0.0)
    lo = jnp.floor(h)
    frac = h - lo
    i_lo = jnp.clip(lo.astype(left.dtype), 0, N - 1)[:, :, None]
    i_hi = jnp.clip(jnp.ceil(h).astype(left.dtype), 0,
                    N - 1)[:, :, None]
    v_lo = jnp.take_along_axis(vs, i_lo, axis=2)[:, :, 0]
    v_hi = jnp.take_along_axis(vs, i_hi, axis=2)[:, :, 0]
    q = v_lo + (v_hi - v_lo) * frac
    return jnp.where(n > 0, q, jnp.nan)


def _instant_device(times, values, left, right, is_rate: bool):
    """irate/idelta on device: delta of the window's last two samples
    (jnp port of the engine's _instant_delta, incl. the irate
    counter-reset rule: a drop means restart, delta = post-reset
    value)."""
    N = values.shape[1]
    has2 = (right - left) >= 2
    i_last = jnp.clip(right - 1, 0, N - 1)
    i_prev = jnp.clip(right - 2, 0, N - 1)
    v_last = jnp.take_along_axis(values, i_last, axis=1)
    dv = v_last - jnp.take_along_axis(values, i_prev, axis=1)
    if is_rate:
        dv = jnp.where(dv < 0, v_last, dv)
    dt = (jnp.take_along_axis(times, i_last, axis=1)
          - jnp.take_along_axis(times, i_prev, axis=1)) / 1e9
    out = dv / jnp.maximum(dt, 1e-9) if is_rate else dv
    return jnp.where(has2, out, jnp.nan)


DEVICE_REDUCERS = ("sum_over_time", "avg_over_time", "count_over_time",
                   "present_over_time", "last_over_time", "irate",
                   "idelta", "min_over_time", "max_over_time",
                   "changes", "resets", "deriv", "stddev_over_time",
                   "stdvar_over_time")


@jax.named_scope("m3.temporal")
def _temporal_eval(fn: str, times, values, steps, range_nanos,
                   horizon=0.0, hw_sf: float = 0.5, hw_tf: float = 0.5,
                   phi=0.5, band: bool = True):
    """One dispatch for the whole windowed temporal family, shared by
    the per-node pipelines and the fused expression interpreter so a
    function gains (or loses) a device form in exactly one place: each
    but the rate family, which goes a chunk of lanes at a time, is
    handed its windows' bounds (left, right).  -> (out, windows):
    windows is int32[2], the stage's lane chunks whose
    windows were searched at the full width, and all of them (the
    others went through the band, which `band` allows:
    _window_bounds_device)."""
    if fn in ("rate", "increase", "delta"):
        # the one family that goes a chunk of lanes at a time, each
        # chunk's windows searched on its own
        out, full = _rate_device(times, values, steps, range_nanos,
                                 is_counter=fn != "delta",
                                 is_rate=fn == "rate", band=band)
        return out, jnp.stack([full, jnp.asarray(
            lane_chunks(values.shape[0]), I32)])
    _, left, right, full = _window_bounds_device(times, steps, range_nanos,
                                                 band)
    if fn in ("irate", "idelta"):
        out = _instant_device(times, values, left, right,
                              is_rate=fn == "irate")
    elif fn == "predict_linear":
        slope, intercept, _ = _linreg_device(times, values, steps,
                                             range_nanos, left, right)
        out = intercept + slope * horizon
    elif fn == "holt_winters":
        out = _holt_winters_device(values, left, right, hw_sf, hw_tf)
    elif fn == "quantile_over_time":
        out = _quantile_window_device(values, left, right, phi)
    else:
        out = _reduce_device(times, values, steps, range_nanos, left,
                             right, fn)
    return out, jnp.stack([full, jnp.ones((), I32)])


class Served(tuple):
    """What a per-node program returns.  It unpacks as the (out, error)
    it has been since the first caller, a benchmark's stand-ins among
    them, and carries beside them `windows`, int32[2] (on a mesh a row
    a shard: no collective carries them): the lane chunks its windowed
    stage served at the full width, and all of them (_temporal_eval).
    A tuple subclass only because benchmark/tests/test_broken_path.py
    unpacks two from the real program and a perf_opt PR may edit no
    benchmark file: a plain third value once a benchmark PR lets that
    stand-in take one (ROADMAP.md Queue 3)."""

    def __new__(cls, out, error, windows):
        self = super().__new__(cls, (out, error))
        self.windows = windows
        return self


jax.tree_util.register_pytree_node(
    Served, lambda s: ((*s, s.windows), None), lambda _, leaves: Served(*leaves))


def _per_node(words, nbits, slots, steps, groups, tiers, open_rows,
              range_nanos, horizon, phi, *, n_lanes, n_cap, n_dp,
              unit_nanos, n_tiers, fn, hw_sf, hw_tf, n_groups, agg,
              axis=None):
    """The per-node program, one body for both entry points and for one
    chip and a mesh: decode + merge, the windowed temporal function,
    and with `groups` the lane reduction (over `axis` when the lanes
    are sharded)."""
    times, values, error = _decode_merge(words, nbits, slots, n_lanes,
                                         n_cap, n_dp, unit_nanos,
                                         tiers, n_tiers, open_rows)
    if groups is not None and fn in ("predict_linear", "holt_winters",
                                     "quantile_over_time"):
        # parameterized temporals never reach the grouped form (the
        # engine's grouped-child gate is single-arg); keep the trace-time
        # error so a future routing bug falls back instead of serving a
        # default-parameter answer
        raise ValueError(f"no grouped device form for {fn}")
    out, windows = _temporal_eval(fn, times, values, steps, range_nanos,
                                  horizon, hw_sf, hw_tf, phi)
    if groups is not None:
        out = _grouped_reduce(out, groups, n_groups, agg, phi, axis=axis)
    return Served(out, error, windows[None] if axis is not None
                  else windows)


def _per_node_on(mesh, words, nbits, slots, steps, groups, tiers,
                 open_rows, range_nanos, horizon, phi, *, n_lanes,
                 **static):
    """`_per_node` as it stands (mesh None: no shard_map, no
    collective), or series-sharded over `mesh`: each shard decodes and
    merges its lane range and runs the windowed kernel locally (the
    multi-tier stitch cut is per slot, so it shards cleanly too), and
    only the grouped form's lane reduction crosses ICI.  Inputs are
    then shard-even row blocks: equal stream rows and equal lanes per
    shard, slots LOCAL per shard, group ids GLOBAL.  The temporal
    matrix and the error come back sharded by series, the grouped
    matrix replicated."""
    if mesh is None:
        return _per_node(words, nbits, slots, steps, groups, tiers,
                         open_rows, range_nanos, horizon, phi,
                         n_lanes=n_lanes, **static)
    # the open arrays are not sharded by lane yet (ROADMAP Queue 2 A3):
    # the engine declines them (open_rows_sharded) before it gets here
    assert open_rows is None, "open rows have no sharded form"
    n_shards = mesh.shape[SERIES_AXIS]
    assert n_lanes % n_shards == 0
    if tiers is None:
        tiers = jnp.zeros_like(nbits, dtype=jnp.int64)
    rows = P(SERIES_AXIS)

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(SERIES_AXIS, None), rows, rows, P(),
                  None if groups is None else rows, rows, P(), P(), P()),
        out_specs=Served(P(SERIES_AXIS, None) if groups is None else P(),
                         rows, P(SERIES_AXIS, None)),
        check_vma=False,
    )
    def step(words_l, nbits_l, slots_l, steps_l, groups_l, tiers_l,
             range_l, horizon_l, phi_l):
        return _per_node(words_l, nbits_l, slots_l, steps_l, groups_l,
                         tiers_l, None, range_l, horizon_l, phi_l,
                         n_lanes=n_lanes // n_shards, axis=SERIES_AXIS,
                         **static)

    return step(words, nbits, slots, steps, groups, tiers,
                *(jnp.asarray(x) for x in (range_nanos, horizon, phi)))


@instrument_kernel("device_temporal_pipeline")
@functools.partial(
    jax.jit,
    static_argnames=("n_lanes", "n_cap", "fn", "unit_nanos", "n_dp",
                     "n_tiers", "hw_sf", "hw_tf", "mesh"))
def device_temporal_pipeline(
    words: jax.Array,      # [M, W] packed compressed block streams
    nbits: jax.Array,      # [M]
    slots: jax.Array,      # [M] output lane per stream (grouped asc)
    steps: jax.Array,      # [S] step times (nanos, ascending)
    n_lanes: int,
    n_cap: int,            # static max samples per lane
    range_nanos,           # TRACED scalar: per-query window duration
    #  must not key the jit cache — arbitrary rate(x[93s]) ranges would
    #  each force a full XLA recompile on the serving path
    fn: str = "rate",
    unit_nanos: int = xtime.SECOND,
    n_dp: int | None = None,  # static max samples per STREAM (block)
    tiers: jax.Array | None = None,  # [M] dense tier ranks, 0 finest
    n_tiers: int = 1,
    horizon=0.0,           # traced: predict_linear's seconds-ahead arg
    hw_sf: float = 0.5,    # static: holt_winters smoothing factors
    hw_tf: float = 0.5,    # (fixed per dashboard; fold into the program)
    phi=0.5,               # traced: quantile_over_time's parameter
    open_rows=None,        # rows that arrive as arrays (_decode_merge)
    mesh: Mesh | None = None,  # series-sharded over it (_per_node_on)
):
    """Compressed blocks -> the [n_lanes, S] matrix of any windowed
    temporal function (_temporal_eval), entirely on device.  Returns
    (out f64[n_lanes, S], error bool[M]; bool[M + R] with open_rows),
    as a `Served`: its `windows` beside them.

    `n_dp` bounds one stream (one sealed block); `n_cap` bounds one
    output lane (all of a series' blocks).  Decoding at block width and
    merging into the lane budget keeps the decode grid at
    [streams, n_dp] instead of [streams, n_cap] — on a 6h/2h-block
    fan-out that is 3x less decode work and HBM traffic."""
    return _per_node_on(mesh, words, nbits, slots, steps, None, tiers,
                        open_rows, range_nanos, horizon, phi,
                        n_lanes=n_lanes, n_cap=n_cap, n_dp=n_dp,
                        unit_nanos=unit_nanos, n_tiers=n_tiers, fn=fn,
                        hw_sf=hw_sf, hw_tf=hw_tf, n_groups=0, agg=None)


DEVICE_GROUP_AGGS = ("sum", "avg", "min", "max", "count", "group",
                     "stddev", "stdvar", "quantile")


def _grouped_quantile(out, groups, n_groups: int, phi):
    """phi-quantile across each group's lanes, per step, on device.
    Lanes sort per step by (group, NaN-last value) in one lexicographic
    lax.sort; each group then occupies a fixed row range
    [base_g, base_g + size_g) with its present values ascending in
    front, so the interpolated quantile is two gathers (upstream promql
    quantile: linear interpolation at h = phi * (n_present - 1);
    group-step with zero present lanes -> NaN).  phi is traced — a
    dashboard sweeping quantiles must not recompile.

    PADDED-LANES-ARE-NaN INVARIANT: unlike the segment-sum reducers in
    _grouped_reduce (where an all-NaN lane is inert on ANY group),
    this sort layout counts EVERY lane of a group — padding included —
    in `sizes`, and distinguishes them only by the NaN->+inf sort key.
    A jit-padding lane parked on group 0 with even one non-NaN value
    would enter group 0's present prefix and corrupt its quantile.
    The engine enforces this at pack time (every padding stream row
    has nbits == 0, every real stream row targets a real lane, so
    lanes >= n_lanes decode to all-NaN) and asserts it before
    dispatching a grouped query (_device_grouped).

    Callers guarantee 0 <= phi <= 1 (the engine declines out-of-range
    phi to the host tier, which answers the upstream ±Inf form)."""
    L, S = out.shape
    gb = jnp.broadcast_to(groups[:, None], (L, S))
    m = ~jnp.isnan(out)
    key = jnp.where(m, out, jnp.inf)  # NaN lanes sort past present
    _, sv = jax.lax.sort((gb, key), dimension=0, num_keys=2)
    npres = jax.ops.segment_sum(m.astype(out.dtype), groups,
                                num_segments=n_groups)  # [G, S]
    sizes = jax.ops.segment_sum(jnp.ones((L,), jnp.int64), groups,
                                num_segments=n_groups)
    base = (jnp.cumsum(sizes) - sizes)[:, None]  # [G, 1]
    h = phi * jnp.maximum(npres - 1.0, 0.0)
    lo = jnp.floor(h)
    frac = h - lo
    i_lo = jnp.clip(base + lo.astype(jnp.int64), 0, L - 1)
    i_hi = jnp.clip(base + jnp.ceil(h).astype(jnp.int64), 0, L - 1)
    v_lo = jnp.take_along_axis(sv, i_lo, axis=0)
    v_hi = jnp.take_along_axis(sv, i_hi, axis=0)
    q = v_lo + (v_hi - v_lo) * frac
    return jnp.where(npres > 0, q, jnp.nan)

@jax.named_scope("m3.group")
def _grouped_reduce(out, groups, n_groups: int, agg: str, phi=0.5,
                    axis: str | None = None):
    """Segment-reduce a served [L, S] temporal matrix over the lane axis
    by group id — the device form of the engine's _eval_agg loop
    (upstream semantics per src/query/functions/aggregation/function.go:
    NaN cells are absent, a group-step with zero present cells is NaN,
    stddev/stdvar use the mean-shifted two-pass form so 1e9-scale
    counters don't cancel to zero).

    Lanes whose row is all-NaN (e.g. jit-padding lanes) contribute
    nothing to any group, so callers may park padding lanes on an
    arbitrary group id.

    With `axis` (the lanes sharded over that mesh axis, inside a
    shard_map; `groups` the GLOBAL ids of this shard's lanes) each
    shard segment-reduces its local lanes and the [n_groups, S]
    partials combine over ICI with the collective matching the
    aggregation — psum for the additive moments, pmin/pmax for the
    order statistics, two psums for stddev/stdvar (global mean first,
    then the shifted squared deviations).  quantile has no
    partial-combining form at all, but the matrix being ranked is the
    REDUCED [lanes, steps] temporal result — small enough to all_gather
    over ICI — after which the per-step lane sort runs identically on
    every shard.  The result is replicated.  Without it no collective
    is emitted: the one-chip program."""
    def across(x, collective=jax.lax.psum):
        return x if axis is None else collective(x, axis)

    if agg == "quantile" and axis is not None:
        out, groups = (jax.lax.all_gather(x, axis, axis=0, tiled=True)
                       for x in (out, groups))
        axis = None
    m = ~jnp.isnan(out)
    vz = jnp.where(m, out, 0.0)
    sums = across(jax.ops.segment_sum(vz, groups, num_segments=n_groups))
    counts = across(jax.ops.segment_sum(m.astype(out.dtype), groups,
                                        num_segments=n_groups))
    if agg == "sum":
        g = sums
    elif agg == "count":
        g = counts
    elif agg == "avg":
        g = sums / jnp.maximum(counts, 1.0)
    elif agg == "min":
        g = across(jax.ops.segment_min(jnp.where(m, out, jnp.inf), groups,
                                       num_segments=n_groups),
                   jax.lax.pmin)
    elif agg == "max":
        g = across(jax.ops.segment_max(jnp.where(m, out, -jnp.inf), groups,
                                       num_segments=n_groups),
                   jax.lax.pmax)
    elif agg == "group":
        g = jnp.ones_like(sums)
    elif agg in ("stddev", "stdvar"):
        mean = sums / jnp.maximum(counts, 1.0)
        d = jnp.where(m, out - mean[groups], 0.0)
        var = (across(jax.ops.segment_sum(d * d, groups,
                                          num_segments=n_groups))
               / jnp.maximum(counts, 1.0))
        g = jnp.sqrt(var) if agg == "stddev" else var
    elif agg == "quantile":
        g = _grouped_quantile(out, groups, n_groups, phi)
    else:
        raise ValueError(f"no device form for aggregation {agg}")
    return jnp.where(counts == 0, jnp.nan, g)


@instrument_kernel("device_grouped_pipeline")
@functools.partial(
    jax.jit,
    static_argnames=("n_lanes", "n_groups", "n_cap", "fn", "agg",
                     "unit_nanos", "n_dp", "n_tiers", "mesh"))
def device_grouped_pipeline(
    words: jax.Array,
    nbits: jax.Array,
    slots: jax.Array,
    steps: jax.Array,
    groups: jax.Array,     # [n_lanes] group id per output lane
    n_lanes: int,
    n_groups: int,
    n_cap: int,
    range_nanos,           # traced: not a jit cache key
    fn: str = "rate",
    agg: str = "sum",
    unit_nanos: int = xtime.SECOND,
    n_dp: int | None = None,
    tiers: jax.Array | None = None,  # [M] dense tier ranks, 0 finest
    n_tiers: int = 1,
    phi=0.5,               # traced: quantile()'s parameter
    open_rows=None,        # rows that arrive as arrays (_decode_merge)
    mesh: Mesh | None = None,  # series-sharded over it (_per_node_on)
):
    """Compressed blocks -> `agg by (...) (fn(x[range]))` matrix,
    entirely on device: the temporal pipeline fused with the grouped
    lane reduction so only the [n_groups, S] result (not the
    [n_lanes, S] intermediate) ever crosses the PCIe/DCN boundary —
    dashboards aggregate thousands of lanes into a handful of groups,
    making this the transfer-optimal serving form.  Returns
    (out f64[n_groups, S], error bool[M]) with the shared error
    contract (_decode_merge), as a `Served`."""
    return _per_node_on(mesh, words, nbits, slots, steps, groups, tiers,
                        open_rows, range_nanos, 0.0, phi,
                        n_lanes=n_lanes, n_cap=n_cap, n_dp=n_dp,
                        unit_nanos=unit_nanos, n_tiers=n_tiers, fn=fn,
                        hw_sf=0.5, hw_tf=0.5, n_groups=n_groups, agg=agg)


# --------------------------------------------------------------------
# whole-query fused execution (query/plan.py is the compiler front end)
# --------------------------------------------------------------------

_EXPR_CMP = frozenset(("==", "!=", ">", "<", ">=", "<="))


def _expr_arith(op: str, a, b):
    """Elementwise arithmetic matching the host tier's numpy forms
    (engine._ARITH): fmod for %, IEEE pow for ^."""
    if op == "+":
        return a + b
    if op == "-":
        return a - b
    if op == "*":
        return a * b
    if op == "/":
        return a / b
    if op == "%":
        return jnp.fmod(a, b)
    if op == "^":
        return jnp.power(a, b)
    raise ValueError(f"no device form for operator {op}")


def _expr_cmp(op: str, a, b):
    if op == "==":
        return a == b
    if op == "!=":
        return a != b
    if op == ">":
        return a > b
    if op == "<":
        return a < b
    if op == ">=":
        return a >= b
    if op == "<=":
        return a <= b
    raise ValueError(f"no device form for comparison {op}")


def _expr_scalar_fn(fn: str, v, extras, steps):
    """Elementwise scalar functions matching engine._ELEMWISE plus the
    parameterized forms (round/clamp*/timestamp).  Every supported fn
    maps NaN -> NaN, so real-NaN cells and padding rows both survive
    (padding is additionally re-masked by the interpreter)."""
    if fn == "abs":
        return jnp.abs(v)
    if fn == "ceil":
        return jnp.ceil(v)
    if fn == "floor":
        return jnp.floor(v)
    if fn == "exp":
        return jnp.exp(v)
    if fn == "sqrt":
        return jnp.sqrt(v)
    if fn == "sgn":
        return jnp.sign(v)
    if fn == "ln":
        return jnp.log(v)
    if fn == "log2":
        return jnp.log2(v)
    if fn == "log10":
        return jnp.log10(v)
    if fn == "round":
        inv = extras[0]  # 1/to, precomputed host-side like the engine
        return jnp.floor(v * inv + 0.5) / inv
    if fn == "clamp_min":
        return jnp.maximum(v, extras[0])
    if fn == "clamp_max":
        return jnp.minimum(v, extras[0])
    if fn == "clamp":
        lo, hi = extras
        # host: np.clip then all-NaN when lo > hi (scalar args only)
        return jnp.where(lo <= hi, jnp.clip(v, lo, hi), jnp.nan)
    if fn == "timestamp":
        return jnp.where(jnp.isnan(v), jnp.nan, steps[None, :] / 1e9)
    raise ValueError(f"no device form for function {fn}()")


def _graphite_grouped_reduce(cv, groups, g_pad: int, op: str, extra,
                             tval):
    """Grouped lane reduction with GRAPHITE NaN semantics — the device
    form of graphite.py's _AGG_REDUCTIONS / _combine family.  Unlike
    the PromQL _grouped_reduce (absent-cell semantics), graphite's
    reducers are numpy nan-reductions: nansum over an all-NaN column
    is 0.0, nanprod is 1.0, count is 0.0, while mean/min/max/stddev/
    median/range go NaN.  Padding lanes are all-NaN (the invariant),
    so parking them on group 0 is inert here too.  The single-row ops
    (diff/median/percentile/last) are lowered single-group only —
    graphite_device.py enforces that."""
    m = ~jnp.isnan(cv)
    vz = jnp.where(m, cv, 0.0)
    sums = jax.ops.segment_sum(vz, groups, num_segments=g_pad)
    counts = jax.ops.segment_sum(m.astype(cv.dtype), groups,
                                 num_segments=g_pad)

    def row0(vals):
        return jnp.where(jnp.arange(g_pad)[:, None] == 0,
                         vals[None, :], jnp.nan)

    if op == "sum":
        return sums
    if op == "count":
        return counts
    if op == "count_series":
        # countSeries: the constant number of input series, NaN-blind;
        # the count is traced (tval) since it's only known at build
        return jnp.full_like(sums, tval)
    if op == "avg":
        return jnp.where(counts == 0, jnp.nan,
                         sums / jnp.maximum(counts, 1.0))
    if op == "min":
        g = jax.ops.segment_min(jnp.where(m, cv, jnp.inf), groups,
                                num_segments=g_pad)
        return jnp.where(counts == 0, jnp.nan, g)
    if op == "max":
        g = jax.ops.segment_max(jnp.where(m, cv, -jnp.inf), groups,
                                num_segments=g_pad)
        return jnp.where(counts == 0, jnp.nan, g)
    if op == "multiply":
        return jax.ops.segment_prod(jnp.where(m, cv, 1.0), groups,
                                    num_segments=g_pad)
    if op == "range":
        hi = jax.ops.segment_max(jnp.where(m, cv, -jnp.inf), groups,
                                 num_segments=g_pad)
        lo = jax.ops.segment_min(jnp.where(m, cv, jnp.inf), groups,
                                 num_segments=g_pad)
        return jnp.where(counts == 0, jnp.nan, hi - lo)
    if op == "stddev":
        mean = sums / jnp.maximum(counts, 1.0)
        d = jnp.where(m, cv - mean[groups], 0.0)
        var = (jax.ops.segment_sum(d * d, groups, num_segments=g_pad)
               / jnp.maximum(counts, 1.0))
        return jnp.where(counts == 0, jnp.nan, jnp.sqrt(var))
    if op == "diff":
        # diffSeries: nan_to_num(first row) - nansum(rest rows); steps
        # where EVERY series is NaN go NaN (single-group: row 0 is the
        # minuend, sums[0] covers every real row)
        vals = 2.0 * vz[0] - sums[0]
        return row0(jnp.where(counts[0] == 0, jnp.nan, vals))
    if op == "median":
        return row0(jnp.nanmedian(cv, axis=0))
    if op == "percentile":
        return row0(jnp.nanpercentile(cv, extra[0], axis=0))
    if op == "last":
        ridx = jnp.argmax(
            jnp.where(m, jnp.arange(cv.shape[0])[:, None], -1), axis=0)
        vals = jnp.take_along_axis(cv, ridx[None, :], axis=0)[0]
        return row0(jnp.where(counts[0] == 0, jnp.nan, vals))
    raise ValueError(f"no device form for graphite reducer {op}")


def _graphite_call(fn: str, cv, statics, fparams, steps):
    """Elementwise / windowed graphite transforms — the device forms
    of graphite.py's registered per-series functions, NaN conventions
    matched op by op.  `statics[0]` is always the REAL step count: the
    padded step columns repeat the last real timestamp (so a leaf's
    padding columns duplicate the last real value), and any op that
    reads across columns (row reductions, shifts, bucketing) would
    otherwise leak them — every call normalizes padding columns to NaN
    first, which is exactly the host's array edge."""
    real_S = statics[0]
    L, Sp = cv.shape
    col = jnp.arange(Sp)
    cv = jnp.where(col[None, :] < real_S, cv, jnp.nan)
    m = ~jnp.isnan(cv)
    if fn == "scale":       # scale / scaleToSeconds (factor premixed)
        return cv * fparams[0]
    if fn == "offset":
        return cv + fparams[0]
    if fn == "absolute":
        return jnp.abs(cv)
    if fn == "invert":
        v = 1.0 / cv
        return jnp.where(jnp.isinf(v), jnp.nan, v)
    if fn == "logarithm":   # fparams[0] = ln(base), host-precomputed
        v = jnp.log(cv) / fparams[0]
        return jnp.where(jnp.isfinite(v), v, jnp.nan)
    if fn == "pow":
        return jnp.power(cv, fparams[0])
    if fn == "squareRoot":
        v = jnp.sqrt(cv)
        return jnp.where(jnp.isfinite(v), v, jnp.nan)
    if fn in ("derivative", "nonNegativeDerivative", "perSecond"):
        d = cv[:, 1:] - cv[:, :-1]
        if fn == "perSecond":
            d = d / fparams[0]  # fparams[0] = step seconds
        if fn != "derivative":
            d = jnp.where(d < 0, jnp.nan, d)  # NaN<0 is False: kept
        return jnp.concatenate(
            [jnp.full((L, 1), jnp.nan), d], axis=1)
    if fn == "integral":
        return jnp.cumsum(jnp.where(m, cv, 0.0), axis=1)
    if fn == "keepLastValue":
        lastidx = jax.lax.cummax(jnp.where(m, col[None, :], -1),
                                 axis=1)
        gap = col[None, :] - lastidx
        fill = jnp.take_along_axis(cv, jnp.clip(lastidx, 0, Sp - 1),
                                   axis=1)
        return jnp.where(m, cv, jnp.where(
            (lastidx >= 0) & (gap <= fparams[0]), fill, jnp.nan))
    if fn == "transformNull":
        return jnp.where(jnp.isnan(cv), fparams[0], cv)
    if fn == "removeAboveValue":
        return jnp.where(cv > fparams[0], jnp.nan, cv)
    if fn == "removeBelowValue":
        return jnp.where(cv < fparams[0], jnp.nan, cv)
    if fn == "isNonNull":
        return m.astype(cv.dtype)
    if fn == "changed":
        ch = ((cv[:, 1:] != cv[:, :-1]) & m[:, 1:] & m[:, :-1])
        return jnp.concatenate(
            [jnp.zeros((L, 1)), ch.astype(cv.dtype)], axis=1)
    if fn == "delay":
        k = statics[1]
        if k >= 0:
            kk = min(k, Sp)
            return jnp.concatenate(
                [jnp.full((L, kk), jnp.nan), cv[:, :Sp - kk]], axis=1)
        kk = min(-k, Sp)
        return jnp.concatenate(
            [cv[:, kk:], jnp.full((L, kk), jnp.nan)], axis=1)
    if fn == "timeSlice":
        lo, hi = fparams
        keep = (steps >= lo) & (steps <= hi)
        return jnp.where(keep[None, :], cv, jnp.nan)
    if fn == "offsetToZero":
        return cv - jnp.nanmin(cv, axis=1, keepdims=True)
    if fn == "minMax":
        mins = jnp.nanmin(cv, axis=1, keepdims=True)
        maxs = jnp.nanmax(cv, axis=1, keepdims=True)
        rng = maxs - mins
        v = (cv - mins) / jnp.where(rng == 0, jnp.nan, rng)
        return jnp.where(jnp.isfinite(v), v, 0.0)
    if fn in ("movingAverage", "movingSum", "movingMax", "movingMin"):
        w = statics[1]
        pad = ((0, 0), (w - 1, 0))
        cnts = jax.lax.reduce_window(
            m.astype(cv.dtype), 0.0, jax.lax.add, (1, w), (1, 1), pad)
        if fn == "movingSum":   # nansum: empty window -> 0.0
            return jax.lax.reduce_window(
                jnp.where(m, cv, 0.0), 0.0, jax.lax.add, (1, w),
                (1, 1), pad)
        if fn == "movingAverage":
            sums = jax.lax.reduce_window(
                jnp.where(m, cv, 0.0), 0.0, jax.lax.add, (1, w),
                (1, 1), pad)
            return jnp.where(cnts == 0, jnp.nan,
                             sums / jnp.maximum(cnts, 1.0))
        if fn == "movingMax":
            mx = jax.lax.reduce_window(
                jnp.where(m, cv, -jnp.inf), -jnp.inf, jax.lax.max,
                (1, w), (1, 1), pad)
            return jnp.where(cnts == 0, jnp.nan, mx)
        mn = jax.lax.reduce_window(
            jnp.where(m, cv, jnp.inf), jnp.inf, jax.lax.min,
            (1, w), (1, 1), pad)
        return jnp.where(cnts == 0, jnp.nan, mn)
    if fn == "summarize":
        k, func = statics[1], statics[2]
        n_out = (real_S + k - 1) // k
        v = cv[:, :real_S]
        if n_out * k > real_S:
            v = jnp.concatenate(
                [v, jnp.full((L, n_out * k - real_S), jnp.nan)],
                axis=1)
        v = v.reshape(L, n_out, k)
        mm = ~jnp.isnan(v)
        c = mm.sum(axis=2).astype(cv.dtype)
        if func in ("sum", "total", ""):
            out = jnp.where(mm, v, 0.0).sum(axis=2)
        elif func in ("avg", "average"):
            out = jnp.where(c == 0, jnp.nan,
                            jnp.where(mm, v, 0.0).sum(axis=2)
                            / jnp.maximum(c, 1.0))
        elif func == "max":
            out = jnp.where(c == 0, jnp.nan,
                            jnp.where(mm, v, -jnp.inf).max(axis=2))
        elif func == "min":
            out = jnp.where(c == 0, jnp.nan,
                            jnp.where(mm, v, jnp.inf).min(axis=2))
        elif func == "count":
            out = c
        elif func in ("range", "rangeOf"):
            out = jnp.where(
                c == 0, jnp.nan,
                jnp.where(mm, v, -jnp.inf).max(axis=2)
                - jnp.where(mm, v, jnp.inf).min(axis=2))
        elif func == "multiply":
            out = jnp.where(mm, v, 1.0).prod(axis=2)
        else:
            raise ValueError(f"no device form for summarize {func!r}")
        out = jnp.repeat(out, k, axis=1)[:, :real_S]
        return jnp.concatenate(
            [out, jnp.full((L, Sp - real_S), jnp.nan)], axis=1)
    if fn == "nPercentile":     # each row becomes its own percentile
        q = statics[1]
        p = jnp.nanpercentile(cv, q, axis=1, keepdims=True)
        out = jnp.broadcast_to(p, cv.shape)
        return jnp.where(col[None, :] < real_S, out, jnp.nan)
    if fn in ("removeAbovePercentile", "removeBelowPercentile"):
        q = statics[1]
        p = jnp.nanpercentile(cv, q, axis=1, keepdims=True)
        # NaN comparisons are False, so NaN cells stay NaN unmasked —
        # same as the host's v[mask] = nan on a NaN-bearing array
        mask = cv > p if fn == "removeAbovePercentile" else cv < p
        return jnp.where(mask, jnp.nan, cv)
    if fn == "integralByInterval":
        # running sum resetting at each interval boundary; NaN -> 0.0
        # (host nan_to_num), dense output.  Zero-padding the tail
        # bucket is inert: cumsum prefixes ignore later elements.
        k = statics[1]
        n_out = (real_S + k - 1) // k
        v = jnp.where(m, cv, 0.0)[:, :real_S]
        if n_out * k > real_S:
            v = jnp.concatenate(
                [v, jnp.zeros((L, n_out * k - real_S))], axis=1)
        out = jnp.cumsum(v.reshape(L, n_out, k), axis=2)
        out = out.reshape(L, n_out * k)[:, :real_S]
        return jnp.concatenate(
            [out, jnp.full((L, Sp - real_S), jnp.nan)], axis=1)
    raise ValueError(f"no device form for graphite function {fn}()")


def _plan_sharded(node) -> bool:
    """Whether a plan node's output is still series-sharded under the
    mesh interpreter.  Pure function of the STATIC plan, shared by the
    sharding-spec builder and the traced interpreter so both always
    agree on where the collectives sit: leaves and the per-lane ops
    above them (call/vs/subq/gcall) stay sharded; a grouped reduce,
    topk, histogram_quantile, absent, vector-vector match, or graphite
    row gather (gsel) produces a replicated result (psum / all-gather
    at that node)."""
    tag = node[0]
    if tag == "leaf":
        return True
    if tag in ("call", "vs", "subq", "gcall"):
        return _plan_sharded(node[-1])
    return False


def _expr_eval(plan, leaves, params, steps, errors,
               axis=None, n_shards: int = 1, band: bool = True):
    """The fused-query interpreter body, shared by the single-chip and
    shard_map'd entry points.  With `axis` set, leaves decode only
    their shard's lane block (lanes_pad // n_shards) and replicating
    nodes insert the matching collective (psum for sum-like grouping
    and absent's presence bit, all_gather ahead of topk /
    histogram_quantile / vector-vector row gathers, whose index maps
    are global).  Returns (out, aux, windows) — aux is (present, rank)
    when the root is a topk node (the host reorders rows by final-step
    rank after the transfer), else (); windows is int32[2], the lane
    chunks the tree's windowed stages searched at the full width and
    all of them (_temporal_eval, which `band` is handed to).

    Scopes: a leaf's stages keep their own (`m3.decode`, `m3.merge`,
    `m3.temporal`), a grouped reduce is `m3.group`, a top-k selection
    `m3.topk`, a histogram_quantile's bucket-row gather and
    interpolation `m3.hq`, every other node's own operations `m3.expr`.
    No scope wraps another's stage: a trace is read by the first `m3.*`
    of an operation's name."""
    aux = ()
    windows = []

    def gather(vals, valid, node):
        if axis is not None and _plan_sharded(node):
            vals = jax.lax.all_gather(vals, axis, axis=0, tiled=True)
            valid = jax.lax.all_gather(valid, axis, axis=0, tiled=True)
        return vals, valid

    def ev(node, steps_cur):
        tag = node[0]
        if tag == "leaf":
            return leaf(node)
        if tag == "vv":
            kids = [ev(node[5], steps_cur), ev(node[6], steps_cur)]
        elif tag == "subq":     # its child evaluates on the inner grid
            kids = [ev(node[-1], params[node[5]][0])]
        else:                   # the child is always the last element
            kids = [ev(node[-1], steps_cur)]
        if tag in ("agg", "topk"):      # m3.group, m3.topk
            return apply(node, kids, steps_cur)
        with jax.named_scope("m3.hq" if tag == "hq" else "m3.expr"):
            return apply(node, kids, steps_cur)

    def leaf(node):
        (_, i, pidx, kind, fn, lanes_pad, n_cap, n_dp, n_tiers,
         _m_pad, _w_pad, _s_pad, hw_sf, hw_tf) = node
        lf = leaves[i]
        if kind == "words":
            times, values, err = _decode_merge(
                lf["words"], lf["nbits"], lf["slots"],
                lanes_pad // n_shards, n_cap, n_dp, xtime.SECOND,
                lf["tiers"], n_tiers)
            errors[i] = err
        else:
            times, values = lf["times"], lf["values"]
        horizon, phi = params[pidx]
        out, searched = _temporal_eval(fn, times, values, lf["steps"],
                                       lf["rng"], horizon=horizon,
                                       hw_sf=hw_sf, hw_tf=hw_tf, phi=phi,
                                       band=band)
        windows.append(searched)
        return jnp.where(lf["valid"][:, None], out,
                         jnp.nan), lf["valid"]

    def apply(node, kids, steps_cur):
        """One node's own operations over its children's (values,
        valid) pairs."""
        nonlocal aux
        tag = node[0]
        cv, cvalid = kids[0]
        if tag == "agg":
            _, op, g_pad, pidx, child = node
            groups, gvalid, phi = params[pidx]
            out = _grouped_reduce(
                cv, groups, g_pad, op, phi,
                axis=axis if _plan_sharded(child) else None)
            return jnp.where(gvalid[:, None], out, jnp.nan), gvalid
        if tag == "call":
            _, fn, pidx, child = node
            out = _expr_scalar_fn(fn, cv, params[pidx], steps_cur)
            return jnp.where(cvalid[:, None], out, jnp.nan), cvalid
        if tag == "vs":
            _, op, bool_mod, mat_on_left, pidx, child = node
            (s,) = params[pidx]
            a, b = (cv, s) if mat_on_left else (s, cv)
            if op in _EXPR_CMP:
                # host matrix-scalar comparison: NaN cells never match
                res = _expr_cmp(op, a, b)
                keep = res & ~jnp.isnan(cv)
                if bool_mod:
                    out = jnp.where(jnp.isnan(cv), jnp.nan,
                                    jnp.where(keep, 1.0, 0.0))
                else:
                    out = jnp.where(keep, cv, jnp.nan)
            else:
                # host matrix-scalar arithmetic does NOT NaN-mask
                # (np semantics: NaN^0 == 1 for real cells)
                out = _expr_arith(op, a, b)
            return jnp.where(cvalid[:, None], out, jnp.nan), cvalid
        if tag == "vv":
            _, op, bool_mod, _out_pad, pidx, lhs, rhs = node
            (lv, lvalid), (rv, rvalid) = kids
            lv, lvalid = gather(lv, lvalid, lhs)
            rv, rvalid = gather(rv, rvalid, rhs)
            lidx, ridx, valid = params[pidx]
            a = lv[lidx]  # [out_pad, S] matched operand rows
            b = rv[ridx]
            nanmask = jnp.isnan(a) | jnp.isnan(b)
            if op in _EXPR_CMP:
                res = _expr_cmp(op, a, b)
                if bool_mod:
                    out = jnp.where(nanmask, jnp.nan,
                                    jnp.where(res, 1.0, 0.0))
                else:
                    out = jnp.where(res & ~nanmask, a, jnp.nan)
            else:
                out = jnp.where(nanmask, jnp.nan,
                                _expr_arith(op, a, b))
            return jnp.where(valid[:, None], out, jnp.nan), valid
        if tag == "topk":
            _, op, k, g_pad, pidx, child = node
            cv, cvalid = gather(cv, cvalid, child)
            (groups,) = params[pidx]
            out, present, rank = masked_topk(cv, groups, g_pad, k,
                                             op == "bottomk")
            aux = (present, rank)
            return jnp.where(cvalid[:, None], out, jnp.nan), cvalid
        if tag == "hq":
            _, g_pad, b_pad, pidx, child = node
            cv, _ = gather(cv, cvalid, child)
            rows_idx, ubs, caps, gvalid, phi = params[pidx]
            counts = cv[rows_idx]  # [g_pad, b_pad, S] bucket gather
            out = bucket_quantile(counts, ubs, caps, phi)
            return jnp.where(gvalid[:, None], out, jnp.nan), gvalid
        if tag == "absent":
            _, pidx, child = node
            (avalid,) = params[pidx]
            present = jnp.any(~jnp.isnan(cv), axis=0)  # [S]
            if axis is not None and _plan_sharded(child):
                # presence is an OR across shards: one cheap [S] psum
                present = jax.lax.psum(present.astype(cv.dtype),
                                       axis) > 0
            row0 = jnp.where(present, jnp.nan, 1.0)
            out = jnp.where(
                jnp.arange(avalid.shape[0])[:, None] == 0,
                row0[None, :], jnp.nan)
            return out, avalid
        if tag == "subq":
            _, fn, _s_in_pad, hw_sf, hw_tf, pidx, child = node
            sub_times, sub_valid, steps_out, rng, horizon = params[pidx]
            # the host packs the inner grid with pack_valid (absent or
            # NaN samples drop, survivors left-justify ascending): one
            # stable row sort keyed +inf-for-dropped reproduces that
            tkey = jnp.where(sub_valid[None, :] & ~jnp.isnan(cv),
                             sub_times[None, :], _INF)
            vm = jnp.where(tkey == _INF, jnp.nan, cv)
            t2, v2 = jax.lax.sort((tkey, vm), dimension=1, num_keys=1)
            out, searched = _temporal_eval(fn, t2, v2, steps_out, rng,
                                           horizon=horizon, hw_sf=hw_sf,
                                           hw_tf=hw_tf, band=band)
            windows.append(searched)
            return jnp.where(cvalid[:, None], out, jnp.nan), cvalid
        if tag == "gsel":
            # graphite row selection: a pure gather by host-computed
            # indices (depth filter / sort / limit / exclude).  The
            # index map is global, so gather the child first.
            _, _out_pad, pidx, child = node
            cv, _ = gather(cv, cvalid, child)
            idx, valid = params[pidx]
            out = cv[idx]
            return jnp.where(valid[:, None], out, jnp.nan), valid
        if tag == "gagg":
            _, op, extra, g_pad, pidx, child = node
            cv, _ = gather(cv, cvalid, child)
            groups, gvalid, tval = params[pidx]
            out = _graphite_grouped_reduce(cv, groups, g_pad, op,
                                           extra, tval)
            return jnp.where(gvalid[:, None], out, jnp.nan), gvalid
        if tag == "gcall":
            _, fn, statics, pidx, child = node
            out = _graphite_call(fn, cv, statics, params[pidx],
                                 steps_cur)
            return jnp.where(cvalid[:, None], out, jnp.nan), cvalid
        raise ValueError(f"unknown plan node {tag!r}")

    out, _valid = ev(plan, steps)
    return out, aux, sum(windows, jnp.zeros((2,), I32))


@instrument_kernel("device_expr_pipeline")
@functools.partial(jax.jit, static_argnames=("plan",))
def device_expr_pipeline(plan, leaves, params, steps):
    """Whole-query fused execution: evaluate a lowered PromQL op-tree
    in ONE compiled program — decode -> step consolidation -> the full
    temporal/aggregation/binop/scalar-fn tree — so only the root
    [rows, S] matrix (plus per-leaf decode-error flags) crosses back to
    the host, instead of one transfer per AST node.

    `plan` is the STATIC node tree produced by query/plan.py — a
    hashable nested tuple that doubles as the compile-cache
    fingerprint (every shape bucket is spelled into it, so two queries
    share a compiled program iff their plans compare equal).  Node
    forms, with `child` a nested node:

      ("leaf", i, pidx, kind, fn, lanes_pad, n_cap, n_dp, n_tiers,
       m_pad, w_pad, s_pad, hw_sf, hw_tf)
          kind "words":  leaves[i] holds the packed compressed batch
          (words/nbits/slots/tiers) -> on-device M3TSZ decode + merge.
          kind "arrays": leaves[i] holds device-ready (times, values)
          grids from the DecodedBlockCache bridge — decode is skipped
          entirely (zero decode_counter bumps on this path).
          params[pidx] = (horizon, phi) — predict_linear's seconds
          ahead and quantile_over_time's parameter, both traced.
      ("agg",  op, g_pad, pidx, child)       grouped lane reduction
      ("call", fn, pidx, child)              elementwise scalar fn
      ("vs",   op, bool_mod, mat_on_left, pidx, child)
                                             vector <op> scalar-literal
      ("vv",   op, bool_mod, out_pad, pidx, lhs, rhs)
                                             vector <op> vector; the
          host-computed match (lhs_idx, rhs_idx row gathers) lives in
          params[pidx] so label matching never runs on device.
      ("topk", op, k, g_pad, pidx, child)    masked top/bottom-k lane
          selection (ops/lane_topk.py); params[pidx] = (groups,) with
          padding lanes parked on a dedicated trash group.  Root-only:
          the aux (present, rank) output drives host row ordering.
      ("hq",   g_pad, b_pad, pidx, child)    histogram_quantile
          bucket interpolation (ops/histo_quantile.py); params[pidx] =
          (rows_idx, ubs, caps, gvalid, phi) — the host groups `le`
          buckets into the dense [g_pad, b_pad] gather layout.
      ("absent", pidx, child)                [8, S] with row 0 = 1.0
          where no child lane has a value (absent / absent_over_time).
      ("subq", fn, s_in_pad, hw_sf, hw_tf, pidx, child)
          nested consolidation: child evaluates on the host-computed
          inner grid, a row sort emulates pack_valid, and the outer
          temporal fn windows over it; params[pidx] = (sub_times,
          sub_valid, steps_out, rng, horizon).
      ("gsel", out_pad, pidx, child)         graphite row gather —
          host-computed selection/reorder (path-depth filter, sort,
          limit); params[pidx] = (idx, valid).
      ("gagg", op, extra, g_pad, pidx, child) graphite grouped reduce
          with numpy nan-reduction semantics (_graphite_grouped_
          reduce); params[pidx] = (groups, gvalid), `extra` a static
          per-op tuple (percentile q, countSeries constant).
      ("gcall", fn, statics, pidx, child)    graphite per-series
          transform (_graphite_call); statics = (real_S, ...) bakes
          window widths / bucket sizes into the plan key, params[pidx]
          carries the traced scalars.

    `leaves`/`params` carry every traced array; `steps` is the padded
    outer step grid (timestamp()), swapped for the inner grid inside a
    subquery.  Each node re-masks padding rows to NaN after applying
    its op (PADDED-LANES-ARE-NaN INVARIANT — e.g. IEEE pow makes
    NaN^0 == 1, which would otherwise leak a padding row into a
    downstream group reduction).

    Returns (out f64[rows, s_pad], aux, errors, windows): aux is
    (present, rank) for a topk root else (); errors is a tuple of
    decode-error vectors for the words-kind leaves in ascending leaf
    index order (the shared _decode_merge contract; any real-stream
    error flag makes the engine fall the whole query back to host);
    windows is int32[2], the lane chunks the tree's windowed stages
    served at the full width and all of them (_temporal_eval).
    """
    errors = {}
    out, aux, windows = _expr_eval(plan, leaves, params, steps, errors)
    return out, aux, tuple(errors[i] for i in sorted(errors)), windows


@instrument_kernel("device_expr_pipeline_batched")
@functools.partial(jax.jit, static_argnames=("plan",))
def device_expr_pipeline_batched(plan, leaves, params, steps):
    """Cross-query megabatch: Q shape-identical queries (equal static
    `plan`) evaluated as ONE compiled program via vmap over a leading
    query axis.

    The serving scheduler (m3_tpu/serving/) stacks Q queries' fused
    inputs — every array in every leaf dict, every traced param, and
    the step grid each gain a leading [Q] axis; np scalars (``rng``)
    stack to [Q] vectors.  Plan equality guarantees the per-query
    pytrees are shape-identical, so the stack is always well-formed.

    Isolation is by construction: vmap evaluates the SAME single-query
    program per slice, and a slice's group ids, vector-match row
    gathers, and topk trash groups only ever index its own lanes — one
    query's aggregation cannot read another's rows any more than two
    separate dispatches could.  The step grid is traced per slice, so
    queries over different time windows (same shape bucket) still
    share the program.

    The windowed stages search the full width here and nowhere else
    (`band=False`): under `vmap` the band's `cond` on a batched
    predicate would be a select that runs BOTH bodies, the band on top
    of the full width.

    Returns the solo contract, without its `windows`, with a leading
    query axis: (out f64[Q, rows, s_pad], aux, errors) — errors is a
    tuple of [Q, ...] decode-error vectors for words-kind leaves in
    ascending leaf index order.  The scheduler demuxes out[qi] back to
    each query's row span and re-slices the error vectors per entry.
    """
    def one(leaves_q, params_q, steps_q):
        errors = {}
        out, aux, _ = _expr_eval(plan, leaves_q, params_q, steps_q,
                                 errors, band=False)
        return out, aux, tuple(errors[i] for i in sorted(errors))

    return jax.vmap(one)(leaves, params, steps)


def _leaf_in_spec(lf):
    """shard_map partition spec for one fused leaf dict: the batch
    arrays split by lane/stream row over the series axis, the step
    grid and window length replicate."""
    return {k: (P(SERIES_AXIS, None) if k in ("words", "times",
                                              "values")
                else P() if k in ("steps", "rng")
                else P(SERIES_AXIS))  # nbits / slots / tiers / valid
            for k in lf}


def _sharded_param_specs(plan, params):
    """Partition specs for the fused params pytree.  Everything
    replicates except a grouped reduce's per-lane group ids over a
    still-sharded child — those split with the lanes they tag."""
    specs = [tuple(P() for _ in p) for p in params]

    def walk(node):
        tag = node[0]
        if tag == "leaf":
            return
        if tag == "agg":
            _, _op, _g_pad, pidx, child = node
            if _plan_sharded(child):
                sp = list(specs[pidx])
                sp[0] = P(SERIES_AXIS)
                specs[pidx] = tuple(sp)
            walk(child)
        elif tag == "vv":
            walk(node[5])
            walk(node[6])
        else:  # call / vs / topk / hq / absent / subq / gsel / gagg /
            walk(node[-1])  # gcall — child is always the last element

    walk(plan)
    return tuple(specs)


@instrument_kernel("device_expr_pipeline_sharded")
@functools.partial(jax.jit, static_argnames=("plan", "mesh"))
def device_expr_pipeline_sharded(plan, mesh, leaves, params, steps):
    """The fused expression interpreter series-sharded over a mesh:
    decode, stitch, consolidate, and every per-lane op subtree run
    fully sharded (lanes partition across chips); the only
    communication is the collective each replicating node inserts —
    psum at sum-like grouping reduces and absent's presence bit,
    all_gather ahead of topk / histogram_quantile / vector-vector row
    gathers (see _plan_sharded).  Inputs are shard-even: words leaves
    arrive through engine._shard_repack (equal stream rows and lanes
    per shard, slots LOCAL), arrays leaves pad lanes to a multiple of
    the shard count.  `mesh` is static alongside `plan` — the compile
    cache keys gain the mesh shape.

    Returns the single-chip contract (out, aux, errors, windows) with
    out/aux replicated, each error vector gathered back to global
    stream row order and the windows a row a shard."""
    n_shards = mesh.shape[SERIES_AXIS]
    leaves_spec = tuple(_leaf_in_spec(lf) for lf in leaves)
    params_spec = _sharded_param_specs(plan, params)
    root_spec = (P(SERIES_AXIS, None) if _plan_sharded(plan) else P())
    aux_spec = (P(), P()) if plan[0] == "topk" else ()
    err_spec = tuple(P(SERIES_AXIS) for lf in leaves if "words" in lf)

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(leaves_spec, params_spec, P()),
        out_specs=(root_spec, aux_spec, err_spec, P(SERIES_AXIS, None)),
        check_vma=False,
    )
    def step(leaves_l, params_l, steps_l):
        errors = {}
        out, aux, windows = _expr_eval(plan, leaves_l, params_l, steps_l,
                                       errors, axis=SERIES_AXIS,
                                       n_shards=n_shards)
        return (out, aux, tuple(errors[i] for i in sorted(errors)),
                windows[None])

    return step(leaves, params, steps)
