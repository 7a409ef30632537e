"""Shard write buffers — the mutable head of each block.

The reference buffers writes per series in per-block encoder chains
(ref: src/dbnode/storage/series/buffer.go:221,290) and coalesces
concurrent writers through async insert queues
(ref: src/dbnode/storage/shard_insert_queue.go:63).  TPU-first, the
buffer is columnar: writes arrive as batches of (lane, timestamp,
value) triples appended to chunk lists, and out-of-order data is
resolved once, by sort, at seal time (SURVEY.md §7.3) instead of via
multi-encoder merges.

Reads of the open block go through a consolidated view of the chunks
(`BufferView`): runs sorted by (lane, time), last write wins, brought
up to date from the chunks appended since the last read and never
rebuilt from all of them, so one lane costs a few binary searches and
a set of lanes one call.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import NamedTuple

import numpy as np


def _sorted_last_wins(lanes, times, values):
    """Sort by (lane, time), stable, and keep the LAST of each
    duplicate (lane, time): the reference's upsert on rewrite."""
    # one stable lexsort (lane primary, time secondary) instead of two
    # argsort+gather rounds; later writes for the same (lane, time)
    # keep their insertion order, so LAST still wins
    order = np.lexsort((times, lanes))
    lanes, times, values = lanes[order], times[order], values[order]
    if len(lanes) > 1:
        same = (lanes[:-1] == lanes[1:]) & (times[:-1] == times[1:])
        keep = np.concatenate([~same, [True]])
        lanes, times, values = lanes[keep], times[keep], values[keep]
    return lanes, times, values


class Run(NamedTuple):
    """Samples sorted by (lane, time), no duplicate (lane, time).  The
    arrays are never written again: a later write makes new ones."""

    lanes: np.ndarray
    times: np.ndarray
    values: np.ndarray

    def bounds(self, lanes) -> tuple[np.ndarray, np.ndarray]:
        """(lo, hi): lane i's samples are [lo[i], hi[i]) of the run."""
        lanes = np.asarray(lanes, dtype=np.int64)
        return (np.searchsorted(self.lanes, lanes, side="left"),
                np.searchsorted(self.lanes, lanes, side="right"))

    def merged(self, lanes, times, values) -> "Run":
        """The run with later writes folded in: O(run + new log run),
        this run's arrays left as they are."""
        lanes, times, values = _sorted_last_wins(lanes, times, values)
        if not len(self.lanes):
            return Run(lanes, times, values)
        lo, hi = self.bounds(lanes)
        # steady ingest appends to its lanes: every new sample is later
        # than the lane's last
        newest = self.times[np.maximum(hi, 1) - 1]
        if ((hi == lo) | (times > newest)).all():
            at, there = hi, np.zeros(len(hi), dtype=bool)
        else:
            # bisect every new sample into its lane's stretch at once
            at, end = lo.copy(), hi.copy()
            while (open_ := at < end).any():
                mid = (at + end) // 2
                less = self.times[np.minimum(mid, len(self.times) - 1)] < times
                at = np.where(open_ & less, mid + 1, at)
                end = np.where(open_ & ~less, mid, end)
            there = (at < hi) & (
                self.times[np.minimum(at, len(self.times) - 1)] == times)
        old_values = self.values
        if there.any():             # a rewrite: the later write wins
            old_values = old_values.copy()
            old_values[at[there]] = values[there]
        new = ~there
        return Run(np.insert(self.lanes, at[new], lanes[new]),
                   np.insert(self.times, at[new], times[new]),
                   np.insert(old_values, at[new], values[new]))


_NO_RUN = Run(np.zeros(0, dtype=np.int64), np.zeros(0, dtype=np.int64),
              np.zeros(0, dtype=np.float64))
# a view's recent run is folded into its base once it holds this many
# samples and an eighth of the base's: folding costs O(base), so a
# buffer's reads stay O(what was written since) however often they come
_RECENT_MIN = 1024


class BufferView(NamedTuple):
    """A buffer's samples at one moment: a large base run and a small
    recent one, which holds the later writes and wins a (lane, time)
    that both have.  Neither is written again, so a reader may keep a
    view past the database lock."""

    base: Run = _NO_RUN
    recent: Run = _NO_RUN

    def merged(self, lanes, times, values) -> "BufferView":
        """The view with later writes folded in."""
        recent = self.recent.merged(lanes, times, values)
        if len(recent.lanes) < max(_RECENT_MIN, len(self.base.lanes) // 8):
            return BufferView(self.base, recent)
        return BufferView(self.base.merged(*recent), _NO_RUN)

    def counts(self, lanes) -> np.ndarray:
        """Samples held for each lane (one written twice counts twice)."""
        (lo, hi), (rlo, rhi) = self.base.bounds(lanes), self.recent.bounds(lanes)
        return (hi - lo) + (rhi - rlo)

    def read_lanes(self, lanes) -> list[tuple[np.ndarray, np.ndarray]]:
        """(times, values) of each lane, time-ascending, in one call."""
        base, recent = self.base, self.recent
        (lo, hi), (rlo, rhi) = base.bounds(lanes), recent.bounds(lanes)
        out = []
        for a, b, c, d in zip(lo.tolist(), hi.tolist(), rlo.tolist(),
                              rhi.tolist()):
            if c == d:
                out.append((base.times[a:b], base.values[a:b]))
            elif a == b:
                out.append((recent.times[c:d], recent.values[c:d]))
            elif recent.times[c] > base.times[b - 1]:
                out.append((np.concatenate([base.times[a:b],
                                            recent.times[c:d]]),
                            np.concatenate([base.values[a:b],
                                            recent.values[c:d]])))
            else:       # a late or rewritten sample: merge, recent wins
                _, t, v = _sorted_last_wins(
                    np.zeros(b - a + d - c, dtype=np.int64),
                    np.concatenate([base.times[a:b], recent.times[c:d]]),
                    np.concatenate([base.values[a:b], recent.values[c:d]]))
                out.append((t, v))
        return out


class OpenRow(NamedTuple):
    """One lane of one open buffer, named but not yet read: what a bulk
    reader takes under the database lock and reads after it."""

    view: BufferView
    lane: int

    def read(self) -> tuple[np.ndarray, np.ndarray]:
        return self.view.read_lanes([self.lane])[0]


def open_rows(view: BufferView, lanes) -> list[OpenRow]:
    """An ``OpenRow`` for each of `lanes` on one view, named in one
    C-level pass (a named tuple's own constructor is a Python call)."""
    return list(map(tuple.__new__, itertools.repeat(OpenRow),
                    zip(itertools.repeat(view), lanes)))


def by_view(items, row_of=lambda item: item):
    """[(view, the items whose OpenRow lies on it)]: a bulk reader asks
    each view once for all its lanes."""
    groups: dict[int, tuple[BufferView, list]] = {}
    for item in items:
        view = row_of(item).view
        groups.setdefault(id(view), (view, []))[1].append(item)
    return list(groups.values())


def open_rows_samples(rows: list[OpenRow]) -> int:
    """How many samples a set of named rows holds: one search a view."""
    return sum(int(view.counts([row.lane for row in mine]).sum())
               for view, mine in by_view(rows))


_EMPTY = BufferView()


@dataclasses.dataclass
class BlockBuffer:
    """Columnar append buffer for one (shard, block_start)."""

    block_start: int
    _lanes: list[np.ndarray] = dataclasses.field(default_factory=list)
    _times: list[np.ndarray] = dataclasses.field(default_factory=list)
    _values: list[np.ndarray] = dataclasses.field(default_factory=list)
    _total: int = 0
    # the consolidated view and how many chunks it holds
    _view: BufferView = _EMPTY
    _viewed: int = 0

    def write_batch(
        self, lanes: np.ndarray, times_nanos: np.ndarray, values: np.ndarray
    ) -> None:
        self._lanes.append(np.asarray(lanes, dtype=np.int64))
        self._times.append(np.asarray(times_nanos, dtype=np.int64))
        self._values.append(np.asarray(values, dtype=np.float64))
        self._total += len(lanes)

    @property
    def num_datapoints(self) -> int:
        return self._total

    def consolidated(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(lanes, times, values) sorted by (lane, time); duplicate
        (lane, time) pairs keep the LAST write, matching the reference's
        upsert on datapoint rewrite."""
        if not self._total:
            z = np.zeros(0, dtype=np.int64)
            return z, z.copy(), np.zeros(0, dtype=np.float64)
        return _sorted_last_wins(np.concatenate(self._lanes),
                                 np.concatenate(self._times),
                                 np.concatenate(self._values))

    def view(self) -> BufferView:
        """The consolidated view, brought up to date from the chunks
        appended since it was last read."""
        n = len(self._lanes)
        if self._viewed < n:
            at = self._viewed
            self._view = self._view.merged(
                np.concatenate(self._lanes[at:]),
                np.concatenate(self._times[at:]),
                np.concatenate(self._values[at:]))
            self._viewed = n
        return self._view

    def read_lane(self, lane: int) -> tuple[np.ndarray, np.ndarray]:
        """(times, values) for one series, consolidated, for reads that
        hit the open block."""
        return self.view().read_lanes([lane])[0]
