"""The node's reading of data time.

Every decision that compares a sample's timestamp with "now" (which
block is open, whether a write is inside the buffer, when a block
seals or expires, the default `time` of a query, an aggregation
window's end, a lag gauge) reads this clock, so that a harness or a
test can put the node at a chosen moment of the block cycle and let
time run from there at the wall clock's rate (ref: the reference
injects clock.Options.NowFn everywhere, and its integration tests turn
blocks over with SetNowFn).  Durations, deadlines and durability
stamps stay on time.perf_counter / monotonic / xtime.stamp_ns.
"""

from __future__ import annotations

import time

_offset_nanos = 0


def now_nanos() -> int:
    return time.time_ns() + _offset_nanos


def now_s() -> float:
    return time.time() + _offset_nanos / 1e9


def offset_nanos() -> int:
    return _offset_nanos


def set_offset_nanos(offset: int) -> None:
    """Shift the node's clock by `offset` nanoseconds against the wall
    clock (0 = the wall clock); it keeps running at the wall's rate."""
    global _offset_nanos
    _offset_nanos = int(offset)
