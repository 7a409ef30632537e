"""Resolution-aware query planning over a retention ladder.

(ref: src/query/storage/m3/cluster_resolver.go — M3's fanout resolver
picks, for each queried time range, the namespaces that can serve it:
the unaggregated namespace while the range is inside raw retention,
then the FINEST aggregated namespace whose retention still covers the
range.  The finest covering tier is exactly the "coarsest necessary"
rung: anything coarser loses detail for no reach, anything finer no
longer holds the data.)

The planner is pure given a clock: ``plan(start, end)`` splits the
inclusive range at every tier's retention horizon (``now -
retention``) into :class:`Band`\\ s, assigns each band its owning
tier, and emits per-namespace :class:`FetchSpec`\\ s, finest-first.

Fetch semantics (load-bearing for correctness):

- every tier's fetch is CLAMPED at its own retention horizon — this
  is the read-cost lever: a year-long query decodes raw streams only
  for the raw-retention suffix;
- coarse tiers are NOT clamped at the fine end.  The engine's
  presence-based stitch already gives finer tiers precedence
  per-series, and a metric whose raw writes are dropped by a drop
  policy (keep_original=False rollups) only exists in rung
  namespaces — an end-clamp would make it invisible inside raw
  retention.
"""

from __future__ import annotations

from dataclasses import dataclass

from m3_tpu.metrics.policy import format_duration

from .ladder import RetentionLadder
from m3_tpu.utils import clock

RAW_RESOLUTION = 0  # sentinel: the unaggregated tier


@dataclass(frozen=True)
class Band:
    """One contiguous sub-range of a query, owned by a single tier."""

    lo: int  # inclusive nanos
    hi: int  # inclusive nanos
    resolution: int  # nanos; RAW_RESOLUTION for the raw tier
    namespace: str

    @property
    def resolution_label(self) -> str:
        if self.resolution == RAW_RESOLUTION:
            return "raw"
        return format_duration(self.resolution)


@dataclass(frozen=True)
class FetchSpec:
    """One namespace read: [lo, hi] inclusive, engine conventions."""

    namespace: str
    resolution: int
    lo: int
    hi: int


@dataclass(frozen=True)
class Plan:
    bands: tuple[Band, ...]  # ascending by lo
    fetches: tuple[FetchSpec, ...]  # finest tier first


class QueryPlanner:
    """Selects the coarsest-necessary rung per query sub-range.

    Tier order is finest-first: the unaggregated namespace, then the
    ladder's rungs ascending by resolution.  ``now_fn`` is injectable
    so tests sweep seams with a fixed clock."""

    def __init__(self, ladder: RetentionLadder, db,
                 raw_namespace: str = "default",
                 now_fn=clock.now_nanos):
        self._ladder = ladder
        self._db = db
        self._raw_ns = raw_namespace
        self._now_fn = now_fn

    def namespaces(self) -> set[str]:
        """Every namespace this planner owns routing for (raw + rungs)."""
        return {self._raw_ns, *self._ladder.namespaces()}

    def _tiers(self) -> list[tuple[int, int, str]]:
        """[(resolution, retention, namespace)] finest-first."""
        raw_ret = self._db.namespace_options(
            self._raw_ns).retention.retention_period
        tiers = [(RAW_RESOLUTION, raw_ret, self._raw_ns)]
        for rung in self._ladder:
            tiers.append((rung.resolution, rung.retention, rung.namespace))
        return tiers

    def plan(self, start_nanos: int, end_nanos: int) -> Plan:
        now = self._now_fn()
        tiers = self._tiers()

        fetches = []
        for resolution, retention, ns in tiers:
            lo = max(start_nanos, now - retention)
            if lo > end_nanos:
                continue  # range entirely past this tier's horizon
            fetches.append(FetchSpec(ns, resolution, lo, end_nanos))

        # Band edges: every tier horizon strictly inside the range.
        cuts = sorted({now - retention for _, retention, _ in tiers
                       if start_nanos < now - retention <= end_nanos})
        edges = [start_nanos] + cuts + [end_nanos + 1]
        bands = []
        for lo, nxt in zip(edges, edges[1:]):
            hi = nxt - 1
            if hi < lo:
                continue
            bands.append(self._band_for(lo, hi, now, tiers))
        return Plan(tuple(bands), tuple(fetches))

    @staticmethod
    def _band_for(lo: int, hi: int, now: int,
                  tiers) -> Band:
        # Owner: the finest tier whose retention covers the band start
        # (== the coarsest rung NECESSARY for the band).  A band older
        # than every retention is charged to the coarsest tier — the
        # data is gone, but the accounting stays total.
        for resolution, retention, ns in tiers:
            if lo >= now - retention:
                return Band(lo, hi, resolution, ns)
        resolution, _, ns = tiers[-1]
        return Band(lo, hi, resolution, ns)

    @staticmethod
    def lookback_for(resolution: int, base_lookback: int) -> int:
        """Seam re-anchoring: inside a coarse band, one sample arrives
        every ``resolution`` nanos, so a step's consolidation window
        must reach back at least two sample intervals or ``rate()``
        sees a phantom gap (then a phantom reset) right after a seam."""
        if resolution == RAW_RESOLUTION:
            return base_lookback
        return max(base_lookback, 2 * resolution)
