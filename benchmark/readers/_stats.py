"""Statistics shared by the readers."""

from __future__ import annotations

import statistics


def stat(values: list[float], how: str) -> float | None:
    if not values:
        return None
    if how == "median":
        return float(statistics.median(values))
    if how == "mean":
        return float(statistics.fmean(values))
    if how == "sum":
        return float(sum(values))
    raise ValueError(f"unknown stat {how!r}")
