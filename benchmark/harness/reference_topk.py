"""The plain reference for `topk(k, sum by (<label>)(rate(m[r])))` and
the comparison that decides `correct` for such a panel.  Imports numpy
and harness/reference.py, nothing of the program.

The answer, per step: the k groups with the largest sums, a group
without a value (NaN) last and never served.  **The tie rule**: among
equal sums the group that comes first in `names` wins, as a stable sort
would have it; the comparison does not hold the system to it.  A group
whose sum lies within `limit` (relative) of the k-th largest at a step
is a tie or a near-tie there and free to be served or not: the program
may sum a group's rows in another order than numpy does, and may rank a
1e-12 apart two sums that are equal here.  Every other group is not
free: above the k-th by more than the limit it must be served, below it
by more than the limit it must not be.
"""

from __future__ import annotations

import numpy as np

from harness import reference


def group_sums(ts_s, values, steps_s, range_s: float, group_of,
               dtype=np.float64):
    """-> (the groups' ids ascending, [groups, steps] sums of the rows'
    rates; NaN where no row of the group has a value)."""
    by = reference.sum_by(group_of, reference.rate(
        ts_s, values, steps_s, range_s, dtype=dtype))
    ids = sorted(by)
    return ids, np.stack([by[g] for g in ids])


def kth_largest(sums, k: int):
    """-> (how many groups a step's answer holds: min(k, groups with a
    value) [steps], the smallest sum among them [steps], NaN where it
    holds none)."""
    has = ~np.isnan(sums)
    n = np.minimum(k, has.sum(axis=0))
    desc = -np.sort(-np.where(has, sums, -np.inf), axis=0, kind="stable")
    kth = desc[np.maximum(n, 1) - 1, np.arange(sums.shape[1])]
    return n, np.where(n > 0, kth, np.nan)


def topk(sums, k: int):
    """bool [groups, steps]: the reference's own selection, ties to the
    group that comes first."""
    order = np.argsort(-np.where(np.isnan(sums), -np.inf, sums), axis=0,
                       kind="stable")
    sel = np.zeros(sums.shape, dtype=bool)
    np.put_along_axis(sel, order[:k], True, axis=0)
    return sel & ~np.isnan(sums)


def served_matrix(rows: dict, label: str, names: list[str], steps_s):
    """A reply's rows ({labels: (steps_s, values)}, loadgen.rows_of) as
    a [groups, steps] matrix, NaN where a row has no point.  -> (the
    matrix, rows whose labels are not exactly one known `label` or whose
    steps are not the panel's, rows without a single point)."""
    steps = np.asarray(steps_s, dtype=np.float64)
    index = {name: g for g, name in enumerate(names)}
    out = np.full((len(names), len(steps)), np.nan)
    strangers = empty = 0
    for key, (t, v) in rows.items():
        g = index.get(key[0][1]) if (
            len(key) == 1 and key[0][0] == label) else None
        at = np.minimum(np.searchsorted(steps, t), len(steps) - 1)
        if g is None or not np.array_equal(steps[at], t):
            strangers += 1
            continue
        if not len(t):
            empty += 1
        out[g, at] = v
    return out, strangers, empty


def compare(rows: dict, label: str, names: list[str], steps_s, sums,
            k: int, limit: float) -> dict:
    """One reply against the reference's `sums` ([groups, steps], the
    groups in the order of `names`).  -> the numbers `correct` is
    decided by, each 0 (or under `limit`) in a sound reply:

    max_rel_gap          largest relative gap of a served point to the
                         reference's sum for that group and step; inf
                         where the reference has no value there
    steps_miscounted     steps whose number of served points is not
                         min(k, groups with a value)
    points_misranked     groups missing at a step where they lie above
                         the k-th largest by more than `limit`, plus
                         groups served where they lie below it by more
    rows_unknown         rows of other labels or other steps
    rows_without_a_point rows that hold no point at all
    """
    served, strangers, empty = served_matrix(rows, label, names, steps_s)
    is_served = ~np.isnan(served)
    n, kth = kth_largest(sums, k)
    with np.errstate(invalid="ignore", divide="ignore"):
        gap = np.abs(served - sums) / np.maximum(np.abs(sums), 1e-300)
        gap = np.where(served == sums, 0.0, gap)
        room = limit * np.abs(kth)
        must = sums - kth > room
        must_not = np.isnan(sums) | (kth - sums > room)
    gap = np.where(is_served & np.isnan(sums), np.inf, gap)
    return {
        "max_rel_gap": float(np.where(is_served, gap, 0.0).max(initial=0.0)),
        "steps_miscounted": int((is_served.sum(axis=0) != n).sum()),
        "points_misranked": int((must & ~is_served).sum()
                                + (must_not & is_served).sum()),
        "rows_unknown": strangers, "rows_without_a_point": empty}
