"""The plain reference for `histogram_quantile(q, rate(m_bucket[r]))`,
for the mean-latency ratio `sum(rate(m_sum[r])) / sum(rate(m_count[r]))`,
and the comparison that decides `correct` for a latency panel.  Imports
numpy and harness/reference.py, nothing of the program.

`quantile` is written from the Prometheus documentation (Querying,
Functions, histogram_quantile()), one label combination a row:

  - the buckets in the order of their upper bounds, the highest +Inf,
    at least two of them (the caller's to give: the generator's are
    DefBuckets);
  - the counts made monotonic over `le` first (a running maximum);
  - no observations (a total of 0, or none at all): NaN;
  - the rank q x total falls in the first bucket whose count reaches
    it; inside it the quantile is interpolated linearly between the
    bucket's bounds, the lower bound of the lowest bucket being 0 where
    its upper bound is positive (and the upper bound itself the answer
    where it is not);
  - where the rank falls in +Inf, the highest finite bound;
  - q < 0: -Inf, q > 1: +Inf, q NaN: NaN.

Departures: a step at which a bucket's rate is NaN (under two samples
in its window) has no value here for the whole histogram; Prometheus
drops that bucket alone.  The generator's series share their
timestamps, so a histogram's buckets are NaN together or not at all.
Prometheus (2.47 and later) also forgives a count that falls by less
than 1e-12 of itself before it makes the counts monotonic; the running
maximum does what that does, without the log line.

**Ties.**  histogram_quantile jumps where the rank equals a bucket's
count and the next bucket holds no further observation: a rank one
rounding above the count is answered from the next bucket that grows,
whose lower bound lies one or more buckets up.  Prometheus itself
answers either way by the last bit of a float64 product.  `tied` marks
such points (the rank within `limit`, relative, of a count that the
next bucket repeats): the comparison leaves them free and counts them.
"""

from __future__ import annotations

import numpy as np

from harness import reference


def bucket_rates(ts_s, buckets, steps_s, range_s: float, dtype=np.float64):
    """[instances, le, steps]: reference.rate of every bucket series
    (`buckets` [instances, le, T])."""
    n_inst, n_le, n_t = buckets.shape
    return reference.rate(ts_s, buckets.reshape(n_inst * n_le, n_t),
                          steps_s, range_s, dtype=dtype).reshape(
                              n_inst, n_le, len(steps_s))


def quantile(q: float, ubs, counts):
    """[rows, steps] from `counts` [rows, le, steps] (cumulative over
    `le`, in the order of `ubs`, whose last is +Inf)."""
    ubs = np.asarray(ubs, dtype=np.float64)
    if len(ubs) < 2 or not np.isposinf(ubs[-1]):
        raise ValueError("at least two buckets, the highest +Inf")
    shape = (counts.shape[0], counts.shape[2])
    if np.isnan(q):
        return np.full(shape, np.nan)
    if q < 0 or q > 1:
        return np.full(shape, -np.inf if q < 0 else np.inf)
    missing = np.isnan(counts).any(axis=1)
    c = np.maximum.accumulate(np.where(np.isnan(counts), 0.0, counts),
                              axis=1)
    total = c[:, -1, :]
    rank = q * total
    b = np.minimum((c < rank[:, None, :]).sum(axis=1), len(ubs) - 1)

    def at(arr, idx):
        return np.take_along_axis(arr, idx[:, None, :], axis=1)[:, 0, :]

    ub3 = np.broadcast_to(ubs[None, :, None], c.shape)
    below = np.maximum(b - 1, 0)
    upper = at(ub3, b)
    lower = np.where(b > 0, at(ub3, below), 0.0)
    c_upper = at(c, b)
    c_lower = np.where(b > 0, at(c, below), 0.0)
    with np.errstate(divide="ignore", invalid="ignore"):
        out = lower + (upper - lower) * (rank - c_lower) / (c_upper - c_lower)
    out = np.where((b == 0) & (ubs[0] <= 0), ubs[0], out)
    out = np.where(b == len(ubs) - 1, ubs[-2], out)
    return np.where(missing | ~(total > 0), np.nan, out)


def tied(q: float, counts, limit: float):
    """bool [rows, steps]: the points at which the quantile jumps (see
    the module's **Ties**)."""
    c = np.maximum.accumulate(np.where(np.isnan(counts), 0.0, counts),
                              axis=1)
    rank = (q * c[:, -1, :])[:, None, :]
    at_count = np.abs(c[:, :-1] - rank) <= limit * np.abs(rank)
    flat = np.abs(c[:, 1:] - c[:, :-1]) <= limit * np.abs(rank)
    return (at_count & flat & (rank > 0)).any(axis=1)


def mean_latency(ts_s, sums, counts, steps_s, range_s: float):
    """[steps]: sum(rate(_sum)) / sum(rate(_count)) over all rows, a
    sum skipping the rows without a value and NaN where none has one."""
    def total(series):
        by = reference.sum_by(np.zeros(len(series), dtype=np.int64),
                              reference.rate(ts_s, series, steps_s, range_s))
        return by[0]

    with np.errstate(divide="ignore", invalid="ignore"):
        return total(sums) / total(counts)


def served_matrix(rows: dict, keys: list[tuple], steps_s):
    """A reply's rows ({labels: (steps_s, values)}, loadgen.rows_of) as
    a [len(keys), steps] matrix, NaN where a row has no point.  -> (the
    matrix, rows whose labels are none of `keys` or whose steps are not
    the panel's, keys without a row)."""
    steps = np.asarray(steps_s, dtype=np.float64)
    index = {key: g for g, key in enumerate(keys)}
    out = np.full((len(keys), len(steps)), np.nan)
    unknown, seen = 0, set()
    for key, (t, v) in rows.items():
        g = index.get(key)
        at = np.minimum(np.searchsorted(steps, t), len(steps) - 1)
        if g is None or not np.array_equal(steps[at], t):
            unknown += 1
            continue
        seen.add(g)
        out[g, at] = v
    return out, unknown, len(keys) - len(seen)


def compare(rows: dict, keys: list[tuple], steps_s, want, free,
            limit: float) -> dict:
    """One reply against the reference's `want` ([len(keys), steps], the
    rows in the order of `keys`; `free` marks its ties).  -> the numbers
    `correct` is decided by, each 0 (or under `limit`) in a sound reply:

    max_rel_gap          largest relative gap of a served point to the
                         reference's value for that row and step, the
                         tied points left out
    points_nan_mismatch  points that one side has and the other has not
    rows_unknown         rows of other labels or other steps
    rows_missing         rows the reference has a value for that the
                         reply does not hold
    points_tied          the points left free (reported, not judged)
    """
    served, unknown, unseen = served_matrix(rows, keys, steps_s)
    both = ~np.isnan(served) & ~np.isnan(want)
    with np.errstate(invalid="ignore", divide="ignore"):
        gap = np.abs(served - want) / np.maximum(np.abs(want), 1e-300)
    gap = np.where(served == want, 0.0, gap)
    # a reference row with no value at all arrives as no row: not missing
    empty = int(np.isnan(want).all(axis=1).sum())
    return {
        "max_rel_gap": float(np.where(both & ~free, gap, 0.0)
                             .max(initial=0.0)),
        "points_nan_mismatch": int((np.isnan(served) != np.isnan(want))
                                   .sum()),
        "rows_unknown": unknown, "rows_missing": max(unseen - empty, 0),
        "points_tied": int((both & free).sum())}
