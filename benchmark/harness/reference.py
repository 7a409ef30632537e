"""The plain reference: PromQL answers in numpy from the generator's
own arrays.  Imports nothing of the program.

`rate` follows Prometheus 2.x `extrapolatedRate` for a counter: samples
in [t - range, t], at least two, reset correction, extrapolation to the
window's edges when the gap is under 1.1 average intervals and half an
interval otherwise, never below the counter's zero.  `dtype` exists for
the control (harness tests): float32 is the precision below the
float64 that the deployment states.
"""

from __future__ import annotations

import numpy as np


def rate(ts_s, values, steps_s, range_s: float, dtype=np.float64):
    """[series, steps] per-second rate; all series share `ts_s`."""
    # times relative to the first sample, so that a lower `dtype`
    # rounds intervals and values, not the epoch
    ts = np.asarray(ts_s, dtype=np.float64) - float(ts_s[0])
    vals = np.asarray(values, dtype=dtype)
    steps = np.asarray(steps_s, dtype=np.float64) - float(ts_s[0])
    lo = np.searchsorted(ts, steps - range_s, side="left")
    hi = np.searchsorted(ts, steps, side="right")
    n = hi - lo
    ok = n >= 2
    first = np.clip(lo, 0, len(ts) - 1)
    last = np.clip(hi - 1, 0, len(ts) - 1)
    t_first, t_last = ts[first].astype(dtype), ts[last].astype(dtype)
    v_first, v_last = vals[:, first], vals[:, last]
    drops = np.where(vals[:, 1:] < vals[:, :-1], vals[:, :-1], 0)
    cum = np.concatenate([np.zeros((len(vals), 1), dtype=dtype),
                          np.cumsum(drops, axis=1, dtype=dtype)], axis=1)
    result = v_last - v_first + (cum[:, last] - cum[:, first])
    sampled = t_last - t_first
    avg = sampled / np.maximum(n - 1, 1).astype(dtype)
    to_start = (t_first - (steps - range_s).astype(dtype))[None, :]
    to_end = (steps.astype(dtype) - t_last)[None, :]
    with np.errstate(divide="ignore", invalid="ignore"):
        to_zero = np.where((result > 0) & (v_first >= 0),
                           sampled * v_first / np.where(result > 0,
                                                        result, 1),
                           np.inf)
    to_start = np.minimum(to_start, to_zero)
    threshold = avg * dtype(1.1)
    ext = (sampled
           + np.where(to_start < threshold, to_start, avg / 2)
           + np.where(to_end < threshold, to_end, avg / 2))
    with np.errstate(divide="ignore", invalid="ignore"):
        out = result * (ext / np.maximum(sampled, 1)) / dtype(range_s)
    return np.where(ok & (sampled > 0), out, np.nan)


def sum_by(groups, matrix):
    """{group: [steps]} NaN-skipping sums of the rows of each group; a
    step where no row of the group has a value stays NaN."""
    groups = np.asarray(groups)
    out = {}
    for g in np.unique(groups):
        rows = matrix[groups == g]
        has = ~np.isnan(rows)
        out[g] = np.where(has.any(axis=0),
                          np.where(has, rows, 0).sum(axis=0), np.nan)
    return out


def max_rel_gap(served: dict, want: dict) -> float:
    """Largest relative gap between two {key: (steps_s, values)} answers;
    inf where keys, steps or NaN positions differ."""
    if set(served) != set(want):
        return float("inf")
    worst = 0.0
    for key, (t_w, v_w) in want.items():
        t_s, v_s = served[key]
        if len(t_s) != len(t_w) or not np.array_equal(t_s, t_w):
            return float("inf")
        gap = np.abs(v_s - v_w) / np.maximum(np.abs(v_w), 1e-300)
        worst = max(worst, float(np.where(v_s == v_w, 0.0, gap)
                                 .max(initial=0.0)))
    return worst


def drop_nan(steps_s, by_group: dict) -> dict:
    """{key: (steps with a value, those values)}: the form in which a
    PromQL matrix arrives over HTTP."""
    steps = np.asarray(steps_s, dtype=np.float64)
    out = {}
    for key, row in by_group.items():
        keep = ~np.isnan(row)
        if keep.any():
            out[key] = (steps[keep], np.asarray(row, np.float64)[keep])
    return out
