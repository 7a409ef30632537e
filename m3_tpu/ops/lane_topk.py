"""Masked top-k / bottom-k selection over the lane axis.

Device form of the engine's ``_eval_topk``: every (group, step) cell
keeps its k best lanes and NaNs the rest.  Selection happens entirely
on device with one stable multi-key sort; grouping arrives as a
host-precomputed per-lane group id (padding lanes parked on a dedicated
trash group so they can never displace a real lane in an under-full
group).

Semantics mirror upstream Prometheus topk/bottomk as implemented by the
host tier (query/engine.py:_eval_topk):

- NaN sorts away from the selected end (``-inf`` for topk, ``+inf`` for
  bottomk) but a NaN-valued lane is still selected once the real values
  run out.
- Ties break by lane order (stable sort), matching the host's
  ``kind="stable"`` argsort.
- Output row order is decided by final-step rank (eval_ordered
  semantics); the kernel returns the per-lane final-step rank and the
  host reorders rows after the root transfer.

Called from inside the jitted fused-query interpreter — no jit here.
The selection runs under the scope ``m3.topk``, beside the interpreter's
other stages (``m3.decode`` ... ``m3.group``), so a device trace prices
it apart.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_NO_RANK = jnp.int64(2**62)


@jax.named_scope("m3.topk")
def masked_topk(values, groups, n_groups, k, bottom):
    """Select the top/bottom k lanes per (group, step) cell.

    values   [L, S] f64, padded lanes all-NaN
    groups   [L]    i64 group ids; padding lanes on a trash group
    n_groups static int (incl. the trash group)
    k        static int >= 1
    bottom   static bool: bottomk when True

    Returns (out [L, S] with unselected cells NaN,
             present [L] bool — lane selected at any step,
             rank [L] i64 — final-step selection position, _NO_RANK
             when the lane is unselected at the final step).
    """
    L, S = values.shape
    sink = jnp.inf if bottom else -jnp.inf
    sortable = jnp.where(jnp.isnan(values), sink, values)
    key = sortable if bottom else -sortable
    lanes = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int64)[:, None], (L, S))
    gcol = jnp.broadcast_to(groups[:, None], (L, S))
    # stable sort by (group, key): within each group's contiguous run the
    # best lanes come first, ties kept in lane order
    _, _, sorted_lanes = jax.lax.sort((gcol, key, lanes),
                                      dimension=0, num_keys=2)
    # invert the permutation per step column: position of lane i in the
    # sorted order
    inv = jnp.argsort(sorted_lanes, axis=0)
    sizes = jax.ops.segment_sum(jnp.ones((L,), dtype=jnp.int64), groups,
                                num_segments=n_groups)
    base = jnp.cumsum(sizes) - sizes
    pos_in_group = inv - base[groups][:, None]
    selected = pos_in_group < k
    out = jnp.where(selected, values, jnp.nan)
    present = selected.any(axis=1)
    rank = jnp.where(selected[:, -1], pos_in_group[:, -1], _NO_RANK)
    return out, present, rank
