"""Device mesh construction and sharding helpers."""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

SERIES_AXIS = "series"
WINDOW_AXIS = "window"


shard_map = jax.shard_map


def make_mesh(
    n_series_shards: int | None = None,
    n_window_shards: int = 1,
    devices: list | None = None,
) -> Mesh:
    """Build a 2D (series x window) mesh over the available devices.

    Defaults to all devices on the series axis — the common deployment,
    mirroring the reference's all-shards-spread placement.
    """
    devices = devices if devices is not None else jax.devices()
    if n_series_shards is None:
        n_series_shards = len(devices) // n_window_shards
    n = n_series_shards * n_window_shards
    if n > len(devices):
        raise ValueError(
            f"mesh {n_series_shards}x{n_window_shards} needs {n} devices, "
            f"have {len(devices)}"
        )
    grid = np.asarray(devices[:n]).reshape(n_series_shards, n_window_shards)
    return Mesh(grid, (SERIES_AXIS, WINDOW_AXIS))


def supports_f64_reduce_scatter(mesh: Mesh) -> bool:
    """Whether the bandwidth-optimal psum_scatter/all_gather schedule can
    carry f64 operands on this mesh's backend.

    TPU has no native f64; JAX emulates X64 via an HLO rewrite pass that
    implements all-reduce but NOT reduce-scatter (compile fails with
    "While rewriting computation to not contain X64 element types, XLA
    encountered an HLO for which this rewriting is not implemented:
    reduce-scatter").  Callers pick the scatter schedule where supported
    and fall back to a plain all-reduce — identical sums, one extra
    gather's worth of ICI traffic — on TPU.

    Allowlist posture: only the CPU backend (native f64) is known-good;
    any accelerator platform takes the safe all-reduce path.
    """
    return mesh.devices.flat[0].platform == "cpu"


def consolidate_windows(partial, axis_name: str, use_scatter: bool):
    """Finish a fleet consolidation over the window axis.

    `partial` is this shard's vector already summed over the series axis.
    With `use_scatter`, runs the sequence-parallel schedule — true
    reduce-scatter so each window shard owns its window range, then
    all_gather to publish — which is the ICI-optimal form for large
    vectors.  Otherwise a single all-reduce (the only f64 collective the
    TPU X64 rewriter implements); the result is numerically the same
    modulo reduction order.
    """
    if use_scatter:
        owned = jax.lax.psum_scatter(
            partial, axis_name, scatter_dimension=0, tiled=True
        )
        return jax.lax.all_gather(owned, axis_name, axis=0, tiled=True)
    return jax.lax.psum(partial, axis_name)


def series_sharding(mesh: Mesh) -> NamedSharding:
    """[L, ...] arrays sharded by lane across the series axis."""
    return NamedSharding(mesh, P(SERIES_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
