"""m3_tpu — a TPU-native distributed time-series metrics platform.

A ground-up re-design of the capabilities of M3 (github.com/m3db/m3,
mounted read-only at /root/reference) for TPU hardware:

- the series-parallel hot paths (M3TSZ codec, windowed downsampling,
  query-side block consolidation) run as batched JAX/XLA/Pallas kernels
  over ``[lanes, time]`` series tensors;
- horizontal scale is expressed as ``jax.sharding.Mesh`` data-parallel
  sharding over the series axis plus time-axis (sequence) parallelism,
  with XLA collectives over ICI in place of goroutine pools;
- the control plane (placement, topology, rules) and IO (filesets,
  commit log, RPC) stay host-side, mirroring the reference's behavior
  but not its implementation.

Layout:
    ops/        device kernels: m3tsz codec, downsample, consolidation
    parallel/   meshes, shardings, collective pipelines
    storage/    dbnode equivalent: buffers, filesets, commitlog, index
    aggregator/ windowed aggregation service (ref: src/aggregator)
    query/      PromQL engine + HTTP API (ref: src/query)
    cluster/    KV, placement, topology (ref: src/cluster)
    models/     end-to-end pipelines ("flagship" = read-path decode+downsample)
    utils/      foundation: config, time, ids, hashing, bit IO (ref: src/x)
"""

import jax

# Timestamps are int64 unix-nanos and values are float64 on the wire
# (ref: src/dbnode/ts values are float64); 64-bit must be on before any
# jax array is created anywhere in the package.
jax.config.update("jax_enable_x64", True)

__version__ = "0.1.0"
