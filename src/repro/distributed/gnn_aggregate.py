"""Ring-partitioned message aggregation for edge-parallel GNNs.

EXPERIMENTS.md §Perf hillclimb 1 found XLA's lowering of edge-parallel
``segment_sum`` materializes a FULL (N, d) scatter partial per device
(4.67 GiB on ogb_products) followed by a dense all-reduce. This shard_map
primitive replaces it: each device scatters its local edges' messages into
one (N/size, d) node-shard accumulator at a time while the accumulators
rotate around the ring — peak buffer shrinks by the device count (4.67 GiB →
18.7 MiB at 256 devices) and the wire traffic halves versus the dense
all-reduce (each accumulator crosses each link once instead of the
reduce+broadcast round trip).

Exactness vs global segment_sum is asserted in tests/test_distributed.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def ring_partitioned_aggregate(
    messages: Array,  # (E_local, d) this device's edge messages
    dst: Array,  # (E_local,) GLOBAL destination node ids
    n_nodes: int,  # global node count (must divide the axis size)
    axis_name: str,
) -> Array:
    """Returns this device's (n_nodes/size, d) fully-reduced node shard.

    Ring schedule (same as collective_matmul.ring_reduce_scatter_matmul):
    device ``i`` seeds the accumulator for shard ``i-1``; every hop passes
    the running sum downstream and adds the local edges' contribution to the
    shard now in hand; after ``size-1`` hops device ``i`` holds shard ``i``.
    """
    size = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    assert n_nodes % size == 0, (n_nodes, size)
    rows = n_nodes // size
    perm = [(i, (i + 1) % size) for i in range(size)]

    def contrib(shard):
        local = dst - shard * rows
        ok = (local >= 0) & (local < rows)
        return jax.ops.segment_sum(
            jnp.where(ok[:, None], messages, 0),
            jnp.where(ok, local, 0),
            num_segments=rows,
        )

    acc = contrib((idx - 1) % size)
    for step in range(1, size):
        acc = jax.lax.ppermute(acc, axis_name, perm)
        acc = acc + contrib((idx - 1 - step) % size)
    return acc  # rows [idx·rows : (idx+1)·rows] of the aggregated nodes
