"""Shared graph/scatter primitives.

One codepath serves both the paper's HELP index machinery and the GNN model
family (DESIGN.md §5): fixed-capacity adjacency tables, reverse-edge
construction, segment reductions, and the sorted-pool merge/dedup utilities
that replace the paper's insertion-sorted candidate lists on TPU.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

Array = jax.Array

#: Sentinel padding id for fixed-capacity neighbor tables / pools.
INVALID = jnp.int32(-1)
#: Padding distance — anything real beats it in a min-merge.
INF = jnp.float32(3.0e38)


def in_degrees(neighbors: Array, n_nodes: int) -> Array:
    """In-degree of every node given an (N, Γ) adjacency table (-1 = pad)."""
    flat = neighbors.reshape(-1)
    valid = flat >= 0
    return jax.ops.segment_sum(
        valid.astype(jnp.int32), jnp.where(valid, flat, 0), num_segments=n_nodes
    )


def reverse_neighbors(neighbors: Array, n_nodes: int, capacity: int) -> Array:
    """Fixed-capacity reverse adjacency: (N, capacity) table of sources.

    For every directed edge i→j, register i in j's reverse list. Slots are
    assigned by sorting edges by destination and ranking within each segment;
    overflow beyond ``capacity`` is dropped (random-ish eviction by source
    order — matches the bulk-synchronous NN-descent sampling of reverse
    neighbors).
    """
    n, gamma = neighbors.shape
    src = jnp.repeat(jnp.arange(n, dtype=jnp.int32), gamma)
    dst = neighbors.reshape(-1)
    valid = dst >= 0
    # Sort edges by destination; invalid edges sort to the end.
    key = jnp.where(valid, dst, jnp.int32(n))
    order = jnp.argsort(key, stable=True)
    dst_s = key[order]
    src_s = src[order]
    # Rank within each destination segment: offset from the segment's first
    # edge. A binary search finds it (an associative scan over N·Γ edges
    # takes the TPU compiler minutes at 100k nodes).
    seg_start = jnp.searchsorted(dst_s, dst_s, side="left")
    rank = jnp.arange(dst_s.shape[0]) - seg_start
    keep = (rank < capacity) & (dst_s < n)
    safe_dst = jnp.where(keep, dst_s, n)  # out-of-range rows are dropped
    table = jnp.full((n, capacity), INVALID)
    table = table.at[safe_dst, jnp.where(keep, rank, 0)].set(src_s, mode="drop")
    return table


def mask_duplicate_ids(ids: Array, dists: Array) -> tuple[Array, Array]:
    """Within each row, keep the best entry per id; duplicates → (INVALID, INF).

    Rows are processed independently: sort by (id asc, dist asc), mark repeats
    of the same id. Callers re-sort by distance afterwards.
    """
    order = jnp.lexsort((dists, ids), axis=-1)
    ids_s = jnp.take_along_axis(ids, order, axis=-1)
    dists_s = jnp.take_along_axis(dists, order, axis=-1)
    dup = jnp.concatenate(
        [jnp.zeros_like(ids_s[..., :1], dtype=bool), ids_s[..., 1:] == ids_s[..., :-1]],
        axis=-1,
    )
    dup = dup | (ids_s < 0)
    ids_s = jnp.where(dup, INVALID, ids_s)
    dists_s = jnp.where(dup, INF, dists_s)
    return ids_s, dists_s


def merge_pools(
    pool_ids: Array,
    pool_dists: Array,
    cand_ids: Array,
    cand_dists: Array,
    capacity: int,
    pool_flags: Optional[Array] = None,
    cand_flags: Optional[Array] = None,
) -> tuple[Array, Array, Optional[Array]]:
    """Merge candidates into a sorted fixed-capacity pool (per row).

    Replaces the paper's insertion sort: concatenate, dedup by id (keeping the
    best distance — flags ride along so `checked` status survives re-insertion
    of an already-expanded node), then take the ``capacity`` smallest.
    Returns pools sorted ascending by distance.
    """
    ids = jnp.concatenate([pool_ids, cand_ids], axis=-1)
    dists = jnp.concatenate([pool_dists, cand_dists], axis=-1)
    if pool_flags is not None:
        if cand_flags is None:
            cand_flags = jnp.zeros_like(cand_ids, dtype=pool_flags.dtype)
        flags = jnp.concatenate([pool_flags, cand_flags], axis=-1)
    else:
        flags = None

    # Dedup by id: sort by (id asc, flag desc, dist asc) so the kept copy of a
    # duplicate id is the checked one (flags dominate: a checked node must not
    # be re-expanded) and otherwise the closest one. Flags are 0/1, so id and
    # flag fold into one key 2·id + (flag == 0): a three-operand sort of a
    # (128, 1600) pool takes the TPU compiler ~30 s, a two-operand one ~5 s.
    if flags is not None:
        key = ids * 2 + (flags == 0).astype(ids.dtype)
        order = jnp.lexsort((dists, key), axis=-1)
    else:
        order = jnp.lexsort((dists, ids), axis=-1)
    ids_s = jnp.take_along_axis(ids, order, axis=-1)
    dists_s = jnp.take_along_axis(dists, order, axis=-1)
    dup = jnp.concatenate(
        [jnp.zeros_like(ids_s[..., :1], dtype=bool), ids_s[..., 1:] == ids_s[..., :-1]],
        axis=-1,
    )
    invalid = ids_s < 0
    kill = dup | invalid
    ids_s = jnp.where(kill, INVALID, ids_s)
    dists_s = jnp.where(kill, INF, dists_s)
    if flags is not None:
        flags_s = jnp.take_along_axis(flags, order, axis=-1)
        flags_s = jnp.where(kill, jnp.zeros_like(flags_s), flags_s)

    # Keep the `capacity` smallest by distance.
    neg_top, take = jax.lax.top_k(-dists_s, capacity)
    new_ids = jnp.take_along_axis(ids_s, take, axis=-1)
    new_dists = -neg_top
    if flags is not None:
        new_flags = jnp.take_along_axis(flags_s, take, axis=-1)
        return new_ids, new_dists, new_flags
    return new_ids, new_dists, None


def gather_rows(table: Array, ids: Array) -> Array:
    """Gather rows of ``table`` at ``ids`` (INVALID-safe: pad rows → row 0)."""
    safe = jnp.maximum(ids, 0)
    return jnp.take(table, safe, axis=0)


# ---------------------------------------------------------------------------
# Message-passing primitives shared with models/gnn.py
# ---------------------------------------------------------------------------


def scatter_sum(messages: Array, dst: Array, n_nodes: int) -> Array:
    """Σ of per-edge messages into destination nodes (GNN aggregation)."""
    return jax.ops.segment_sum(messages, dst, num_segments=n_nodes)


def scatter_max(messages: Array, dst: Array, n_nodes: int) -> Array:
    return jax.ops.segment_max(messages, dst, num_segments=n_nodes)


def scatter_mean(messages: Array, dst: Array, n_nodes: int) -> Array:
    s = scatter_sum(messages, dst, n_nodes)
    cnt = jax.ops.segment_sum(
        jnp.ones((messages.shape[0],), jnp.float32), dst, num_segments=n_nodes
    )
    return s / jnp.maximum(cnt, 1.0)[:, None]


def degree_normalized_adjacency_apply(
    x: Array, src: Array, dst: Array, n_nodes: int
) -> Array:
    """GCN-style Â·X via gather → scale → scatter (no sparse matrices)."""
    deg = jax.ops.segment_sum(
        jnp.ones_like(src, dtype=jnp.float32), dst, num_segments=n_nodes
    )
    inv_sqrt = jax.lax.rsqrt(jnp.maximum(deg, 1.0))
    msgs = x[src] * (inv_sqrt[src] * inv_sqrt[dst])[:, None]
    return scatter_sum(msgs, dst, n_nodes)
