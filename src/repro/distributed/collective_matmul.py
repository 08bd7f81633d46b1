"""Collective (ppermute-pipelined) matmuls: compute/communication overlap.

Standard TP computes ``psum(x_local @ w_local)`` — the all-reduce is fully
exposed after the MXU finishes. These ring decompositions break the
collective into ``size-1`` ppermute hops interleaved with adds, which XLA's
latency-hiding scheduler can overlap with neighboring computation (Wang et
al., ASPLOS'23 — the decomposition pattern behind Megatron/MaxText overlap).
Used under ``shard_map``; exactness is asserted against psum in tests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def ring_allreduce_matmul(
    x_local: Array, w_local: Array, axis_name: str
) -> Array:
    """Full (B, N) = Σ_s x_s @ w_s via a ring of ppermute+add hops.

    x_local (B, K_s): this device's shard of the contraction dim;
    w_local (K_s, N): the matching weight rows. Equivalent to
    ``psum(x_local @ w_local, axis)`` but decomposed for overlap.
    """
    size = jax.lax.axis_size(axis_name)
    perm = [(i, (i + 1) % size) for i in range(size)]
    partial = x_local @ w_local  # (B, N) local term
    acc = partial
    for _ in range(size - 1):
        acc = jax.lax.ppermute(acc, axis_name, perm) + partial
    return acc


def ring_reduce_scatter_matmul(
    x_local: Array, w_local: Array, axis_name: str
) -> Array:
    """This device's (B/size, N) rows of Σ_s x_s @ w_s (reduce-scatter form).

    The down-projection of sequence-parallel TP: each hop reduces one row
    chunk while the next chunk's add is still in flight.
    """
    size = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % size) for i in range(size)]
    y = x_local @ w_local  # (B, N) partial term (summand of the full result)
    b = y.shape[0]
    assert b % size == 0, (b, size)
    chunk = b // size
    y_blocks = y.reshape(size, chunk, -1)

    # ring reduce-scatter: device d starts with its partial of chunk d-1;
    # each hop passes the running sum downstream and adds the local partial
    # of the chunk now in hand. After size-1 hops device d holds chunk d,
    # fully reduced. (Exactness vs psum+slice asserted in tests.)
    acc = jnp.take(y_blocks, (idx - 1) % size, axis=0, mode="wrap")
    for step in range(1, size):
        acc = jax.lax.ppermute(acc, axis_name, perm)
        take = (idx - 1 - step) % size
        acc = acc + jnp.take(y_blocks, take, axis=0, mode="wrap")
    return acc  # (chunk, N) — rows [idx·chunk : (idx+1)·chunk] of the result
