"""Jit'd public wrapper for the fused AUTO scorer kernel.

Runs the Pallas kernel compiled on the accelerator and interpreted on the
CPU backend (``kernels.common.interpret_mode``).
Also exposes a top-k convenience used by the retrieval serving path.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.common import interpret_mode
from repro.kernels.fused_auto.fused_auto import fused_auto_scores
from repro.kernels.fused_auto.ref import fused_auto_ref

Array = jax.Array


def fused_auto(
    qv: Array,
    qa: Array,
    xv: Array,
    xa: Array,
    alpha: float = 1.0,
    mode: str = "auto",
    mask: Optional[Array] = None,
    block_b: int = 128,
    block_n: int = 256,
    block_m: int = 512,
) -> Array:
    """(B, N) squared fused AUTO distances (Pallas; interpreted on CPU).
    ``qa`` is (B, L) point targets or (B, L, 2) [lo, hi] interval targets."""
    return fused_auto_scores(
        qv, qa, xv, xa, alpha=alpha, mode=mode, mask=mask,
        block_b=block_b, block_n=block_n, block_m=block_m,
        interpret=interpret_mode(),
    )


def fused_auto_topk(
    qv: Array,
    qa: Array,
    xv: Array,
    xa: Array,
    k: int,
    alpha: float = 1.0,
    mode: str = "auto",
    mask: Optional[Array] = None,
) -> tuple[Array, Array]:
    """Exact hybrid top-k over a candidate set via the fused kernel."""
    scores = fused_auto(qv, qa, xv, xa, alpha=alpha, mode=mode, mask=mask)
    neg, idx = jax.lax.top_k(-scores, k)
    return -neg, idx


__all__ = ["fused_auto", "fused_auto_topk", "fused_auto_ref"]
