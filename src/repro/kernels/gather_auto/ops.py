"""Jit'd wrapper for the gathered-candidate fused AUTO scorer."""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels.common import interpret_mode
from repro.kernels.gather_auto.gather_auto import gather_auto_scores
from repro.kernels.gather_auto.ref import gather_auto_ref

Array = jax.Array


def gather_auto(
    qv: Array,
    qa: Array,
    cv: Array,
    ca: Array,
    alpha: float = 1.0,
    mode: str = "auto",
    mask: Optional[Array] = None,
) -> Array:
    """(B, C) squared fused distances over pre-gathered candidates. ``qa``
    is (B, L) point targets or (B, L, 2) [lo, hi] interval targets."""
    return gather_auto_scores(
        qv, qa, cv, ca, alpha=alpha, mode=mode, mask=mask,
        interpret=interpret_mode(),
    )


__all__ = ["gather_auto", "gather_auto_ref"]
