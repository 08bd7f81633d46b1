"""Jit'd wrapper for the FM interaction kernel."""
from __future__ import annotations

import jax

from repro.kernels.common import interpret_mode
from repro.kernels.fm_interaction.fm_interaction import fm_interaction_pallas
from repro.kernels.fm_interaction.ref import (
    fm_interaction_pairwise_ref,
    fm_interaction_ref,
)

Array = jax.Array


def fm_interaction(emb: Array) -> Array:
    """(B,) FM second-order term (compiled Pallas; interpreted on CPU)."""
    return fm_interaction_pallas(emb, interpret=interpret_mode())


__all__ = ["fm_interaction", "fm_interaction_ref", "fm_interaction_pairwise_ref"]
