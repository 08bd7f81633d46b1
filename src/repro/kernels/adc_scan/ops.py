"""Jit'd public wrapper for the fused ADC scan kernel.

Runs the Pallas kernel compiled on the accelerator and interpreted on the
CPU backend (``kernels.common.interpret_mode``).
``packed=True`` routes to the 4-bit variant (codes two-per-byte, S×16 LUT).
Also exposes a top-k convenience used by the quantized serving path.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.adc_scan.adc_scan import adc_scan4_scores, adc_scan_scores
from repro.kernels.adc_scan.ref import adc_scan4_ref, adc_scan_ref
from repro.kernels.common import interpret_mode

Array = jax.Array


def adc_scan(
    lut: Array,
    codes: Array,
    qa: Array,
    xa: Array,
    alpha: float = 1.0,
    mode: str = "auto",
    mask: Optional[Array] = None,
    packed: bool = False,
) -> Array:
    """(B, N) squared fused ADC distances (Pallas; interpreted on CPU).
    ``qa`` is (B, L) point targets or (B, L, 2) [lo, hi] interval targets.
    ``codes`` are laid out by ``layout_codes``
    (``QuantizedVectors.scan_codes``); ``packed`` selects the 4-bit
    variant."""
    fn = adc_scan4_scores if packed else adc_scan_scores
    return fn(
        lut, codes, qa, xa, alpha=alpha, mode=mode, mask=mask,
        interpret=interpret_mode(),
    )


def adc_scan_topk(
    lut: Array,
    codes: Array,
    qa: Array,
    xa: Array,
    k: int,
    alpha: float = 1.0,
    mode: str = "auto",
    mask: Optional[Array] = None,
    packed: bool = False,
) -> tuple[Array, Array]:
    """Approximate hybrid top-k over PQ codes via the fused ADC kernel."""
    scores = adc_scan(
        lut, codes, qa, xa, alpha=alpha, mode=mode, mask=mask, packed=packed
    )
    neg, idx = jax.lax.top_k(-scores, k)
    return -neg, idx


__all__ = ["adc_scan", "adc_scan_topk", "adc_scan_ref", "adc_scan4_ref"]
