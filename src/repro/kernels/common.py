"""Shared helpers for the kernel wrappers.

One canonical parse of the attribute-target operand: every scorer family
accepts (B, L) point targets or (B, L, 2) [lo, hi] interval targets and
lowers them to the two (B, L) bound tiles its kernel consumes — a single
definition so the families can never disagree on the contract.

``interpret_mode`` is the one place that decides how a Pallas kernel runs:
interpreted on the CPU backend (tests), compiled everywhere else — a
kernel the accelerator's compiler refuses fails loudly instead of falling
back to the interpreter.
"""
from __future__ import annotations

import jax

Array = jax.Array


def interpret_mode() -> bool:
    """True only on the CPU backend, where Pallas TPU kernels cannot
    compile and run through the interpreter instead."""
    return jax.default_backend() == "cpu"


def split_targets(qa: Array) -> tuple[Array, Array]:
    """Normalize (B, L) point / (B, L, 2) interval targets to (qlo, qhi).

    Point targets duplicate into a degenerate lo = hi pair — the kernels'
    interval-gap penalty max(lo − a, a − hi, 0) is then bit-identical to
    the legacy |a − q| Manhattan term.
    """
    if qa.ndim == 3:
        if qa.shape[-1] != 2:
            raise ValueError(
                f"interval targets must be (B, L, 2), got {qa.shape}"
            )
        return qa[..., 0], qa[..., 1]
    return qa, qa
