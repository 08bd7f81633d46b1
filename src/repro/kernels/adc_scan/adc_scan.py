"""Pallas TPU kernel: fused ADC scan over PQ codes + AUTO attribute penalty.

Asymmetric distance computation for product-quantized databases: the query's
(S, K) look-up table of partial squared distances is precomputed once (see
``repro.quant.pq.adc_lut``); the kernel then scores a (B, N) block without
ever touching f32 feature vectors — per candidate it reads S bytes of codes
instead of M·4 bytes of floats (~64× less HBM traffic at M=128, S=8).

TPU adaptation: the S table lookups per candidate are re-expressed as a
one-hot matmul so they land on the **MXU** — codes (bn, S) expand to a
one-hot (bn, S·K) tile and  sv2 = LUT_flat @ one_hotᵀ  computes all B×N
ADC sums in one (bb × S·K) @ (S·K × bn) pass (gathers are VPU-hostile on
TPU; one-hot contraction is the standard trick). The AUTO attribute
consistency penalty (1 + S_A/α)² is applied in the same VMEM tile pass,
exactly like ``fused_auto`` — so quantized routing keeps hybrid semantics.
As there, the query target is an [lo, hi] interval per attribute dimension
(two (bb, L) tiles; point targets are the lo = hi degenerate case) and the
per-dimension penalty is the interval gap max(lo − a, a − hi, 0).

Blocking: grid = (B/bb, N/bn). Defaults (bb, bn) = (8, 256) with S·K = 2048:
LUT tile 64 KiB + one-hot tile 2 MiB + codes/attr tiles ≲ 20 KiB ≪ VMEM,
and the contraction dim S·K is a multiple of the 128-lane MXU tile.

4-bit variant (``adc_scan4_scores``): codes arrive packed two-per-byte
(K=16, one nibble each); the kernel body unpacks them **in-register** and
contracts the same one-hot matmul against an S×16 LUT — the contraction dim
shrinks 16× vs the 8-bit path (S·16 lanes), and HBM code traffic halves.
The unpack never interleaves nibbles along lanes (Mosaic refuses that
reshape): each byte is broadcast over 32 one-hot columns, the first 16 test
its low nibble and the next 16 its high nibble, which is already the
subspace order 2i, 2i+1 of the LUT. Odd S pads one zero-LUT subspace so the
pad nibble contributes nothing. The one-hot tile is identical to what the
8-bit kernel builds from pre-unpacked codes, so the two paths are bit-exact
against each other (asserted in tests).

Both variants contract at ``Precision.HIGHEST``: the one-hot operand is
exact in bf16 but the LUT is not, and a default-precision f32 dot on the
TPU rounds it to bf16.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import split_targets

Array = jax.Array

DEFAULT_BLOCK_B = 8
DEFAULT_BLOCK_N = 256


def _kernel(lut_ref, codes_ref, qlo_ref, qhi_ref, xa_ref, mask_ref, o_ref, *,
            n_subspaces: int, n_centroids: int, alpha: float, mode: str,
            attr_dim: int, packed: bool = False):
    lut = lut_ref[...].astype(jnp.float32)  # (bb, S·K)
    codes = codes_ref[...]  # (bn, S) int32 — or (bn, S/2) packed nibbles
    bn = codes.shape[0]
    if packed:
        # byte i holds subspaces (2i, 2i+1): column c of its 32 one-hot
        # columns tests nibble c // 16 (shift 0 or 4) against centroid c % 16
        col = jax.lax.broadcasted_iota(
            jnp.int32, (bn, n_subspaces // 2, 2 * n_centroids), 2
        )
        nibble = (codes[:, :, None] >> ((col // n_centroids) * 4)) & 0xF
        onehot = nibble == col % n_centroids
    else:
        col = jax.lax.broadcasted_iota(
            jnp.int32, (bn, n_subspaces, n_centroids), 2
        )
        onehot = col == codes[:, :, None]
    onehot = onehot.astype(jnp.float32).reshape(bn, n_subspaces * n_centroids)
    sv2 = jax.lax.dot_general(
        lut, onehot, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # MXU: (bb, bn) ADC partial-distance sums
    sv2 = jnp.maximum(sv2, 0.0)
    if mode == "l2":
        o_ref[...] = sv2
        return
    qlo = qlo_ref[...].astype(jnp.float32)  # (bb, L)
    qhi = qhi_ref[...].astype(jnp.float32)  # (bb, L)
    xa = xa_ref[...].astype(jnp.float32)  # (bn, L)
    m = mask_ref[...].astype(jnp.float32)  # (bb, L)
    sa = jnp.zeros(sv2.shape, jnp.float32)
    for l in range(attr_dim):  # L is small & static — unrolled on VPU
        a = xa[:, l][None, :]
        gap = jnp.maximum(
            jnp.maximum(qlo[:, l][:, None] - a, a - qhi[:, l][:, None]), 0.0
        )
        sa += gap * m[:, l][:, None]
    pen = 1.0 + sa * (1.0 / alpha)
    o_ref[...] = sv2 * pen * pen


def _pad_to(x: Array, axis: int, mult: int) -> Array:
    size = x.shape[axis]
    target = ((size + mult - 1) // mult) * mult
    if target == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - size)
    return jnp.pad(x, pads)


@functools.partial(
    jax.jit,
    static_argnames=("alpha", "mode", "block_b", "block_n", "interpret"),
)
def adc_scan_scores(
    lut: Array,  # (B, S, K) f32 per-query ADC tables
    codes: Array,  # (N, S) int PQ codes (values < K)
    qa: Array,  # (B, L) int
    xa: Array,  # (N, L) int
    alpha: float = 1.0,
    mode: str = "auto",
    mask: Optional[Array] = None,
    block_b: int = DEFAULT_BLOCK_B,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = True,
) -> Array:
    """(B, N) squared fused ADC distances. ``qa`` is (B, L) point targets or
    (B, L, 2) [lo, hi] interval targets. See module docstring for blocking."""
    if mode not in ("auto", "l2"):
        raise ValueError(f"adc_scan supports modes ('auto', 'l2'), got {mode!r}")
    b, s_dim, k_dim = lut.shape
    n = codes.shape[0]
    l_dim = qa.shape[1]
    if mask is None:
        mask = jnp.ones((b, l_dim), jnp.int32)
    qlo, qhi = split_targets(qa)

    lut_p = _pad_to(lut.reshape(b, s_dim * k_dim), 0, block_b)
    codes_p = _pad_to(codes.astype(jnp.int32), 0, block_n)
    qlo_p = _pad_to(qlo, 0, block_b)
    qhi_p = _pad_to(qhi, 0, block_b)
    xa_p = _pad_to(xa, 0, block_n)
    mask_p = _pad_to(mask, 0, block_b)

    grid = (lut_p.shape[0] // block_b, codes_p.shape[0] // block_n)
    out = pl.pallas_call(
        functools.partial(
            _kernel, n_subspaces=s_dim, n_centroids=k_dim,
            alpha=float(alpha), mode=mode, attr_dim=l_dim,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, s_dim * k_dim), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, s_dim), lambda i, j: (j, 0)),
            pl.BlockSpec((block_b, l_dim), lambda i, j: (i, 0)),
            pl.BlockSpec((block_b, l_dim), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, l_dim), lambda i, j: (j, 0)),
            pl.BlockSpec((block_b, l_dim), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(
            (lut_p.shape[0], codes_p.shape[0]), jnp.float32
        ),
        interpret=interpret,
    )(lut_p, codes_p, qlo_p, qhi_p, xa_p, mask_p)
    return out[:b, :n]


@functools.partial(
    jax.jit,
    static_argnames=("alpha", "mode", "block_b", "block_n", "interpret"),
)
def adc_scan4_scores(
    lut: Array,  # (B, S, 16) f32 per-query ADC tables
    codes: Array,  # (N, ⌈S/2⌉) uint8 packed nibble codes
    qa: Array,  # (B, L) int
    xa: Array,  # (N, L) int
    alpha: float = 1.0,
    mode: str = "auto",
    mask: Optional[Array] = None,
    block_b: int = DEFAULT_BLOCK_B,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = True,
) -> Array:
    """4-bit packed variant of ``adc_scan_scores``: same fused output, codes
    arrive two-per-byte and unpack in-register inside the kernel. Odd S is
    handled by padding the LUT with one all-zero subspace (the pad nibble is
    always 0, so it contributes 0 to every ADC sum)."""
    if mode not in ("auto", "l2"):
        raise ValueError(f"adc_scan supports modes ('auto', 'l2'), got {mode!r}")
    b, s_dim, k_dim = lut.shape
    if k_dim != 16:
        raise ValueError(f"packed ADC requires K=16 LUTs, got K={k_dim}")
    n, s_packed = codes.shape
    s_eff = 2 * s_packed
    if s_dim not in (s_eff, s_eff - 1):
        raise ValueError(
            f"LUT has S={s_dim} subspaces but packed codes carry {s_eff}"
        )
    if s_dim < s_eff:  # odd S: zero-LUT pad subspace absorbs the pad nibble
        lut = jnp.pad(lut, ((0, 0), (0, s_eff - s_dim), (0, 0)))
    l_dim = qa.shape[1]
    if mask is None:
        mask = jnp.ones((b, l_dim), jnp.int32)
    qlo, qhi = split_targets(qa)

    lut_p = _pad_to(lut.reshape(b, s_eff * k_dim), 0, block_b)
    codes_p = _pad_to(codes.astype(jnp.int32), 0, block_n)
    qlo_p = _pad_to(qlo, 0, block_b)
    qhi_p = _pad_to(qhi, 0, block_b)
    xa_p = _pad_to(xa, 0, block_n)
    mask_p = _pad_to(mask, 0, block_b)

    grid = (lut_p.shape[0] // block_b, codes_p.shape[0] // block_n)
    out = pl.pallas_call(
        functools.partial(
            _kernel, n_subspaces=s_eff, n_centroids=k_dim,
            alpha=float(alpha), mode=mode, attr_dim=l_dim, packed=True,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, s_eff * k_dim), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, s_packed), lambda i, j: (j, 0)),
            pl.BlockSpec((block_b, l_dim), lambda i, j: (i, 0)),
            pl.BlockSpec((block_b, l_dim), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, l_dim), lambda i, j: (j, 0)),
            pl.BlockSpec((block_b, l_dim), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(
            (lut_p.shape[0], codes_p.shape[0]), jnp.float32
        ),
        interpret=interpret,
    )(lut_p, codes_p, qlo_p, qhi_p, xa_p, mask_p)
    return out[:b, :n]
