"""Pallas TPU kernel: fused ADC scan over PQ codes + AUTO attribute penalty.

Asymmetric distance computation for product-quantized databases: the query's
(S, K) look-up table of partial squared distances is precomputed once (see
``repro.quant.pq.adc_lut``); the kernel then scores a (B, N) block without
ever touching f32 feature vectors — per candidate it reads S bytes of codes
instead of M·4 bytes of floats (~64× less HBM traffic at M=128, S=8).

TPU adaptation: the S table lookups per candidate are re-expressed as a
one-hot matmul so they land on the **MXU** (gathers are VPU-hostile on TPU;
one-hot contraction is the standard trick). The AUTO attribute consistency
penalty (1 + S_A/α)² is applied in the same VMEM tile pass, exactly like
``fused_auto`` — so quantized routing keeps hybrid semantics. As there, the
query target is an [lo, hi] interval per attribute dimension (two (bb, L)
tiles; point targets are the lo = hi degenerate case) and the per-dimension
penalty is the interval gap max(lo − a, a − hi, 0).

Layout: rows of the corpus lie on the 128 lanes. ``layout_codes`` lays
the codes out once as (W, N_pad) int32 words: the row's code bytes read as
little-endian words, so byte i sits in bits 8(i mod 4) … 8(i mod 4)+7 of
word ⌊i/4⌋. For 8-bit PQ a byte is one subspace (W = ⌈S/4⌉); for 4-bit PQ
byte i packs subspaces (2i, 2i+1) in its (low, high) nibble, the LUT's
order, so subspace 8w+j sits in bits 4j … 4j+3 of word w (W = ⌈S/8⌉).
``QuantizedVectors.scan_codes`` holds this array beside the codes; both
kernels take only it. Attributes, read only in ``auto`` mode (no serving
path scans in it), are laid out per call as (L, N_pad); ``l2`` mode does
not take them. N_pad (``padded_rows``) is N rounded up to the 128 lanes,
or to ROW_TILE rows past one tile, so every row tile the kernel picks
divides it.

One-hot: for each subspace s the kernel takes its code row (1, bn) — a
shift and mask of one word — broadcasts it over K sublanes and compares
it with a (K, 1) iota, a (K, bn) block exactly 0/1 in bf16. Blocks stack
along sublanes in subspace order into the transposed one-hot (S·K, bn):
no 3-D intermediate, no lane reshape.

Contraction: the f32 LUT is split exactly into three bf16 parts,
hi + mid + lo (``_split3``: each part is the next 8 significant bits, cut
by masking the bit pattern, so no compiler may fold the split away),
stacked as 3·bb LHS rows; one MXU contraction (bf16 operands, f32
accumulation) against the one-hot gives the three partial sums of every
query, added on the VPU (mid + lo, then hi). Every product is a LUT part
times 0 or 1, so it is exact, and no LUT value is rounded to bf16: the
sum equals the f32 ADC sum up to f32 accumulation rounding. S·K is
contracted in chunks of at most ONEHOT_ROWS one-hot rows (one chunk for
4-bit codes up to S=32; pairs of subspaces for K=256). Both variants share
the kernel, so the 4-bit kernel is bit-exact against the 8-bit one fed the
unpacked codes and the same K=16 LUT (asserted in tests).

Blocking: the whole batch is one block of bb = B rounded up to 16 rows (the
bf16 sublane tile), so the grid runs over row tiles only and each one-hot
tile is built once for every query of the batch. The row tile bn is the
largest up to ROW_TILE whose VMEM estimate (one-hot chunk, contraction
result, double-buffered inputs and output) stays within VMEM_BUDGET, below
the 16 MiB default scoped VMEM of a v5e core, and it divides N_pad. The
output is (B, N) exactly: writes of the last tile past N are dropped, so no
slice of the scores follows the call.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import split_targets

Array = jax.Array

LANE = 128
ROW_TILE = 8192  # largest row tile; corpora past it pad to a multiple of it
ONEHOT_ROWS = 512  # one-hot rows (of S·K) per MXU contraction
VMEM_BUDGET = 10 * 2**20  # row-tile estimate cap, under 16 MiB scoped VMEM
_SUBLANE_BF16 = 16


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def padded_rows(n: int) -> int:
    """Rows of the laid-out codes for an N-row corpus (see module doc)."""
    lanes = _round_up(max(n, 1), LANE)
    return lanes if lanes <= ROW_TILE else _round_up(n, ROW_TILE)


def _rows_on_lanes(x: Array) -> Array:
    """(N, C) → (C, N_pad) int32: rows on lanes, zero pad rows."""
    x = x.astype(jnp.int32)
    return jnp.pad(x, ((0, padded_rows(x.shape[0]) - x.shape[0]), (0, 0))).T


@jax.jit
def layout_codes(codes: Array) -> Array:
    """(N, C) code bytes — the (N, S) codes of 8-bit PQ or the (N, ⌈S/2⌉)
    packed nibbles of 4-bit PQ — → (⌈C/4⌉, N_pad) int32 words with the
    rows on lanes: word w holds the row's bytes 4w … 4w+3 in little-endian
    order. Pad bytes and pad rows are zero."""
    n, c = codes.shape
    w = -(-c // 4)
    b = jnp.pad(codes.astype(jnp.uint32) & 0xFF, ((0, 0), (0, 4 * w - c)))
    b = b.reshape(n, w, 4)
    words = b[..., 0] | b[..., 1] << 8 | b[..., 2] << 16 | b[..., 3] << 24
    return _rows_on_lanes(jax.lax.bitcast_convert_type(words, jnp.int32))


def _bf16_head(x: Array) -> Array:
    """f32 ``x`` cut to its leading 8 significant bits (a bf16 value), by
    masking the bit pattern: a mask, unlike a round trip through bf16, is
    not a conversion a compiler may drop."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _split3(lut: Array) -> Array:
    """(bb, C) f32 → (3·bb, C) bf16 rows hi, mid, lo with hi + mid + lo ==
    lut exactly: each part carries the next 8 significant bits, and each
    difference below is exact in f32."""
    hi = _bf16_head(lut)
    rest = lut - hi
    mid = _bf16_head(rest)
    lo = rest - mid  # at most 8 significant bits left
    return jnp.concatenate([hi, mid, lo], axis=0).astype(jnp.bfloat16)


def _contract(parts_ref, onehot, n_sub: int, k: int) -> Array:
    """(3·bb, bn) f32: the LUT parts against the transposed one-hot, in
    chunks of ≤ ONEHOT_ROWS one-hot rows. ``onehot(s)`` is subspace s's
    (K, bn) bf16 block."""
    per = max(1, ONEHOT_ROWS // k)
    sums = None
    for s0 in range(0, n_sub, per):
        s1 = min(n_sub, s0 + per)
        blocks = [onehot(s) for s in range(s0, s1)]
        oh = blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks, 0)
        part = jax.lax.dot_general(
            parts_ref[:, s0 * k:s1 * k], oh, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # MXU: bf16 × {0, 1} products, exact; f32 accumulation
        sums = part if sums is None else sums + part
    return sums


def _kernel(parts_ref, codes_ref, *refs, n_sub: int, k: int, bits: int,
            alpha: float, mode: str, attr_dim: int, n_out: int):
    o_ref = refs[-1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (k, 1), 0)
    per_word = 32 // bits
    words = {}

    def onehot(s: int) -> Array:  # subspace s: field s % per_word of a word
        w = s // per_word
        if w not in words:
            words[w] = codes_ref[w:w + 1, :]
        row = jax.lax.shift_right_logical(
            words[w], bits * (s % per_word)) & ((1 << bits) - 1)
        return jnp.where(row == iota, 1.0, 0.0).astype(jnp.bfloat16)

    sums = _contract(parts_ref, onehot, n_sub, k)
    bb = sums.shape[0] // 3
    sv2 = sums[:bb] + (sums[bb:2 * bb] + sums[2 * bb:])
    sv2 = jnp.maximum(sv2, 0.0)  # (bb, bn) ADC sums
    if mode == "auto":
        qlo_ref, qhi_ref, xa_ref, mask_ref = refs[:4]
        qlo = qlo_ref[...].astype(jnp.float32)  # (bb, L)
        qhi = qhi_ref[...].astype(jnp.float32)  # (bb, L)
        m = mask_ref[...].astype(jnp.float32)  # (bb, L)
        sa = jnp.zeros(sv2.shape, jnp.float32)
        for l in range(attr_dim):  # L is small & static — unrolled on VPU
            a = xa_ref[l:l + 1, :].astype(jnp.float32)  # (1, bn)
            gap = jnp.maximum(
                jnp.maximum(qlo[:, l:l + 1] - a, a - qhi[:, l:l + 1]), 0.0
            )
            sa += gap * m[:, l:l + 1]
        pen = 1.0 + sa * (1.0 / alpha)
        sv2 = sv2 * pen * pen
    o_ref[...] = sv2[:n_out]


def _row_tile(bb: int, b: int, n_sub: int, k: int, in_rows: int,
              n_pad: int) -> int:
    """Largest lane-multiple row tile, at most ROW_TILE rows, that divides
    N_pad and whose per-row VMEM estimate fits VMEM_BUDGET."""
    per_row = (
        2 * min(n_sub * k, max(k, ONEHOT_ROWS))  # bf16 one-hot chunk
        + 2 * 4 * 3 * bb  # the chunk's contraction result and the sums
        + 4 * bb  # the ADC sums
        + 2 * 4 * b  # output tile, double-buffered
        + 2 * 4 * in_rows  # code (and attribute) tiles, double-buffered
    )
    units = n_pad // LANE
    d = max(1, min(units, VMEM_BUDGET // (per_row * LANE), ROW_TILE // LANE))
    while units % d:
        d -= 1
    return d * LANE


def _scan(lut, codes_t, qa, xa, alpha, mode, mask, interpret, bits):
    """Shared wrapper: checks, LUT parts, the one pallas_call."""
    if mode not in ("auto", "l2"):
        raise ValueError(f"adc_scan supports modes ('auto', 'l2'), got {mode!r}")
    b, s_dim, k_dim = lut.shape
    n = xa.shape[0]
    want = (-(-s_dim * bits // 32), padded_rows(n))
    if codes_t.dtype != jnp.int32 or codes_t.shape != want:
        raise ValueError(
            f"codes {codes_t.shape} {codes_t.dtype} are not laid out for "
            f"S={s_dim}, N={n}: expected int32 {want} from layout_codes"
        )
    code_rows, n_pad = want
    bb = _round_up(b, _SUBLANE_BF16)
    lut2 = lut.reshape(b, s_dim * k_dim).astype(jnp.float32)
    parts = _split3(jnp.pad(lut2, ((0, bb - b), (0, 0))))
    operands = [parts, codes_t]
    in_rows = code_rows
    if mode == "auto":
        l_dim = qa.shape[1]
        if mask is None:
            mask = jnp.ones((b, l_dim), jnp.int32)
        qlo, qhi = split_targets(qa)
        pad_b = lambda x: jnp.pad(x.astype(jnp.int32), ((0, bb - b), (0, 0)))
        operands += [pad_b(qlo), pad_b(qhi), _rows_on_lanes(xa), pad_b(mask)]
        in_rows += l_dim
    else:
        l_dim = 0
    bn = _row_tile(bb, b, s_dim, k_dim, in_rows, n_pad)
    whole = lambda shape: pl.BlockSpec(shape, lambda j: (0, 0))
    tile = lambda rows: pl.BlockSpec((rows, bn), lambda j: (0, j))
    in_specs = [whole(parts.shape), tile(code_rows)]
    if mode == "auto":
        in_specs += [whole((bb, l_dim)), whole((bb, l_dim)), tile(l_dim),
                     whole((bb, l_dim))]
    kernel = functools.partial(
        _kernel, n_sub=s_dim, k=k_dim, bits=bits, alpha=float(alpha),
        mode=mode, attr_dim=l_dim, n_out=b,
    )
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(n, bn),),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((b, bn), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.float32),
        interpret=interpret,
        name="adc_scan4_scores" if bits == 4 else "adc_scan_scores",
    )(*operands)


@functools.partial(jax.jit, static_argnames=("alpha", "mode", "interpret"))
def adc_scan_scores(
    lut: Array,  # (B, S, K) f32 per-query ADC tables, K ≤ 256
    codes: Array,  # (⌈S/4⌉, N_pad) int32 from layout_codes of (N, S) codes
    qa: Array,  # (B, L) int
    xa: Array,  # (N, L) int — fixes N; only its shape is read in l2 mode
    alpha: float = 1.0,
    mode: str = "auto",
    mask: Optional[Array] = None,
    interpret: bool = True,
) -> Array:
    """(B, N) squared fused ADC distances. ``qa`` is (B, L) point targets or
    (B, L, 2) [lo, hi] interval targets. See module docstring for layout
    and blocking."""
    if lut.shape[2] > 256:
        raise ValueError(f"8-bit ADC takes K ≤ 256 LUTs, got K={lut.shape[2]}")
    return _scan(lut, codes, qa, xa, alpha, mode, mask, interpret, bits=8)


@functools.partial(jax.jit, static_argnames=("alpha", "mode", "interpret"))
def adc_scan4_scores(
    lut: Array,  # (B, S, 16) f32 per-query ADC tables
    codes: Array,  # (⌈S/8⌉, N_pad) int32 from layout_codes of packed codes
    qa: Array,  # (B, L) int
    xa: Array,  # (N, L) int — fixes N; only its shape is read in l2 mode
    alpha: float = 1.0,
    mode: str = "auto",
    mask: Optional[Array] = None,
    interpret: bool = True,
) -> Array:
    """4-bit packed variant of ``adc_scan_scores``: same fused output, from
    the nibble-packed codes laid out by ``layout_codes``. The kernel reads
    each subspace's nibble in-register and never builds a one-hot row for
    an odd S's pad nibble."""
    if lut.shape[2] != 16:
        raise ValueError(f"packed ADC requires K=16 LUTs, got K={lut.shape[2]}")
    return _scan(lut, codes, qa, xa, alpha, mode, mask, interpret, bits=4)
