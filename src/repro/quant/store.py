"""Quantized vector store: codec selection, training, persistence.

``QuantizedVectors`` owns whatever a codec needs at search time (codes +
dequantization parameters or codebooks) and produces the flat array operand
tuple the jitted router consumes (`routing_operand`). Codec choice is a
config string so the index/serving layers stay codec-agnostic.

Codec family:

* ``sq8`` — per-dimension scalar quantization, M bytes/vector.
* ``pq`` — product quantization, K=256, S bytes/vector.
* ``pq4`` — 4-bit PQ, K=16, two codes per byte → ⌈S/2⌉ bytes/vector.
* ``opq-pq`` / ``opq-pq4`` — the same with a learned orthogonal rotation
  (OPQ) before the subspace split. The rotation is codec state exactly like
  the codebooks: frozen after build, applied at encode time and inside the
  query-LUT build (``lut``), invisible to traversal/scan code paths.

Persistence is versioned: every save writes a ``codec`` block
(``{"version", "bits", "rotation"}``) into the quant meta. Readers that
predate a codec (e.g. a pq4 store opened by a pre-4-bit build) fail loudly
on the unknown mode string rather than misreading packed codes, and this
reader refuses ``codec.version`` values newer than it understands.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.adc_scan.adc_scan import layout_codes
from repro.quant.opq import opq_train, rotate
from repro.quant.pq import (
    PQCodebook,
    adc_lut,
    pack_nibbles,
    pq_encode,
    pq_train,
)
from repro.quant.sq import SQParams, sq8_encode

Array = jax.Array

#: codec modes shared by RoutingConfig.quant_mode and the launch flags.
QUANT_MODES = ("none", "sq8", "pq", "pq4", "opq-pq", "opq-pq4")

#: every mode that scores through ADC tables over PQ codes.
PQ_MODES = ("pq", "pq4", "opq-pq", "opq-pq4")

#: newest quant meta ``codec.version`` this reader understands.
#: v1 = sq8 / unpacked 8-bit pq; v2 adds packed 4-bit codes + OPQ rotation.
CODEC_VERSION = 2


def is_pq_mode(mode: str) -> bool:
    """True for every PQ-family codec (plain, packed, rotated)."""
    return mode in PQ_MODES


def pq_bits(mode: str) -> int:
    """Code width in bits for a PQ-family mode (8 or 4)."""
    return 4 if mode.endswith("4") else 8


def is_packed_mode(mode: str) -> bool:
    """True when codes are stored two-per-byte (4-bit family)."""
    return is_pq_mode(mode) and pq_bits(mode) == 4


def has_rotation(mode: str) -> bool:
    """True when the codec carries a learned OPQ rotation."""
    return mode.startswith("opq")


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    mode: str = "none"
    pq_subspaces: int = 8
    pq_centroids: int = 256
    pq_train_iters: int = 15
    pq_train_samples: int = 16384
    seed: int = 0
    opq_iters: int = 6  # OPQ alternating-minimization rounds (opq-* modes)
    anisotropic: float = 0.0  # magnitude-weighted loss toward score direction

    def __post_init__(self):
        if self.mode not in QUANT_MODES:
            raise ValueError(f"unknown quant mode {self.mode!r} (have {QUANT_MODES})")

    @property
    def effective_centroids(self) -> int:
        """K for the PQ codebook: 4-bit modes force K=16 (one nibble)."""
        if is_pq_mode(self.mode) and pq_bits(self.mode) == 4:
            return 16
        return self.pq_centroids


@dataclasses.dataclass
class QuantizedVectors:
    """Codes + codec state for one database; ``None`` stands for mode='none'.

    PQ modes also hold ``scan_codes``, the codes laid out for the
    ``adc_scan`` kernels (``layout_codes``). It is derived from ``codes``
    whenever an instance is made (build, load, a partition's slice,
    ``dataclasses.replace``), so it can never lag them, and it is not
    saved."""

    cfg: QuantConfig
    codes: Array  # sq8: (N, M) int8 · pq: (N, S) u8 · pq4: (N, ⌈S/2⌉) u8 packed
    sq_params: Optional[SQParams] = None
    codebook: Optional[PQCodebook] = None
    rotation: Optional[Array] = None  # (Mp, Mp) orthogonal, opq-* only
    scan_codes: Optional[Array] = dataclasses.field(
        init=False, default=None, repr=False, compare=False
    )  # PQ: (⌈S/4⌉ or ⌈S/8⌉ packed, N_pad) int32 words, rows on lanes

    def __post_init__(self):
        if is_pq_mode(self.cfg.mode):
            self.scan_codes = layout_codes(self.codes)

    @classmethod
    def build(cls, features, cfg: QuantConfig) -> Optional["QuantizedVectors"]:
        """Train the configured codec over the database; None for mode='none'."""
        if cfg.mode == "none":
            return None
        features = jnp.asarray(features, jnp.float32)
        if cfg.mode == "sq8":
            codes, params = sq8_encode(features)
            return cls(cfg=cfg, codes=codes, sq_params=params)
        rotation = None
        if has_rotation(cfg.mode):
            rotation, codebook = opq_train(
                features,
                n_subspaces=cfg.pq_subspaces,
                n_centroids=cfg.effective_centroids,
                n_iters=cfg.pq_train_iters,
                opq_iters=cfg.opq_iters,
                n_samples=cfg.pq_train_samples,
                seed=cfg.seed,
                anisotropic=cfg.anisotropic,
            )
            enc_in = rotate(features, rotation)
        else:
            codebook = pq_train(
                features,
                n_subspaces=cfg.pq_subspaces,
                n_centroids=cfg.effective_centroids,
                n_iters=cfg.pq_train_iters,
                n_samples=cfg.pq_train_samples,
                seed=cfg.seed,
            )
            enc_in = features
        codes = pq_encode(enc_in, codebook)
        if is_packed_mode(cfg.mode):
            codes = pack_nibbles(codes)
        return cls(cfg=cfg, codes=codes, codebook=codebook, rotation=rotation)

    # -- codec-aware views ---------------------------------------------------

    @property
    def packed(self) -> bool:
        return is_packed_mode(self.cfg.mode)

    def lut(self, qv: Array) -> Array:
        """Per-query ADC tables (B, S, K) — the OPQ rotation is applied here,
        so every downstream consumer (kernel, gather path) stays
        rotation-oblivious."""
        if self.rotation is not None:
            qv = rotate(qv, self.rotation)
        return adc_lut(qv, self.codebook)

    def encode_rows(self, features: Array) -> Array:
        """Encode new rows with the *frozen* codec state (params/rotation/
        codebooks from build time) — the mutable merge path; result matches
        ``self.codes`` layout and dtype."""
        features = jnp.asarray(features, jnp.float32)
        if self.cfg.mode == "sq8":
            rows, _ = sq8_encode(features, self.sq_params)
            return rows
        if self.rotation is not None:
            features = rotate(features, self.rotation)
        rows = pq_encode(features, self.codebook)
        if self.packed:
            rows = pack_nibbles(rows)
        return rows.astype(self.codes.dtype)

    def routing_operand(self, qv: Array) -> tuple[Array, ...]:
        """Flat array tuple for ``routing``'s jitted search (query-dependent
        for PQ: the per-query ADC tables are computed here, outside the jit
        cache key)."""
        if self.cfg.mode == "sq8":
            return (self.codes, self.sq_params.scale, self.sq_params.zero)
        return (self.codes, self.lut(qv))

    @property
    def code_bytes(self) -> int:
        return int(self.codes.size * self.codes.dtype.itemsize)

    @property
    def code_bytes_per_row(self) -> int:
        """Device bytes one encoded row occupies (sq8: M · pq: S ·
        pq4: ⌈S/2⌉) — the cold-tier cost the hot/cold memory accounting
        in ``repro.cache`` and the cache benchmark compare against the
        4·M bytes of a full-precision hot row."""
        return int(self.codes.shape[1] * self.codes.dtype.itemsize)

    # -- persistence (piggybacks on StableIndex.save/load) -------------------

    def save(self, path: str) -> dict:
        """Write code/codebook arrays under ``path``; returns meta json dict."""
        np.save(os.path.join(path, "quant_codes.npy"), np.asarray(self.codes))
        if self.sq_params is not None:
            np.save(os.path.join(path, "quant_sq_scale.npy"),
                    np.asarray(self.sq_params.scale))
            np.save(os.path.join(path, "quant_sq_zero.npy"),
                    np.asarray(self.sq_params.zero))
        if self.codebook is not None:
            np.save(os.path.join(path, "quant_centroids.npy"),
                    np.asarray(self.codebook.centroids))
        if self.rotation is not None:
            np.save(os.path.join(path, "quant_rotation.npy"),
                    np.asarray(self.rotation))
        return {"cfg": dataclasses.asdict(self.cfg),
                "dim": self.codebook.dim if self.codebook else None,
                "codec": codec_spec(self.cfg)}

    @classmethod
    def load(cls, path: str, meta: dict, mmap: bool = False) -> "QuantizedVectors":
        cfg = QuantConfig(**meta["cfg"])
        check_codec_spec(meta.get("codec"), cfg)
        codes = jnp.asarray(np.load(
            os.path.join(path, "quant_codes.npy"),
            mmap_mode="r" if mmap else None,
        ))
        sq_params = None
        codebook = None
        rotation = None
        if cfg.mode == "sq8":
            sq_params = SQParams(
                scale=jnp.asarray(np.load(os.path.join(path, "quant_sq_scale.npy"))),
                zero=jnp.asarray(np.load(os.path.join(path, "quant_sq_zero.npy"))),
            )
        else:
            codebook = PQCodebook(
                centroids=jnp.asarray(
                    np.load(os.path.join(path, "quant_centroids.npy"))
                ),
                dim=int(meta["dim"]),
            )
            if has_rotation(cfg.mode):
                rotation = jnp.asarray(
                    np.load(os.path.join(path, "quant_rotation.npy"))
                )
        return cls(cfg=cfg, codes=codes, sq_params=sq_params,
                   codebook=codebook, rotation=rotation)


# ---------------------------------------------------------------------------
# versioned codec spec — one meta block shared by every save format
# ---------------------------------------------------------------------------


def codec_spec(cfg: QuantConfig) -> dict:
    """The versioned codec descriptor recorded next to saved codec state."""
    bits = pq_bits(cfg.mode) if is_pq_mode(cfg.mode) else 8
    v2 = is_packed_mode(cfg.mode) or has_rotation(cfg.mode)
    return {
        "version": CODEC_VERSION if v2 else 1,
        "bits": bits,
        "rotation": has_rotation(cfg.mode),
    }


def check_codec_spec(codec: Optional[dict], cfg: QuantConfig) -> None:
    """Reject stores written by a newer codec than this reader understands,
    and stores whose codec block disagrees with their config (corruption)."""
    if codec is None:  # pre-versioning store: plain sq8/pq only
        if is_packed_mode(cfg.mode) or has_rotation(cfg.mode):
            raise ValueError(
                f"quant store in mode {cfg.mode!r} has no codec spec block — "
                "written by an incompatible build; re-save the index"
            )
        return
    version = int(codec.get("version", 1))
    if version > CODEC_VERSION:
        raise ValueError(
            f"quant store codec version {version} is newer than this reader "
            f"(supports ≤ {CODEC_VERSION}); upgrade before loading"
        )
    expect = codec_spec(cfg)
    if (int(codec.get("bits", 8)) != expect["bits"]
            or bool(codec.get("rotation", False)) != expect["rotation"]):
        raise ValueError(
            f"quant store codec block {codec!r} does not match configured "
            f"mode {cfg.mode!r} (expected {expect!r}) — store is corrupt or "
            "was rewritten by a mismatched build"
        )
