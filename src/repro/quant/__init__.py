"""Quantized search subsystem: compressed-code scanning + exact rerank.

STABLE's hot path is fused AUTO distance evaluation over full-precision f32
feature vectors; at serving scale the HBM read of those vectors is the
throughput ceiling. This package adds the standard production counter-move
(cf. HQANN, the FANNS survey's compressed-index taxonomy): scan *compressed*
codes to build an oversized candidate pool, then rerank a small top slice at
full precision — trading a bounded recall loss for a large cut in
full-precision distance evaluations and memory traffic.

Codecs
------
``sq8``  — int8 per-dimension affine scalar quantization (4× compression).
           Gathered codes are dequantized in-register and scored with the
           exact fused-AUTO math; the saving is pure memory traffic.
``pq``   — product quantization: S subspaces × 256 K-means centroids
           (trained in JAX, ``pq.pq_train``), a vector compresses to S bytes
           (e.g. 64× at M=128, S=8). Distances use asymmetric distance
           computation (ADC): a per-query (S, 256) LUT of partial squared
           distances, S lookups+adds per candidate — never touching f32.
``pq4``  — 4-bit PQ: K=16 centroids per subspace, two codes packed per byte
           (⌈S/2⌉ bytes/vector — half of pq at equal S); the packed
           ``adc_scan`` variant unpacks nibbles in-register and contracts
           an S×16 one-hot LUT on the MXU. Both ``adc_scan`` variants read
           the codes laid out once with rows on lanes
           (``QuantizedVectors.scan_codes``).
``opq-pq`` / ``opq-pq4`` — OPQ: a learned orthogonal rotation before the
           subspace split (``opq.opq_train``, alternating minimization with
           a Procrustes update) cuts codebook error on correlated
           dimensions. The rotation is frozen codec state, applied at
           encode time and inside the query-LUT build only — scan and
           traversal code paths never see it. Optional ``anisotropic``
           weighting biases training loss toward high-magnitude
           (score-dominant) rows.

Layers
------
* ``sq`` / ``pq``          — codec math (encode/decode/train/LUT).
* ``store.QuantizedVectors`` — codes + codec state + persistence; produces
  the flat operand tuple the jitted router consumes.
* ``kernels/adc_scan``     — Pallas kernel fusing the ADC scan with the AUTO
  attribute-consistency penalty (one-hot MXU contraction; see its docstring).
* ``core/routing``         — ``RoutingConfig(quant_mode=..., rerank_size=...)``
  drives graph traversal over codes and reranks the pool top slice with
  exact fused distances; ``SearchResult.n_dist_evals`` then counts *only*
  full-precision evaluations per query (``n_code_evals`` the compressed
  ones; ``total_dist_evals``/``total_code_evals`` aggregate).
* ``api/engine``           — the Engine planner derives ``quant_mode`` from
  the index's code store; its brute-force backend scans PQ codes through
  the fused ``adc_scan`` kernel for small/residual shards.

Typical use::

    from repro.api import Engine, QueryBatch, SearchParams
    from repro.quant import QuantConfig

    eng = Engine.build(features, attrs, quant_cfg=QuantConfig(mode="pq"))
    res = eng.search(QueryBatch.match(qv, qa), SearchParams(k=10))
    res.n_dist_evals                         # (B,) rerank evals only

"""
from repro.kernels.adc_scan.ops import adc_scan, adc_scan_topk
from repro.quant.opq import opq_reconstruct, opq_train, rotate
from repro.quant.pq import (
    PQCodebook, adc_gathered_sqdist, adc_lut, pack_nibbles, pq_decode,
    pq_encode, pq_train, unpack_nibbles,
)
from repro.quant.sq import SQParams, sq8_decode, sq8_encode, sq8_train
from repro.quant.store import (
    CODEC_VERSION, PQ_MODES, QUANT_MODES, QuantConfig, QuantizedVectors,
    codec_spec, has_rotation, is_packed_mode, is_pq_mode, pq_bits,
)

__all__ = [
    "CODEC_VERSION",
    "PQ_MODES",
    "QUANT_MODES",
    "QuantConfig",
    "QuantizedVectors",
    "PQCodebook",
    "SQParams",
    "adc_gathered_sqdist",
    "adc_lut",
    "adc_scan",
    "adc_scan_topk",
    "codec_spec",
    "has_rotation",
    "is_packed_mode",
    "is_pq_mode",
    "opq_reconstruct",
    "opq_train",
    "pack_nibbles",
    "pq_bits",
    "pq_decode",
    "pq_encode",
    "pq_train",
    "rotate",
    "sq8_decode",
    "sq8_encode",
    "sq8_train",
    "unpack_nibbles",
]
