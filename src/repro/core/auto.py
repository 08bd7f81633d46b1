"""AUTO metric: enhanced heterogeneous semantic perception (paper §III-B).

Implements, faithfully:
  Eq. 2  S_A(A_i, Â)   = Σ_l |a_l - â_l|                (Manhattan, integer-mapped)
  Eq. 3  S_V(V_i, V̂)   = sqrt(Σ_m (v_m - v̂_m)²)          (Euclidean)
  Eq. 4  U(D_i, Q)     = S_V · (1 + S_A / α)
  Eq. 5  α             = Norm(N / S̄_V) + Norm(S̄_A / L)
  Eq. 8  masked S_A    = Σ_l m_l · |a_l - â_l|            (subset / missing-value)

TPU adaptation (documented in DESIGN.md §2): hot paths rank by the *squared*
fused metric  U² = S_V² · (1 + S_A/α)²  which induces the identical ordering
(U ≥ 0, squaring is monotone) while avoiding sqrt on the VPU and letting the
S_V² term come out of an MXU matmul via ‖q-x‖² = ‖q‖² + ‖x‖² - 2 q·x.

Interval targets (§III-E generalization): every scorer accepts the query
attribute targets either as points ``(…, L)`` — the legacy Eq. 2 form — or
as per-dimension ``[lo, hi]`` intervals ``(…, L, 2)``, detected by the extra
trailing axis. The per-dimension penalty generalizes to the interval gap

    gap_l(a) = max(lo_l - a_l, a_l - hi_l, 0)

which is zero anywhere inside the interval and reduces *bit-exactly* to
|a_l - q_l| when lo = hi = q (max(q-a, a-q, 0) and |a-q| are the same f32
value), so the point path and the degenerate-interval path rank
identically. This is what lets value-set (ONE_OF → covering interval) and
range (BETWEEN) predicates ride the HELP graph instead of the O(N) brute
oracle.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

# ---------------------------------------------------------------------------
# Numerical mapping (paper Eq. 1)
# ---------------------------------------------------------------------------


def numerical_map(raw_attrs: np.ndarray) -> tuple[np.ndarray, list[np.ndarray]]:
    """Map raw (possibly categorical) attribute columns to position ids.

    Returns the int32 mapped matrix and the per-dimension value tables
    (``MAP(a_u) = u`` — position in the sorted distinct-value set).
    """
    raw_attrs = np.asarray(raw_attrs)
    n, l = raw_attrs.shape
    mapped = np.empty((n, l), dtype=np.int32)
    tables = []
    for j in range(l):
        values, inverse = np.unique(raw_attrs[:, j], return_inverse=True)
        mapped[:, j] = inverse.astype(np.int32)
        tables.append(values)
    return mapped, tables


def map_query_attrs(raw_query: np.ndarray, tables: list[np.ndarray]) -> np.ndarray:
    """Map query attribute values through the dataset's value tables."""
    raw_query = np.asarray(raw_query)
    out = np.empty_like(raw_query, dtype=np.int32)
    for j, table in enumerate(tables):
        idx = np.searchsorted(table, raw_query[..., j])
        idx = np.clip(idx, 0, len(table) - 1)
        out[..., j] = idx
    return out.astype(np.int32)


# ---------------------------------------------------------------------------
# Basic measurements (Eq. 2, Eq. 3, Eq. 8)
# ---------------------------------------------------------------------------


def is_interval_targets(targets: Array, attrs: Array) -> bool:
    """True iff ``targets`` carries the extra trailing [lo, hi] axis
    relative to the database attribute array it scores against.

    Point targets must match the database rank exactly (insert explicit
    axes on both operands to broadcast); an extra-rank operand whose
    trailing axis is not the two interval bounds is rejected up front
    rather than mis-sliced into nonsense lo/hi views.
    """
    if targets.ndim != attrs.ndim + 1:
        return False
    if targets.shape[-1] != 2:
        raise ValueError(
            "attribute targets one rank above the attrs must be [lo, hi] "
            f"intervals with a trailing axis of 2, got shape "
            f"{targets.shape} against attrs {attrs.shape}; point targets "
            "must match the attrs rank"
        )
    return True


def interval_bounds(targets: Array) -> tuple[Array, Array]:
    """Split ``(…, L, 2)`` interval targets into f32 (lo, hi) views."""
    return (
        targets[..., 0].astype(jnp.float32),
        targets[..., 1].astype(jnp.float32),
    )


def attribute_distance(a: Array, b: Array, mask: Optional[Array] = None) -> Array:
    """Manhattan attribute consistency S_A (Eq. 2); masked variant (Eq. 8).

    ``a`` holds the query targets: either point values broadcastable against
    ``b`` (trailing axis L) or ``[lo, hi]`` intervals with one extra trailing
    axis of size 2, in which case the per-dimension term is the interval gap
    ``max(lo - b, b - hi, 0)`` (≡ |b - q| when lo = hi = q). ``b`` are the
    integer-mapped database attribute vectors. ``mask`` (same trailing L)
    selects the active dimensions: 0 ⇒ wildcard / missing value.
    """
    bf = b.astype(jnp.float32)
    if is_interval_targets(a, b):
        lo, hi = interval_bounds(a)
        diff = jnp.maximum(jnp.maximum(lo - bf, bf - hi), 0.0)
    else:
        diff = jnp.abs(a.astype(jnp.float32) - bf)
    if mask is not None:
        diff = diff * mask.astype(jnp.float32)
    return diff.sum(axis=-1)


def attribute_violation(a: Array, b: Array) -> Array:
    """Bool per-dimension mismatch (the Hamming term's generalization):
    point targets ⇒ inequality; interval targets ⇒ outside [lo, hi]."""
    if is_interval_targets(a, b):
        lo, hi = interval_bounds(a)
        bf = b.astype(jnp.float32)
        return (bf < lo) | (bf > hi)
    return a != b


def feature_distance(x: Array, y: Array) -> Array:
    """Euclidean feature similarity S_V (Eq. 3)."""
    d = x.astype(jnp.float32) - y.astype(jnp.float32)
    return jnp.sqrt(jnp.maximum((d * d).sum(axis=-1), 0.0))


def feature_sqdist(x: Array, y: Array) -> Array:
    d = x.astype(jnp.float32) - y.astype(jnp.float32)
    return jnp.maximum((d * d).sum(axis=-1), 0.0)


# ---------------------------------------------------------------------------
# α calibration (Eq. 5)
# ---------------------------------------------------------------------------


def norm_to_unit(x: float) -> float:
    """Paper's Norm(·): scale by powers of 10 into (0.1, 1]."""
    if not np.isfinite(x) or x <= 0.0:
        return 0.1
    while x > 1.0:
        x /= 10.0
    while x <= 0.1:
        x *= 10.0
    return float(x)


@dataclasses.dataclass(frozen=True)
class DatasetStats:
    """Sampled statistics feeding Eq. 5 (and Table I style reporting)."""

    n_total: int
    feat_dim: int
    attr_dim: int
    mean_feature_dist: float
    mean_attribute_dist: float
    min_feature_dist: float
    max_feature_dist: float
    min_attribute_dist: float
    max_attribute_dist: float

    @property
    def alpha(self) -> float:
        return compute_alpha(
            self.n_total, self.mean_feature_dist, self.mean_attribute_dist, self.attr_dim
        )


def compute_alpha(n_total: int, mean_sv: float, mean_sa: float, attr_dim: int) -> float:
    """Eq. 5: α = Norm(N / S̄_V) + Norm(S̄_A / L)."""
    return norm_to_unit(n_total / max(mean_sv, 1e-12)) + norm_to_unit(
        mean_sa / max(attr_dim, 1)
    )


def sample_stats(
    features: np.ndarray,
    attrs: np.ndarray,
    n_samples: int = 1000,
    seed: int = 0,
) -> DatasetStats:
    """Sample ≤``n_samples`` nodes, compute pairwise distance statistics.

    Mirrors the paper's calibration pass (§III-B2, 1,000 sampled nodes). All
    pairwise distances among the sample are used (≈ n²/2 pairs), computed with
    the matmul decomposition so this stays cheap at 1,000 nodes.
    """
    features = np.asarray(features, dtype=np.float32)
    attrs = np.asarray(attrs)
    n = features.shape[0]
    rng = np.random.default_rng(seed)
    take = min(n_samples, n)
    idx = rng.choice(n, size=take, replace=False)
    f = features[idx]
    a = attrs[idx].astype(np.float32)

    sq = (f * f).sum(-1)
    d2 = sq[:, None] + sq[None, :] - 2.0 * (f @ f.T)
    np.maximum(d2, 0.0, out=d2)
    fd = np.sqrt(d2)
    ad = np.abs(a[:, None, :] - a[None, :, :]).sum(-1)
    iu = np.triu_indices(take, k=1)
    fd, ad = fd[iu], ad[iu]

    return DatasetStats(
        n_total=n,
        feat_dim=features.shape[1],
        attr_dim=attrs.shape[1],
        mean_feature_dist=float(fd.mean()),
        mean_attribute_dist=float(ad.mean()),
        min_feature_dist=float(fd.min()),
        max_feature_dist=float(fd.max()),
        min_attribute_dist=float(ad.min()),
        max_attribute_dist=float(ad.max()),
    )


# ---------------------------------------------------------------------------
# Fused metric (Eq. 4) — pointwise and blocked-brute-force forms
# ---------------------------------------------------------------------------

#: metric modes shared by index construction, routing and the baselines.
#:   auto      — paper Eq. 4 (multiplicative fusion)
#:   l2        — pure feature distance ("w/o AttributeDis"; post-filter stage)
#:   attr      — attribute distance only   ("w/o FeatureDis" ablation)
#:   additive  — S_V + S_A                  ("w/o AUTO" ablation)
#:   nhq       — S_V + w · Hamming(A, Â)    (NHQ-style static fusion baseline)
METRIC_MODES = ("auto", "l2", "attr", "additive", "nhq")


@dataclasses.dataclass(frozen=True)
class MetricConfig:
    mode: str = "auto"
    alpha: float = 1.0
    nhq_weight: float = 1.0

    def __post_init__(self):
        if self.mode not in METRIC_MODES:
            raise ValueError(f"unknown metric mode {self.mode!r}")


def auto_distance(
    qv: Array,
    qa: Array,
    xv: Array,
    xa: Array,
    alpha: float,
    mask: Optional[Array] = None,
) -> Array:
    """Paper-exact U(D, Q) (Eq. 4), broadcasting over leading dims.
    ``qa`` may be point targets or ``[lo, hi]`` interval targets."""
    sv = feature_distance(qv, xv)
    sa = attribute_distance(qa, xa, mask)
    return sv * (1.0 + sa / alpha)


def fused_sqdist_from_sv2(
    sv2: Array,
    qa: Array,
    xa: Array,
    cfg: MetricConfig,
    mask: Optional[Array] = None,
) -> Array:
    """Apply the mode's attribute fusion to a precomputed squared feature
    term. Shared by the exact path (sv2 from f32 vectors) and the quantized
    path (sv2 from ADC/SQ8 codes — attributes stay full-precision).
    ``qa`` may be point targets or ``[lo, hi]`` interval targets."""
    if cfg.mode == "l2":
        return sv2
    sa = attribute_distance(qa, xa, mask)
    if cfg.mode == "attr":
        return sa * sa + 1e-6 * sv2  # feature term only tie-breaks
    if cfg.mode == "auto":
        pen = 1.0 + sa / cfg.alpha
        return sv2 * pen * pen
    if cfg.mode == "additive":
        u = jnp.sqrt(sv2) + sa
        return u * u
    # nhq: static-weight fusion over Hamming distance (interval form:
    # a dimension counts iff the value falls outside [lo, hi])
    ham = attribute_violation(qa, xa)
    if mask is not None:
        ham = jnp.logical_and(ham, mask.astype(bool))
    ham = ham.astype(jnp.float32).sum(axis=-1)
    u = jnp.sqrt(sv2) + cfg.nhq_weight * ham
    return u * u


def fused_sqdist(
    qv: Array,
    qa: Array,
    xv: Array,
    xa: Array,
    cfg: MetricConfig,
    mask: Optional[Array] = None,
) -> Array:
    """Squared fused metric for ranking (ordering ≡ the mode's distance).

    Pointwise/broadcast form used by routing over gathered candidates.
    ``qa`` may be point targets (broadcastable against ``xa``) or interval
    targets with an extra trailing [lo, hi] axis.
    ``l2``/``additive``/``nhq`` square their respective distances so every
    mode ranks identically to its un-squared definition.
    """
    return fused_sqdist_from_sv2(feature_sqdist(qv, xv), qa, xa, cfg, mask)


def _penalty(sa: Array, cfg: MetricConfig) -> Array:
    """Multiplicative AUTO penalty (1 + S_A/α)² from a precomputed S_A —
    the S_A may come from point |a-q| terms or interval gaps alike."""
    if cfg.mode == "auto":
        p = 1.0 + sa / cfg.alpha
        return p * p
    raise ValueError(cfg.mode)


@partial(jax.jit, static_argnames=("cfg", "chunk"))
def brute_fused_sqdist(
    qv: Array,
    qa: Array,
    db_v: Array,
    db_a: Array,
    cfg: MetricConfig,
    mask: Optional[Array] = None,
    chunk: int = 16384,
) -> Array:
    """(B, N) squared fused distances, MXU decomposition, chunked over N.

    ``qa`` is (B, L) point targets or (B, L, 2) interval targets. This is
    the pure-jnp oracle twin of ``kernels/fused_auto`` (same math, same
    blocking philosophy) used for ground truth, reranking and the
    ``retrieval_cand`` recsys path on CPU.
    """
    if qv.shape[0] == 1:
        # XLA's CPU backend runs a one-row product as a matrix-vector kernel
        # that rounds differently from the matrix-matrix kernel of a batch:
        # a duplicate row keeps a query's scores bit-identical whether it is
        # scored alone or inside a padded serving bucket
        two = lambda a: None if a is None else jnp.concatenate([a, a])
        return brute_fused_sqdist(
            two(qv), two(qa), db_v, db_a, cfg, two(mask), chunk
        )[:1]
    qv = qv.astype(jnp.float32)
    db_v = db_v.astype(jnp.float32)
    qsq = (qv * qv).sum(-1)[:, None]  # (B, 1)
    n = db_v.shape[0]
    n_chunks = max(1, (n + chunk - 1) // chunk)
    # (B, 1, L[, 2]) query targets against (1, N', L) database rows
    qae = qa[:, None]
    me = mask[:, None, :] if mask is not None else None

    def score_block(xv, xa):
        xsq = (xv * xv).sum(-1)[None, :]
        # HIGHEST: a default-precision f32 dot is one bf16 pass on the TPU,
        # and the ‖q‖² + ‖x‖² − 2q·x cancellation then reorders near
        # neighbours at sift magnitudes (this scan is the exact oracle)
        qx = jnp.matmul(qv, xv.T, precision=jax.lax.Precision.HIGHEST)
        sv2 = jnp.maximum(qsq + xsq - 2.0 * qx, 0.0)
        return fused_sqdist_from_sv2(sv2, qae, xa[None, :, :], cfg, me)

    if n_chunks == 1:
        return score_block(db_v, db_a)

    pad = n_chunks * chunk - n
    db_vp = jnp.pad(db_v, ((0, pad), (0, 0)))
    db_ap = jnp.pad(db_a, ((0, pad), (0, 0)))
    db_vp = db_vp.reshape(n_chunks, chunk, -1)
    db_ap = db_ap.reshape(n_chunks, chunk, -1)

    def body(_, blocks):
        xv, xa = blocks
        return None, score_block(xv, xa)

    _, scores = jax.lax.scan(body, None, (db_vp, db_ap))
    scores = jnp.moveaxis(scores, 0, 1).reshape(qv.shape[0], n_chunks * chunk)
    return scores[:, :n]


def brute_topk(
    qv: Array,
    qa: Array,
    db_v: Array,
    db_a: Array,
    k: int,
    cfg: MetricConfig,
    mask: Optional[Array] = None,
) -> tuple[Array, Array]:
    """Exact top-k under the fused metric: (sq-dists, ids), ascending."""
    scores = brute_fused_sqdist(qv, qa, db_v, db_a, cfg, mask)
    neg, idx = jax.lax.top_k(-scores, k)
    return -neg, idx
