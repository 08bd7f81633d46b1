"""Dynamic Heterogeneity Routing (paper §III-D, Alg. 3), batched for TPU.

Coarse phase: a compact pioneer set P (the first P entries of the result pool
R — the paper maintains P ⊆ R with the same ordering, so on fixed-width sorted
pools P *is* R[:P]) expands only the first ⌈Γ/2⌉ neighbors of each unchecked
pioneer, until no iteration improves P. Fine phase: greedy refinement expands
the full neighbor list of every unchecked pool entry until the pool is fully
checked.

TPU adaptation (DESIGN.md §2): a whole query batch advances in lock-step
`lax.while_loop` iterations; *all* currently-unchecked pioneers of a query are
expanded in one iteration (bulk) instead of one at a time; insertion sort is
replaced by a dedup-merge + `top_k`; an optional (B, N) visited map suppresses
re-scoring. Distance evaluations are counted exactly so efficiency comparisons
against baselines are architecture-neutral.

Quantized two-stage mode (``RoutingConfig.quant_mode`` ∈ {sq8, pq, pq4,
opq-pq, opq-pq4}): the traversal scores candidates from compressed codes
only — SQ8 codes decode in-register, PQ-family codes go through the
per-query ADC tables (4-bit codes unpack nibble-wise after the gather; the
OPQ rotation lives inside the LUT and the encode, never here) — filling the
(oversized) pool without touching f32 vectors; the final ``rerank_size``
pool entries are then re-scored with exact fused distances before emitting
top-k. ``n_dist_evals`` counts *only* full-precision evaluations (the rerank);
compressed-code evaluations are reported separately as ``n_code_evals``.

Interval targets: ``qa`` is accepted either as (B, L) point targets or as
(B, L, 2) per-dimension [lo, hi] intervals (see ``core.auto``); the AUTO
penalty, the quantized rerank and the ``enforce_equality`` output filter
(which becomes interval *containment*) all honor both forms, so value-set
and range predicates traverse the HELP graph exactly like equality queries.

Stage layout: the search is composed from four reusable pieces —
``init_state`` (seed pool), ``coarse_stage``, ``refine_stage`` (both thin
wrappers over ``_expand``) and ``emit_topk`` (pool head or quantized exact
rerank + optional hard filter). ``_search_jit`` is the jitted single-host
composition; ``distributed/search.py`` composes the same stages inside its
``shard_map`` body (``traverse_pool`` + its own cross-shard rerank built on
``score_exact``/``enforce_filter``), so rerank semantics cannot drift
between the single-host and sharded paths.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import auto as auto_mod
from repro.core import graph_ops as gops
from repro.core.auto import MetricConfig
from repro.core.graph_ops import INF, INVALID
from repro.quant import pq as pq_mod
from repro.quant import sq as sq_mod
from repro.quant.store import QUANT_MODES, is_packed_mode

Array = jax.Array

#: Incremented once per *trace* of a routing search body (single-host or
#: per-shard). jit caching makes repeated same-signature calls trace-free;
#: tests assert plan-cache hits add nothing here. Python-side effect — only
#: runs while jax is tracing, never per execution.
_TRACE_COUNT = [0]


def trace_count() -> int:
    """Total routing-search traces so far in this process."""
    return _TRACE_COUNT[0]


@dataclasses.dataclass(frozen=True)
class RoutingConfig:
    k: int = 10  # K: results returned
    pool_size: int = 64  # |R| ≥ K (paper sweeps K=10..500 as the pool)
    pioneer_size: int = 8  # P (paper default: pool/2 … we default smaller)
    coarse_max_iters: int = 64
    refine_max_iters: int = 256
    use_visited: bool = True  # (B, N) scored-map; disable for huge shards
    enforce_equality: bool = False  # final hard filter (off: paper behavior)
    quant_mode: str = "none"  # none | sq8 | pq — traversal scoring codec
    rerank_size: int = 0  # pool entries re-scored exactly (0 → pool_size)
    coarse_fixed: bool = False  # run coarse for exactly coarse_max_iters
    # (no dynamic pioneer-set exit) — the "w/o Dynamic" ablation

    def __post_init__(self):
        if self.k > self.pool_size:
            raise ValueError("k must be ≤ pool_size")
        if self.pioneer_size > self.pool_size:
            raise ValueError("pioneer_size must be ≤ pool_size")
        if self.quant_mode not in QUANT_MODES:
            raise ValueError(f"unknown quant_mode {self.quant_mode!r}")
        if self.rerank_size:
            if not (self.k <= self.rerank_size <= self.pool_size):
                raise ValueError("need k ≤ rerank_size ≤ pool_size")

    @property
    def effective_rerank(self) -> int:
        return self.rerank_size or self.pool_size


class SearchResult(NamedTuple):
    ids: Array  # (B, K) node ids (INVALID-padded)
    dists: Array  # (B, K) fused distances U (paper Eq. 4 scale, sqrt applied)
    sqdists: Array  # (B, K) squared fused metric (ranking scale)
    n_dist_evals: Array  # (B,) full-precision distance evaluations per query
    n_hops: Array  # () total expansion iterations executed
    n_code_evals: Array | int = 0  # (B,) compressed-code evaluations (quant)

    # Eval counters are per-query so serving can report per-request cost;
    # the aggregate properties below are the host-side reporting conveniences.
    # They reduce with numpy: counters already on host never round-trip to
    # the device, and device counters pay one transfer (not a compile).

    @property
    def total_dist_evals(self) -> int:
        return int(np.sum(np.asarray(self.n_dist_evals)))

    @property
    def total_code_evals(self) -> int:
        return int(np.sum(np.asarray(self.n_code_evals)))

    @property
    def mean_dist_evals(self) -> float:
        return self.total_dist_evals / max(self.ids.shape[0], 1)

    @property
    def mean_code_evals(self) -> float:
        return self.total_code_evals / max(self.ids.shape[0], 1)


def _score_candidates(
    db_v: Array,
    db_a: Array,
    cand: Array,  # (B, C) node ids (INVALID allowed)
    qv: Array,
    qa: Array,
    metric_cfg: MetricConfig,
    mask: Optional[Array],
    quant: tuple,
    quant_mode: str,
) -> Array:
    """(B, C) squared fused distances for gathered candidates.

    ``qa`` is (B, L) point targets or (B, L, 2) interval targets.
    quant_mode='none' reads f32 vectors; 'sq8' dequantizes gathered int8
    codes in-register; 'pq' sums per-query ADC table entries. Attributes are
    never quantized — the AUTO penalty is exact in every mode.
    """
    ca = gops.gather_rows(db_a, cand)
    m = mask[:, None, :] if mask is not None else None
    qae = qa[:, None]  # (B, 1, L[, 2]) against (B, C, L) candidates
    if quant_mode == "none":
        cv = gops.gather_rows(db_v, cand)
        return auto_mod.fused_sqdist(qv[:, None, :], qae, cv, ca, metric_cfg, m)
    if quant_mode == "sq8":
        codes, scale, zero = quant
        cv = sq_mod.sq8_decode(
            gops.gather_rows(codes, cand), sq_mod.SQParams(scale, zero)
        )
        return auto_mod.fused_sqdist(qv[:, None, :], qae, cv, ca, metric_cfg, m)
    # pq family: ADC — Σ_s lut[b, s, code] replaces the f32 squared feature
    # term. OPQ rotation never appears here: it is already folded into the
    # LUT (and the codes were encoded in rotated space). 4-bit modes gather
    # packed bytes and unpack nibbles in-register after the gather.
    codes, lut = quant
    cc = gops.gather_rows(codes, cand)  # (B, C, S) — or (B, C, ⌈S/2⌉) packed
    if is_packed_mode(quant_mode):
        cc = pq_mod.unpack_nibbles(cc, lut.shape[1])
    sv2 = jnp.maximum(pq_mod.adc_gathered_sqdist(lut, cc), 0.0)
    return auto_mod.fused_sqdist_from_sv2(sv2, qae, ca, metric_cfg, m)


class _State(NamedTuple):
    r_ids: Array  # (B, R) sorted ascending by dist
    r_d: Array  # (B, R)
    checked: Array  # (B, R) int8
    visited: Array  # (B, N) int8 or (B, 1) dummy
    active: Array  # (B,) rows still making progress
    evals: Array  # (B,) per-query counter
    hops: Array  # ()
    it: Array  # ()


def _expand(
    state: _State,
    db_v: Array,
    db_a: Array,
    graph: Array,
    qv: Array,
    qa: Array,
    metric_cfg: MetricConfig,
    mask: Optional[Array],
    scope: int,  # entries of R eligible for expansion (P or pool_size)
    fanout: int,  # neighbors taken per expanded entry (Γ/2 or Γ)
    watch: int,  # improvement watched over R[:watch] (P or pool_size)
    use_visited: bool,
    quant: tuple = (),
    quant_mode: str = "none",
    force_active: bool = False,  # expand regardless of the dynamic-exit flag
) -> _State:
    b, pool = state.r_ids.shape

    # --- choose expansion entries: all unchecked among R[:scope] -------------
    elig = (state.checked[:, :scope] == 0) & (state.r_ids[:, :scope] >= 0)
    if not force_active:
        elig = elig & state.active[:, None]
    exp_ids = jnp.where(elig, state.r_ids[:, :scope], INVALID)  # (B, scope)

    # Each phase below runs under a ``jax.named_scope``: the ops it lowers
    # to carry the phase in their metadata (a profile viewer's op names),
    # which changes nothing that is computed.

    # --- gather neighbor candidates ------------------------------------------
    with jax.named_scope("traversal.gather"):
        # (B, scope, fanout)
        nbrs = gops.gather_rows(graph, exp_ids)[:, :, :fanout]
        cand = nbrs.reshape(b, scope * fanout)
        cand = jnp.where(
            (exp_ids < 0)[:, :, None].repeat(fanout, 2).reshape(b, -1),
            INVALID, cand,
        )
        if use_visited:
            seen = jnp.take_along_axis(
                state.visited, jnp.maximum(cand, 0), axis=1
            ).astype(bool)
            cand = jnp.where(seen, INVALID, cand)

    # --- score ----------------------------------------------------------------
    with jax.named_scope("traversal.score"):
        cd = _score_candidates(
            db_v, db_a, cand, qv, qa, metric_cfg, mask, quant, quant_mode
        )
        cd = jnp.where(cand < 0, INF, cd)
        n_new_evals = (cand >= 0).sum(axis=1).astype(jnp.int32)

    # --- bookkeeping: expanded entries become checked; candidates visited ----
    with jax.named_scope("traversal.visited"):
        checked = state.checked.at[:, :scope].max(elig.astype(jnp.int8))
        visited = state.visited
        if use_visited:
            # INVALID candidates are routed out of range and dropped.
            safe_cand = jnp.where(cand >= 0, cand, state.visited.shape[1])
            visited = visited.at[
                jnp.arange(b)[:, None], safe_cand
            ].set(jnp.int8(1), mode="drop")

    # --- merge ----------------------------------------------------------------
    with jax.named_scope("traversal.merge"):
        old_watch = state.r_ids[:, :watch]
        r_ids, r_d, checked = gops.merge_pools(
            state.r_ids, state.r_d, cand, cd, pool,
            pool_flags=checked,
            cand_flags=jnp.zeros_like(cand, dtype=jnp.int8),
        )
        # pads never expand
        checked = jnp.where(r_ids < 0, jnp.int8(1), checked)
        improved = (r_ids[:, :watch] != old_watch).any(axis=1)
        still_unchecked = (
            (checked[:, :scope] == 0) & (r_ids[:, :scope] >= 0)
        ).any(axis=1)
        active = state.active & (improved | still_unchecked)

    return _State(
        r_ids=r_ids,
        r_d=r_d,
        checked=checked,
        visited=visited,
        active=active,
        evals=state.evals + n_new_evals,
        hops=state.hops + 1,
        it=state.it + 1,
    )


# ---------------------------------------------------------------------------
# Composable stages — shared by _search_jit and distributed/search.py
# ---------------------------------------------------------------------------


def init_state(
    db_v: Array,
    db_a: Array,
    qv: Array,
    qa: Array,
    entry_ids: Array,  # (B, pool) initial pool node ids
    metric_cfg: MetricConfig,
    cfg: RoutingConfig,
    n_nodes: int,
    mask: Optional[Array] = None,
    quant: tuple = (),
) -> _State:
    """Stage 1 (paper Alg. 3 init): score the random-K seed pool, sorted
    ascending, with the visited map primed on the seeds."""
    b = qv.shape[0]
    pool = cfg.pool_size
    d0 = _score_candidates(
        db_v, db_a, entry_ids, qv, qa, metric_cfg, mask, quant, cfg.quant_mode
    )
    d0 = jnp.where(entry_ids < 0, INF, d0)
    r_ids, r_d, _ = gops.merge_pools(
        jnp.full((b, pool), INVALID), jnp.full((b, pool), INF),
        entry_ids, d0, pool,
    )
    checked = jnp.where(r_ids < 0, jnp.int8(1), jnp.int8(0))
    if cfg.use_visited:
        visited = jnp.zeros((b, n_nodes), jnp.int8)
        visited = visited.at[
            jnp.arange(b)[:, None], jnp.maximum(entry_ids, 0)
        ].set(jnp.int8(1), mode="drop")
    else:
        visited = jnp.zeros((b, 1), jnp.int8)
    return _State(
        r_ids=r_ids, r_d=r_d, checked=checked, visited=visited,
        active=jnp.ones((b,), bool),
        evals=(entry_ids >= 0).sum(axis=1).astype(jnp.int32),
        hops=jnp.zeros((), jnp.int32),
        it=jnp.zeros((), jnp.int32),
    )


def coarse_stage(
    state: _State,
    db_v: Array,
    db_a: Array,
    graph: Array,
    qv: Array,
    qa: Array,
    metric_cfg: MetricConfig,
    cfg: RoutingConfig,
    mask: Optional[Array] = None,
    quant: tuple = (),
) -> _State:
    """Stage 2 — Dynamic Coarse Routing: pioneer set = R[:P], half-fanout
    expansion until no iteration improves P (or, with ``cfg.coarse_fixed``,
    for exactly ``coarse_max_iters`` iterations — the NHQ-style strict
    first-stage exit of the "w/o Dynamic" ablation)."""
    half = max(1, graph.shape[1] // 2)

    def cond(s):
        budget = s.it < cfg.coarse_max_iters
        if cfg.coarse_fixed:
            return budget
        return s.active.any() & budget

    def body(s):
        return _expand(
            s, db_v, db_a, graph, qv, qa, metric_cfg, mask,
            scope=cfg.pioneer_size, fanout=half, watch=cfg.pioneer_size,
            use_visited=cfg.use_visited, quant=quant,
            quant_mode=cfg.quant_mode, force_active=cfg.coarse_fixed,
        )

    return jax.lax.while_loop(cond, body, state)


def refine_stage(
    state: _State,
    db_v: Array,
    db_a: Array,
    graph: Array,
    qv: Array,
    qa: Array,
    metric_cfg: MetricConfig,
    cfg: RoutingConfig,
    mask: Optional[Array] = None,
    quant: tuple = (),
) -> _State:
    """Stage 3 — Greedy Refinement Routing: full pool, full fanout, until the
    pool is fully checked."""
    b = qv.shape[0]
    pool = cfg.pool_size
    gamma = graph.shape[1]
    state = state._replace(
        active=jnp.ones((b,), bool), it=jnp.zeros((), jnp.int32)
    )

    def cond(s):
        unchecked = ((s.checked == 0) & (s.r_ids >= 0)).any()
        return unchecked & (s.it < cfg.refine_max_iters)

    def body(s):
        return _expand(
            s, db_v, db_a, graph, qv, qa, metric_cfg, mask,
            scope=pool, fanout=gamma, watch=pool,
            use_visited=cfg.use_visited, quant=quant,
            quant_mode=cfg.quant_mode,
        )

    return jax.lax.while_loop(cond, body, state)


def traverse_pool(
    db_v: Array,
    db_a: Array,
    graph: Array,
    qv: Array,
    qa: Array,
    entry_ids: Array,
    metric_cfg: MetricConfig,
    cfg: RoutingConfig,
    n_nodes: int,
    mask: Optional[Array] = None,
    quant: tuple = (),
) -> _State:
    """Stages 1–3: seed + coarse + refine, returning the final pool state
    (ids sorted ascending by traversal-codec distance). The sharded path
    stops here and reranks across shards; ``_search_jit`` finishes with
    ``emit_topk`` locally."""
    state = init_state(
        db_v, db_a, qv, qa, entry_ids, metric_cfg, cfg, n_nodes, mask, quant
    )
    state = coarse_stage(
        state, db_v, db_a, graph, qv, qa, metric_cfg, cfg, mask, quant
    )
    return refine_stage(
        state, db_v, db_a, graph, qv, qa, metric_cfg, cfg, mask, quant
    )


def score_exact(
    db_v: Array,
    db_a: Array,
    ids: Array,  # (B, C), INVALID allowed
    qv: Array,
    qa: Array,
    metric_cfg: MetricConfig,
    mask: Optional[Array] = None,
) -> Array:
    """(B, C) exact full-precision fused sqdists for gathered candidates
    (INF on INVALID slots) — the rerank primitive shared by the single-host
    tail and the sharded cross-shard rerank."""
    d = _score_candidates(
        db_v, db_a, ids, qv, qa, metric_cfg, mask, (), "none"
    )
    return jnp.where(ids < 0, INF, d)


def enforce_filter(
    out_ids: Array,
    out_sq: Array,
    db_a: Array,
    qa: Array,
    mask: Optional[Array] = None,
) -> tuple[Array, Array]:
    """Hard predicate filter on emitted ids: equality for point targets,
    [lo, hi] containment for interval targets; masked-out dims always pass."""
    oa = gops.gather_rows(db_a, out_ids)
    if qa.ndim == 3:  # interval targets: containment in [lo, hi]
        okl = (oa >= qa[:, None, :, 0]) & (oa <= qa[:, None, :, 1])
    else:
        okl = oa == qa[:, None, :]
    if mask is not None:
        okl = okl | (mask[:, None, :] == 0)
    ok = okl.all(-1)
    return jnp.where(ok, out_ids, INVALID), jnp.where(ok, out_sq, INF)


def emit_topk(
    state: _State,
    db_v: Array,
    db_a: Array,
    qv: Array,
    qa: Array,
    metric_cfg: MetricConfig,
    cfg: RoutingConfig,
    mask: Optional[Array] = None,
) -> SearchResult:
    """Stage 4 — two-stage output: exact mode emits the pool head directly;
    quant mode reranks the top rerank_size pool entries with exact fused
    distances (the only full-precision evaluations of the whole search)."""
    b = state.r_ids.shape[0]
    if cfg.quant_mode == "none":
        out_ids = state.r_ids[:, : cfg.k]
        out_sq = state.r_d[:, : cfg.k]
        n_dist_evals = state.evals
        n_code_evals = jnp.zeros((b,), jnp.int32)
    else:
        r_ids = state.r_ids[:, : cfg.effective_rerank]
        rd = score_exact(db_v, db_a, r_ids, qv, qa, metric_cfg, mask)
        neg, take = jax.lax.top_k(-rd, cfg.k)
        out_sq = -neg
        out_ids = jnp.take_along_axis(r_ids, take, axis=1)
        out_ids = jnp.where(out_sq < INF / 2, out_ids, INVALID)
        n_dist_evals = (r_ids >= 0).sum(axis=1).astype(jnp.int32)
        n_code_evals = state.evals
    if cfg.enforce_equality:
        out_ids, out_sq = enforce_filter(out_ids, out_sq, db_a, qa, mask)
    return SearchResult(
        ids=out_ids,
        dists=jnp.sqrt(jnp.maximum(out_sq, 0.0)),
        sqdists=out_sq,
        n_dist_evals=n_dist_evals,
        n_hops=state.hops,
        n_code_evals=n_code_evals,
    )


@partial(
    jax.jit,
    static_argnames=("metric_cfg", "cfg", "n_nodes"),
)
def _search_jit(
    db_v: Array,
    db_a: Array,
    graph: Array,
    qv: Array,
    qa: Array,
    entry_ids: Array,  # (B, pool) initial pool node ids
    metric_cfg: MetricConfig,
    cfg: RoutingConfig,
    n_nodes: int,
    mask: Optional[Array] = None,
    quant: tuple = (),
) -> SearchResult:
    _TRACE_COUNT[0] += 1  # runs only while tracing (see trace_count)
    state = traverse_pool(
        db_v, db_a, graph, qv, qa, entry_ids, metric_cfg, cfg, n_nodes,
        mask, quant,
    )
    return emit_topk(state, db_v, db_a, qv, qa, metric_cfg, cfg, mask)


@partial(
    jax.jit,
    static_argnames=("metric_cfg", "cfg", "n_nodes"),
)
def _traverse_jit(
    db_v: Array,
    db_a: Array,
    graph: Array,
    qv: Array,
    qa: Array,
    entry_ids: Array,
    metric_cfg: MetricConfig,
    cfg: RoutingConfig,
    n_nodes: int,
    mask: Optional[Array] = None,
    quant: tuple = (),
) -> tuple[Array, Array, Array]:
    _TRACE_COUNT[0] += 1  # runs only while tracing (see trace_count)
    state = traverse_pool(
        db_v, db_a, graph, qv, qa, entry_ids, metric_cfg, cfg, n_nodes,
        mask, quant,
    )
    return state.r_ids[:, : cfg.effective_rerank], state.evals, state.hops


def search_pool(
    db_a: Array,
    graph: Array,
    qv: Array,
    qa: Array,
    entry_ids: Array,
    metric_cfg: MetricConfig,
    cfg: RoutingConfig,
    n_nodes: int,
    mask: Optional[Array] = None,
    quant: tuple = (),
) -> tuple[Array, Array, Array]:
    """Stages 1–3 only, for callers that source the rerank vectors
    themselves (the hot/cold tier in ``repro.cache``): traverse over
    compressed codes and return ``(r_ids, evals, hops)`` where ``r_ids`` is
    the pool head trimmed to ``cfg.effective_rerank``.

    Quantized modes never read ``db_v`` during traversal (codes carry the
    feature term — see ``_score_candidates``), so no f32 matrix is taken as
    an operand at all; a (1, M) dummy satisfies the shared stage signatures.
    """
    if cfg.quant_mode == "none":
        raise ValueError("search_pool requires a quantized traversal codec")
    dummy_v = jnp.zeros((1, qv.shape[1]), jnp.float32)
    return _traverse_jit(
        dummy_v, db_a, graph, qv, qa, entry_ids, metric_cfg, cfg, n_nodes,
        mask, quant,
    )


@partial(jax.jit, static_argnames=("metric_cfg", "cfg"))
def rerank_gathered(
    cv: Array,  # (B, R, M) candidate f32 rows, pre-gathered (INVALID → row 0)
    db_a: Array,
    r_ids: Array,  # (B, R) pool-head ids (INVALID-padded)
    qv: Array,
    qa: Array,
    metric_cfg: MetricConfig,
    cfg: RoutingConfig,
    mask: Optional[Array] = None,
    evals: Optional[Array] = None,
    hops: Optional[Array] = None,
) -> SearchResult:
    """Stage 4 for pre-gathered candidates: the exact op sequence of
    ``emit_topk``'s quantized branch, with the f32 gather replaced by the
    caller-supplied ``cv`` (the tier routes hot rows to a contiguous device
    slice and cold rows to the host store — ``repro.cache.HotTier``). Feeding
    the same row values ``gops.gather_rows(db_v, r_ids)`` would produce
    keeps the emitted ids/distances bit-identical to the in-jit rerank
    (asserted in ``tests/test_cache.py``)."""
    _TRACE_COUNT[0] += 1  # runs only while tracing (see trace_count)
    b = r_ids.shape[0]
    ca = gops.gather_rows(db_a, r_ids)
    m = mask[:, None, :] if mask is not None else None
    rd = auto_mod.fused_sqdist(qv[:, None, :], qa[:, None], cv, ca, metric_cfg, m)
    rd = jnp.where(r_ids < 0, INF, rd)
    neg, take = jax.lax.top_k(-rd, cfg.k)
    out_sq = -neg
    out_ids = jnp.take_along_axis(r_ids, take, axis=1)
    out_ids = jnp.where(out_sq < INF / 2, out_ids, INVALID)
    n_dist_evals = (r_ids >= 0).sum(axis=1).astype(jnp.int32)
    n_code_evals = evals if evals is not None else jnp.zeros((b,), jnp.int32)
    if cfg.enforce_equality:
        out_ids, out_sq = enforce_filter(out_ids, out_sq, db_a, qa, mask)
    return SearchResult(
        ids=out_ids,
        dists=jnp.sqrt(jnp.maximum(out_sq, 0.0)),
        sqdists=out_sq,
        n_dist_evals=n_dist_evals,
        n_hops=hops if hops is not None else jnp.zeros((), jnp.int32),
        n_code_evals=n_code_evals,
    )


def make_entry_ids(n_nodes: int, batch: int, pool_size: int, seed: int = 0) -> Array:
    """Paper Alg. 3 init: random-K seed nodes, shared across the batch.

    The draw depends only on (n_nodes, pool_size, seed) — every row gets the
    same seed pool, so a query's result is invariant to its row position and
    to the batch size it is served in. That invariance is what lets the
    serving layer coalesce requests into padded bucket batches (repro.serve)
    with bit-identical per-query results: all remaining traversal state is
    per-row. Per-row recall is unaffected (each query still sees pool_size
    uniform seeds; rows are merely correlated with each other).
    """
    import numpy as np

    rng = np.random.default_rng(seed)
    row = rng.integers(0, n_nodes, size=(1, pool_size), dtype=np.int32)
    return jnp.asarray(np.broadcast_to(row, (batch, pool_size)))


def search(
    db_v: Array,
    db_a: Array,
    graph: Array,
    qv: Array,
    qa: Array,
    metric_cfg: MetricConfig,
    cfg: RoutingConfig = RoutingConfig(),
    mask: Optional[Array] = None,
    entry_ids: Optional[Array] = None,
    seed: int = 0,
    quant=None,  # Optional[repro.quant.QuantizedVectors]
) -> SearchResult:
    """Batched hybrid ANNS over a HELP index (public entry point).

    ``qa`` carries the per-query attribute targets as (B, L) points or
    (B, L, 2) [lo, hi] intervals (value-set / range predicates).
    Pass a ``QuantizedVectors`` store to run the traversal over compressed
    codes with a full-precision rerank (quant_mode is taken from the store
    when the config leaves it at 'none').
    """
    qv = jnp.asarray(qv, jnp.float32)
    qa = jnp.asarray(qa, jnp.int32)
    n = db_v.shape[0]
    if entry_ids is None:
        entry_ids = make_entry_ids(n, qv.shape[0], cfg.pool_size, seed)
    operand: tuple = ()
    if quant is not None:
        if cfg.quant_mode == "none":
            cfg = dataclasses.replace(cfg, quant_mode=quant.cfg.mode)
        elif cfg.quant_mode != quant.cfg.mode:
            raise ValueError(
                f"cfg.quant_mode={cfg.quant_mode!r} != store mode {quant.cfg.mode!r}"
            )
        operand = quant.routing_operand(qv)
    elif cfg.quant_mode != "none":
        raise ValueError(f"quant_mode={cfg.quant_mode!r} needs a quant store")
    return _search_jit(
        db_v, db_a, graph, qv, qa, entry_ids, metric_cfg, cfg, n, mask, operand
    )


# ---------------------------------------------------------------------------
# Ablation: the "w/o DCR" and "w/o Dynamic" routing variants (paper Fig. 6)
# ---------------------------------------------------------------------------


def search_greedy_only(
    db_v, db_a, graph, qv, qa, metric_cfg,
    cfg: RoutingConfig = RoutingConfig(), mask=None, entry_ids=None, seed: int = 0,
):
    """'w/o DCR': skip the coarse phase — plain greedy refinement."""
    c = dataclasses.replace(cfg, coarse_max_iters=0)
    return search(db_v, db_a, graph, qv, qa, metric_cfg, c, mask, entry_ids, seed)


def search_two_stage(
    db_v, db_a, graph, qv, qa, metric_cfg,
    cfg: RoutingConfig = RoutingConfig(), mask=None, entry_ids=None, seed: int = 0,
):
    """'w/o Dynamic': NHQ-style fixed two-stage routing — the coarse stage
    runs to a *fixed* iteration budget (no dynamic pioneer-set exit), then
    refinement. Models the strict first-stage exit the paper criticizes.
    ``coarse_fixed`` force-keeps rows active for exactly ``coarse_max_iters``
    iterations: unchecked pioneers are expanded every iteration even after
    the pioneer set stops improving."""
    c = dataclasses.replace(
        cfg, pioneer_size=max(cfg.pool_size // 2, 1), coarse_fixed=True
    )
    return search(db_v, db_a, graph, qv, qa, metric_cfg, c, mask, entry_ids, seed)
