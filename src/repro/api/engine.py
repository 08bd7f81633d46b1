"""Engine facade: one search entry point over every backend.

``Engine.search(QueryBatch, SearchParams) -> SearchResult`` is the public
contract; serve/build launchers, the examples and the benchmark harness all
go through it. Underneath runs an explicit plan→compile→execute pipeline:

  plan     — ``api.planner``: a ``CostModel`` calibrated from one probe
             traversal on the engine's own index (or a bundled measured
             table) predicts per-query brute vs graph cost for this (N,
             pool, predicate width, batch, codec) and picks the backend;
             the resolved quantization mode always comes *from the index*
             so callers never copy codec state into configs
  compile  — ``api.executor``: the plan signature (batch shape × predicate
             kind × resolved RoutingConfig × codec) keys a cache of
             compiled executables (widened exec plan, cached entry pool,
             post-filter decision); repeated serving batches reuse the
             executable and hit the jit cache with zero new traces
  execute  — a ``Searcher`` backend:
    graph    — single-host HELP traversal (``StableIndex`` + dynamic routing)
    sharded  — mesh traversal + cross-shard rerank + exact merge
               (``ShardedStableIndex``)
    brute    — exact predicate oracle: hard filter + L2 top-k; on a
               PQ-quantized index the scan runs over codes via the fused
               ``adc_scan`` Pallas kernel with a full-precision rerank
               (small/residual shards never touch most f32 vectors)

Planning rules live in ``api.planner.make_plan`` (override → sharded →
graph-less → deprecated fixed threshold → cost-model crossover).

Predicate *class* never forces the brute oracle: value-set (ONE_OF) and
range (BETWEEN) batches compile to per-dimension [lo, hi] interval targets
that every scorer — exact, SQ8, PQ/ADC, single-host and sharded — consumes
natively, so they traverse the HELP graph like any equality batch. ONE_OF
membership stays exact on *every* backend: after a traversal backend
returns, the engine hard-filters the top-k by set membership host-side
(the covering-interval penalty may admit in-hull non-members).

Semantics note — the brute backend is the exact predicate *oracle*: MATCH
and BETWEEN are hard filters there, so sparse queries can return fewer
than k ids (INVALID padding), while traversal backends treat MATCH/BETWEEN
as the soft AUTO penalty unless ``enforce_equality=True``. Auto-planning
therefore trades semantics as well as algorithm at the cost-model
crossover. Callers that need size-invariant behavior pin it:
``enforce_equality=True`` for hard semantics everywhere, or an explicit
``backend=`` override.

Every future backend (4-bit PQ, OPQ, multi-host) implements ``Searcher``
and registers here; ``Engine.save/load`` round-trips the whole surface —
single-host *and* sharded engines (per-shard arrays + codec/mesh meta,
resharded onto the current mesh on load).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Protocol, Union, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import auto as auto_mod
from repro.core import baselines as baselines_mod
from repro.core import routing as routing_mod
from repro.obs import trace as obs_trace
from repro.core.auto import DatasetStats, MetricConfig
from repro.core.graph_ops import INF, INVALID
from repro.core.help_graph import HelpConfig
from repro.core.index import StableIndex
from repro.core.routing import RoutingConfig, SearchResult
from repro.partition.index import PartitionedStableIndex
from repro.quant import (
    QUANT_MODES, QuantConfig, QuantizedVectors, adc_scan, is_pq_mode,
)
from repro.api import executor as executor_mod
from repro.api import planner as planner_mod
from repro.api.executor import Executor
from repro.api.planner import CostModel, Plan
from repro.api.query import QueryBatch

Array = jax.Array

BACKENDS = ("auto", "graph", "sharded", "brute", "partitioned")
QUANT_PARAMS = ("auto",) + QUANT_MODES


@dataclasses.dataclass(frozen=True)
class SearchParams:
    """Consolidated per-request knobs (the four legacy config surfaces).

    Derived defaults reproduce the legacy ``StableIndex.search`` behavior
    exactly: ``pool_size=0`` → max(4k, 32), ``pioneer_size=0`` → 8 (capped
    at the pool), ``rerank_size=0`` → whole pool. ``quant="auto"`` resolves
    from the index's code store; ``quant="none"`` forces a full-precision
    search even on a quantized index (impossible through the legacy path).

    ``brute_threshold`` is deprecated: leave it at ``None`` and the planner
    picks brute vs graph from the calibrated cost model. An explicit value
    is still honored as a hard fixed-N override (with a DeprecationWarning).

    ``nprobe`` applies to partitioned engines only: how many coarse
    partitions each query probes after summary pruning. 0 → the planner's
    default (≈√P, clamped to [1, P]); ``nprobe = P`` probes everything,
    which makes the oracle sub-backend bit-identical to an unpartitioned
    brute search.
    """

    k: int = 10
    pool_size: int = 0
    pioneer_size: int = 0
    rerank_size: int = 0
    quant: str = "auto"
    seed: int = 0
    enforce_equality: bool = False
    backend: str = "auto"
    brute_threshold: Optional[int] = None  # deprecated fixed-N override
    coarse_max_iters: int = 64
    refine_max_iters: int = 256
    use_visited: bool = True
    nprobe: int = 0  # partitioned backend: probes per query (0 → auto)
    #: partitioned backend: per-partition execution mode. "auto" lets the
    #: cost model pick; "brute" scans every probed partition (with
    #: nprobe=P this is bit-identical to the unpartitioned brute oracle);
    #: "graph" forces the HELP subgraph traversal. Ignored elsewhere.
    sub_backend: str = "auto"

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r} ({BACKENDS})")
        if self.quant not in QUANT_PARAMS:
            raise ValueError(f"unknown quant {self.quant!r} ({QUANT_PARAMS})")
        if self.k <= 0:
            raise ValueError("k must be positive")
        if self.nprobe < 0:
            raise ValueError("nprobe must be nonnegative (0 → auto)")
        if self.sub_backend not in ("auto", "graph", "brute"):
            raise ValueError(
                f"unknown sub_backend {self.sub_backend!r} "
                "(auto | graph | brute)"
            )

    @property
    def effective_pool(self) -> int:
        return self.pool_size or max(4 * self.k, 32)

    def routing_config(self, quant_mode: str, enforce: bool) -> RoutingConfig:
        pool = self.effective_pool
        return RoutingConfig(
            k=self.k,
            pool_size=pool,
            pioneer_size=self.pioneer_size or min(8, pool),
            coarse_max_iters=self.coarse_max_iters,
            refine_max_iters=self.refine_max_iters,
            use_visited=self.use_visited,
            enforce_equality=enforce,
            quant_mode=quant_mode,
            rerank_size=self.rerank_size,
        )


@runtime_checkable
class Searcher(Protocol):
    """Backend contract: execute a compiled plan over an index.

    ``entry_ids`` is the executor-cached seed pool (graph backend); backends
    that derive their own entry pools (sharded: per-shard rows) or have none
    (brute) ignore it.
    """

    name: str

    def search(
        self, engine: "Engine", queries: QueryBatch, params: SearchParams,
        plan: Plan, entry_ids: Optional[Array] = None,
    ) -> SearchResult:
        ...


def _mask_jnp(queries: QueryBatch) -> Optional[Array]:
    return None if queries.mask is None else jnp.asarray(queries.mask)


def _targets_jnp(queries: QueryBatch) -> Array:
    """(B, L) point or (B, L, 2) interval scorer targets."""
    return jnp.asarray(queries.targets, jnp.int32)


class GraphSearcher:
    """Single-host HELP-graph traversal (``StableIndex`` routing)."""

    name = "graph"

    def search(self, engine, queries, params, plan, entry_ids=None):
        idx = engine.index
        quant = idx.quant if plan.quant_mode != "none" else None
        return routing_mod.search(
            idx.features, idx.attrs, idx.graph,
            jnp.asarray(queries.vectors, jnp.float32),
            _targets_jnp(queries),
            idx.metric_cfg, plan.routing_cfg,
            mask=_mask_jnp(queries), entry_ids=entry_ids,
            seed=params.seed, quant=quant,
        )


class ShardedSearcher:
    """Mesh traversal + cross-shard rerank + exact top-k merge
    (``ShardedStableIndex``; entry pools are per-shard-local, so the
    executor-cached global entry pool is ignored)."""

    name = "sharded"

    def search(self, engine, queries, params, plan, entry_ids=None):
        return engine.index.search(
            jnp.asarray(queries.vectors, jnp.float32),
            _targets_jnp(queries),
            k=params.k, routing_cfg=plan.routing_cfg,
            mask=_mask_jnp(queries), seed=params.seed,
        )


class BruteForceSearcher:
    """Exact predicate oracle: hard filter + L2 ranking over the full shard.

    Three paths, cheapest applicable wins:
      * point (match/any) predicates, full precision — delegates to the
        legacy ``brute_force_hybrid`` (bit-identical results by
        construction);
      * ONE_OF / BETWEEN predicates — same scan with exact set-membership /
        interval-containment filtering;
      * PQ codes + ``quant != "none"`` — two-stage: the fused ``adc_scan``
        kernel scores every code (LUT lookups, no f32 traffic), the top
        ``pool`` survivors are reranked with exact L2. ``n_dist_evals``
        then counts only the rerank; the N code evals are reported in
        ``n_code_evals``.
    """

    name = "brute"

    def search(self, engine, queries, params, plan, entry_ids=None):
        idx = engine.index
        qv = jnp.asarray(queries.vectors, jnp.float32)
        if is_pq_mode(plan.quant_mode) and idx.quant is not None:
            return self._adc_two_stage(engine, queries, qv, params)
        if not (queries.has_one_of or queries.has_intervals):
            return baselines_mod.brute_force_hybrid(
                idx.features, idx.attrs, qv,
                jnp.asarray(queries.attrs, jnp.int32), params.k,
                mask=_mask_jnp(queries),
            )
        ok = _ok_matrix(engine, queries)
        sv2 = auto_mod.brute_fused_sqdist(
            qv, jnp.asarray(queries.attrs, jnp.int32),
            idx.features, idx.attrs, MetricConfig(mode="l2")
        )
        return _filtered_topk(sv2, ok, params.k, full_evals=idx.features.shape[0])

    def _adc_two_stage(self, engine, queries, qv, params):
        """ADC code scan → hard filter → exact rerank of the pool head.
        ``rerank_size`` bounds the full-precision stage exactly as in the
        traversal path (0 → whole pool). Each stage is dispatched eagerly
        and runs as programs of its own (``jit_adc_scan4_scores``,
        ``jit_top_k``, ...); its host span names the dispatch gaps between
        them. A ``jax.named_scope`` here would name nothing: a jitted
        function called outside a trace lowers with a fresh name stack."""
        idx = engine.index
        with obs_trace.span("brute.lut"):
            lut = idx.quant.lut(qv)  # OPQ rotation (if any) folds in here
        with obs_trace.span("brute.scan"):
            scores = adc_scan(
                lut, idx.quant.scan_codes,
                jnp.asarray(queries.attrs, jnp.int32),
                jnp.asarray(idx.attrs), mode="l2", packed=idx.quant.packed,
            )  # (B, N) approximate squared L2 from codes only
        with obs_trace.span("brute.select"):
            ok = _ok_matrix(engine, queries)
            pool = min(params.effective_pool, scores.shape[1])
            pool = min(max(params.rerank_size or pool, params.k), pool)
            neg, cand = jax.lax.top_k(-jnp.where(ok, scores, INF), pool)
        with obs_trace.span("brute.rerank"):
            cv = jnp.take(idx.features, jnp.maximum(cand, 0), axis=0)
            rd = auto_mod.feature_sqdist(qv[:, None, :], cv)
            rd = jnp.where(-neg < INF / 2, rd, INF)
            res = _filtered_topk(
                rd, jnp.ones_like(rd, bool), params.k, full_evals=pool,
                ids=cand,
            )
        n = idx.quant.codes.shape[0]
        return res._replace(
            n_code_evals=jnp.full((qv.shape[0],), n, jnp.int32)
        )


def _ok_matrix(engine: "Engine", queries: QueryBatch) -> Array:
    """(B, N) admissibility for the brute backend. The common predicate
    classes stay on-device (no host transfer in the serving hot path):
    point batches via equality, interval (BETWEEN / covering-hull) batches
    via containment; ONE_OF set membership falls back to the cached host
    attrs."""
    if queries.has_one_of:
        return jnp.asarray(queries.admissible(engine.host_attrs))
    if queries.intervals is None:
        return baselines_mod._equality_ok(
            jnp.asarray(queries.attrs, jnp.int32), engine.index.attrs,
            _mask_jnp(queries),
        )
    iv = jnp.asarray(queries.intervals, jnp.int32)
    xa = engine.index.attrs[None, :, :]
    okl = (xa >= iv[:, None, :, 0]) & (xa <= iv[:, None, :, 1])
    if queries.mask is not None:
        okl = okl | (jnp.asarray(queries.mask)[:, None, :] == 0)
    return okl.all(-1)


def _filtered_topk(
    sq_scores: Array,
    ok: Array,
    k: int,
    full_evals: int,
    ids: Optional[Array] = None,
) -> SearchResult:
    """Top-k of masked scores → INVALID-padded SearchResult."""
    b = sq_scores.shape[0]
    scores = jnp.where(ok, sq_scores, INF)
    neg, take = jax.lax.top_k(-scores, k)
    sq = -neg
    out = take if ids is None else jnp.take_along_axis(ids, take, axis=1)
    out = jnp.where(jnp.isfinite(sq) & (sq < INF / 2), out, INVALID)
    sq = jnp.where(out >= 0, sq, INF)
    return SearchResult(
        ids=out,
        dists=jnp.sqrt(jnp.maximum(sq, 0.0)),
        sqdists=sq,
        n_dist_evals=jnp.full((b,), full_evals, jnp.int32),
        n_hops=jnp.zeros((), jnp.int32),
        n_code_evals=jnp.zeros((b,), jnp.int32),
    )


_SEARCHERS: dict[str, Searcher] = {
    s.name: s for s in (GraphSearcher(), ShardedSearcher(), BruteForceSearcher())
}


@dataclasses.dataclass
class Engine:
    """The one search facade. Wraps a single-host ``StableIndex`` or a mesh
    ``ShardedStableIndex`` and dispatches compiled query batches through the
    plan→compile→execute pipeline onto a ``Searcher`` backend.

    ``cost_model`` may be injected at construction (e.g. loaded from a
    measured ``BENCH_planner.json`` table via
    ``planner.cost_model_from_table``); otherwise it is calibrated lazily
    from one probe traversal the first time an auto-plan needs it."""

    #: monotone index-content version for result caching. Immutable engines
    #: stay at 0 forever; ``MutableEngine`` shadows this with an instance
    #: counter bumped inside the write lock (see ``repro.mutable.engine``),
    #: and ``repro.cache.ResultCache`` only serves entries whose recorded
    #: epoch equals the engine's current one. Class attribute (not a
    #: dataclass field) so equality/repr semantics are untouched.
    write_epoch = 0

    index: Union[StableIndex, "ShardedStableIndex"]  # noqa: F821
    cost_model_override: Optional[CostModel] = dataclasses.field(
        default=None, repr=False, compare=False
    )
    #: bound on resident compiled executables (multi-tenant serving streams
    #: produce many distinct plan signatures; see api.executor)
    executor_max_entries: int = dataclasses.field(
        default=executor_mod.CACHE_SIZE, repr=False, compare=False
    )
    _attrs_np: Optional[np.ndarray] = dataclasses.field(
        default=None, repr=False, compare=False
    )
    _cost_model: Optional[CostModel] = dataclasses.field(
        default=None, repr=False, compare=False
    )
    _executor: Optional[Executor] = dataclasses.field(
        default=None, repr=False, compare=False
    )

    @property
    def host_attrs(self) -> np.ndarray:
        """Host copy of the attribute matrix (cached: the device→host
        transfer for predicate filtering happens once per engine)."""
        if self._attrs_np is None:
            self._attrs_np = np.asarray(self.index.attrs)
        return self._attrs_np

    @property
    def cost_model(self) -> CostModel:
        """The calibrated planner cost model (probe runs on first access
        unless one was injected)."""
        if self._cost_model is None:
            if self.cost_model_override is not None:
                self._cost_model = self.cost_model_override
            elif self.is_sharded:
                raise ValueError(
                    "cost_model applies to single-host engines only — a "
                    "sharded index always plans onto the sharded backend, "
                    "so there is no brute/graph crossover to calibrate"
                )
            elif self.is_partitioned:
                # no global arrays to probe; the model only prices the
                # sub-backend/nprobe choice — defaults are fine, and a
                # measured table can still be injected
                self._cost_model = planner_mod.default_cost_model(
                    self.index.n_items
                )
            else:
                self._cost_model = planner_mod.calibrate(self.index)
        return self._cost_model

    @property
    def executor(self) -> Executor:
        """The plan-signature → compiled-executable cache for this engine."""
        if self._executor is None:
            self._executor = Executor(self, max_entries=self.executor_max_entries)
        return self._executor

    def searcher(self, name: str) -> Searcher:
        if name not in _SEARCHERS and name == "partitioned":
            # lazy registration: partition.search imports this module, so
            # it cannot be imported at engine module-import time
            from repro.partition.search import PartitionedSearcher

            _SEARCHERS[name] = PartitionedSearcher()
        return _SEARCHERS[name]

    def invalidate_caches(self) -> None:
        """Refresh derived state after ``self.index`` is swapped in place
        (the ``repro.mutable`` merge path): the cached host attribute copy
        and every compiled executable (closures hold the old arrays and
        entry pools sized for the old N). The calibrated cost model is
        *kept* — ``CostModel._scale`` extrapolates across corpus growth, so
        a merge must not re-probe."""
        self._attrs_np = None
        if self._executor is not None:
            self._executor.clear()

    # -- construction --------------------------------------------------------

    @classmethod
    def build(
        cls,
        features,
        attrs,
        help_cfg: HelpConfig = HelpConfig(),
        quant_cfg: QuantConfig = QuantConfig(),
        build_graph: bool = True,
        **kw,
    ) -> "Engine":
        """Build a single-host engine. ``build_graph=False`` skips the HELP
        construction for scan-only corpora (the planner then always picks
        the brute-force backend)."""
        return cls(StableIndex.build(
            features, attrs, help_cfg=help_cfg, quant_cfg=quant_cfg,
            build_graph=build_graph, **kw,
        ))

    @classmethod
    def build_partitioned(
        cls, features, attrs, n_partitions: int, **kw
    ) -> "Engine":
        """Build an out-of-core engine: IVF coarse partitions over HELP
        subgraphs with streaming residency (see ``repro.partition``).
        Keywords forward to ``PartitionedStableIndex.build``."""
        return cls(PartitionedStableIndex.build(
            features, attrs, n_partitions, **kw
        ))

    @classmethod
    def from_parts(
        cls,
        features,
        attrs,
        graph,
        metric_cfg: MetricConfig,
        stats: Optional[DatasetStats] = None,
        quant: Optional[QuantizedVectors] = None,
        help_cfg: HelpConfig = HelpConfig(),
    ) -> "Engine":
        """Wrap prebuilt arrays (benchmark harness / external builders)."""
        features = jnp.asarray(features, jnp.float32)
        attrs = jnp.asarray(attrs, jnp.int32)
        if stats is None:
            stats = auto_mod.sample_stats(
                np.asarray(features), np.asarray(attrs)
            )
        return cls(StableIndex(
            features=features, attrs=attrs, graph=jnp.asarray(graph),
            metric_cfg=metric_cfg, help_cfg=help_cfg, stats=stats,
            quant=quant,
        ))

    # -- introspection -------------------------------------------------------

    @property
    def is_sharded(self) -> bool:
        return not isinstance(
            self.index, (StableIndex, PartitionedStableIndex)
        )

    @property
    def is_partitioned(self) -> bool:
        return isinstance(self.index, PartitionedStableIndex)

    @property
    def n_items(self) -> int:
        if self.is_partitioned:
            return self.index.n_items
        return int(self.index.features.shape[0])

    @property
    def attr_dim(self) -> int:
        return int(self.index.attrs.shape[1])

    @property
    def quant_mode(self) -> str:
        """Codec attached to the index ("none" when unquantized)."""
        if self.is_sharded or self.is_partitioned:
            return self.index.quant_mode
        return self.index.quant.cfg.mode if self.index.quant is not None else "none"

    @property
    def has_graph(self) -> bool:
        if self.is_partitioned:
            return self.index.has_graph
        return int(self.index.graphs.shape[1] if self.is_sharded
                   else self.index.graph.shape[1]) > 0

    # -- planning ------------------------------------------------------------

    def _resolve_quant(self, params: SearchParams, backend: str) -> str:
        stored = self.quant_mode
        if params.quant == "auto":
            if backend == "brute" and stored == "sq8":
                return "none"  # no SQ8 scan kernel; exact scan is the oracle
            return stored
        if params.quant == "sq8" and backend == "brute":
            raise ValueError(
                "the brute-force backend has no sq8 scan path; "
                "use quant='auto' or 'none'"
            )
        if params.quant == "none":
            if self.is_sharded and stored != "none":
                raise ValueError(
                    "quant='none' on a quantized sharded index is not "
                    "supported (codes are sharded in place of f32 reads)"
                )
            return "none"
        if params.quant != stored:
            raise ValueError(
                f"params.quant={params.quant!r} but the index holds "
                f"{stored!r} codes"
            )
        return params.quant

    def plan(self, queries: QueryBatch, params: SearchParams) -> Plan:
        """Resolve (backend, quant_mode, routing_cfg, predicted costs) for
        one batch — see ``api.planner.make_plan`` for the rules."""
        return planner_mod.make_plan(self, queries, params)

    # -- execution -----------------------------------------------------------

    def search(
        self,
        queries: Union[QueryBatch, tuple],
        params: SearchParams = SearchParams(),
    ) -> SearchResult:
        """Execute a compiled query batch: plan → executor (compiled-
        executable cache keyed on the plan signature) → backend. Also
        accepts a plain ``(query_vectors, query_attrs)`` tuple as an
        all-MATCH batch."""
        if isinstance(queries, tuple):
            queries = QueryBatch.match(*queries)
        with obs_trace.span("engine.search"):
            with obs_trace.span("engine.plan") as sp:
                plan = self.plan(queries, params)
                if sp:
                    sp.set("backend", plan.backend)
                    sp.set("quant_mode", plan.quant_mode)
                    sp.set("reason", plan.reason)
                    sp.set("cost_brute", plan.cost_brute)
                    sp.set("cost_graph", plan.cost_graph)
                    if plan.backend == "partitioned":
                        sp.set("nprobe", plan.nprobe)
                        sp.set("sub_backend", plan.sub_backend)
            return self.executor.run(queries, params, plan)

    def _predicate_filter(
        self, res: SearchResult, queries: QueryBatch, full: bool
    ) -> SearchResult:
        """Hard-filter traversal output host-side: ONE_OF membership always,
        every predicate (equality / interval containment) when ``full``."""
        attrs = self.host_attrs
        ids = np.asarray(res.ids)
        taken = attrs[np.maximum(ids, 0)]  # (B, K, L)
        ok = jnp.asarray(queries.admissible_rows(taken, one_of_only=not full))
        ok = ok & (jnp.asarray(ids) >= 0)
        # re-sort so survivors stay ascending with INVALID padding at the
        # tail (the SearchResult ordering invariant)
        sq = jnp.where(ok, res.sqdists, INF)
        neg, take = jax.lax.top_k(-sq, sq.shape[1])
        sq = -neg
        out = jnp.take_along_axis(
            jnp.where(ok, jnp.asarray(ids), INVALID), take, axis=1
        )
        return res._replace(
            ids=out,
            dists=jnp.sqrt(jnp.maximum(sq, 0.0)),
            sqdists=sq,
        )

    # -- persistence ---------------------------------------------------------

    def save(self, path: str) -> None:
        """Persist the engine under ``path``. Single-host engines write the
        flat ``StableIndex`` layout (features, attrs, graph, metric
        calibration, codes and codebooks); sharded engines write one
        subdirectory per model shard (arrays + local HELP graph + codes)
        plus replicated codec state and mesh metadata — see
        ``ShardedStableIndex.save``.

        The calibrated planner ``CostModel`` is persisted in the meta of
        both formats, so ``Engine.load`` skips the calibration probe
        entirely. A single-host graph engine that has not planned yet runs
        the probe once here — save time is the natural place to pay it;
        graph-less engines never calibrate (they always plan brute) and
        sharded engines persist a model only when one was injected."""
        extra = {}
        cm = self._cost_model or self.cost_model_override
        if cm is None and not self.is_sharded and self.has_graph:
            cm = self.cost_model  # probe once at save time, not per load
        if cm is not None:
            extra["cost_model"] = cm.to_json()
        self.index.save(path, extra_meta=extra)

    @classmethod
    def load(
        cls,
        path: str,
        mesh=None,
        mmap: bool = False,
        residency_rows: Optional[int] = None,
    ) -> "Engine":
        """Load a saved engine, sniffing the on-disk format. Sharded
        layouts reshard onto ``mesh`` (or a freshly built local mesh with
        the saved model-shard count when ``mesh`` is None). A persisted
        cost model in the saved meta (written by ``save``) is restored as
        ``cost_model_override`` — load performs zero probe traversals.

        ``mmap`` memory-maps the single-host array files instead of
        reading them into host RAM before the device transfer (partitioned
        layouts always mmap — their arrays reach the device per partition,
        on residency). ``residency_rows`` caps the partitioned layout's
        resident rows (see ``partition.SegmentStore``)."""
        import json as json_mod
        import os as os_mod

        from repro.distributed.search import (
            SHARDED_META, ShardedStableIndex, is_sharded_dir,
        )
        from repro.partition.index import is_partitioned_dir

        if is_sharded_dir(path):
            index = ShardedStableIndex.load(path, mesh=mesh)
            meta_file = os_mod.path.join(path, SHARDED_META)
        elif is_partitioned_dir(path):
            if mesh is not None:
                raise ValueError(
                    f"{path} holds a partitioned engine; mesh= only "
                    "applies to sharded layouts"
                )
            index = PartitionedStableIndex.load(
                path, residency_rows=residency_rows
            )
            meta_file = os_mod.path.join(path, "meta.json")
        else:
            if mesh is not None:
                raise ValueError(
                    f"{path} holds a single-host engine; mesh= only applies "
                    "to sharded layouts"
                )
            if residency_rows is not None:
                raise ValueError(
                    "residency_rows only applies to partitioned layouts"
                )
            index = StableIndex.load(path, mmap=mmap)
            meta_file = os_mod.path.join(path, "meta.json")
        with open(meta_file) as f:
            saved_cm = json_mod.load(f).get("cost_model")
        override = (
            planner_mod.cost_model_from_table(saved_cm)
            if saved_cm is not None else None
        )
        return cls(index, cost_model_override=override)
