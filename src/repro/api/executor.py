"""Executor: the compile/execute half of plan→compile→execute.

``Engine.search`` used to re-run the whole Python dispatch pipeline per
batch — plan resolution, ONE_OF cut-widening, entry-pool RNG, backend
selection — before ever reaching the jitted search. The ``Executor`` hoists
everything signature-invariant out of the hot path: a *plan signature*
(batch shape × predicate kind × resolved ``RoutingConfig`` × codec ×
backend) keys a small LRU cache of compiled executables. A cache hit runs a
prebuilt closure holding the widened exec plan, the cached entry pool and
the post-filter decision; the underlying jit cache is hit by construction
(same signature ⇒ same static args + shapes ⇒ zero new traces — asserted
via ``core.routing.trace_count`` in the tests).

Repeated serving batches (the common case: fixed batch shape, fixed params)
therefore pay one dict lookup + the device computation, nothing else.

The serving layer (``repro.serve``) leans on two properties here:

* signatures are *bucket-friendly* — batch size is part of the signature, so
  the microbatcher pads every coalesced batch up to a fixed bucket ladder
  (1/8/32/…) and the whole serving stream collapses onto a handful of
  resident executables;
* padded rows can never perturb real rows — all traversal state is per-row
  and the entry pool is row-invariant (``routing.make_entry_ids`` draws one
  seed set shared by every row), so a query returns bit-identical top-k
  whether it is served alone or coalesced into a padded bucket batch.

A multi-tenant stream can still produce many distinct signatures (tenants ×
predicate kinds × buckets), so the cache is an explicitly bounded LRU:
``max_entries`` caps resident executables and ``stats()`` reports evictions
(an evicted signature recompiles on its next miss — correct, just slower).
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import TYPE_CHECKING, Callable, NamedTuple, Optional

from repro.core import lru_get
from repro.core import routing as routing_mod
from repro.core.routing import RoutingConfig, SearchResult
from repro.obs import trace as obs_trace
from repro.api.query import QueryBatch

if TYPE_CHECKING:
    from repro.api.engine import Engine, SearchParams
    from repro.api.planner import Plan

__all__ = ["Executor", "PlanSignature"]

#: Default executables kept per engine; least-recently-used beyond this are
#: dropped (signatures are tiny — this bounds closures + cached entry pools).
#: Override per engine via ``Engine(executor_max_entries=...)``.
CACHE_SIZE = 256


class PlanSignature(NamedTuple):
    """Everything that changes the compiled executable. Two batches with
    equal signatures are served by the same closure (and the same jit
    trace); array *values* — query vectors, targets, mask bits — are
    runtime operands, not signature."""

    backend: str
    batch: int  # B
    feat_dim: int  # M
    targets_ndim: int  # 2 point | 3 interval
    has_mask: bool
    has_one_of: bool
    routing_cfg: Optional[RoutingConfig]
    quant_mode: str
    k: int
    seed: int
    enforce: bool
    pool: int  # effective pool — the brute two-stage cut (None routing_cfg)
    rerank: int  # rerank_size — bounds the brute ADC exact rerank
    # partitioned backend only (defaults keep legacy signatures equal):
    nprobe: int = 0  # partitions probed per query
    sub_backend: str = ""  # per-partition execution mode


class Executor:
    """Per-engine plan-signature cache of compiled search executables."""

    def __init__(self, engine: "Engine", max_entries: int = CACHE_SIZE):
        if max_entries <= 0:
            raise ValueError("max_entries must be positive")
        self._engine = engine
        self.max_entries = max_entries
        self._cache: OrderedDict[PlanSignature, Callable] = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def stats(self) -> dict:
        """Host-side cache counters (no device traffic): hits, misses,
        evictions, resident size and the configured bound."""
        return {
            "hits": self.hits, "misses": self.misses,
            "evictions": self.evictions, "size": len(self._cache),
            "max_entries": self.max_entries,
        }

    def clear(self) -> None:
        """Drop every resident executable (the engine's index was swapped —
        e.g. a ``repro.mutable`` merge — so cached entry pools and closures
        are stale). Counters survive: ``ServerStats`` snapshots them at
        construction and reports deltas, which must stay monotone across
        merges."""
        self._cache.clear()

    def signature(
        self, queries: QueryBatch, params: "SearchParams", plan: "Plan"
    ) -> PlanSignature:
        return PlanSignature(
            backend=plan.backend,
            batch=queries.batch_size,
            feat_dim=queries.vectors.shape[1],
            targets_ndim=queries.targets.ndim,
            has_mask=queries.mask is not None,
            has_one_of=queries.has_one_of,
            routing_cfg=plan.routing_cfg,
            quant_mode=plan.quant_mode,
            k=params.k,
            seed=params.seed,
            enforce=params.enforce_equality,
            pool=params.effective_pool,
            rerank=params.rerank_size,
            nprobe=plan.nprobe,
            sub_backend=plan.sub_backend,
        )

    def run(
        self, queries: QueryBatch, params: "SearchParams", plan: "Plan"
    ) -> SearchResult:
        # a lookup in the signature cache; a miss builds a closure, and the
        # XLA compile (if any) happens at its first dispatch
        with obs_trace.span("engine.lookup") as sp:
            sig = self.signature(queries, params, plan)
            size0 = len(self._cache)
            fn, hit = lru_get(
                self._cache, sig, lambda: self._compile(params, plan, sig),
                self.max_entries,
            )
            if hit:
                self.hits += 1
            else:
                self.misses += 1
                if len(self._cache) == size0:  # insert displaced the LRU
                    self.evictions += 1
            if sp:
                sp.set("hit", hit)
                sp.set("backend", sig.backend)
                sp.set("batch", sig.batch)
        # the asynchronous launch of the device programs: the caller waits
        # for the result (the batcher's ``engine.wait``), never this span
        with obs_trace.span("engine.dispatch"):
            return fn(queries)

    # -- compilation ---------------------------------------------------------

    def _compile(
        self, params: "SearchParams", plan: "Plan", sig: PlanSignature
    ) -> Callable[[QueryBatch], SearchResult]:
        """Build the executable for one signature: resolve the widened exec
        plan and the post-filter once, pre-generate the entry pool, and
        close over the backend."""
        engine = self._engine
        needs_filter = sig.has_one_of or (
            sig.enforce and sig.targets_ndim == 3
        )
        # A partitioned plan with a brute sub-backend scans every probed row
        # exactly like the flat brute backend — same in-kernel predicate
        # handling, so no cut-widening and no host post-filter pass.
        acts_like_brute = plan.backend == "brute" or (
            plan.backend == "partitioned" and plan.sub_backend == "brute"
        )
        exec_params, exec_plan = params, plan
        if needs_filter and not acts_like_brute:
            # Widen the traversal cut from k to the whole exactly-scored
            # head: the covering-interval penalty admits in-hull non-members
            # with zero gap, so the membership filter below needs surplus
            # candidates to backfill the slots they displace. On the exact
            # path the entire pool is exactly scored (rerank_size only
            # bounds the quantized rerank stage).
            cfg = plan.routing_cfg
            repl = {}
            if plan.quant_mode == "none":
                wide_k = cfg.pool_size
                repl["rerank_size"] = 0  # unused on the exact path
            else:
                wide_k = cfg.effective_rerank
            if wide_k > params.k:
                exec_params = dataclasses.replace(params, k=wide_k)
                exec_plan = dataclasses.replace(
                    plan,
                    routing_cfg=dataclasses.replace(cfg, k=wide_k, **repl),
                )

        entry_ids = None
        if exec_plan.backend == "graph":
            # entry pool is a pure function of (N, B, pool, seed): generate
            # the host RNG draw + device transfer once per signature
            entry_ids = routing_mod.make_entry_ids(
                engine.n_items, sig.batch,
                exec_plan.routing_cfg.pool_size, sig.seed,
            )
        searcher = engine.searcher(exec_plan.backend)
        do_filter = needs_filter and not acts_like_brute
        k = params.k
        enforce = params.enforce_equality

        def run(queries: QueryBatch) -> SearchResult:
            res = searcher.search(
                engine, queries, exec_params, exec_plan, entry_ids=entry_ids
            )
            if do_filter:
                # ONE_OF membership is exact on every backend; full
                # predicate enforcement (MATCH/BETWEEN included) only under
                # enforce_equality — the host-side pass also re-sorts so
                # survivors keep the ascending-with-INVALID-tail invariant.
                res = engine._predicate_filter(res, queries, enforce)
                if res.ids.shape[1] > k:
                    res = res._replace(
                        ids=res.ids[:, :k],
                        dists=res.dists[:, :k],
                        sqdists=res.sqdists[:, :k],
                    )
            return res

        return run
