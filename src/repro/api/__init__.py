"""Unified query/engine API — the stable public surface of the repo.

The paper's masking mechanism (§III-E, Eq. 8) promises one index for every
query class: full-equality, subset/wildcard and missing-value hybrid
queries. This package is that promise as an API:

* ``Query`` / ``QueryBatch`` — declarative hybrid queries. A feature vector
  plus per-attribute ``MATCH`` / ``ANY`` / ``ONE_OF`` / ``BETWEEN``
  predicates that compile to the (qa, mask) pair of Eq. 8 plus, for wide
  predicates, per-dimension [lo, hi] interval targets every scorer
  consumes natively — value-set and range queries ride the HELP graph.
* ``SearchParams`` — one consolidated knob surface (k, pool, rerank, quant,
  seed, enforce-equality, backend override).
* ``Engine`` — the single search facade, an explicit plan→compile→execute
  pipeline: a calibrated ``CostModel`` (``api.planner``) predicts per-query
  brute vs graph cost and picks the backend per batch; an ``Executor``
  (``api.executor``) caches compiled executables by plan signature so
  repeated serving batches skip Python dispatch and jit re-tracing; a
  ``Searcher`` protocol executes over three backends (single-host graph,
  mesh-sharded, brute-force oracle). Codec state is derived from the index,
  never copied by callers.

Typical use::

    from repro.api import Engine, QueryBatch, SearchParams, MATCH, ANY

    eng = Engine.build(features, attrs)              # or Engine.load(path)
    res = eng.search(QueryBatch.match(qv, qa), SearchParams(k=10))

    # subset query: constrain only the first two attributes
    res = eng.search(QueryBatch.match(qv, qa, active=[0, 1]))

    # fully declarative single requests
    from repro.api import Query, ONE_OF
    batch = QueryBatch.from_queries(
        [Query(v, [MATCH(2), ANY, ONE_OF(0, 1)]) for v in vectors]
    )
    res = eng.search(batch, SearchParams(k=10, enforce_equality=True))

``Engine.plan(batch, params)`` exposes the planner decision (backend,
resolved quant mode, routing config, predicted brute/graph costs, reason)
without executing it; ``Engine.executor.stats()`` reports plan-cache
hits/misses.
"""
from repro.api.engine import (
    Engine,
    Searcher,
    SearchParams,
)
from repro.api.executor import Executor, PlanSignature
from repro.api.planner import CostModel, Plan, cost_model_from_table
from repro.api.query import (
    ANY, BETWEEN, MATCH, ONE_OF, Predicate, Query, QueryBatch,
)
from repro.core.routing import SearchResult

__all__ = [
    "ANY",
    "BETWEEN",
    "CostModel",
    "Engine",
    "Executor",
    "MATCH",
    "ONE_OF",
    "Plan",
    "PlanSignature",
    "Predicate",
    "Query",
    "QueryBatch",
    "SearchParams",
    "SearchResult",
    "Searcher",
    "cost_model_from_table",
]
