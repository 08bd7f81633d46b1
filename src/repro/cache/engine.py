"""TieredEngine: frequency-aware hot/cold tiering over an ``api.Engine``.

Wraps a built engine the way ``MutableEngine`` does — same ``search``
surface, its own ``Executor`` so compiled closures resolve *tiered*
searcher backends — and adds the frequency feedback loop:

    search → observe returned row ids (host-side already) → every
    ``epoch_queries`` queries: decay counters, recompute the hot set with
    hysteresis, rebuild the contiguous device slice / pinned partitions.

Execution changes only where the full-precision rerank gathers its rows:

* **flat quantized engines** (sq8/pq/pq4/opq-*): the graph backend runs the
  traversal over codes with ``routing.search_pool`` (no f32 operand at
  all), gathers the pool head through ``HotTier.gather`` (hot rows: direct
  device take; cold rows: host gather + one small transfer) and emits via
  ``routing.rerank_gathered`` — the same op sequence as ``emit_topk``. The
  brute ADC backend splices the identical tier gather into its (already
  eager) two-stage scan. Both are bit-identical to the untiered engine.
* **partitioned engines**: tiering is partition-granular (the chunk design
  of freq-aware embedding caches): hot rows vote for their partitions and
  the top partitions under the row budget pin resident in the
  ``SegmentStore`` (the LRU never evicts them, prefetch skips them), so
  skewed probe streams stop paying reload/transfer for their head.
* **unquantized plans** pass through: the rerank *is* the scan there, a
  full f32 matrix is already resident, and there is nothing to tier.

Sharded engines are rejected (rerank lives inside ``shard_map``);
``MutableEngine`` is rejected as a base (merges renumber rows under the
tracker — the serve-layer ``ResultCache`` epoch covers write traffic
instead).
"""
from __future__ import annotations

from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import auto as auto_mod
from repro.core import routing as routing_mod
from repro.core.graph_ops import INF
from repro.obs import trace as obs_trace
from repro.quant import adc_scan, is_pq_mode
from repro.api import engine as engine_mod
from repro.api.engine import Engine, SearchParams
from repro.api.executor import Executor
from repro.api.planner import Plan
from repro.api.query import QueryBatch
from repro.cache.freq import FrequencyTracker
from repro.cache.tier import HotTier

__all__ = ["TieredEngine"]


class _TieredGraphSearcher:
    """HELP traversal over codes + tier-routed exact rerank."""

    name = "graph"

    def __init__(self):
        self._base = engine_mod.GraphSearcher()

    def search(self, engine, queries, params, plan, entry_ids=None):
        if plan.quant_mode == "none":
            # exact plans gather nothing beyond the traversal itself
            return self._base.search(engine, queries, params, plan, entry_ids)
        idx = engine.index
        cfg = plan.routing_cfg
        qv = jnp.asarray(queries.vectors, jnp.float32)
        qa = jnp.asarray(queries.targets, jnp.int32)
        mask = None if queries.mask is None else jnp.asarray(queries.mask)
        n = idx.features.shape[0]
        if entry_ids is None:
            entry_ids = routing_mod.make_entry_ids(
                n, qv.shape[0], cfg.pool_size, params.seed
            )
        r_ids, evals, hops = routing_mod.search_pool(
            idx.attrs, idx.graph, qv, qa, entry_ids, idx.metric_cfg, cfg, n,
            mask, idx.quant.routing_operand(qv),
        )
        cv = engine.tier.gather(np.asarray(r_ids))
        return routing_mod.rerank_gathered(
            cv, idx.attrs, r_ids, qv, qa, idx.metric_cfg, cfg, mask,
            evals, hops,
        )


class _TieredBruteSearcher:
    """ADC two-stage scan with the f32 rerank gather routed via the tier.

    Mirrors ``BruteForceSearcher._adc_two_stage`` op for op — the path is
    eager, so substituting value-identical ``cv`` rows keeps every
    downstream bit identical. Non-ADC brute plans (exact oracle) pass
    through: they scan the full f32 matrix, nothing to tier.
    """

    name = "brute"

    def __init__(self):
        self._base = engine_mod.BruteForceSearcher()

    def search(self, engine, queries, params, plan, entry_ids=None):
        idx = engine.index
        if not (is_pq_mode(plan.quant_mode) and idx.quant is not None):
            return self._base.search(engine, queries, params, plan, entry_ids)
        qv = jnp.asarray(queries.vectors, jnp.float32)
        lut = idx.quant.lut(qv)
        scores = adc_scan(
            lut, idx.quant.scan_codes,
            jnp.asarray(queries.attrs, jnp.int32),
            jnp.asarray(idx.attrs), mode="l2", packed=idx.quant.packed,
        )
        ok = engine_mod._ok_matrix(engine, queries)
        pool = min(params.effective_pool, scores.shape[1])
        pool = min(max(params.rerank_size or pool, params.k), pool)
        neg, cand = jax.lax.top_k(-jnp.where(ok, scores, INF), pool)
        cv = engine.tier.gather(np.asarray(cand))
        rd = auto_mod.feature_sqdist(qv[:, None, :], cv)
        rd = jnp.where(-neg < INF / 2, rd, INF)
        res = engine_mod._filtered_topk(
            rd, jnp.ones_like(rd, bool), params.k, full_evals=pool, ids=cand
        )
        n = idx.quant.codes.shape[0]
        return res._replace(
            n_code_evals=jnp.full((qv.shape[0],), n, jnp.int32)
        )


class TieredEngine:
    """Engine wrapper adding frequency-tracked hot/cold tiering."""

    def __init__(
        self,
        engine: Engine,
        hot_rows: int = 0,
        epoch_queries: int = 512,
        decay: float = 0.5,
        hysteresis: float = 1.5,
    ):
        if not isinstance(engine, Engine):
            raise TypeError(
                "TieredEngine wraps a built api.Engine (wrap the engine, "
                "not a MutableEngine — tier row ids do not survive merges; "
                "write traffic is covered by the serve ResultCache epoch)"
            )
        if engine.is_sharded:
            raise ValueError(
                "sharded engines rerank inside shard_map; tiering applies "
                "to flat and partitioned engines"
            )
        if epoch_queries <= 0:
            raise ValueError("epoch_queries must be positive")
        self.base = engine
        self.hot_rows = int(hot_rows)
        self.epoch_queries = int(epoch_queries)
        self.tracker = FrequencyTracker(engine.n_items, decay=decay)
        self._since_epoch = 0
        self._graph = _TieredGraphSearcher()
        self._brute = _TieredBruteSearcher()
        self._executor: Optional[Executor] = None
        self._pid_of: Optional[np.ndarray] = None  # partitioned: row → pid
        if engine.is_partitioned:
            self.tier = None
        else:
            self.tier = HotTier(
                np.asarray(engine.index.features),
                hot_rows,
                hysteresis=hysteresis,
            )

    # -- engine facade (duck-typed like MutableEngine) ---------------------

    @property
    def index(self):
        return self.base.index

    @property
    def is_sharded(self) -> bool:
        return False

    @property
    def is_partitioned(self) -> bool:
        return self.base.is_partitioned

    @property
    def n_items(self) -> int:
        return self.base.n_items

    @property
    def attr_dim(self) -> int:
        return self.base.attr_dim

    @property
    def quant_mode(self) -> str:
        return self.base.quant_mode

    @property
    def has_graph(self) -> bool:
        return self.base.has_graph

    @property
    def cost_model(self):
        return self.base.cost_model

    @property
    def host_attrs(self) -> np.ndarray:
        return self.base.host_attrs

    @property
    def write_epoch(self) -> int:
        return getattr(self.base, "write_epoch", 0)

    @property
    def executor(self) -> Executor:
        """Own executable cache — closures must resolve *tiered* backends."""
        if self._executor is None:
            self._executor = Executor(
                self, max_entries=self.base.executor_max_entries
            )
        return self._executor

    def searcher(self, name: str):
        if self.tier is not None and name == "graph":
            return self._graph
        if self.tier is not None and name == "brute":
            return self._brute
        return self.base.searcher(name)

    def plan(self, queries: QueryBatch, params: SearchParams) -> Plan:
        return self.base.plan(queries, params)

    def _predicate_filter(self, res, queries, full):
        return self.base._predicate_filter(res, queries, full)

    def invalidate_caches(self) -> None:
        self.base.invalidate_caches()
        if self._executor is not None:
            self._executor.clear()

    def save(self, path: str) -> None:
        self.base.save(path)

    # -- search + feedback loop --------------------------------------------

    def search(
        self,
        queries: Union[QueryBatch, tuple],
        params: SearchParams = SearchParams(),
    ):
        if isinstance(queries, tuple):
            queries = QueryBatch.match(*queries)
        with obs_trace.span("engine.search") as sp:
            with obs_trace.span("engine.plan") as psp:
                plan = self.plan(queries, params)
                if psp:
                    psp.set("backend", plan.backend)
                    psp.set("quant_mode", plan.quant_mode)
                    psp.set("reason", plan.reason)
                    psp.set("cost_brute", plan.cost_brute)
                    psp.set("cost_graph", plan.cost_graph)
            if sp and self.tier is not None:
                hot0 = self.tier.hot_row_hits
                cold0 = self.tier.cold_row_gathers
            res = self.executor.run(queries, params, plan)
            if sp and self.tier is not None:
                # the gather happened inside the executor's dispatch span;
                # report the tier split for this request as counter deltas
                sp.set("tier_hot_hits", self.tier.hot_row_hits - hot0)
                sp.set("tier_cold_gathers", self.tier.cold_row_gathers - cold0)
        ids = np.asarray(res.ids)
        self.tracker.observe(ids)
        self._since_epoch += int(ids.shape[0])
        if self._since_epoch >= self.epoch_queries:
            self._since_epoch = 0
            self.refresh_tier()
        return res

    def refresh_tier(self) -> None:
        """End a frequency epoch: recompute the hot set (with hysteresis),
        rebuild the device slice / re-pin partitions, decay counters."""
        counts = self.tracker.snapshot()
        if self.tier is not None:
            self.tier.promote(counts)
        elif self.hot_rows > 0:
            self._pin_partitions(counts)
        self.tracker.end_epoch()

    # -- partitioned tiering: pin hot partitions resident ------------------

    def _row_to_pid(self) -> np.ndarray:
        """(N,) global row id → partition id, built once from the
        per-partition ``row_ids`` arrays (mmaps when disk-backed)."""
        if self._pid_of is None:
            idx = self.base.index
            pid_of = np.full(self.n_items, -1, np.int32)
            for pid in range(idx.n_partitions):
                rows = np.asarray(idx._load_partition(pid).row_ids)
                pid_of[rows] = pid
            self._pid_of = pid_of
        return self._pid_of

    def _pin_partitions(self, counts: np.ndarray) -> None:
        """Partition-granular promotion: sum row frequency per partition,
        greedily pin the hottest partitions whose padded row buckets fit
        under min(hot_rows, cap_rows)."""
        from repro.partition.store import row_bucket

        idx = self.base.index
        store = idx.store
        per_pid = np.zeros(idx.n_partitions, np.float64)
        np.add.at(per_pid, self._row_to_pid(), counts)
        budget = min(self.hot_rows, store.cap_rows)
        pinned, rows = [], 0
        for pid in np.argsort(-per_pid, kind="stable"):
            if per_pid[pid] <= 0:
                break
            b = row_bucket(int(idx.summaries.n_rows[pid]), store.bucket_min)
            if rows + b > budget:
                continue  # a smaller hot partition may still fit
            pinned.append(int(pid))
            rows += b
        store.pin(pinned)

    # -- introspection -----------------------------------------------------

    def tier_stats(self) -> dict:
        """Tier counters for ``ServerStats``/launchers: flat engines report
        the ``HotTier`` gather split, partitioned engines the pinned set +
        ``SegmentStore`` residency counters (pinned partitions turn probe
        loads into hits)."""
        out = {
            "hot_rows_budget": self.hot_rows,
            "epoch_queries": self.epoch_queries,
            "tracker": self.tracker.stats(),
        }
        if self.tier is not None:
            out.update(self.tier.stats())
        else:
            store = self.base.index.store
            s = store.stats()
            total = s["hits"] + s["loads"]
            out.update(s)
            out["tier_hit_rate"] = (s["hits"] / total) if total else 0.0
        return out
