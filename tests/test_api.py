"""Unified query/engine API: predicate→mask compilation semantics, planner
rules (calibrated cost model + deprecated fixed-threshold shim), executor
plan-cache semantics, and engine-vs-legacy bit-exact parity on all three
backends (including after ``Engine.save/load``, sharded layouts included)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (
    ANY, BETWEEN, MATCH, ONE_OF, CostModel, Engine, Predicate, Query,
    QueryBatch, SearchParams, cost_model_from_table,
)
from repro.core import auto as auto_mod
from repro.core import routing as routing_mod
from repro.core.auto import MetricConfig
from repro.core.baselines import brute_force_hybrid, recall_at_k
from repro.core.help_graph import HelpConfig
from repro.core.index import StableIndex
from repro.core.routing import RoutingConfig
from repro.data.synthetic import make_hybrid_dataset
from repro.quant import QuantConfig, QuantizedVectors

HELP_CFG = HelpConfig(gamma=12, gamma_new=4, max_rounds=3,
                      quality_sample=64, node_block=512)


@pytest.fixture(scope="module")
def ds():
    return make_hybrid_dataset(
        n=3000, n_queries=24, profile="sift", attr_dim=5, labels_per_dim=3,
        n_clusters=8, attr_cluster_corr=0.6, seed=0,
    )


@pytest.fixture(scope="module")
def engines(ds):
    """One engine per quant mode over the same dataset."""
    out = {}
    for mode in ("none", "sq8", "pq"):
        out[mode] = Engine.build(
            ds.features, ds.attrs, HELP_CFG,
            quant_cfg=QuantConfig(mode=mode, pq_subspaces=8, pq_train_iters=4),
        )
    return out


# ---------------------------------------------------------------------------
# Predicate → mask compilation semantics
# ---------------------------------------------------------------------------


class TestPredicateCompile:
    def test_match_compiles_to_active_dim(self):
        q = Query(np.zeros(4), [MATCH(2), MATCH(0), MATCH(1)])
        b = QueryBatch.from_queries([q])
        assert b.attrs.tolist() == [[2, 0, 1]]
        assert b.mask is None  # all-MATCH ≡ legacy mask-free path
        assert b.allowed is None and not b.has_one_of

    def test_any_compiles_to_zero_mask(self):
        q = Query(np.zeros(4), [MATCH(2), ANY, MATCH(1)])
        b = QueryBatch.from_queries([q])
        assert b.mask.tolist() == [[1, 0, 1]]
        assert b.has_wildcard and not b.is_pure_ann

    def test_all_wildcard_is_pure_ann(self):
        b = QueryBatch.from_queries([Query(np.zeros(4), [ANY, ANY])])
        assert b.is_pure_ann
        assert QueryBatch.pure_ann(np.zeros((2, 4)), 3).is_pure_ann

    def test_one_of_target_and_membership(self):
        p = ONE_OF(0, 4)
        assert p.target in (0, 4)  # hull midpoint 2 → nearest member
        assert ONE_OF(1, 2, 9).target == 2  # mid 5 → 2 closer than 9? |2-5|=3 <
        assert ONE_OF(3).target == 3
        assert p.interval == (0, 4)  # traversal rides the covering hull
        assert p.admits(0) and p.admits(4) and not p.admits(2)
        q = Query(np.zeros(4), [ONE_OF(0, 2), MATCH(1)])
        b = QueryBatch.from_queries([q])
        assert b.has_one_of and b.has_intervals
        assert b.mask is None  # both dims active
        assert b.intervals[0].tolist() == [[0, 2], [1, 1]]
        assert sorted(v for v in b.allowed[0, 0] if v >= 0) == [0, 2]
        ok = b.admissible(np.array([[0, 1], [2, 1], [1, 1], [0, 0]]))
        assert ok.tolist() == [[True, True, False, False]]

    def test_between_compiles_to_interval(self):
        p = BETWEEN(1, 3)
        assert p.interval == (1, 3) and p.active and not p.is_point
        assert p.admits(1) and p.admits(2) and p.admits(3)
        assert not p.admits(0) and not p.admits(4)
        q = Query(np.zeros(4), [BETWEEN(1, 3), MATCH(0), ANY])
        b = QueryBatch.from_queries([q])
        assert b.has_intervals and not b.has_one_of
        assert b.intervals[0].tolist() == [[1, 3], [0, 0], [0, 0]]
        assert b.mask.tolist() == [[1, 1, 0]]
        # exact hard-filter semantics: containment + equality + wildcard
        ok = b.admissible(np.array([[2, 0, 5], [0, 0, 5], [3, 1, 5]]))
        assert ok.tolist() == [[True, False, False]]

    def test_point_batches_skip_intervals(self):
        """MATCH/ANY/degenerate-interval predicates compile to the legacy
        point path (intervals=None) — the bit-exactness precondition."""
        qs = [Query(np.zeros(4), [MATCH(2), ANY, ONE_OF(1), BETWEEN(3, 3)])]
        b = QueryBatch.from_queries(qs)
        assert b.intervals is None and b.targets is b.attrs
        assert b.attrs.tolist() == [[2, 0, 1, 3]]
        assert b.has_one_of  # single-member ONE_OF still hard-filters

    def test_match_batch_with_active_equals_manual_mask(self, ds):
        b = QueryBatch.match(ds.query_features, ds.query_attrs, active=[0, 2])
        mask = np.zeros_like(ds.query_attrs)
        mask[:, [0, 2]] = 1
        np.testing.assert_array_equal(b.mask, mask)
        np.testing.assert_array_equal(b.attrs, ds.query_attrs)

    def test_bad_predicates_rejected(self):
        with pytest.raises(ValueError):
            Predicate("match", ())
        with pytest.raises(ValueError):
            ONE_OF()
        with pytest.raises(ValueError):
            BETWEEN(3, 1)  # lo > hi
        with pytest.raises(ValueError):
            Predicate("between", (1,))  # needs both bounds
        with pytest.raises(ValueError):
            Predicate("less_than", (1,))


# ---------------------------------------------------------------------------
# Planner
# ---------------------------------------------------------------------------


class TestPlanner:
    def test_small_index_plans_brute(self, ds, engines):
        plan = engines["none"].plan(
            QueryBatch.match(ds.query_features, ds.query_attrs),
            SearchParams(k=10, brute_threshold=5000),
        )
        assert plan.backend == "brute" and plan.routing_cfg is None

    def test_large_index_plans_graph(self, ds, engines):
        plan = engines["none"].plan(
            QueryBatch.match(ds.query_features, ds.query_attrs),
            SearchParams(k=10, brute_threshold=100),
        )
        assert plan.backend == "graph"
        assert plan.routing_cfg == RoutingConfig(k=10, pool_size=40)

    def test_quant_mode_derived_from_index(self, ds, engines):
        qb = QueryBatch.match(ds.query_features, ds.query_attrs)
        for mode in ("none", "sq8", "pq"):
            plan = engines[mode].plan(qb, SearchParams(k=10, brute_threshold=100))
            assert plan.quant_mode == mode
            assert plan.routing_cfg.quant_mode == mode

    def test_one_of_plans_graph(self, ds, engines):
        """Predicate class no longer forces the brute oracle: ONE_OF and
        BETWEEN batches traverse the HELP graph (interval targets), brute
        stays a purely size/graph-less decision."""
        for preds in ([ONE_OF(0, 2), ANY, ANY, ANY, ANY],
                      [BETWEEN(0, 1), ANY, ANY, ANY, ANY]):
            qs = [Query(ds.query_features[0], preds)]
            plan = engines["none"].plan(
                QueryBatch.from_queries(qs),
                SearchParams(k=5, brute_threshold=100),
            )
            assert plan.backend == "graph", preds
        # …but the size rule still wins below the threshold
        qs = [Query(ds.query_features[0], [ONE_OF(0, 2), ANY, ANY, ANY, ANY])]
        plan = engines["none"].plan(
            QueryBatch.from_queries(qs), SearchParams(k=5, brute_threshold=5000)
        )
        assert plan.backend == "brute"

    def test_graphless_engine_plans_brute(self, ds):
        eng = Engine.build(ds.features[:500], ds.attrs[:500], build_graph=False)
        assert not eng.has_graph
        plan = eng.plan(QueryBatch.match(ds.query_features, ds.query_attrs),
                        SearchParams(k=5, brute_threshold=1))
        assert plan.backend == "brute"
        with pytest.raises(ValueError):
            eng.plan(QueryBatch.match(ds.query_features, ds.query_attrs),
                     SearchParams(k=5, backend="graph"))

    def test_quant_mismatch_rejected(self, ds, engines):
        qb = QueryBatch.match(ds.query_features, ds.query_attrs)
        with pytest.raises(ValueError):
            engines["sq8"].plan(qb, SearchParams(k=10, quant="pq"))

    def test_bad_params_rejected(self):
        with pytest.raises(ValueError):
            SearchParams(backend="gpu")
        with pytest.raises(ValueError):
            SearchParams(quant="fp4")


# ---------------------------------------------------------------------------
# Cost-model planner + deprecated threshold shim
# ---------------------------------------------------------------------------


class TestCostModelPlanner:
    def test_cost_model_monotonicity(self, ds, engines):
        """Predicted graph cost grows with pool size, brute with N (and
        graph never shrinks with N either)."""
        cm = engines["none"].cost_model
        pools = [16, 32, 64, 128, 256]
        g = [cm.graph_cost(n=3000, pool=p, batch=16) for p in pools]
        assert all(a < b for a, b in zip(g, g[1:])), g
        ns = [1000, 5000, 20000, 100000, 1000000]
        b = [cm.brute_cost(n=n, pool=64) for n in ns]
        assert all(x < y for x, y in zip(b, b[1:])), b
        gn = [cm.graph_cost(n=n, pool=64, batch=16) for n in ns]
        assert all(x <= y for x, y in zip(gn, gn[1:])), gn
        # quantized scans discount the N term but still grow with N
        bq = [cm.brute_cost(n=n, pool=64, quant_mode="pq") for n in ns]
        assert all(x < y for x, y in zip(bq, bq[1:])), bq
        assert bq[-1] < b[-1]  # ADC scan cheaper than exact at scale

    def test_auto_plan_uses_cost_model(self, ds, engines):
        """Without overrides the planner must decide from the calibrated
        crossover and expose both predicted costs on the Plan."""
        plan = engines["none"].plan(
            QueryBatch.match(ds.query_features, ds.query_attrs),
            SearchParams(k=10),
        )
        assert plan.cost_brute is not None and plan.cost_graph is not None
        assert plan.backend in ("brute", "graph")
        assert (plan.backend == "brute") == (
            plan.cost_brute <= plan.cost_graph
        )
        assert "cost model" in plan.reason

    def test_widening_predicates_raise_graph_cost(self, ds, engines):
        """The width surcharge prices the executor's cut-widening — charged
        exactly when the widening will run: ONE_OF always, BETWEEN only
        under enforce_equality (soft BETWEEN traverses at plain k, so its
        graph cost must match the point batch's)."""
        eng = engines["none"]
        point = QueryBatch.match(ds.query_features[:8], ds.query_attrs[:8])
        one_of = QueryBatch.from_queries([
            Query(ds.query_features[i],
                  [ONE_OF(0, 2), BETWEEN(0, 1), ANY, ANY, ANY])
            for i in range(8)
        ])
        soft_between = QueryBatch.from_queries([
            Query(ds.query_features[i],
                  [BETWEEN(0, 2), BETWEEN(0, 1), ANY, ANY, ANY])
            for i in range(8)
        ])
        p_point = eng.plan(point, SearchParams(k=10))
        p_one_of = eng.plan(one_of, SearchParams(k=10))
        p_soft = eng.plan(soft_between, SearchParams(k=10))
        p_hard = eng.plan(soft_between,
                          SearchParams(k=10, enforce_equality=True))
        assert p_one_of.cost_graph > p_point.cost_graph
        assert p_soft.cost_graph == pytest.approx(p_point.cost_graph)
        assert p_hard.cost_graph > p_soft.cost_graph
        for p in (p_one_of, p_soft, p_hard):
            assert p.cost_brute == pytest.approx(p_point.cost_brute)

    def test_cost_model_table_roundtrip(self, engines):
        cm = engines["none"].cost_model
        cm2 = cost_model_from_table({"cost_model": cm.to_json()})
        assert cm2 == cm
        # injected models skip the probe entirely
        eng = Engine(engines["none"].index, cost_model_override=cm2)
        assert eng.cost_model == cm
        with pytest.raises(ValueError):
            CostModel(unit_evals=0.0, probe_pool=32, probe_n=100)

    def test_brute_threshold_deprecated_but_honored(self, ds, engines):
        """The old knob survives as a hard override: explicitly set, it
        pins the decision (warning emitted); unset, the cost model rules."""
        eng = engines["none"]
        qb = QueryBatch.match(ds.query_features, ds.query_attrs)
        with pytest.warns(DeprecationWarning, match="brute_threshold"):
            plan = eng.plan(qb, SearchParams(k=10, brute_threshold=10**6))
        assert plan.backend == "brute"
        assert plan.cost_brute is None  # cost model never consulted
        with pytest.warns(DeprecationWarning, match="brute_threshold"):
            plan = eng.plan(qb, SearchParams(k=10, brute_threshold=1))
        assert plan.backend == "graph"
        # the override also flows through Engine.search end to end
        with pytest.warns(DeprecationWarning):
            res = eng.search(qb, SearchParams(k=10, brute_threshold=10**6))
        truth = brute_force_hybrid(
            ds.features, ds.attrs, ds.query_features, ds.query_attrs, 10
        )
        np.testing.assert_array_equal(np.asarray(res.ids),
                                      np.asarray(truth.ids))

    def test_tiny_graph_index_auto_plans_without_crash(self, ds):
        """Calibration must cope with indexes smaller than the probe shape
        (k/pioneer clamp to the pool, pool clamps to N)."""
        eng = Engine.build(
            ds.features[:6], ds.attrs[:6],
            HelpConfig(gamma=4, gamma_new=2, max_rounds=2,
                       quality_sample=4, node_block=64),
        )
        plan = eng.plan(
            QueryBatch.match(ds.query_features, ds.query_attrs),
            SearchParams(k=2),
        )
        assert plan.backend in ("brute", "graph")
        assert plan.cost_brute is not None

    def test_quant_none_priced_at_full_precision(self, ds, engines):
        """quant='none' forces full-precision execution, so the planner
        must price the N-row fp scan, not the ADC code scan that won't
        run."""
        eng = engines["pq"]
        qb = QueryBatch.match(ds.query_features, ds.query_attrs)
        p_auto = eng.plan(qb, SearchParams(k=10))
        p_none = eng.plan(qb, SearchParams(k=10, quant="none"))
        assert p_none.quant_mode == "none"
        assert p_none.cost_brute > p_auto.cost_brute

    def test_sharded_cost_model_raises_clearly(self):
        """cost_model is single-host only (sharded always plans sharded) —
        accessing it on a sharded engine must fail with a clear error, not
        an AttributeError from the probe poking missing fields."""

        class _FakeShardedIndex:  # anything that isn't a StableIndex
            pass

        eng = Engine(_FakeShardedIndex())
        assert eng.is_sharded
        with pytest.raises(ValueError, match="single-host"):
            eng.cost_model

    def test_graphless_engine_skips_calibration(self, ds):
        eng = Engine.build(ds.features[:500], ds.attrs[:500],
                           build_graph=False)
        plan = eng.plan(QueryBatch.match(ds.query_features, ds.query_attrs),
                        SearchParams(k=5))
        assert plan.backend == "brute" and plan.cost_brute is None
        assert eng._cost_model is None  # probe never ran


# ---------------------------------------------------------------------------
# Executor plan cache
# ---------------------------------------------------------------------------


class TestExecutorCache:
    def test_same_signature_hits_cache_and_never_retraces(self, ds, engines):
        """Two consecutive searches with the same (batch shape, predicate
        kind, params) signature: the second must reuse the compiled
        executable and add zero new jit traces."""
        eng = engines["none"]
        params = SearchParams(k=7, pool_size=48, pioneer_size=6, seed=3,
                              backend="graph")
        qb = QueryBatch.match(ds.query_features, ds.query_attrs)
        r1 = eng.search(qb, params)
        before = eng.executor.stats()
        t0 = routing_mod.trace_count()
        r2 = eng.search(qb, params)
        assert routing_mod.trace_count() == t0  # zero new traces
        after = eng.executor.stats()
        assert after["hits"] == before["hits"] + 1
        assert after["misses"] == before["misses"]
        np.testing.assert_array_equal(np.asarray(r1.ids), np.asarray(r2.ids))
        np.testing.assert_array_equal(np.asarray(r1.sqdists),
                                      np.asarray(r2.sqdists))

    def test_different_batch_shape_misses(self, ds, engines):
        eng = engines["none"]
        params = SearchParams(k=7, pool_size=48, pioneer_size=6, seed=3,
                              backend="graph")
        eng.search(QueryBatch.match(ds.query_features, ds.query_attrs),
                   params)
        before = eng.executor.stats()
        eng.search(QueryBatch.match(ds.query_features[:8],
                                    ds.query_attrs[:8]), params)
        after = eng.executor.stats()
        assert after["misses"] == before["misses"] + 1

    def test_different_predicate_kind_misses(self, ds, engines):
        eng = engines["none"]
        params = SearchParams(k=7, pool_size=48, pioneer_size=6, seed=3,
                              backend="graph")
        point = QueryBatch.match(ds.query_features[:8], ds.query_attrs[:8])
        interval = QueryBatch.from_queries([
            Query(ds.query_features[i], [BETWEEN(0, 1), ANY, ANY, ANY, ANY])
            for i in range(8)
        ])
        eng.search(point, params)
        before = eng.executor.stats()
        eng.search(interval, params)
        after = eng.executor.stats()
        assert after["misses"] == before["misses"] + 1
        # …and repeating the interval batch is now a hit
        t0 = routing_mod.trace_count()
        eng.search(interval, params)
        assert routing_mod.trace_count() == t0
        assert eng.executor.stats()["hits"] == after["hits"] + 1

    def test_changed_params_miss(self, ds, engines):
        eng = engines["none"]
        qb = QueryBatch.match(ds.query_features[:8], ds.query_attrs[:8])
        eng.search(qb, SearchParams(k=7, pool_size=48, pioneer_size=6,
                                    seed=3, backend="graph"))
        before = eng.executor.stats()
        eng.search(qb, SearchParams(k=7, pool_size=64, pioneer_size=6,
                                    seed=3, backend="graph"))
        assert eng.executor.stats()["misses"] == before["misses"] + 1


# ---------------------------------------------------------------------------
# Engine vs legacy parity (bit-exact)
# ---------------------------------------------------------------------------


class TestEngineLegacyParity:
    @pytest.mark.parametrize("mode", ["none", "sq8", "pq"])
    def test_graph_backend_matches_stable_index(self, ds, engines, mode):
        eng = engines[mode]
        params = SearchParams(k=10, backend="graph")
        res = eng.search(QueryBatch.match(ds.query_features, ds.query_attrs),
                         params)
        legacy = eng.index.search(ds.query_features, ds.query_attrs, 10)
        np.testing.assert_array_equal(np.asarray(res.ids),
                                      np.asarray(legacy.ids))
        np.testing.assert_array_equal(np.asarray(res.sqdists),
                                      np.asarray(legacy.sqdists))

    @pytest.mark.parametrize("mode", ["none", "sq8", "pq"])
    def test_parity_survives_save_load(self, ds, engines, tmp_path, mode):
        eng = engines[mode]
        path = os.path.join(tmp_path, f"eng_{mode}")
        eng.save(path)
        eng2 = Engine.load(path)
        params = SearchParams(k=10, backend="graph")
        qb = QueryBatch.match(ds.query_features, ds.query_attrs)
        np.testing.assert_array_equal(
            np.asarray(eng.search(qb, params).ids),
            np.asarray(eng2.search(qb, params).ids),
        )

    def test_graph_backend_masked_matches_legacy(self, ds, engines):
        qb = QueryBatch.match(ds.query_features, ds.query_attrs, active=[0, 1])
        res = engines["none"].search(qb, SearchParams(k=10, backend="graph"))
        legacy = engines["none"].index.search(
            ds.query_features, ds.query_attrs, 10, mask=qb.mask
        )
        np.testing.assert_array_equal(np.asarray(res.ids),
                                      np.asarray(legacy.ids))

    def test_brute_backend_matches_oracle(self, ds, engines):
        res = engines["none"].search(
            QueryBatch.match(ds.query_features, ds.query_attrs),
            SearchParams(k=10, backend="brute"),
        )
        truth = brute_force_hybrid(
            ds.features, ds.attrs, ds.query_features, ds.query_attrs, 10
        )
        np.testing.assert_array_equal(np.asarray(res.ids),
                                      np.asarray(truth.ids))
        np.testing.assert_array_equal(np.asarray(res.sqdists),
                                      np.asarray(truth.sqdists))

    def test_tuple_queries_accepted(self, ds, engines):
        res = engines["none"].search(
            (ds.query_features, ds.query_attrs), SearchParams(k=5)
        )
        assert np.asarray(res.ids).shape == (ds.query_features.shape[0], 5)


# ---------------------------------------------------------------------------
# Engine semantics beyond the legacy surface
# ---------------------------------------------------------------------------


class TestEngineSemantics:
    def test_per_query_counters(self, ds, engines):
        b = ds.query_features.shape[0]
        res = engines["pq"].search(
            QueryBatch.match(ds.query_features, ds.query_attrs),
            SearchParams(k=10, backend="graph"),
        )
        assert np.asarray(res.n_dist_evals).shape == (b,)
        assert np.asarray(res.n_code_evals).shape == (b,)
        assert res.total_dist_evals == int(np.sum(np.asarray(res.n_dist_evals)))
        assert res.total_code_evals > 0
        assert res.mean_dist_evals == pytest.approx(res.total_dist_evals / b)

    def test_quant_none_forces_full_precision(self, ds, engines):
        res = engines["sq8"].search(
            QueryBatch.match(ds.query_features, ds.query_attrs),
            SearchParams(k=10, backend="graph", quant="none"),
        )
        assert res.total_code_evals == 0
        exact = engines["none"].search(
            QueryBatch.match(ds.query_features, ds.query_attrs),
            SearchParams(k=10, backend="graph"),
        )
        np.testing.assert_array_equal(np.asarray(res.ids),
                                      np.asarray(exact.ids))

    def test_pure_ann_equals_unfiltered_topk(self, ds, engines):
        qb = QueryBatch.pure_ann(ds.query_features, ds.attrs.shape[1])
        res = engines["none"].search(qb, SearchParams(k=5, backend="brute"))
        sv2 = auto_mod.brute_fused_sqdist(
            jnp.asarray(ds.query_features), jnp.asarray(ds.query_attrs),
            jnp.asarray(ds.features), jnp.asarray(ds.attrs),
            MetricConfig(mode="l2"),
        )
        _, tids = jax.lax.top_k(-sv2, 5)
        np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(tids))

    def test_one_of_brute_exact_membership(self, ds, engines):
        qs = [
            Query(ds.query_features[i],
                  [MATCH(int(ds.query_attrs[i, 0])), ONE_OF(0, 2),
                   ANY, ANY, ANY])
            for i in range(8)
        ]
        qb = QueryBatch.from_queries(qs)
        # pin the oracle backend: auto-planning now routes ONE_OF through
        # graph traversal (covered by the traversal membership tests below)
        res = engines["none"].search(qb, SearchParams(k=10, backend="brute"))
        ids = np.asarray(res.ids)
        attrs = np.asarray(ds.attrs)
        # numpy oracle: L2 rank over rows satisfying the predicates
        feats = np.asarray(ds.features, np.float64)
        for i in range(8):
            sat = (attrs[:, 0] == int(ds.query_attrs[i, 0])) & (
                (attrs[:, 1] == 0) | (attrs[:, 1] == 2)
            )
            d = ((feats - ds.query_features[i].astype(np.float64)) ** 2).sum(1)
            want = np.argsort(np.where(sat, d, np.inf), kind="stable")[:10]
            got = ids[i][ids[i] >= 0]
            assert set(got) <= set(np.where(sat)[0])
            # ≥9/10 id overlap tolerates f32-vs-f64 near-tie reordering
            assert len(set(got) & set(want)) >= min(len(got), 9)

    def test_one_of_graph_backend_with_enforcement(self, ds, engines):
        qs = [
            Query(ds.query_features[i],
                  [ANY, ONE_OF(0, 2), ANY, ANY, ANY])
            for i in range(8)
        ]
        qb = QueryBatch.from_queries(qs)
        res = engines["none"].search(
            qb, SearchParams(k=10, backend="graph", enforce_equality=True)
        )
        ids = np.asarray(res.ids)
        a1 = np.asarray(ds.attrs)[np.maximum(ids, 0), 1]
        assert (((a1 == 0) | (a1 == 2)) | (ids < 0)).all()

    @pytest.mark.parametrize("mode", ["none", "sq8", "pq"])
    def test_one_of_membership_exact_on_traversal_without_enforcement(
            self, ds, engines, mode):
        """ONE_OF is a hard predicate on every backend — after the planner
        change, value-set batches auto-plan onto graph traversal (exact,
        SQ8 and PQ alike) and must never return an out-of-set value even
        when MATCH enforcement is off."""
        qs = [
            Query(ds.query_features[i],
                  [MATCH(int(ds.query_attrs[i, 0])), ONE_OF(0, 2),
                   ANY, ANY, ANY])
            for i in range(8)
        ]
        qb = QueryBatch.from_queries(qs)
        params = SearchParams(k=10, brute_threshold=100)
        eng = engines[mode]
        assert eng.plan(qb, params).backend == "graph"
        res = eng.search(qb, params)
        ids = np.asarray(res.ids)
        a1 = np.asarray(ds.attrs)[np.maximum(ids, 0), 1]
        assert (((a1 == 0) | (a1 == 2)) | (ids < 0)).all()
        # MATCH dims stay soft without enforce_equality: some returned ids
        # may miss the equality — they must not have been filtered out.
        assert (ids >= 0).sum() > 0
        # traversal touches a small fraction of the corpus — the whole
        # point of lifting the ONE_OF → brute special case
        n = ds.features.shape[0]
        assert res.total_dist_evals + res.total_code_evals < 8 * n

    def test_one_of_traversal_recall_vs_oracle(self, ds, engines):
        """Covering-interval guidance + exact membership post-filter must
        recover (almost all of) the filtered oracle's top-k."""
        from repro.core.baselines import recall_at_k

        qs = [
            Query(ds.query_features[i], [ANY, ONE_OF(0, 2), ANY, ANY, ANY])
            for i in range(16)
        ]
        qb = QueryBatch.from_queries(qs)
        truth = engines["none"].search(
            qb, SearchParams(k=10, backend="brute")
        )
        res = engines["none"].search(
            qb, SearchParams(k=10, pool_size=128, brute_threshold=100)
        )
        assert recall_at_k(res.ids, truth.ids, 10) >= 0.9
        # and it does so while touching a fraction of the corpus
        assert res.total_dist_evals < 16 * ds.features.shape[0]
        # rerank_size must not cap the membership backfill on the exact
        # path (routing scores the whole pool exactly regardless)
        res_rr = engines["none"].search(
            qb, SearchParams(k=10, pool_size=128, rerank_size=10,
                             brute_threshold=100)
        )
        np.testing.assert_array_equal(np.asarray(res_rr.ids),
                                      np.asarray(res.ids))

    @pytest.mark.parametrize("mode", ["none", "sq8", "pq"])
    def test_between_traversal_soft_and_enforced(self, ds, engines, mode):
        """BETWEEN rides traversal on every codec: soft interval penalty by
        default, hard containment under enforce_equality."""
        qs = [
            Query(ds.query_features[i], [BETWEEN(0, 1), ANY, ANY, ANY, ANY])
            for i in range(8)
        ]
        qb = QueryBatch.from_queries(qs)
        params = SearchParams(k=10, brute_threshold=100)
        eng = engines[mode]
        assert eng.plan(qb, params).backend == "graph"
        soft = eng.search(qb, params)
        assert (np.asarray(soft.ids) >= 0).all()  # soft: never filtered
        hard = eng.search(
            qb, SearchParams(k=10, brute_threshold=100, enforce_equality=True)
        )
        ids = np.asarray(hard.ids)
        a0 = np.asarray(ds.attrs)[np.maximum(ids, 0), 0]
        assert (((a0 >= 0) & (a0 <= 1)) | (ids < 0)).all()
        d = np.asarray(hard.dists)
        assert (np.diff(d, axis=1) >= -1e-4).all()  # sorted, INF at tail
        valid = ids >= 0
        assert (valid[:, :-1] >= valid[:, 1:]).all()

    def test_between_brute_matches_numpy_oracle(self, ds, engines):
        qs = [
            Query(ds.query_features[i], [BETWEEN(1, 2), ANY, ANY, ANY, ANY])
            for i in range(8)
        ]
        qb = QueryBatch.from_queries(qs)
        res = engines["none"].search(qb, SearchParams(k=10, backend="brute"))
        ids = np.asarray(res.ids)
        attrs = np.asarray(ds.attrs)
        feats = np.asarray(ds.features, np.float64)
        for i in range(8):
            sat = (attrs[:, 0] >= 1) & (attrs[:, 0] <= 2)
            d = ((feats - ds.query_features[i].astype(np.float64)) ** 2).sum(1)
            want = np.argsort(np.where(sat, d, np.inf), kind="stable")[:10]
            got = ids[i][ids[i] >= 0]
            assert set(got) <= set(np.where(sat)[0])
            assert len(set(got) & set(want)) >= min(len(got), 9)

    def test_single_member_one_of_still_hard_filtered(self, ds, engines):
        """ONE_OF(v) must hard-filter like any ONE_OF — not degrade to a
        soft MATCH — and survivors stay sorted with INVALID at the tail."""
        qs = [
            Query(ds.query_features[i],
                  [ANY, ONE_OF(int(ds.query_attrs[i, 1])), ANY, ANY, ANY])
            for i in range(8)
        ]
        qb = QueryBatch.from_queries(qs)
        res = engines["none"].search(qb, SearchParams(k=10, backend="graph"))
        ids = np.asarray(res.ids)
        a1 = np.asarray(ds.attrs)[np.maximum(ids, 0), 1]
        want = np.asarray([int(ds.query_attrs[i, 1]) for i in range(8)])
        assert ((a1 == want[:, None]) | (ids < 0)).all()
        d = np.asarray(res.dists)
        assert (np.diff(d, axis=1) >= -1e-4).all()  # sorted, INF at tail
        valid = ids >= 0  # INVALID entries only as a suffix
        assert (valid[:, :-1] >= valid[:, 1:]).all()

    def test_brute_pq_rerank_size_bounds_fp_evals(self, ds, engines):
        params = SearchParams(k=10, backend="brute", rerank_size=16)
        res = engines["pq"].search(
            QueryBatch.match(ds.query_features, ds.query_attrs), params
        )
        assert (np.asarray(res.n_dist_evals) <= 16).all()

    def test_sq8_brute_explicitly_rejected(self, ds, engines):
        qb = QueryBatch.match(ds.query_features, ds.query_attrs)
        with pytest.raises(ValueError, match="sq8"):
            engines["sq8"].plan(
                qb, SearchParams(k=10, backend="brute", quant="sq8")
            )
        # auto resolution normalizes sq8 → full-precision oracle instead
        plan = engines["sq8"].plan(qb, SearchParams(k=10, backend="brute"))
        assert plan.quant_mode == "none"

    def test_brute_pq_uses_adc_two_stage(self, ds, engines):
        params = SearchParams(k=10, backend="brute")
        res = engines["pq"].search(
            QueryBatch.match(ds.query_features, ds.query_attrs), params
        )
        b, n = ds.query_features.shape[0], ds.features.shape[0]
        # every code is scanned, only the pool head is read at f32
        np.testing.assert_array_equal(
            np.asarray(res.n_code_evals), np.full((b,), n)
        )
        assert (np.asarray(res.n_dist_evals) <= params.effective_pool).all()
        truth = brute_force_hybrid(
            ds.features, ds.attrs, ds.query_features, ds.query_attrs, 10
        )
        assert recall_at_k(res.ids, truth.ids, 10) >= 0.85

    def test_engine_load_sniffs_on_disk_format(self, ds, engines, tmp_path):
        """Engine.load distinguishes the flat single-host layout from the
        per-shard sharded layout (full sharded round-trip parity is covered
        under 8 fake devices below); passing mesh= for a single-host dir is
        a clear error, and saved single-host meta carries its format tag."""
        from repro.distributed.search import is_sharded_dir

        path = os.path.join(tmp_path, "single")
        engines["none"].save(path)
        assert not is_sharded_dir(path)
        with open(os.path.join(path, "meta.json")) as f:
            assert json.load(f)["format"] == "stable-single-v1"
        with pytest.raises(ValueError, match="single-host"):
            Engine.load(path, mesh=object())
        eng2 = Engine.load(path)
        qb = QueryBatch.match(ds.query_features, ds.query_attrs)
        p = SearchParams(k=10, backend="graph")
        np.testing.assert_array_equal(
            np.asarray(eng2.search(qb, p).ids),
            np.asarray(engines["none"].search(qb, p).ids),
        )

    def test_engine_from_parts_matches_build(self, ds, engines):
        idx = engines["none"].index
        eng = Engine.from_parts(
            idx.features, idx.attrs, idx.graph, idx.metric_cfg, stats=idx.stats
        )
        qb = QueryBatch.match(ds.query_features, ds.query_attrs)
        p = SearchParams(k=10, backend="graph")
        np.testing.assert_array_equal(
            np.asarray(eng.search(qb, p).ids),
            np.asarray(engines["none"].search(qb, p).ids),
        )


# ---------------------------------------------------------------------------
# Sharded backend parity (8 fake devices, subprocess-isolated)
# ---------------------------------------------------------------------------


def test_engine_sharded_backend_parity():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH="src")
    code = textwrap.dedent("""
        import json, os, tempfile
        import numpy as np, jax, jax.numpy as jnp
        from repro.api import (ANY, BETWEEN, MATCH, ONE_OF, Engine, Query,
                               QueryBatch, SearchParams)
        from repro.launch.mesh import make_local_mesh
        from repro.distributed.search import ShardedStableIndex
        from repro.core.auto import MetricConfig
        from repro.core.help_graph import HelpConfig
        from repro.data.synthetic import make_hybrid_dataset
        from repro.quant import QuantConfig

        ds = make_hybrid_dataset(n=2048, n_queries=32, profile="sift",
                                 attr_dim=5, labels_per_dim=3, n_clusters=8,
                                 attr_cluster_corr=0.8, seed=5)
        mesh = make_local_mesh(data=2, model=4)
        help_cfg = HelpConfig(gamma=16, gamma_new=4, max_rounds=4,
                              quality_sample=64, node_block=512)
        idx = ShardedStableIndex.build(
            mesh, ds.features, ds.attrs, MetricConfig(mode="auto", alpha=1.0),
            help_cfg,
        )
        eng = Engine(idx)
        qb = QueryBatch.match(ds.query_features, ds.query_attrs)
        params = SearchParams(k=10)
        plan = eng.plan(qb, params)
        wild = QueryBatch.match(ds.query_features, ds.query_attrs,
                                active=[0, 1])
        ivq = QueryBatch.from_queries([
            Query(ds.query_features[i],
                  [ONE_OF(0, 2), BETWEEN(0, 1), ANY, ANY, ANY])
            for i in range(16)
        ])
        with mesh:
            res = eng.search(qb, params)
            legacy = idx.search(ds.query_features, ds.query_attrs, k=10)
            res_m = eng.search(wild, params)
            legacy_m = idx.search(ds.query_features, ds.query_attrs, k=10,
                                  mask=jnp.asarray(wild.mask))
            res_iv = eng.search(ivq, params)
        d = np.asarray(res_m.dists)
        iv_ids = np.asarray(res_iv.ids)
        a = np.asarray(ds.attrs)[np.maximum(iv_ids, 0)]
        # ONE_OF membership is hard on every backend; BETWEEN stays a soft
        # penalty without enforce_equality, so only dim 0 is checked.
        iv_ok = ((iv_ids < 0) | (a[:, :, 0] == 0) | (a[:, :, 0] == 2)).all()

        # sharded persistence: save -> load -> bit-exact round trip (the
        # regression test that replaced the old NotImplementedError check)
        tmp = tempfile.mkdtemp()
        eng.save(os.path.join(tmp, "plain"))
        eng_rt = Engine.load(os.path.join(tmp, "plain"), mesh=mesh)
        with mesh:
            res_rt = eng_rt.search(qb, params)
        rt_exact = (np.array_equal(np.asarray(res.ids),
                                   np.asarray(res_rt.ids))
                    and np.array_equal(np.asarray(res.sqdists),
                                       np.asarray(res_rt.sqdists)))

        # ...and with PQ codes: codes/codebooks must survive bit-exactly,
        # loading through the default-mesh branch (8 devices / 4 shards)
        idxq = ShardedStableIndex.build(
            mesh, ds.features, ds.attrs, MetricConfig(mode="auto", alpha=1.0),
            help_cfg,
            quant_cfg=QuantConfig(mode="pq", pq_subspaces=8,
                                  pq_train_iters=4),
        )
        engq = Engine(idxq)
        with mesh:
            resq = engq.search(qb, params)
        engq.save(os.path.join(tmp, "pq"))
        engq_rt = Engine.load(os.path.join(tmp, "pq"))  # default mesh
        with engq_rt.index.mesh:
            resq_rt = engq_rt.search(qb, params)
        pq_rt_exact = (np.array_equal(np.asarray(resq.ids),
                                      np.asarray(resq_rt.ids))
                       and np.array_equal(np.asarray(resq.sqdists),
                                          np.asarray(resq_rt.sqdists))
                       and np.array_equal(np.asarray(resq.n_code_evals),
                                          np.asarray(resq_rt.n_code_evals)))
        pq_codes_exact = np.array_equal(np.asarray(idxq.codes),
                                        np.asarray(engq_rt.index.codes))
        print(json.dumps({
            "backend": plan.backend,
            "ids_equal": bool(np.array_equal(np.asarray(res.ids),
                                             np.asarray(legacy.ids))),
            "per_query_shape": list(np.asarray(res.n_dist_evals).shape),
            "evals_positive": bool(res.total_dist_evals > 0),
            "masked_ids_equal": bool(np.array_equal(np.asarray(res_m.ids),
                                                    np.asarray(legacy_m.ids))),
            "masked_differs": bool(not np.array_equal(np.asarray(res_m.ids),
                                                      np.asarray(res.ids))),
            "masked_sorted": bool((np.diff(d, axis=1) >= -1e-4).all()),
            "interval_plan": eng.plan(ivq, params).backend,
            "interval_ok": bool(iv_ok),
            "interval_nonempty": bool((iv_ids >= 0).any()),
            "roundtrip_exact": bool(rt_exact),
            "pq_roundtrip_exact": bool(pq_rt_exact),
            "pq_codes_exact": bool(pq_codes_exact),
            "pq_quant_mode": engq_rt.quant_mode,
            "pq_rerank_bounded": bool(
                (np.asarray(resq.n_dist_evals)
                 <= params.effective_pool).all()),
        }))
    """)
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert proc.returncode == 0, f"stderr:\n{proc.stderr[-3000:]}"
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["backend"] == "sharded"
    assert out["ids_equal"], out
    assert out["per_query_shape"] == [32] and out["evals_positive"]
    assert out["masked_ids_equal"], out
    assert out["masked_differs"] and out["masked_sorted"], out
    # interval (ONE_OF + BETWEEN) batches run on the sharded backend with
    # exact ONE_OF membership
    assert out["interval_plan"] == "sharded"
    assert out["interval_ok"] and out["interval_nonempty"], out
    # sharded Engine.save/load round-trips bit-exactly, pq codes included,
    # and the pooled cross-shard rerank bounds fp evals by one global pool
    assert out["roundtrip_exact"], out
    assert out["pq_roundtrip_exact"] and out["pq_codes_exact"], out
    assert out["pq_quant_mode"] == "pq"
    assert out["pq_rerank_bounded"], out
