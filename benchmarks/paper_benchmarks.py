"""One benchmark per paper table/figure (DESIGN.md §6 experiment index).

Scales are CPU-sized; every function emits ``benchmark,name,metric,value``
rows and a CSV under artifacts/bench/.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import (
    built_engine, built_index, dataset, emit, flush_csv, ground_truth,
    timed_search,
)
from repro.api import QueryBatch, SearchParams
from repro.core import auto as auto_mod
from repro.core.auto import MetricConfig
from repro.core.baselines import (
    brute_force_hybrid, post_filter_search, pre_filter_search, recall_at_k,
)
from repro.core.routing import search_greedy_only, search_two_stage
from repro.data.synthetic import PROFILES, make_hybrid_dataset


# ---------------------------------------------------------------------------
# Table I — similarity-magnitude statistics across dataset profiles
# ---------------------------------------------------------------------------


def tab1_magnitude_stats(fast: bool = True) -> None:
    bench = "tab1_magnitude_stats"
    for profile in PROFILES:
        ds = dataset(profile, 5, 3, 5000, 64)
        st = auto_mod.sample_stats(ds.features, ds.attrs, seed=0)
        emit(bench, profile, "feat_min", round(st.min_feature_dist, 2))
        emit(bench, profile, "feat_max", round(st.max_feature_dist, 2))
        emit(bench, profile, "feat_avg", round(st.mean_feature_dist, 2))
        emit(bench, profile, "attr_min", round(st.min_attribute_dist, 2))
        emit(bench, profile, "attr_max", round(st.max_attribute_dist, 2))
        emit(bench, profile, "attr_avg", round(st.mean_attribute_dist, 2))
        emit(bench, profile, "alpha", round(st.alpha, 3))
    flush_csv(bench)


# ---------------------------------------------------------------------------
# Fig. 3 — QPS vs Recall@10: STABLE vs baseline strategies
# ---------------------------------------------------------------------------


def fig3_qps_recall(fast: bool = True) -> None:
    bench = "fig3_qps_recall"
    n = 10000 if fast else 50000
    profiles = ["sift", "glove", "crawl"]
    attr_dims = [5] if fast else [5, 6, 7]
    pools = [16, 32, 64, 128]
    for profile in profiles:
        for L in attr_dims:
            ds = dataset(profile, L, 3, n, 128)
            truth = ground_truth(ds)
            name = f"{profile}-{L}-3"

            eng = built_engine(ds, "auto")
            for pool in pools:
                res, qps, evals = timed_search(ds, eng, pool)
                r = recall_at_k(res.ids, truth.ids, 10)
                emit(bench, f"{name}/stable/pool{pool}", "recall", round(r, 4))
                emit(bench, f"{name}/stable/pool{pool}", "qps", round(qps, 1))
                emit(bench, f"{name}/stable/pool{pool}", "evals", evals)

            # additive fusion ("w/o AUTO" — static linear metric)
            res, qps, evals = timed_search(ds, built_engine(ds, "additive"), 64)
            emit(bench, f"{name}/additive/pool64", "recall",
                 round(recall_at_k(res.ids, truth.ids, 10), 4))
            emit(bench, f"{name}/additive/pool64", "qps", round(qps, 1))

            # NHQ-style static-weight Hamming fusion
            res, qps, evals = timed_search(ds, built_engine(ds, "nhq"), 64)
            emit(bench, f"{name}/nhq/pool64", "recall",
                 round(recall_at_k(res.ids, truth.ids, 10), 4))
            emit(bench, f"{name}/nhq/pool64", "qps", round(qps, 1))

            # post-filter (VSP) on a pure-L2 graph, K' sweep
            mc_l2, graph_l2, _, _ = built_index(ds, "l2")
            for kp in (40, 160):
                t0 = time.perf_counter()
                res = post_filter_search(
                    ds.features, ds.attrs, graph_l2,
                    ds.query_features, ds.query_attrs, 10, kp,
                )
                jax.block_until_ready(res.ids)
                dt = time.perf_counter() - t0
                emit(bench, f"{name}/postfilter/k{kp}", "recall",
                     round(recall_at_k(res.ids, truth.ids, 10), 4))
                emit(bench, f"{name}/postfilter/k{kp}", "qps",
                     round(ds.query_features.shape[0] / dt, 1))

            # pre-filter (SSP): exact but pays |match| feature evals
            res = pre_filter_search(
                ds.features, ds.attrs, ds.query_features, ds.query_attrs, 10
            )
            emit(bench, f"{name}/prefilter", "recall",
                 round(recall_at_k(res.ids, truth.ids, 10), 4))
            emit(bench, f"{name}/prefilter", "evals", res.total_dist_evals)
    flush_csv(bench)


# ---------------------------------------------------------------------------
# Table IV — robustness across attribute cardinality Θ
# ---------------------------------------------------------------------------


def tab4_cardinality_robustness(fast: bool = True) -> None:
    bench = "tab4_cardinality_robustness"
    n = 8000 if fast else 30000
    # Θ = labels^L
    grid = [(5, 2, 32), (5, 3, 243), (5, 4, 1024), (7, 3, 2187)]
    if not fast:
        grid.append((8, 3, 6561))
    for L, labels, theta in grid:
        ds = dataset("sift", L, labels, n, 128)
        truth = ground_truth(ds)
        res, qps, _ = timed_search(ds, built_engine(ds, "auto"), 64)
        emit(bench, f"stable/theta{theta}", "recall",
             round(recall_at_k(res.ids, truth.ids, 10), 4))
        emit(bench, f"stable/theta{theta}", "qps", round(qps, 1))
        res, _, _ = timed_search(ds, built_engine(ds, "additive"), 64)
        emit(bench, f"additive/theta{theta}", "recall",
             round(recall_at_k(res.ids, truth.ids, 10), 4))
    flush_csv(bench)


# ---------------------------------------------------------------------------
# Fig. 5 — query-selectivity stress test (masking, F = 1..L)
# ---------------------------------------------------------------------------


def fig5_selectivity(fast: bool = True) -> None:
    bench = "fig5_selectivity"
    L = 7
    n = 10000 if fast else 50000
    ds = dataset("sift", L, 3, n, 128)
    eng = built_engine(ds, "auto")
    params = SearchParams(k=10, pool_size=64, pioneer_size=8, backend="graph")
    for f_active in range(1, L + 1):
        # subset query declared via predicates: first F attrs active
        batch = QueryBatch.match(ds.query_features, ds.query_attrs,
                                 active=range(f_active))
        truth = brute_force_hybrid(
            ds.features, ds.attrs, ds.query_features, ds.query_attrs, 10,
            mask=jnp.asarray(batch.mask),
        )
        t0 = time.perf_counter()
        res = eng.search(batch, params)
        jax.block_until_ready(res.ids)
        res = eng.search(batch, params)
        jax.block_until_ready(res.ids)
        dt = (time.perf_counter() - t0) / 2
        sel = (1 / 3) ** f_active
        emit(bench, f"F{f_active}(sel={sel:.2%})", "recall",
             round(recall_at_k(res.ids, truth.ids, 10), 4))
        emit(bench, f"F{f_active}(sel={sel:.2%})", "qps",
             round(ds.query_features.shape[0] / dt, 1))
    flush_csv(bench)


# ---------------------------------------------------------------------------
# Fig. 6 — ablations
# ---------------------------------------------------------------------------


def fig6_ablations(fast: bool = True) -> None:
    bench = "fig6_ablations"
    n = 10000 if fast else 50000
    ds = dataset("sift", 7, 3, n, 128)
    truth = ground_truth(ds)
    eng = built_engine(ds, "auto")

    def run_one(name, engine, fn=None):
        res, qps, evals = timed_search(ds, engine, 64, search_fn=fn)
        emit(bench, name, "recall", round(recall_at_k(res.ids, truth.ids, 10), 4))
        emit(bench, name, "qps", round(qps, 1))
        emit(bench, name, "evals", evals)

    run_one("stable", eng)
    run_one("wo_AttributeDis", built_engine(ds, "l2"))
    run_one("wo_FeatureDis", built_engine(ds, "attr"))
    run_one("wo_AUTO", built_engine(ds, "additive"))
    run_one("wo_HSP", built_engine(ds, "auto", prune=False))
    # routing ablations are not engine backends — low-level escape hatch
    run_one("wo_DCR", eng, fn=search_greedy_only)
    run_one("wo_Dynamic", eng, fn=search_two_stage)
    flush_csv(bench)


# ---------------------------------------------------------------------------
# Fig. 7 — index build time
# ---------------------------------------------------------------------------


def fig7_build_time(fast: bool = True) -> None:
    bench = "fig7_build_time"
    n = 10000 if fast else 50000
    for profile in ("sift", "glove", "crawl"):
        ds = dataset(profile, 5, 3, n, 64)
        _, _, report, _ = built_index(ds, "auto")
        emit(bench, f"{profile}/stable", "build_s", round(report.build_seconds, 2))
        emit(bench, f"{profile}/stable", "rounds", report.rounds)
        emit(bench, f"{profile}/stable", "psi_final",
             round(report.psi_history[-1], 3))
        emit(bench, f"{profile}/stable", "pruned_frac",
             round(report.pruned_edge_fraction, 3))
        _, _, rep_l2, _ = built_index(ds, "l2")
        emit(bench, f"{profile}/l2-graph", "build_s",
             round(rep_l2.build_seconds, 2))
    flush_csv(bench)


# ---------------------------------------------------------------------------
# Fig. 8 — α validation: computed α vs empirical sweep
# ---------------------------------------------------------------------------


def fig8_alpha_sweep(fast: bool = True) -> None:
    bench = "fig8_alpha_sweep"
    n = 5000 if fast else 20000
    alphas = [0.25, 0.5, 0.8, 1.2, 1.6, 2.0]
    for profile in ("sift", "glove", "crawl"):
        ds = dataset(profile, 5, 3, n, 128)
        truth = ground_truth(ds)
        stats = auto_mod.sample_stats(ds.features, ds.attrs, seed=0)
        emit(bench, f"{profile}/computed_alpha", "alpha", round(stats.alpha, 3))
        best_a, best_r = None, -1.0
        for a in alphas + [round(stats.alpha, 3)]:
            eng = built_engine(ds, "auto", alpha=a, max_rounds=6)
            res, _, _ = timed_search(ds, eng, 64, repeats=1)
            r = recall_at_k(res.ids, truth.ids, 10)
            emit(bench, f"{profile}/alpha{a}", "recall", round(r, 4))
            if r > best_r:
                best_a, best_r = a, r
        emit(bench, f"{profile}/empirical_best", "alpha", best_a)
        emit(bench, f"{profile}/empirical_best", "recall", round(best_r, 4))
    flush_csv(bench)


# ---------------------------------------------------------------------------
# Fig. 9 — σ sensitivity
# ---------------------------------------------------------------------------


def fig9_sigma_sweep(fast: bool = True) -> None:
    bench = "fig9_sigma_sweep"
    n = 5000 if fast else 20000
    ds = dataset("sift", 5, 3, n, 128)
    truth = ground_truth(ds)
    for sigma in (0.2, 0.3, 0.44, 0.6, 0.8):
        _, _, rep, _ = built_index(ds, "auto", sigma=sigma, max_rounds=6)
        eng = built_engine(ds, "auto", sigma=sigma, max_rounds=6)
        res, _, evals = timed_search(ds, eng, 64, repeats=1)
        emit(bench, f"sigma{sigma}", "recall",
             round(recall_at_k(res.ids, truth.ids, 10), 4))
        emit(bench, f"sigma{sigma}", "pruned_frac",
             round(rep.pruned_edge_fraction, 3))
        emit(bench, f"sigma{sigma}", "evals", evals)
    flush_csv(bench)


# ---------------------------------------------------------------------------
# Fig. 10 — Γ sweep (index size vs retrieval performance)
# ---------------------------------------------------------------------------


def fig10_gamma_sweep(fast: bool = True) -> None:
    bench = "fig10_gamma_sweep"
    n = 5000 if fast else 20000
    ds = dataset("sift", 5, 3, n, 128)
    truth = ground_truth(ds)
    for gamma in (12, 24, 48, 96):
        eng = built_engine(ds, "auto", gamma=gamma, max_rounds=6)
        res, qps, _ = timed_search(ds, eng, 64, repeats=1)
        size_mb = eng.index.graph.size * 4 / 2**20
        emit(bench, f"gamma{gamma}", "recall",
             round(recall_at_k(res.ids, truth.ids, 10), 4))
        emit(bench, f"gamma{gamma}", "qps", round(qps, 1))
        emit(bench, f"gamma{gamma}", "index_mb", round(size_mb, 2))
    flush_csv(bench)


# ---------------------------------------------------------------------------
# Table V — kernel-fusion overhead (the SIMD/AVX2 analog on TPU)
# ---------------------------------------------------------------------------


def tab5_kernel_fusion(fast: bool = True) -> None:
    bench = "tab5_kernel_fusion"
    rng = np.random.default_rng(0)
    b, n, m, l = 128, 100_000, 128, 7
    qv = jnp.asarray(rng.normal(size=(b, m)), jnp.float32)
    xv = jnp.asarray(rng.normal(size=(n, m)), jnp.float32)
    qa = jnp.asarray(rng.integers(0, 3, (b, l)), jnp.int32)
    xa = jnp.asarray(rng.integers(0, 3, (n, l)), jnp.int32)

    # HLO-level: flops/bytes of fused-AUTO scorer vs pure-L2 scorer
    from repro.kernels.fused_auto.ref import fused_auto_ref

    costs = {}
    for mode in ("l2", "auto"):
        c = (
            jax.jit(lambda a, b_, c_, d_: fused_auto_ref(a, b_, c_, d_, 0.8, mode))
            .lower(qv, qa, xv, xa).compile().cost_analysis()
        )
        costs[mode] = (float(c["flops"]), float(c["bytes accessed"]))
    for mode, (fl, by) in costs.items():
        emit(bench, mode, "hlo_flops", f"{fl:.4g}")
        emit(bench, mode, "hlo_bytes", f"{by:.4g}")
    emit(bench, "overhead", "flops_pct",
         round(100 * (costs["auto"][0] / costs["l2"][0] - 1), 2))
    emit(bench, "overhead", "bytes_pct",
         round(100 * (costs["auto"][1] / costs["l2"][1] - 1), 2))

    # wall-clock on CPU (compiled jnp twins — the scalar-vs-vectorized analog)
    for mode in ("l2", "auto"):
        cfg = MetricConfig(mode=mode, alpha=0.8)
        f = jax.jit(lambda a, b_, c_, d_: auto_mod.brute_fused_sqdist(
            a, b_, c_, d_, cfg))
        f(qv, qa, xv, xa).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(3):
            f(qv, qa, xv, xa).block_until_ready()
        dt = (time.perf_counter() - t0) / 3
        emit(bench, mode, "qps", round(b / dt, 1))
        emit(bench, mode, "us_per_call", round(dt * 1e6, 1))
    flush_csv(bench)


# ---------------------------------------------------------------------------
# Quantized serving — recall vs throughput: exact vs sq8 vs pq (+rerank)
# ---------------------------------------------------------------------------


def quant_sweep(fast: bool = True, n: int = 0) -> None:
    """Two-stage quantized search memory-vs-recall frontier; also emits
    ``BENCH_quant.json`` (bytes/vector, qps, recall@10, eval counts per
    mode) and prints the frontier table. pq4 packs two 4-bit codes per
    byte (half of pq at equal subspaces); opq-* add the learned rotation
    at zero code bytes (the (Mp, Mp) matrix is per-index, not per-row)."""
    import json
    import os

    from benchmarks.common import BENCH_DIR
    from repro.quant import QuantConfig, QuantizedVectors

    bench = "quant_sweep"
    n = n or (10000 if fast else 50000)
    pool = 64
    # equal subspace count across the PQ family so pq4's "half the bytes"
    # claim is apples-to-apples (two 4-bit codes pack into one pq byte)
    sub = 64
    ds = dataset("sift", 5, 3, n, 128)
    truth = ground_truth(ds)

    def qcfg(mode):
        return QuantConfig(mode=mode, pq_subspaces=sub,
                           pq_train_iters=8 if fast else 15, opq_iters=3)

    stores = {
        "none": None,
        "sq8": QuantizedVectors.build(ds.features, QuantConfig(mode="sq8")),
        "pq": QuantizedVectors.build(ds.features, qcfg("pq")),
        "pq4": QuantizedVectors.build(ds.features, qcfg("pq4")),
        "opq-pq": QuantizedVectors.build(ds.features, qcfg("opq-pq")),
        "opq-pq4": QuantizedVectors.build(ds.features, qcfg("opq-pq4")),
    }
    reranks = [pool // 2, pool] if fast else [16, pool // 2, pool]

    fp_bytes = ds.features.shape[1] * 4
    bytes_per_vec = {
        m: (fp_bytes if s is None else int(s.code_bytes) // n)
        for m, s in stores.items()
    }
    summary = {}
    batch = QueryBatch.match(ds.query_features, ds.query_attrs)
    for mode, store in stores.items():
        # quant mode is derived from the engine's code store (quant="auto")
        eng = built_engine(ds, "auto", quant=store)
        sweeps = [0] if mode == "none" else reranks
        for rr in sweeps:
            res, qps, _ = timed_search(ds, eng, pool, rerank_size=rr)
            nq = ds.query_features.shape[0]
            r = recall_at_k(res.ids, truth.ids, 10)
            name = mode if mode == "none" else f"{mode}/rerank{rr}"
            emit(bench, name, "recall", round(r, 4))
            emit(bench, name, "qps", round(qps, 1))
            emit(bench, name, "fp_evals_per_q", res.total_dist_evals // nq)
            emit(bench, name, "code_evals_per_q", res.total_code_evals // nq)
            emit(bench, name, "bytes_per_vector", bytes_per_vec[mode])
            summary[name] = {
                "recall_at_10": round(float(r), 4),
                "qps": round(float(qps), 1),
                "fp_evals_per_query": res.total_dist_evals // nq,
                "code_evals_per_query": res.total_code_evals // nq,
                "bytes_per_vector": bytes_per_vec[mode],
            }
    flush_csv(bench)

    # memory-vs-recall frontier at the deepest rerank
    rr = reranks[-1]
    print(f"\n  memory/recall frontier (n={n}, rerank={rr}):")
    print(f"  {'mode':<10} {'bytes/vec':>9} {'x-compress':>10} {'recall@10':>9}")
    for mode in stores:
        name = mode if mode == "none" else f"{mode}/rerank{rr}"
        row = summary[name]
        print(f"  {mode:<10} {row['bytes_per_vector']:>9} "
              f"{fp_bytes / row['bytes_per_vector']:>9.1f}x "
              f"{row['recall_at_10']:>9.4f}")

    # CI smoke bars: packed codes halve pq bytes at equal subspaces, and
    # the OPQ rotation never hurts at equal bytes (a learned rotation is a
    # strict superset of identity). 4-bit recall: within 0.01 of pq at the
    # deepest rerank (measured: equal), within 0.025 at the shallow one —
    # at half the bits the ADC head ordering pays ~2 points when only the
    # top-32 is reranked (training levers plateau there; measured).
    assert bytes_per_vec["pq4"] <= 0.55 * bytes_per_vec["pq"], bytes_per_vec
    assert bytes_per_vec["opq-pq4"] <= 0.55 * bytes_per_vec["opq-pq"], bytes_per_vec
    r_pq = summary[f"pq/rerank{rr}"]["recall_at_10"]
    r_pq4 = summary[f"pq4/rerank{rr}"]["recall_at_10"]
    r_opq = summary[f"opq-pq/rerank{rr}"]["recall_at_10"]
    assert r_pq4 >= r_pq - 0.01, (r_pq4, r_pq)
    assert r_opq >= r_pq - 0.005, (r_opq, r_pq)
    r_pq_s = summary[f"pq/rerank{reranks[0]}"]["recall_at_10"]
    r_pq4_s = summary[f"pq4/rerank{reranks[0]}"]["recall_at_10"]
    assert r_pq4_s >= r_pq_s - 0.025, (r_pq4_s, r_pq_s)

    os.makedirs(BENCH_DIR, exist_ok=True)
    with open(os.path.join(BENCH_DIR, "BENCH_quant.json"), "w") as f:
        json.dump({"n": n, "pool": pool, "fp_bytes_per_vector": fp_bytes,
                   "modes": summary}, f, indent=2)


# ---------------------------------------------------------------------------
# Filter sweep — ONE_OF set size / BETWEEN selectivity: traversal vs brute
# ---------------------------------------------------------------------------


def filter_sweep(fast: bool = True, n: int = 0) -> None:
    """Recall@10 and evals/query vs. ONE_OF set size and BETWEEN
    selectivity, graph traversal vs the brute oracle, exact vs sq8/pq.
    Also emits ``BENCH_filters.json``. Pass ``--n`` (benchmarks.run) for a
    tiny CI-sized run.

    The headline claim this chart backs: since the planner change, ONE_OF
    and BETWEEN batches ride the HELP graph with the interval penalty and
    exact membership, at sub-linear evals/query — the brute baseline always
    pays N evals.
    """
    import json
    import os

    from benchmarks.common import BENCH_DIR
    from repro.api import ANY, BETWEEN, MATCH, ONE_OF, Query
    from repro.quant import QuantConfig, QuantizedVectors

    bench = "filter_sweep"
    n = n or (8000 if fast else 30000)
    labels = 8  # wide label range so set size / interval width can vary
    pool = 128
    ds = dataset("sift", 5, labels, n, 64)
    nq = ds.query_features.shape[0]

    stores = {
        "none": None,
        "sq8": QuantizedVectors.build(ds.features, QuantConfig(mode="sq8")),
        "pq": QuantizedVectors.build(
            ds.features,
            QuantConfig(mode="pq", pq_subspaces=16,
                        pq_train_iters=6 if fast else 15),
        ),
    }
    engines = {m: built_engine(ds, "auto", quant=s) for m, s in stores.items()}
    oracle = engines["none"]

    def batch_for(pred0) -> QueryBatch:
        return QueryBatch.from_queries([
            Query(ds.query_features[i],
                  [pred0, MATCH(int(ds.query_attrs[i, 1])), ANY, ANY, ANY])
            for i in range(nq)
        ])

    def run_case(name: str, qb: QueryBatch, selectivity: float) -> dict:
        truth = oracle.search(qb, SearchParams(k=10, backend="brute"))
        case = {"selectivity": round(selectivity, 4), "modes": {}}
        for mode, eng in engines.items():
            for backend in ("graph", "brute"):
                if backend == "brute" and mode == "sq8":
                    continue  # no sq8 scan kernel; auto would run exact
                params = SearchParams(k=10, pool_size=pool,
                                      pioneer_size=max(4, pool // 8),
                                      backend=backend)
                t0 = time.time()
                res = eng.search(qb, params)
                jax.block_until_ready(res.ids)
                dt = time.time() - t0
                r = recall_at_k(res.ids, truth.ids, 10)
                fp = res.total_dist_evals // nq
                code = res.total_code_evals // nq
                tag = f"{name}/{mode}/{backend}"
                emit(bench, tag, "recall", round(r, 4))
                emit(bench, tag, "fp_evals_per_q", fp)
                emit(bench, tag, "code_evals_per_q", code)
                emit(bench, tag, "qps", round(nq / dt, 1))
                case["modes"][f"{mode}/{backend}"] = {
                    "recall_at_10": round(float(r), 4),
                    "fp_evals_per_query": int(fp),
                    "code_evals_per_query": int(code),
                    "evals_frac_of_n": round(float(fp + code) / n, 4),
                }
        return case

    summary: dict = {"n": n, "labels_per_dim": labels, "pool": pool,
                     "one_of": {}, "between": {}}
    for set_size in (1, 2, 4) if fast else (1, 2, 4, 6):
        vals = list(range(set_size))
        qb = batch_for(ONE_OF(*vals))
        summary["one_of"][f"set{set_size}"] = run_case(
            f"one_of{set_size}", qb, set_size / labels / labels
        )
    for width in (1, 3, 6):
        qb = batch_for(BETWEEN(0, width))
        summary["between"][f"width{width + 1}"] = run_case(
            f"between{width + 1}", qb, (width + 1) / labels / labels
        )
    flush_csv(bench)
    os.makedirs(BENCH_DIR, exist_ok=True)
    with open(os.path.join(BENCH_DIR, "BENCH_filters.json"), "w") as f:
        json.dump(summary, f, indent=2)


# ---------------------------------------------------------------------------
# Planner sweep — measured brute vs graph crossover audits the cost model
# ---------------------------------------------------------------------------


def planner_sweep(fast: bool = True, n: int = 0) -> None:
    """Audit the calibrated cost-model planner against ground truth:
    measured latency + evals/query for the brute and graph backends across
    N × batch size × codec, the measured latency crossover, and the
    planner's auto choice (with its predicted costs) at every point.

    Emits ``BENCH_planner.json``: the measurement grid, per-(codec, batch)
    measured/predicted crossovers, and the fitted ``CostModel`` of the
    largest exact engine — loadable via ``planner.cost_model_from_table``
    as the bundled-calibration alternative to the build-time probe.
    Pass ``--n`` (benchmarks.run) for a tiny CI-sized run.
    """
    import json
    import os

    from benchmarks.common import BENCH_DIR
    from repro.quant import QuantConfig, QuantizedVectors

    bench = "planner_sweep"
    if n:
        grid = sorted({max(512, n // 4), max(1000, n // 2), n})
    elif fast:
        grid = [1000, 2000, 5000, 10000]
    else:
        grid = [1000, 2000, 5000, 10000, 20000, 50000]
    batches = [16, 128] if fast else [16, 64, 256]
    codecs = ["none", "pq"]
    k, pool = 10, 64
    repeats = 3

    points: list = []
    table_model = None
    for codec in codecs:
        for ni in grid:
            ds = dataset("sift", 5, 3, ni, max(batches))
            store = None
            if codec == "pq":
                store = QuantizedVectors.build(
                    ds.features,
                    QuantConfig(mode="pq", pq_subspaces=16, pq_train_iters=6),
                )
            eng = built_engine(ds, "auto", quant=store)
            cm = eng.cost_model  # probe calibration happens here
            if codec == "none":
                table_model = cm  # largest exact engine wins (grid ascends)
            for b in batches:
                qb = QueryBatch.match(ds.query_features[:b],
                                      ds.query_attrs[:b])

                def timed(backend: str):
                    params = SearchParams(
                        k=k, pool_size=pool, pioneer_size=max(4, pool // 8),
                        backend=backend,
                    )
                    res = eng.search(qb, params)  # compile + cache executable
                    jax.block_until_ready(res.ids)
                    t0 = time.perf_counter()
                    for _ in range(repeats):
                        res = eng.search(qb, params)
                        jax.block_until_ready(res.ids)
                    return res, (time.perf_counter() - t0) / repeats

                res_b, dt_b = timed("brute")
                res_g, dt_g = timed("graph")
                auto = eng.plan(
                    qb, SearchParams(k=k, pool_size=pool,
                                     pioneer_size=max(4, pool // 8))
                )
                tag = f"{codec}/n{ni}/b{b}"
                emit(bench, tag, "brute_ms", round(dt_b * 1e3, 3))
                emit(bench, tag, "graph_ms", round(dt_g * 1e3, 3))
                emit(bench, tag, "planner_choice", auto.backend)
                points.append({
                    "codec": codec, "n": ni, "batch": b,
                    "brute_ms": round(dt_b * 1e3, 3),
                    "graph_ms": round(dt_g * 1e3, 3),
                    "brute_fp_evals_per_q": res_b.total_dist_evals // b,
                    "brute_code_evals_per_q": res_b.total_code_evals // b,
                    "graph_fp_evals_per_q": res_g.total_dist_evals // b,
                    "graph_code_evals_per_q": res_g.total_code_evals // b,
                    "planner_choice": auto.backend,
                    "cost_brute": round(auto.cost_brute, 1),
                    "cost_graph": round(auto.cost_graph, 1),
                    "measured_faster": (
                        "brute" if dt_b <= dt_g else "graph"
                    ),
                })

    # crossover fits: per (codec, batch), the measured latency crossover
    # region [last N where brute is faster, first N where graph is faster]
    # and the planner's chosen crossover (first N routed to graph)
    crossovers: dict = {}
    for codec in codecs:
        for b in batches:
            ps = [p for p in points
                  if p["codec"] == codec and p["batch"] == b]
            brute_faster = [p["n"] for p in ps
                            if p["measured_faster"] == "brute"]
            graph_faster = [p["n"] for p in ps
                            if p["measured_faster"] == "graph"]
            chosen = [p["n"] for p in ps if p["planner_choice"] == "graph"]
            cross = {
                "measured_region": [
                    max(brute_faster) if brute_faster else None,
                    min(graph_faster) if graph_faster else None,
                ],
                "planner_crossover_n": min(chosen) if chosen else None,
            }
            crossovers[f"{codec}/b{b}"] = cross
            emit(bench, f"{codec}/b{b}", "planner_crossover_n",
                 cross["planner_crossover_n"])

    flush_csv(bench)
    os.makedirs(BENCH_DIR, exist_ok=True)
    with open(os.path.join(BENCH_DIR, "BENCH_planner.json"), "w") as f:
        json.dump({
            "k": k, "pool": pool, "grid": grid, "batches": batches,
            "points": points,
            "crossovers": crossovers,
            "cost_model": table_model.to_json() if table_model else None,
        }, f, indent=2)


# ---------------------------------------------------------------------------
# Serve sweep — multi-tenant micro-batching vs the unbatched baseline
# ---------------------------------------------------------------------------


def serve_sweep(fast: bool = True, n: int = 0, skew: float = 0.0) -> None:
    """Throughput + end-to-end p99 of the serving loop across micro-batch
    window × bucket ladder × tenant count, against the unbatched per-query
    baseline on the same engine.

    Requests arrive on a deterministic virtual clock via the shared
    ``benchmarks.trace`` generator (``skew`` > 0 draws queries Zipfian from
    the distinct pool — ``--skew`` in benchmarks.run), so coalescing
    decisions are reproducible; throughput is measured as completed
    requests per second of *wall* batch-execution time (``service_qps`` —
    padding overhead is charged), and p99 is the end-to-end request latency
    (virtual queueing + wall service). Emits ``BENCH_serve.json``. Pass
    ``--n`` (benchmarks.run) for the CI smoke.
    """
    import json
    import os

    from benchmarks.common import BENCH_DIR
    from benchmarks.trace import zipf_query_trace
    from repro.serve import (
        ServerStats, TenantPolicy, TenantRegistry, serve_loop,
    )

    bench = "serve_sweep"
    n = n or (10_000 if fast else 20_000)
    n_requests = 256 if fast else 512
    windows_ms = [0.5, 2.0, 8.0]
    ladders = [(1,), (1, 8, 32), (1, 8, 32, 128)]
    tenant_counts = [1, 4] if fast else [1, 4, 16]
    arrival_spacing_s = 5e-5  # 20k offered QPS — keeps windows full
    k, pool = 10, 64

    ds = dataset("sift", 5, 3, n, n_requests)
    eng = built_engine(ds, "auto")
    params = SearchParams(k=k, pool_size=pool,
                          pioneer_size=max(4, pool // 8))

    trace_info = {}

    def requests_for(n_tenants: int):
        trace, info = zipf_query_trace(
            ds, n_requests, skew=skew, n_tenants=n_tenants,
            spacing_s=arrival_spacing_s, seed=0,
        )
        trace_info.update(info)
        return trace

    # -- unbatched baseline: one Engine.search per request, no coalescing --
    singles = [QueryBatch.match(ds.query_features[i:i + 1],
                                ds.query_attrs[i:i + 1])
               for i in range(n_requests)]
    jax.block_until_ready(eng.search(singles[0], params).ids)  # warm compile
    lat = []
    for qb in singles:
        t0 = time.perf_counter()
        jax.block_until_ready(eng.search(qb, params).ids)
        lat.append(time.perf_counter() - t0)
    unbatched = {
        "qps": round(n_requests / sum(lat), 1),
        "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 3),
        "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 3),
    }
    emit(bench, "unbatched", "qps", unbatched["qps"])
    emit(bench, "unbatched", "p99_ms", unbatched["p99_ms"])

    points = []
    for n_tenants in tenant_counts:
        reg_proto = TenantPolicy(params=params)
        for ladder in ladders:
            for w in windows_ms:
                reg = TenantRegistry(default_policy=reg_proto)
                trace = requests_for(n_tenants)
                # warm the executables for this ladder, then measure
                serve_loop(eng, trace, reg, window_ms=w, buckets=ladder)
                stats = ServerStats(eng)
                resp, stats = serve_loop(
                    eng, trace, TenantRegistry(default_policy=reg_proto),
                    window_ms=w, buckets=ladder, stats=stats,
                )
                snap = stats.snapshot()
                tag = f"t{n_tenants}/b{'-'.join(map(str, ladder))}/w{w}"
                emit(bench, tag, "service_qps", snap["service_qps"])
                emit(bench, tag, "p99_ms", snap["latency_ms"]["p99"])
                emit(bench, tag, "fill", snap["batch_fill_ratio"])
                points.append({
                    "tenants": n_tenants,
                    "buckets": list(ladder),
                    "window_ms": w,
                    "completed": snap["completed"],
                    "batches": snap["batches"],
                    "service_qps": snap["service_qps"],
                    "p50_ms": snap["latency_ms"]["p50"],
                    "p99_ms": snap["latency_ms"]["p99"],
                    "batch_fill_ratio": snap["batch_fill_ratio"],
                    "retraces": snap["retraces"],
                    "plan_cache_hit_rate": snap["plan_cache"]["hit_rate"],
                    "speedup_vs_unbatched": round(
                        snap["service_qps"] / unbatched["qps"], 2
                    ) if unbatched["qps"] else None,
                })

    flush_csv(bench)
    os.makedirs(BENCH_DIR, exist_ok=True)
    with open(os.path.join(BENCH_DIR, "BENCH_serve.json"), "w") as f:
        json.dump({
            "n": n, "n_requests": n_requests, "k": k, "pool": pool,
            "arrival_spacing_s": arrival_spacing_s,
            "trace": trace_info,
            "unbatched": unbatched,
            "points": points,
        }, f, indent=2)


# ---------------------------------------------------------------------------
# Cache sweep — hot/cold tiering + result cache under Zipfian traffic
# ---------------------------------------------------------------------------


def cache_sweep(fast: bool = True, n: int = 0) -> None:
    """Hot/cold tiering + serve-layer result cache vs Zipf skew × hot-row
    budget, against the PR 5 serving baselines.

    The engine serves PQ codes with a full-precision rerank. The *tiered*
    variants hold only ``hot_rows`` f32 rows on device (the frequency-
    tracked head) and gather the cold tail from host — ``hot=0`` is the
    equal-device-memory baseline (codes only, every rerank row crosses the
    bus). The untiered engine (full f32 matrix resident, PR 5 behavior) is
    the memory-unconstrained reference, measured unbatched and batched.
    Traffic comes from the shared ``benchmarks.trace`` generator at
    s ∈ {0, 0.8, 1.2}; the result cache variant answers verbatim repeats
    without device work. Self-asserts: tiering is bit-identical to the
    untiered engine, the hot tier actually absorbs gathers on skewed
    traffic, and the result cache never slows serving on a repeat-heavy
    trace. Emits ``BENCH_cache.json``. Pass ``--n`` (benchmarks.run) for
    the CI smoke.
    """
    import json
    import os

    from benchmarks.common import BENCH_DIR
    from benchmarks.trace import zipf_query_trace
    from repro.cache import ResultCache, TieredEngine
    from repro.quant import QuantConfig, QuantizedVectors
    from repro.serve import (
        ServerStats, TenantPolicy, TenantRegistry, serve_loop,
    )

    bench = "cache_sweep"
    n = n or (10_000 if fast else 20_000)
    n_requests = 512 if fast else 2048
    n_distinct = 64 if fast else 128  # query pool — repeats appear at skew>0
    skews = [0.0, 0.8, 1.2]
    hot_budgets = [0, n // 8] if fast else [0, n // 8, n // 2]
    k, pool = 10, 64
    window_ms, ladder = 2.0, (1, 8, 32)
    spacing_s = 5e-5

    ds = dataset("sift", 5, 3, n, n_distinct)
    quant = QuantizedVectors.build(
        ds.features,
        QuantConfig(mode="pq", pq_subspaces=32,
                    pq_train_iters=8 if fast else 15),
    )
    eng = built_engine(ds, "auto", quant=quant)  # untiered PR 5 reference
    params = SearchParams(k=k, pool_size=pool,
                          pioneer_size=max(4, pool // 8))
    reg_proto = TenantPolicy(params=params)
    m = ds.features.shape[1]
    mem = {
        "f32_bytes": int(n * m * 4),
        "code_bytes": int(quant.code_bytes),
        "code_bytes_per_row": int(quant.code_bytes_per_row),
    }

    # -- bit-exactness self-check: tiered == untiered, ids AND distances --
    qb = QueryBatch.match(ds.query_features, ds.query_attrs)
    tiered_chk = TieredEngine(eng, hot_rows=max(hot_budgets) or n // 8,
                              epoch_queries=n_distinct)
    ref = eng.search(qb, params)
    for _ in range(2):  # cold pass, then a promoted-hot-set pass
        got = tiered_chk.search(qb, params)
        assert np.array_equal(np.asarray(got.ids), np.asarray(ref.ids)), \
            "tiered ids diverge from untiered engine"
        assert np.array_equal(np.asarray(got.dists), np.asarray(ref.dists)), \
            "tiered distances diverge from untiered engine"
    emit(bench, "invariant", "bit_identical", 1)

    # -- PR 5 baselines: unbatched per-query + batched serve (full f32) --
    singles = [QueryBatch.match(ds.query_features[i:i + 1],
                                ds.query_attrs[i:i + 1])
               for i in range(n_distinct)]
    jax.block_until_ready(eng.search(singles[0], params).ids)
    lat = []
    for qb1 in singles:
        t0 = time.perf_counter()
        jax.block_until_ready(eng.search(qb1, params).ids)
        lat.append(time.perf_counter() - t0)
    pr5_unbatched = {
        "qps": round(n_distinct / sum(lat), 1),
        "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 3),
    }
    emit(bench, "pr5_unbatched", "qps", pr5_unbatched["qps"])

    def served(engine, trace, cache=None):
        """Warm (compile + promote), reset counters, measure one pass."""
        serve_loop(engine, trace, TenantRegistry(default_policy=reg_proto),
                   window_ms=window_ms, buckets=ladder, result_cache=cache)
        if cache is not None:
            cache.clear()
            cache.reset_counters()
        tier = getattr(engine, "tier", None)
        if tier is not None:
            tier.reset_counters()
        stats = ServerStats(engine)
        _, stats = serve_loop(
            engine, trace, TenantRegistry(default_policy=reg_proto),
            window_ms=window_ms, buckets=ladder, stats=stats,
            result_cache=cache,
        )
        return stats.snapshot()

    points = []
    traces = {}
    for skew in skews:
        trace, info = zipf_query_trace(
            ds, n_requests, skew=skew, n_tenants=4, spacing_s=spacing_s,
            mean_burst=4.0, seed=0,
        )
        traces[str(skew)] = info

        # PR 5 batched reference on this trace (untiered, no cache)
        snap = served(eng, trace)
        base_qps = snap["service_qps"]
        points.append({
            "skew": skew, "variant": "pr5_batched", "hot_rows": None,
            "result_cache": False, "service_qps": snap["service_qps"],
            "p99_ms": snap["latency_ms"]["p99"],
            "device_bytes": mem["f32_bytes"] + mem["code_bytes"],
        })
        emit(bench, f"s{skew}/pr5_batched", "service_qps",
             snap["service_qps"])

        for hot in hot_budgets:
            for use_cache in (False, True):
                tiered = TieredEngine(
                    eng, hot_rows=hot,
                    epoch_queries=max(64, n_requests // 4),
                )
                cache = ResultCache(max_entries=4 * n_distinct) \
                    if use_cache else None
                snap = served(tiered, trace, cache)
                tier = snap.get("tier", {})
                rc = snap.get("result_cache", {})
                tag = (f"s{skew}/hot{hot}" + ("/cache" if use_cache else ""))
                emit(bench, tag, "service_qps", snap["service_qps"])
                emit(bench, tag, "p99_ms", snap["latency_ms"]["p99"])
                if tier:
                    emit(bench, tag, "tier_hit_rate",
                         round(tier.get("tier_hit_rate", 0.0), 4))
                if rc:
                    emit(bench, tag, "cache_hit_rate",
                         round(rc.get("hit_rate", 0.0), 4))
                points.append({
                    "skew": skew, "variant": "tiered", "hot_rows": hot,
                    "result_cache": use_cache,
                    "service_qps": snap["service_qps"],
                    "p99_ms": snap["latency_ms"]["p99"],
                    "completed": snap["completed"],
                    "tier_hit_rate": round(tier.get("tier_hit_rate", 0.0), 4),
                    "cache_hit_rate": round(rc.get("hit_rate", 0.0), 4)
                    if rc else None,
                    "cache_served": rc.get("served") if rc else None,
                    "device_bytes": mem["code_bytes"] + hot * m * 4,
                    "speedup_vs_pr5_batched": round(
                        snap["service_qps"] / base_qps, 3
                    ) if base_qps else None,
                })

    # -- self-asserts the CI smoke relies on ------------------------------
    skewed = [p for p in points if p["variant"] == "tiered"
              and p["skew"] >= 0.8]
    hot_hits = max(p["tier_hit_rate"] for p in skewed
                   if p["hot_rows"] and not p["result_cache"])
    assert hot_hits > 0, \
        "hot tier absorbed no rerank gathers on Zipf-skewed traffic"
    emit(bench, "invariant", "hot_tier_hit_rate_max", round(hot_hits, 4))
    for skew in (s for s in skews if s >= 0.8):
        for hot in hot_budgets:
            off = next(p for p in points
                       if p["variant"] == "tiered" and p["skew"] == skew
                       and p["hot_rows"] == hot and not p["result_cache"])
            on = next(p for p in points
                      if p["variant"] == "tiered" and p["skew"] == skew
                      and p["hot_rows"] == hot and p["result_cache"])
            assert on["cache_served"] > 0, \
                f"result cache served nothing at skew {skew}"
            speedup = (on["service_qps"] / off["service_qps"]
                       if off["service_qps"] else 1.0)
            emit(bench, f"s{skew}/hot{hot}", "cache_speedup",
                 round(speedup, 3))
            assert speedup >= 1.0, (
                f"result cache slowed serving at skew {skew} hot {hot}: "
                f"{on['service_qps']} vs {off['service_qps']} qps"
            )

    flush_csv(bench)
    os.makedirs(BENCH_DIR, exist_ok=True)
    with open(os.path.join(BENCH_DIR, "BENCH_cache.json"), "w") as f:
        json.dump({
            "n": n, "n_requests": n_requests, "n_distinct": n_distinct,
            "k": k, "pool": pool, "window_ms": window_ms,
            "buckets": list(ladder), "quant_mode": "pq",
            "memory": mem, "traces": traces,
            "pr5_unbatched": pr5_unbatched,
            "points": points,
        }, f, indent=2)


def mutate_sweep(fast: bool = True, n: int = 0) -> None:
    """Freshness cost of the LSM write path: Recall@10 and p50 query
    latency as the delta segment grows to 0–30% of the corpus, before and
    after the background merge folds it into the main index, plus the
    sustained write-absorb rate. Emits ``BENCH_mutate.json`` (with the
    ``BENCH_serve.json`` read-only baseline referenced when present).
    Pass ``--n`` (benchmarks.run) for the CI smoke.
    """
    import json
    import os

    from benchmarks.common import BENCH_DIR
    from repro.mutable import CompactionPolicy, MutableEngine

    bench = "mutate_sweep"
    n = n or (10_000 if fast else 20_000)
    fractions = [0.0, 0.1, 0.3] if fast else [0.0, 0.05, 0.1, 0.2, 0.3]
    k, pool = 10, 128
    repeats = 3
    n_queries = 64
    max_w = max(int(max(fractions) * n), 1)

    ds = dataset("sift", 5, 3, n, n_queries)  # the frozen main corpus
    extra = dataset("sift", 5, 3, max_w, 8, seed=1)  # rows streamed in
    params = SearchParams(k=k, pool_size=pool,
                          pioneer_size=max(4, pool // 8), backend="graph")
    qb = QueryBatch.match(ds.query_features, ds.query_attrs)
    rng = np.random.default_rng(0)

    def oracle(m):
        """Exact post-write truth: main ∪ inserted rows, dead ids pushed
        out of range so they can never rank."""
        n_ins = m._next_id - n
        feats = np.concatenate([ds.features, extra.features[:n_ins]])
        attrs = np.concatenate([ds.attrs, extra.attrs[:n_ins]])
        dead = [i for i in range(m._next_id) if not m.exists(i)]
        if dead:
            feats = feats.copy()
            feats[np.asarray(dead)] = 1e6
        return brute_force_hybrid(
            feats, attrs, ds.query_features, ds.query_attrs, k,
        )

    def measure(m):
        jax.block_until_ready(m.search(qb, params).ids)
        laps = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            res = m.search(qb, params)
            jax.block_until_ready(res.ids)
            laps.append(time.perf_counter() - t0)
        rec = recall_at_k(np.asarray(res.ids), oracle(m).ids, k)
        p50_ms = float(np.percentile(laps, 50)) * 1e3 / n_queries
        return round(float(rec), 4), round(p50_ms, 4)

    points = []
    for frac in fractions:
        # each fraction starts from an identical frozen main index (the
        # graph build is cached per dataset by built_index; from_parts is
        # cheap) and streams in frac·n inserts plus frac·n/5 deletes
        m = MutableEngine(built_engine(ds),
                          CompactionPolicy(max_delta_rows=10**9))
        n_writes = int(frac * n)
        n_deletes = n_writes // 5
        t_w = time.perf_counter()
        for i in range(n_writes):
            m.upsert(extra.features[i], extra.attrs[i], id=n + i)
        dels = rng.choice(n, size=n_deletes, replace=False) if n_deletes \
            else np.empty(0, np.int64)
        for i in dels:
            m.delete(int(i))
        write_s = time.perf_counter() - t_w
        writes_per_s = round((n_writes + n_deletes) / write_s, 1) \
            if n_writes else None

        rec_pre, p50_pre = measure(m)
        merged = m.merge()
        rec_post, p50_post = measure(m)

        tag = f"frac{frac}"
        emit(bench, tag, "recall_pre_merge", rec_pre)
        emit(bench, tag, "recall_post_merge", rec_post)
        emit(bench, tag, "p50_ms_pre_merge", p50_pre)
        emit(bench, tag, "p50_ms_post_merge", p50_post)
        if writes_per_s is not None:
            emit(bench, tag, "writes_per_s", writes_per_s)
        if merged is not None:
            emit(bench, tag, "merge_wall_ms", round(merged["wall_ms"], 1))
        points.append({
            "delta_fraction": frac,
            "n_upserts": n_writes,
            "n_deletes": n_deletes,
            "writes_per_s": writes_per_s,
            "recall_pre_merge": rec_pre,
            "recall_post_merge": rec_post,
            "p50_ms_pre_merge": p50_pre,
            "p50_ms_post_merge": p50_post,
            "merge": merged and {
                "wall_ms": round(merged["wall_ms"], 1),
                "linked": merged["linked"],
                "repaired": merged["repaired"],
                "tombstones": merged["tombstones"],
            },
        })

    flush_csv(bench)
    os.makedirs(BENCH_DIR, exist_ok=True)
    serve_ref = None
    serve_path = os.path.join(BENCH_DIR, "BENCH_serve.json")
    if os.path.exists(serve_path):
        with open(serve_path) as f:
            ref = json.load(f)
        serve_ref = {"n": ref.get("n"), "unbatched": ref.get("unbatched")}
    with open(os.path.join(BENCH_DIR, "BENCH_mutate.json"), "w") as f:
        json.dump({
            "n": n, "k": k, "pool": pool, "n_queries": n_queries,
            "read_only_baseline": serve_ref,
            "points": points,
        }, f, indent=2)


# ---------------------------------------------------------------------------
# Scale sweep — out-of-core IVF partitions: recall/qps vs nprobe under a
# bounded-residency segment store
# ---------------------------------------------------------------------------


def scale_sweep(fast: bool = True, n: int = 0, partitions: int = 0) -> None:
    """Out-of-core scaling of the IVF-partitioned engine: Recall@10 / qps /
    resident-row gauges vs ``nprobe``, with the partitions streamed from
    their on-disk layout through a ``SegmentStore`` whose cap is a small
    fraction of the corpus, plus the bit-exact full-probe (``nprobe = P``,
    brute sub-backend) parity check against the flat brute oracle. Emits
    ``BENCH_scale.json``. Pass ``--n``/``--partitions`` (benchmarks.run)
    for the CI smoke; ``--full`` defaults to the paper's 1M-row regime.
    """
    import json
    import math
    import os
    import shutil
    import tempfile

    from benchmarks.common import BENCH_DIR
    from repro.api import Engine
    from repro.core.help_graph import HelpConfig
    from repro.partition.store import row_bucket

    bench = "scale_sweep"
    n = n or (200_000 if fast else 1_000_000)
    k, n_queries, repeats = 10, 128, 2
    p = partitions or max(8, 2 ** int(round(math.log2(max(n // 8000, 8)))))
    sp = max(1, int(round(math.sqrt(p))))  # the classic IVF default probe

    ds = dataset("sift", 5, 3, n, n_queries)
    qb = QueryBatch.match(
        ds.query_features, ds.query_attrs, active=[0]
    )  # one hard MATCH dim — hybrid, ~1/labels selectivity
    mask = np.zeros_like(ds.query_attrs)
    mask[:, 0] = 1
    truth = brute_force_hybrid(
        ds.features, ds.attrs, ds.query_features, ds.query_attrs, k,
        mask=jnp.asarray(mask),
    )

    t0 = time.time()
    eng_build = Engine.build_partitioned(
        ds.features, ds.attrs, n_partitions=p,
        help_cfg=HelpConfig(gamma=12, gamma_new=4, max_rounds=4),
    )
    build_s = time.time() - t0
    emit(bench, f"n{n}_p{p}", "build_s", round(build_s, 1))

    # residency cap ≪ corpus: the largest partition must fit (documented
    # SegmentStore bound), a √P-probe working set should mostly fit
    buckets = [
        row_bucket(int(r)) for r in eng_build.index.summaries.n_rows
    ]
    cap = max(buckets) * max(4, sp)
    tmp = tempfile.mkdtemp(prefix="scale_sweep_")
    try:
        out_dir = os.path.join(tmp, "index")
        eng_build.save(out_dir)
        del eng_build
        eng = Engine.load(out_dir, residency_rows=cap)
        store = eng.index.store
        emit(bench, f"n{n}_p{p}", "cap_rows", cap)
        emit(bench, f"n{n}_p{p}", "cap_fraction", round(cap / n, 4))

        def point(params):
            res = eng.search(qb, params)  # compile + cold loads
            jax.block_until_ready(res.ids)
            t0 = time.perf_counter()
            for _ in range(repeats):
                res = eng.search(qb, params)
                jax.block_until_ready(res.ids)
            qps = n_queries / ((time.perf_counter() - t0) / repeats)
            return res, qps

        sweep = {}
        for np_ in sorted({1, max(1, sp // 2), sp, min(2 * sp, p)}):
            store.evict_all()
            store.reset_counters()
            res, qps = point(
                SearchParams(k=k, nprobe=np_, sub_backend="brute")
            )
            r = recall_at_k(res.ids, truth.ids, k)
            st = store.stats()
            name = f"nprobe{np_}"
            emit(bench, name, "recall", round(float(r), 4))
            emit(bench, name, "qps", round(float(qps), 1))
            emit(bench, name, "peak_resident_rows", st["peak_resident_rows"])
            sweep[np_] = {
                "recall_at_10": round(float(r), 4),
                "qps": round(float(qps), 1),
                "fp_evals_per_query": res.total_dist_evals // n_queries,
                "store": st,
                "cap_respected": st["peak_resident_rows"] <= cap,
            }

        # HELP-subgraph sub-backend at the default probe point (traversal
        # inside each probed partition instead of a full scan)
        store.evict_all()
        store.reset_counters()
        res_g, qps_g = point(
            SearchParams(k=k, nprobe=sp, sub_backend="graph", pool_size=64,
                         enforce_equality=True)
        )
        r_g = recall_at_k(res_g.ids, truth.ids, k)
        emit(bench, f"graph_nprobe{sp}", "recall", round(float(r_g), 4))
        emit(bench, f"graph_nprobe{sp}", "qps", round(float(qps_g), 1))
        graph_point = {
            "nprobe": sp,
            "recall_at_10": round(float(r_g), 4),
            "qps": round(float(qps_g), 1),
            "fp_evals_per_query": res_g.total_dist_evals // n_queries,
            "store": store.stats(),
        }

        # full probe (nprobe = P, brute sub-backend) must be bit-identical
        # to the flat brute oracle — the partition layer's correctness
        # anchor at full scale
        store.evict_all()
        store.reset_counters()
        res_full = eng.search(
            qb, SearchParams(k=k, nprobe=p, sub_backend="brute")
        )
        parity = bool(
            np.array_equal(np.asarray(res_full.ids), np.asarray(truth.ids))
            and np.array_equal(
                np.asarray(res_full.sqdists), np.asarray(truth.sqdists)
            )
        )
        emit(bench, f"full_probe_p{p}", "bit_exact_vs_oracle", parity)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    flush_csv(bench)
    os.makedirs(BENCH_DIR, exist_ok=True)
    with open(os.path.join(BENCH_DIR, "BENCH_scale.json"), "w") as f:
        json.dump(
            {
                "n": n,
                "partitions": p,
                "k": k,
                "n_queries": n_queries,
                "build_s": round(build_s, 1),
                "residency_cap_rows": cap,
                "residency_cap_fraction": round(cap / n, 4),
                "nprobe_sweep": {str(np_): v for np_, v in sweep.items()},
                "graph_sub_backend": graph_point,
                "full_probe_parity": {
                    "nprobe": p,
                    "bit_exact_vs_brute_oracle": parity,
                },
                "recall_target": {
                    "nprobe": sp,
                    "recall_at_10": sweep[sp]["recall_at_10"],
                    "target": 0.9,
                    "met": sweep[sp]["recall_at_10"] >= 0.9,
                },
            },
            f,
            indent=2,
        )


# ---------------------------------------------------------------------------
# PR 10 — observability: tracing overhead + trace decomposition
# ---------------------------------------------------------------------------


def obs_sweep(fast: bool = True, n: int = 0) -> None:
    """Self-asserting observability benchmark (PR 10 acceptance gates).

    * **overhead** — serve throughput with a *disabled* tracer attached
      (``Tracer(sample_every=0)`` — the no-op span/sampling hooks are the
      only code difference) must stay within 2% of the ``tracer=None``
      path, best-of-3 each arm on the same warmed executables;
    * **decomposition** — a fully sampled run's traces must decompose
      end-to-end latency: root = queue + batch exactly by construction,
      the batch's children (assemble/plan/compile/execute) cover ≥ half
      of the batch wall, and the root's recorded ``queue_ms + service_ms``
      attributes match its duration within tolerance;
    * **span set** — a quantized *partitioned* engine's sampled trace
      carries the full hierarchy: plan (backend/nprobe attrs), compile
      (hit/miss), execute (partition probe counters), serve (batch);
    * **exposition** — the run's registry renders a Prometheus text
      exposition whose every sample line parses and whose
      ``serve_total_ms_count`` equals the completions recorded.

    Emits ``BENCH_obs.json`` under artifacts/bench/. Pass ``--n``
    (benchmarks.run) for the CI smoke.
    """
    import json
    import os
    import re

    from benchmarks.common import BENCH_DIR
    from benchmarks.trace import zipf_query_trace
    from repro.api import Engine
    from repro.obs import Tracer, prometheus_text
    from repro.quant import QuantConfig
    from repro.serve import (
        ServerStats, TenantPolicy, TenantRegistry, serve_loop,
    )

    bench = "obs_sweep"
    n = n or (10_000 if fast else 20_000)
    n_requests = 256 if fast else 512
    window_ms, ladder = 2.0, (1, 8, 32)
    k, pool = 10, 64

    ds = dataset("sift", 5, 3, n, n_requests)
    eng = built_engine(ds, "auto")
    params = SearchParams(k=k, pool_size=pool,
                          pioneer_size=max(4, pool // 8))
    policy = TenantPolicy(params=params)

    def run_loop(engine, tracer, n_req=n_requests):
        trace, _ = zipf_query_trace(
            ds, n_req, n_tenants=4, spacing_s=5e-5, seed=0,
        )
        stats = ServerStats(engine)
        _, stats = serve_loop(
            engine, trace, TenantRegistry(default_policy=policy),
            window_ms=window_ms, buckets=ladder, stats=stats, tracer=tracer,
        )
        return stats

    run_loop(eng, None)  # warm the ladder executables once for both arms

    # -- gate 1: disabled-tracer overhead ≤ 2% ------------------------------
    qps_none = qps_disabled = 0.0
    for _ in range(3):
        qps_none = max(
            qps_none, run_loop(eng, None).snapshot()["service_qps"]
        )
        qps_disabled = max(
            qps_disabled,
            run_loop(eng, Tracer(sample_every=0)).snapshot()["service_qps"],
        )
    overhead = 1.0 - qps_disabled / qps_none if qps_none else 0.0
    assert qps_disabled >= 0.98 * qps_none, (
        f"disabled-tracer serve throughput {qps_disabled:.1f} qps fell "
        f"more than 2% below the untraced path {qps_none:.1f} qps"
    )
    emit(bench, "overhead", "qps_untraced", round(qps_none, 1))
    emit(bench, "overhead", "qps_tracer_disabled", round(qps_disabled, 1))
    emit(bench, "overhead", "overhead_frac", round(overhead, 4))

    # informational cross-run reference: PR 9's serve artifact, if present
    baseline_qps = None
    ref = os.path.join(BENCH_DIR, "BENCH_serve.json")
    if os.path.exists(ref):
        try:
            with open(ref) as f:
                pts = json.load(f)["points"]
            baseline_qps = max(p["service_qps"] for p in pts)
        except (KeyError, ValueError, OSError):
            baseline_qps = None

    # -- gate 2: sampled traces decompose end-to-end latency ----------------
    tracer = Tracer(sample_every=1)
    stats = run_loop(eng, tracer)
    traces = tracer.traces()
    assert traces, "sample_every=1 over a full run must record traces"
    max_exact_err_ms, max_attr_err_ms, min_cover = 0.0, 0.0, 1.0
    for tr in traces:
        root = tr.root
        total_ms = root.duration * 1e3
        inbox, queue, batch = (root.find("serve.inbox"),
                               root.find("serve.queue"),
                               root.find("serve.flush"))
        assert None not in (inbox, queue, batch), (
            "every request trace carries inbox + queue + flush spans"
        )
        # exact by construction: root is pinned to inbox + queue + flush
        exact_err = abs(total_ms - (inbox.duration + queue.duration
                                    + batch.duration) * 1e3)
        assert exact_err <= 1e-3, (
            f"root span ({total_ms:.3f}ms) != inbox + queue + flush "
            f"(err {exact_err:.4f}ms)"
        )
        max_exact_err_ms = max(max_exact_err_ms, exact_err)
        # recorded latency attrs re-derive the same total within tolerance
        # (service_ms excludes batch assembly; queue_ms is driver-clock)
        attr_ms = root.attrs["queue_ms"] + root.attrs["service_ms"]
        attr_err = abs(total_ms - attr_ms)
        assert attr_err <= max(1.0, 0.25 * total_ms), (
            f"trace total {total_ms:.3f}ms vs recorded queue+service "
            f"{attr_ms:.3f}ms drifted past tolerance"
        )
        max_attr_err_ms = max(max_attr_err_ms, attr_err)
        if batch.duration > 0:
            cover = sum(c.duration for c in batch.children) / batch.duration
            assert cover >= 0.5, (
                f"batch children cover only {cover:.0%} of the batch span"
            )
            min_cover = min(min_cover, cover)
    emit(bench, "decomposition", "n_traces", len(traces))
    emit(bench, "decomposition", "max_exact_err_ms",
         round(max_exact_err_ms, 4))
    emit(bench, "decomposition", "min_child_coverage", round(min_cover, 3))

    # -- gate 3: quantized partitioned engine's trace has the full span set -
    p_eng = Engine.build_partitioned(
        ds.features, ds.attrs, n_partitions=8,
        quant_cfg=QuantConfig(mode="pq", pq_subspaces=16, pq_train_iters=6),
    )
    run_loop(p_eng, None, n_req=32)  # warm compile off the traced run
    p_tracer = Tracer(sample_every=1)
    run_loop(p_eng, p_tracer, n_req=32)
    p_traces = p_tracer.traces()
    assert p_traces, "partitioned serve run must record traces"
    root = p_traces[0].root
    names = ("serve.flush", "engine.plan", "engine.lookup", "engine.dispatch")
    spans = {s: root.find(s) for s in names}
    missing = [s for s, sp in spans.items() if sp is None]
    assert not missing, f"partitioned trace missing spans: {missing}"
    assert spans["engine.plan"].attrs.get("backend") == "partitioned"
    assert "nprobe" in spans["engine.plan"].attrs
    assert "hit" in spans["engine.lookup"].attrs
    assert "partitions_probed" in spans["engine.dispatch"].attrs, (
        "engine.dispatch span must carry the probe counters"
    )
    emit(bench, "partitioned_trace", "spans", len(spans))
    emit(bench, "partitioned_trace", "partitions_probed",
         spans["engine.dispatch"].attrs["partitions_probed"])

    # -- gate 4: the Prometheus exposition parses ---------------------------
    text = prometheus_text(stats.registry)
    sample_re = re.compile(
        r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? -?[0-9+\-.eEinfa]+$"
    )
    lines = [l for l in text.splitlines() if l and not l.startswith("#")]
    bad = [l for l in lines if not sample_re.match(l)]
    assert lines and not bad, f"unparseable exposition lines: {bad[:3]}"
    count_line = next(
        l for l in lines if l.startswith("serve_total_ms_count")
    )
    assert float(count_line.split()[-1]) == stats.completed, (
        "histogram count must equal completions recorded"
    )
    emit(bench, "exposition", "sample_lines", len(lines))

    flush_csv(bench)
    os.makedirs(BENCH_DIR, exist_ok=True)
    with open(os.path.join(BENCH_DIR, "BENCH_obs.json"), "w") as f:
        json.dump({
            "n": n, "n_requests": n_requests, "k": k, "pool": pool,
            "window_ms": window_ms, "buckets": list(ladder),
            "overhead": {
                "qps_untraced": round(qps_none, 1),
                "qps_tracer_disabled": round(qps_disabled, 1),
                "overhead_frac": round(overhead, 4),
                "threshold": 0.02,
                "passed": True,
                "pr9_serve_best_qps": baseline_qps,
            },
            "decomposition": {
                "n_traces": len(traces),
                "max_exact_err_ms": round(max_exact_err_ms, 4),
                "max_attr_err_ms": round(max_attr_err_ms, 3),
                "min_child_coverage": round(min_cover, 3),
                "passed": True,
            },
            "partitioned_trace": {
                "spans": sorted(spans),
                "plan_backend": spans["plan"].attrs["backend"],
                "nprobe": spans["plan"].attrs["nprobe"],
                "partitions_probed":
                    spans["execute"].attrs["partitions_probed"],
                "passed": True,
            },
            "exposition": {
                "sample_lines": len(lines),
                "histogram_count_matches": True,
            },
        }, f, indent=2)


ALL = [
    tab1_magnitude_stats,
    fig3_qps_recall,
    tab4_cardinality_robustness,
    fig5_selectivity,
    fig6_ablations,
    fig7_build_time,
    fig8_alpha_sweep,
    fig9_sigma_sweep,
    fig10_gamma_sweep,
    tab5_kernel_fusion,
    quant_sweep,
    filter_sweep,
    planner_sweep,
    serve_sweep,
    cache_sweep,
    mutate_sweep,
    scale_sweep,
    obs_sweep,
]
