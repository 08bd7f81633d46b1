#!/usr/bin/env python3
"""Chip smoke test: serve STABLE on one TPU at SIFT1M scale.

    python chip_smoke.py                # one chip, every phase below
    python chip_smoke.py --four-chips   # only the sharded index on 4 chips

Deployment (the paper's own regime): sift profile (128-d), L=5 categorical
attributes at 3 labels each (Θ=243), MATCH traffic, HELP built with Γ=24,
Γ_new=6 and 8 rounds, N=1,000,000 rows. Data is generated from ``--seed``
on every run; nothing is loaded.

Phases, in one process:
  chip check — the default JAX backend must be "tpu"; anything else exits
               non-zero before any work is done.
  set-up     — data, reference, index build and bucket warm-up. Their wall
               seconds are printed as set-up time, not as device metrics.
  exact      — backend="brute", quant="none", 128 queries: recall@10 ≥ 0.99.
  served     — 512 MATCH requests through ThreadedServer (ladder 1/8/32/128,
               no rate limit, traversal pool 512): all completed, none shed
               or failed, no retrace after warm-up, recall@10 ≥ 0.5;
               then recall at the launcher's pool 64, reported with no bar.
  parity     — the served phase at N=20,000: recall within 0.02 of what the
               CPU serving launcher reports for the same corpus.
  quantized  — pq4 codes (32 subspaces), backend="brute": the 4-bit
               ``adc_scan`` Pallas kernel runs compiled on the laid-out
               codes the brute path scans, its scores agree with the jnp
               ADC reference run on the host's CPU (bare L2 sums and with
               the AUTO penalty), and recall@10 ≥ 0.90 after the exact
               rerank of the pool.

Every recall is measured against an independent reference: NumPy float64
on the host, hard-filter (all attributes equal) exact L2 top-10.
``--four-chips`` builds the same corpus as a ``ShardedStableIndex`` over a
(data=1, model=4) mesh and checks recall and per-device memory only.

The last line of stdout is one JSON object; any failed bar exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

K = 10
N_REQUESTS = 512  # served-phase stream length
N_BATCH = 128  # queries per exact / quantized / sharded batch
BUCKETS = (1, 8, 32, 128)
WINDOW_MS = 2.0
N_TENANTS = 4
STREAM_TIMEOUT_S = 600  # a request still pending then fails the run
LAUNCHER_POOL = 64  # the serving launcher's traversal pool (parity phase)
#: traversal pool of the full-N served phase. The launcher's 64 is sized for
#: 20k rows: at 100k it reaches recall@10 0.41 on the CPU and 0.43 on the
#: chip, with 47% of returned ids failing the MATCH predicate (the AUTO
#: penalty is soft); 512 reaches 0.96 at 100k on the CPU.
SERVED_POOL = 512
PQ_SUBSPACES = 32
#: exact-rerank pool of the quantized phase. pq4 codes rank a 1M-row,
#: Θ=243 corpus coarsely (~4.5k rows match a query); 2048 keeps the rerank
#: head deep enough that recall@10 tests the kernel's numerics, not pq4.
PQ_POOL = 2048

EXACT_BAR = 0.99
SERVED_BAR = 0.5
PQ_BAR = 0.90
ADC_REL_TOL = 1e-4  # compiled kernel vs jnp ADC reference (f32 sums)

#: recall@10 of the same served stream on the CPU backend, from
#:   PYTHONPATH=src JAX_PLATFORMS=cpu python -m repro.launch.serve \
#:       --n 20000 --requests 512
#: whose planner served it on the HELP graph backend.
CPU_PARITY_N = 20_000
CPU_PARITY_RECALL = 0.800
CPU_PARITY_BACKEND = "graph"
PARITY_TOL = 0.02


def say(*parts) -> None:
    print(*parts, flush=True)


class Bars:
    """Collects every bar's outcome; the run fails if any bar failed."""

    def __init__(self):
        self.failed: list[str] = []

    def check(self, ok: bool, what: str) -> None:
        say(f"  [{'pass' if ok else 'FAIL'}] {what}")
        if not ok:
            self.failed.append(what)


class SetupClock:
    """Host wall seconds of each set-up step (compiles included)."""

    def __init__(self):
        self.total = 0.0

    def step(self, label: str, t0: float) -> None:
        dt = time.perf_counter() - t0
        self.total += dt
        say(f"  [set-up] {label}: {dt:.2f} s")


# ---------------------------------------------------------------------------
# Independent reference: NumPy float64 on the host
# ---------------------------------------------------------------------------


def reference_topk(features, attrs, qf, qa, k: int = K) -> np.ndarray:
    """Exact MATCH top-k: rows whose every attribute equals the query's,
    ranked by float64 squared L2 (ties by id). (Q, k) ids, -1 padded."""
    groups, inverse = np.unique(attrs, axis=0, return_inverse=True)
    inverse = inverse.reshape(-1)
    order = np.argsort(inverse, kind="stable")
    bounds = np.searchsorted(inverse[order], np.arange(len(groups) + 1))
    lookup = {tuple(g): i for i, g in enumerate(groups.tolist())}
    out = np.full((len(qf), k), -1, np.int64)
    for i, (v, a) in enumerate(zip(qf, qa)):
        g = lookup.get(tuple(a.tolist()))
        if g is None:
            continue
        rows = order[bounds[g]:bounds[g + 1]]
        diff = features[rows].astype(np.float64) - v.astype(np.float64)
        d = np.einsum("ij,ij->i", diff, diff)
        top = rows[np.lexsort((rows, d))[:k]]
        out[i, : len(top)] = top
    return out


def recall(ids: np.ndarray, truth: np.ndarray) -> float:
    """Mean |returned ∩ truth| / |truth| (paper §IV-A) over the queries
    that match at least one row."""
    per = []
    for r, t in zip(np.asarray(ids), truth):
        t = set(t[t >= 0].tolist())
        if t:
            per.append(len(t & set(r[r >= 0].tolist())) / len(t))
    return float(np.mean(per))


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def make_corpus(n: int, seed: int):
    """The serving launcher's corpus: sift, L=5 × 3 labels, 16 clusters."""
    from repro.data.synthetic import make_hybrid_dataset

    return make_hybrid_dataset(
        n=n, n_queries=N_REQUESTS, profile="sift", attr_dim=5,
        labels_per_dim=3, n_clusters=16, attr_cluster_corr=0.6, seed=seed,
    )


def prepare(n: int, seed: int, clock: SetupClock):
    """Data and its reference answers for every request."""
    t0 = time.perf_counter()
    ds = make_corpus(n, seed)
    clock.step(f"data N={n} (seed {seed})", t0)
    t0 = time.perf_counter()
    truth = reference_topk(
        ds.features, ds.attrs, ds.query_features, ds.query_attrs
    )
    clock.step(f"float64 reference for {N_REQUESTS} queries", t0)
    return ds, truth


def build_engine(ds, clock: SetupClock):
    from repro.api import Engine
    from repro.core.help_graph import HelpConfig

    t0 = time.perf_counter()
    eng = Engine.build(
        ds.features, ds.attrs, HelpConfig(gamma=24, gamma_new=6, max_rounds=8)
    )
    eng.index.graph.block_until_ready()
    rep = eng.index.report
    clock.step(
        f"HELP build ({rep.rounds} rounds, ψ={rep.psi_history[-1]:.3f}, "
        f"α={eng.index.metric_cfg.alpha:.3f})", t0,
    )
    return eng


def platform_of(arr) -> str:
    return ",".join(sorted({d.platform for d in arr.devices()}))


def exact_phase(eng, ds, truth, bars: Bars, clock: SetupClock) -> None:
    from repro.api import QueryBatch, SearchParams

    say("== exact: backend=brute quant=none ==")
    qb = QueryBatch.match(ds.query_features[:N_BATCH], ds.query_attrs[:N_BATCH])
    params = SearchParams(k=K, backend="brute", quant="none")
    t0 = time.perf_counter()
    res = eng.search(qb, params)
    ids = np.asarray(res.ids)
    clock.step("exact search (compile + run)", t0)
    say(f"  arrays on: {platform_of(res.ids)}")
    r = recall(ids, truth[:N_BATCH])
    bars.check(r >= EXACT_BAR, f"exact recall@10={r:.4f} ≥ {EXACT_BAR}")


def serve_phase(eng, ds, truth, bars: Bars, clock: SetupClock, *,
                label: str, bar: float, pool: int,
                backend: str = "auto") -> float:
    """Warm every bucket, then serve the whole request stream through
    ThreadedServer; returns recall@10 of the completed requests."""
    from repro.api import MATCH, Query, QueryBatch, SearchParams
    from repro.serve import (
        Request, TenantPolicy, TenantRegistry, ThreadedServer, serve_loop,
    )

    say(f"== {label}: {N_REQUESTS} MATCH requests through ThreadedServer, "
        f"pool {pool} ==")
    params = SearchParams(
        k=K, pool_size=pool, pioneer_size=max(4, pool // 8), backend=backend
    )
    tenants = [f"tenant-{t}" for t in range(N_TENANTS)]
    reg = TenantRegistry()
    for t in tenants:
        reg.register(t, TenantPolicy(params=params))
    reqs = [
        Request(tenants[i % N_TENANTS],
                Query(ds.query_features[i],
                      [MATCH(int(v)) for v in ds.query_attrs[i]]),
                request_id=i)
        for i in range(N_REQUESTS)
    ]
    t0 = time.perf_counter()  # the first plan calibrates the cost model
    plan = eng.plan(
        QueryBatch.match(ds.query_features[:1], ds.query_attrs[:1]), params
    )
    say(f"  planned backend: {plan.backend} ({plan.reason})")
    warm_reg = TenantRegistry(default_policy=TenantPolicy(params=params))
    for b in BUCKETS:  # one full batch per bucket: every shape compiles here
        serve_loop(eng, reqs[:b], warm_reg, window_ms=WINDOW_MS,
                   buckets=BUCKETS)
    clock.step(f"plan + warm buckets {BUCKETS}", t0)

    t0 = time.perf_counter()
    with ThreadedServer(eng, reg, window_ms=WINDOW_MS, buckets=BUCKETS) as srv:
        futs = [srv.submit(r) for r in reqs]
        errors = [f.exception(timeout=STREAM_TIMEOUT_S) for f in futs]
    wall = time.perf_counter() - t0
    failed = sum(e is not None for e in errors)
    done = [f.result() for f, e in zip(futs, errors) if e is None]
    done = [r for r in done if r.ok]
    snap = srv.stats.snapshot()
    say(f"  stream: {wall:.2f} s host wall, {snap['batches']} batches, "
        f"fill={snap['batch_fill_ratio']:.2f}")
    bars.check(
        snap["completed"] == snap["submitted"] == N_REQUESTS
        and snap["rejected"] == 0 and failed == 0,
        f"completed={snap['completed']} submitted={snap['submitted']} "
        f"shed={snap['rejected']} failed={failed}",
    )
    bars.check(snap["retraces"] == 0,
               f"retraces={snap['retraces']} after warm-up")
    ids = np.stack([r.ids for r in done]) if done else np.zeros((0, K))
    take = [r.request_id for r in done]
    r = recall(ids, truth[take])
    bars.check(r >= bar, f"{label} recall@10={r:.4f} ≥ {bar}")
    return r


def launcher_pool_recall(eng, ds, truth) -> float:
    """Recall@10 of every request at the serving launcher's own pool, in
    batches of N_BATCH through Engine.search. Printed with no bar: the
    served phase's bar runs at SERVED_POOL."""
    from repro.api import QueryBatch, SearchParams

    params = SearchParams(k=K, pool_size=LAUNCHER_POOL,
                          pioneer_size=max(4, LAUNCHER_POOL // 8))
    qb = QueryBatch.match(ds.query_features[:N_BATCH],
                          ds.query_attrs[:N_BATCH])
    plan = eng.plan(qb, params)
    ids = np.concatenate([
        np.asarray(eng.search(QueryBatch.match(
            ds.query_features[i:i + N_BATCH],
            ds.query_attrs[i:i + N_BATCH]), params).ids)
        for i in range(0, N_REQUESTS, N_BATCH)
    ])
    r = recall(ids, truth)
    say(f"  at the launcher's pool {LAUNCHER_POOL} ({plan.backend}, "
        f"{N_REQUESTS} requests in batches of {N_BATCH}): recall@10={r:.4f} "
        "(reported, no bar)")
    return r


def quantized_phase(ds, truth, bars: Bars, clock: SetupClock) -> None:
    import jax
    import jax.numpy as jnp

    from repro.api import Engine, QueryBatch, SearchParams
    from repro.kernels.adc_scan.ops import adc_scan
    from repro.kernels.adc_scan.ref import adc_scan4_ref
    from repro.quant import QuantConfig

    say(f"== quantized: pq4 ({PQ_SUBSPACES} subspaces), backend=brute ==")
    t0 = time.perf_counter()
    eng = Engine.build(
        ds.features, ds.attrs, build_graph=False,
        quant_cfg=QuantConfig(mode="pq4", pq_subspaces=PQ_SUBSPACES),
    )
    clock.step("pq4 codec train + encode", t0)
    store = eng.index.quant
    say(f"  codes: {store.codes.shape} {store.codes.dtype} on "
        f"{platform_of(store.codes)}")

    qv = jnp.asarray(ds.query_features[:N_BATCH])
    qa = jnp.asarray(ds.query_attrs[:N_BATCH])
    lut = store.lut(qv)
    rows = min(16384, store.codes.shape[0])
    alpha = eng.index.metric_cfg.alpha
    # the reference runs on the host's CPU device: an f32 gather-sum that
    # no TPU compiler rewrites (the chip may lower a small-table gather as
    # a one-hot contraction at bf16)
    ref_in = [np.asarray(a) for a in (lut, store.codes[:rows], qa,
                                      eng.index.attrs[:rows])]
    for mode in ("l2", "auto"):  # bare ADC sums; fused AUTO penalty
        scan = jax.jit(lambda lut, codes, qa, xa, mode=mode: adc_scan(
            lut, codes, qa, xa, alpha=alpha, mode=mode, packed=True))
        hlo = scan.lower(lut, store.scan_codes, qa,
                         eng.index.attrs).as_text()
        bars.check("tpu_custom_call" in hlo, f"4-bit adc_scan (mode={mode}) "
                   "lowers to a compiled Pallas TPU kernel")
        got = np.asarray(scan(lut, store.scan_codes, qa,
                              eng.index.attrs)[:, :rows])
        with jax.default_device(jax.devices("cpu")[0]):
            want = np.asarray(adc_scan4_ref(*ref_in, alpha, mode=mode))
        err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
        bars.check(err <= ADC_REL_TOL,
                   f"kernel vs jnp ADC reference (mode={mode}): max rel err "
                   f"{err:.2e} ≤ {ADC_REL_TOL:g} ({rows} rows)")

    qb = QueryBatch.match(ds.query_features[:N_BATCH], ds.query_attrs[:N_BATCH])
    params = SearchParams(k=K, pool_size=PQ_POOL, backend="brute")
    plan = eng.plan(qb, params)
    say(f"  plan: backend={plan.backend} quant={plan.quant_mode}, "
        f"exact rerank pool={PQ_POOL}")
    bars.check(plan.quant_mode == "pq4", f"plan scans pq4 codes "
               f"(got {plan.quant_mode})")
    t0 = time.perf_counter()
    ids = np.asarray(eng.search(qb, params).ids)
    clock.step("pq4 brute search (compile + run)", t0)
    r = recall(ids, truth[:N_BATCH])
    bars.check(r >= PQ_BAR, f"pq4 recall@10={r:.4f} ≥ {PQ_BAR} "
               f"(rerank pool {PQ_POOL})")


def one_chip(args, bars: Bars, clock: SetupClock) -> None:
    ds, truth = prepare(args.n, args.seed, clock)
    eng = build_engine(ds, clock)
    say(f"  index arrays on: {platform_of(eng.index.features)}")
    exact_phase(eng, ds, truth, bars, clock)
    serve_phase(eng, ds, truth, bars, clock, label="served", bar=SERVED_BAR,
                pool=SERVED_POOL)
    launcher_pool_recall(eng, ds, truth)
    del eng
    quantized_phase(ds, truth, bars, clock)
    del ds, truth

    say(f"== parity: N={CPU_PARITY_N}, backend={CPU_PARITY_BACKEND} as on "
        f"the CPU ==")
    ds, truth = prepare(CPU_PARITY_N, 0, clock)  # the launcher's seed
    eng = build_engine(ds, clock)
    r = serve_phase(eng, ds, truth, bars, clock, label="parity",
                    bar=SERVED_BAR, pool=LAUNCHER_POOL,
                    backend=CPU_PARITY_BACKEND)
    bars.check(abs(r - CPU_PARITY_RECALL) <= PARITY_TOL,
               f"parity recall {r:.4f} within {PARITY_TOL} of the CPU's "
               f"{CPU_PARITY_RECALL:.3f}")


def four_chips(args, bars: Bars, clock: SetupClock) -> None:
    import jax

    from repro.api import Engine, QueryBatch, SearchParams
    from repro.core.auto import MetricConfig, sample_stats
    from repro.core.help_graph import HelpConfig
    from repro.distributed.search import ShardedStableIndex
    from repro.launch.mesh import make_local_mesh

    devs = jax.devices()
    if len(devs) != 4:
        raise SystemExit(f"--four-chips needs 4 devices, found {len(devs)}")
    ds, truth = prepare(args.n, args.seed, clock)
    say("== sharded: ShardedStableIndex on a (data=1, model=4) mesh ==")
    mesh = make_local_mesh(data=1, model=4)
    t0 = time.perf_counter()
    stats = sample_stats(ds.features, ds.attrs)
    idx = ShardedStableIndex.build(
        mesh, ds.features, ds.attrs, MetricConfig(mode="auto", alpha=stats.alpha),
        HelpConfig(gamma=24, gamma_new=6, max_rounds=8),
    )
    idx.graphs.block_until_ready()
    clock.step("4 per-shard HELP builds", t0)
    eng = Engine(idx)
    qb = QueryBatch.match(ds.query_features[:N_BATCH], ds.query_attrs[:N_BATCH])
    params = SearchParams(
        k=K, pool_size=SERVED_POOL, pioneer_size=max(4, SERVED_POOL // 8)
    )
    plan = eng.plan(qb, params)
    say(f"  planned backend: {plan.backend} ({plan.reason})")
    t0 = time.perf_counter()
    ids = np.asarray(eng.search(qb, params).ids)
    clock.step("sharded search (compile + run)", t0)
    r = recall(ids, truth[:N_BATCH])
    bars.check(r >= SERVED_BAR, f"sharded recall@10={r:.4f} ≥ {SERVED_BAR}")

    shard_bytes = idx.features.addressable_shards[0].data.nbytes
    owners = {s.device for s in idx.features.addressable_shards}
    used = [d.memory_stats()["bytes_in_use"] for d in devs]
    for d, b in zip(devs, used):
        say(f"  device {d.id} ({d.device_kind}): bytes_in_use={b}")
    bars.check(len(owners) == 4 and min(used) >= shard_bytes,
               f"feature shards on {len(owners)} devices, each holding ≥ "
               f"one {shard_bytes}-byte shard")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=1_000_000,
                    help="corpus rows (SIFT1M scale by default)")
    ap.add_argument("--seed", type=int, default=0, help="data seed")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded index on a 4-chip mesh")
    args = ap.parse_args(argv)

    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip check failed: JAX default backend is {backend!r}, "
              "not 'tpu'", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    dev = jax.devices()[0]
    say(f"device: {dev.platform} / {dev.device_kind} × {len(jax.devices())}")
    say(f"compile cache: {cache}")
    say(f"N={args.n}" + ("" if args.n == 1_000_000 else
                         " (cut from SIFT1M's 1,000,000)"))

    bars, clock = Bars(), SetupClock()
    t0 = time.perf_counter()
    (four_chips if args.four_chips else one_chip)(args, bars, clock)
    say(f"set-up total: {clock.total:.2f} s; whole run: "
        f"{time.perf_counter() - t0:.2f} s (host wall clock)")
    if bars.failed:
        print(f"{len(bars.failed)} bar(s) failed:", *bars.failed,
              sep="\n  ", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
