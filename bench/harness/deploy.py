"""Build one configuration's deployment through the program's public path.

``repro.api.Engine`` holds the index, ``repro.serve.ThreadedServer`` serves
it; the configuration file gives every setting. Set-up is timed step by
step on the host clock and printed, so a run shows where its set-up went.
"""
from __future__ import annotations

import dataclasses
import time
from types import ModuleType

import numpy as np

from harness.spec import SpecError

#: every setting a configuration's groups may hold; the harness reads each
#: one, so a key outside these is refused rather than silently ignored
SETTINGS = {
    "corpus": {"rows", "dim", "profile", "noise_scale", "cluster_spread",
               "clusters", "attr_dims", "labels_per_dim", "attr_cluster_corr"},
    "index": {"kind", "gamma", "gamma_new", "max_rounds", "quant",
              "pq_subspaces", "alpha"},
    "search": {"backend", "k", "pool_size", "pioneer_size", "rerank_size"},
    "serve": {"buckets", "window_ms", "tenants", "result_cache"},
}
INDEX_KINDS = ("help", "codes")


def check_config(cfg: dict) -> None:
    """Refuse a configuration with a setting the harness would not apply."""
    for group, known in SETTINGS.items():
        unknown = set(cfg.get(group, {})) - known
        if unknown:
            raise SpecError(f"{cfg.get('name')}: {group} has settings the "
                            f"harness does not apply: {sorted(unknown)}")
    kind = cfg["index"]["kind"]
    if kind not in INDEX_KINDS:
        raise SpecError(f"{cfg.get('name')}: index kind {kind!r} is not "
                        f"one of {INDEX_KINDS}")


class SetupClock:
    """Host seconds of each set-up step, printed as they end."""

    def __init__(self, say):
        self.say = say

    def step(self, label: str, t0: float) -> None:
        self.say(f"[set-up] {label}: {time.perf_counter() - t0:.3f} s")


@dataclasses.dataclass
class Deployment:
    engine: object  # repro.api.Engine
    params: object  # repro.api.SearchParams every tenant serves with
    buckets: tuple
    window_ms: float
    tenants: tuple
    planned_backend: str
    predicate: ModuleType  # the traffic mix's predicate file
    result_cache: bool  # serve from the program's ResultCache


def _requests(dep: Deployment, corpus, idx, first_id=0):
    from repro.api import Query
    from repro.serve import Request

    return [
        Request(dep.tenants[(first_id + j) % len(dep.tenants)],
                Query(corpus.query_features[i],
                      dep.predicate.program(corpus.query_attrs[i])),
                request_id=first_id + j)
        for j, i in enumerate(idx)
    ]


def make_request(dep: Deployment, corpus, pool_idx: int, request_id: int):
    return _requests(dep, corpus, [pool_idx], request_id)[0]


def new_result_cache(dep: Deployment):
    """A fresh ``repro.cache.ResultCache`` (the program's defaults) for the
    window's server when ``serve.result_cache`` is true, else None."""
    if not dep.result_cache:
        return None
    from repro.cache.results import ResultCache

    return ResultCache()


def _policy(dep: Deployment):
    """Every tenant serves with the configuration's params, its pool cap
    raised to that pool; no rate limit."""
    from repro.serve import TenantPolicy

    return TenantPolicy(params=dep.params,
                        max_pool=max(1024, dep.params.effective_pool))


def registry(dep: Deployment):
    from repro.serve import TenantRegistry

    reg = TenantRegistry()
    for t in dep.tenants:
        reg.register(t, _policy(dep))
    return reg


def build(cfg: dict, corpus, predicate: ModuleType,
          clock: SetupClock) -> Deployment:
    import jax

    from repro.api import Engine, QueryBatch, SearchParams
    from repro.core.help_graph import HelpConfig
    from repro.quant import QuantConfig

    ix, sr, sv = cfg["index"], cfg["search"], cfg["serve"]
    t0 = time.perf_counter()
    feats = jax.device_put(corpus.features)
    attrs = jax.device_put(corpus.attrs)
    jax.block_until_ready((feats, attrs))
    clock.step("corpus to the device", t0)

    t0 = time.perf_counter()
    graph = ix["kind"] == "help"
    eng = Engine.build(
        feats, attrs,
        HelpConfig(gamma=ix["gamma"], gamma_new=ix["gamma_new"],
                   max_rounds=ix["max_rounds"]) if graph else HelpConfig(),
        quant_cfg=QuantConfig(mode=ix["quant"],
                              pq_subspaces=ix.get("pq_subspaces", 8)),
        build_graph=graph,
        alpha=ix.get("alpha"),  # None: Eq. 5 from the corpus
    )
    idx = eng.index
    jax.block_until_ready(idx.graph if graph else idx.quant.codes)
    if graph:
        rep = idx.report
        clock.step(f"HELP build ({rep.rounds} rounds, "
                   f"psi={rep.psi_history[-1]:.3f}, "
                   f"alpha={idx.metric_cfg.alpha:.3f})", t0)
    else:
        clock.step(f"{ix['quant']} codec train + encode "
                   f"(codes {tuple(idx.quant.codes.shape)} "
                   f"{idx.quant.codes.dtype})", t0)

    params = SearchParams(
        k=sr["k"], pool_size=sr["pool_size"],
        pioneer_size=sr.get("pioneer_size", 0),
        rerank_size=sr.get("rerank_size", 0), backend=sr["backend"],
    )
    dep = Deployment(
        engine=eng, params=params, buckets=tuple(sv["buckets"]),
        window_ms=float(sv["window_ms"]),
        tenants=tuple(f"tenant-{t}" for t in range(sv["tenants"])),
        planned_backend="", predicate=predicate,
        result_cache=bool(sv["result_cache"]),
    )
    t0 = time.perf_counter()  # an "auto" backend would calibrate here
    plan = eng.plan(
        QueryBatch.from_queries([_requests(dep, corpus, [0])[0].query]),
        params)
    dep.planned_backend = plan.backend
    clock.step(f"plan (backend {plan.backend}, quant {plan.quant_mode}: "
               f"{plan.reason})", t0)
    return dep


def warm(dep: Deployment, corpus, clock: SetupClock) -> None:
    """One full batch per bucket of the ladder through the serving path, so
    every shape the window can use is compiled before it opens."""
    from repro.serve import TenantRegistry, serve_loop

    t0 = time.perf_counter()
    reg = TenantRegistry(default_policy=_policy(dep))
    for b in dep.buckets:
        reqs = _requests(dep, corpus, np.arange(b))
        serve_loop(dep.engine, reqs, reg, window_ms=dep.window_ms,
                   buckets=dep.buckets)
    clock.step(f"warm-up of buckets {dep.buckets}", t0)
