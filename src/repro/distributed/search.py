"""Distributed hybrid search: database sharded over `model`, queries over
`data`, exact per-shard top-k merge (DESIGN.md §4).

Each model-shard owns an independent HELP sub-index over its slice of the
database (sub-indices are built per shard — embarrassingly parallel at fleet
scale). A query batch is searched on every shard via `shard_map`; local ids
are offset to global ids and the per-shard top-k results are all-gathered
over `model` and reduced with one global top-k — an EXACT merge (top-k of a
union equals top-k of per-shard top-k's).

Quantized serving (``quant_cfg.mode`` ∈ {sq8, pq, pq4, opq-pq, opq-pq4}):
codes are sharded over
`model` alongside the graph; codec state (SQ8 affine params / PQ codebooks)
is replicated, and PQ ADC tables are computed per data-shard inside the
shard_map body. The rerank is *pooled across shards*: every shard traverses
over codes only (``routing.traverse_pool`` — the same stages the single-host
path composes), the per-shard *code* top-k heads are all-gathered over
`model` and reduced to one global code top-k, and only those candidates are
re-scored at full precision — each shard scores the candidates it owns and a
``pmin`` over `model` assembles the exact distances. Full-precision work per
query is therefore one global ``rerank_size`` pool instead of one per shard.

The compiled search fn is cached per (routing config, k, mask/target
arity): repeated serving batches reuse one ``jax.jit``-wrapped ``shard_map``
callable (and its cached entry pools) instead of re-wrapping and re-tracing
the mesh program every call.

Persistence: ``save``/``load`` round-trip the whole sharded index through
one subdirectory per model shard (that shard's feature/attr/code rows and
its *local* HELP graph — independently writable per host at fleet scale)
plus replicated codec arrays and mesh/codec metadata. Loading reshards onto
the current mesh; the model-axis size must match the saved shard count
(per-shard graphs are local to those boundaries), while the data axis is
free to differ.
"""
from __future__ import annotations

import dataclasses
import json
import os
from collections import OrderedDict
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import lru_get
from repro.core import routing as routing_mod
from repro.core.auto import MetricConfig
from repro.core.graph_ops import INF, INVALID
from repro.core.help_graph import HelpConfig, build_help_graph
from repro.core.routing import RoutingConfig
from repro.quant import (
    PQCodebook, QuantConfig, QuantizedVectors, adc_lut, has_rotation,
    is_pq_mode, rotate,
)

Array = jax.Array

SHARDED_META = "sharded_meta.json"
SHARDED_FORMAT = "stable-sharded-v1"

#: per-index executable/entry-pool caches are LRU-bounded so a long-running
#: server cycling seeds or params cannot grow them without limit
CACHE_SIZE = 64


def is_sharded_dir(path: str) -> bool:
    """True when ``path`` holds the sharded on-disk layout."""
    return os.path.exists(os.path.join(path, SHARDED_META))


@dataclasses.dataclass
class ShardedStableIndex:
    """Database + per-shard HELP graphs laid out for a (data, model) mesh."""

    mesh: Mesh
    features: Array  # (N, M) sharded P("model", None)
    attrs: Array  # (N, L) sharded P("model", None)
    graphs: Array  # (N, Γ) per-shard LOCAL adjacency, sharded P("model", None)
    metric_cfg: MetricConfig
    shard_rows: int  # rows per model shard
    quant_mode: str = "none"
    codes: Optional[Array] = None  # sharded P("model", None) alongside graph
    sq_scale: Optional[Array] = None  # (M,) replicated
    sq_zero: Optional[Array] = None  # (M,) replicated
    pq_centroids: Optional[Array] = None  # (S, K, D_sub) replicated
    pq_dim: int = 0  # codebook-native feature dim (padded/rotated space)
    pq_rotation: Optional[Array] = None  # (Mp, Mp) OPQ rotation, replicated
    # per-instance executable/entry caches (see search): keyed on the static
    # search signature so serving batches reuse one jitted mesh program;
    # LRU-bounded at CACHE_SIZE
    _fn_cache: OrderedDict = dataclasses.field(
        default_factory=OrderedDict, repr=False, compare=False
    )
    _entry_cache: OrderedDict = dataclasses.field(
        default_factory=OrderedDict, repr=False, compare=False
    )

    @classmethod
    def build(
        cls,
        mesh: Mesh,
        features: np.ndarray,
        attrs: np.ndarray,
        metric_cfg: MetricConfig,
        help_cfg: HelpConfig = HelpConfig(),
        quant_cfg: QuantConfig = QuantConfig(),
    ) -> "ShardedStableIndex":
        """Build one HELP sub-index per model shard (host-side loop here; a
        real deployment builds shards on their owning hosts in parallel).
        The quant codec trains once on the full database (codebooks are
        global), codes shard row-aligned with the features."""
        n = features.shape[0]
        n_shards = mesh.shape["model"]
        assert n % n_shards == 0, (n, n_shards)
        rows = n // n_shards
        graphs = np.full((n, help_cfg.gamma), -1, np.int32)
        for s in range(n_shards):
            sl = slice(s * rows, (s + 1) * rows)
            g, _, _ = build_help_graph(
                features[sl], attrs[sl], metric_cfg, help_cfg
            )
            graphs[sl] = np.asarray(g)  # LOCAL ids within the shard
        fsh = NamedSharding(mesh, P("model", None))
        rep = NamedSharding(mesh, P())
        kw: dict = {}
        store = QuantizedVectors.build(features, quant_cfg)
        if store is not None:
            kw["quant_mode"] = quant_cfg.mode
            kw["codes"] = jax.device_put(store.codes, fsh)
            if store.sq_params is not None:
                kw["sq_scale"] = jax.device_put(store.sq_params.scale, rep)
                kw["sq_zero"] = jax.device_put(store.sq_params.zero, rep)
            if store.codebook is not None:
                kw["pq_centroids"] = jax.device_put(store.codebook.centroids, rep)
                kw["pq_dim"] = store.codebook.dim
            if store.rotation is not None:
                kw["pq_rotation"] = jax.device_put(store.rotation, rep)
        return cls(
            mesh=mesh,
            features=jax.device_put(jnp.asarray(features, jnp.float32), fsh),
            attrs=jax.device_put(jnp.asarray(attrs, jnp.int32), fsh),
            graphs=jax.device_put(jnp.asarray(graphs), fsh),
            metric_cfg=metric_cfg,
            shard_rows=rows,
            **kw,
        )

    # -- search ---------------------------------------------------------------

    def _entry_ids(self, b: int, pool: int, seed: int) -> Array:
        entry, _ = lru_get(
            self._entry_cache, (b, pool, seed),
            lambda: routing_mod.make_entry_ids(self.shard_rows, b, pool, seed),
            CACHE_SIZE,
        )
        return entry

    def _compile_search(
        self, cfg: RoutingConfig, k: int, has_mask: bool, qa_ndim: int
    ):
        """One jitted shard_map program per static search signature."""
        mesh = self.mesh
        rows = self.shard_rows
        metric_cfg = self.metric_cfg
        qmode = cfg.quant_mode
        pq_dim = self.pq_dim

        def local_search(feats, attrs, graph, qv, qa, entry, *rest):
            # one model shard: this data-shard's query block vs the local
            # sub-index (NOTE: shapes here are per-device, not global)
            routing_mod._TRACE_COUNT[0] += 1  # per-shard trace (see routing)
            b_loc = qv.shape[0]
            m, qops = (rest[0], rest[1:]) if has_mask else (None, rest)
            if qmode == "sq8":
                codes, scale, zero = qops
                operand = (codes, scale, zero)
            elif is_pq_mode(qmode):
                # per data-shard ADC tables from the replicated codebook;
                # the OPQ rotation (replicated) folds into the query here,
                # so codes/LUT shapes are rotation-oblivious downstream
                if has_rotation(qmode):
                    codes, centroids, rot = qops
                    qv_lut = rotate(qv, rot)
                else:
                    codes, centroids = qops
                    qv_lut = qv
                operand = (
                    codes, adc_lut(qv_lut, PQCodebook(centroids, pq_dim))
                )
            else:
                operand = ()
            shard_id = jax.lax.axis_index("model")
            lo = shard_id * rows
            state = routing_mod.traverse_pool(
                feats, attrs, graph, qv, qa, entry, metric_cfg, cfg, rows,
                m, operand,
            )
            if qmode == "none":
                # exact traversal: per-shard top-k heads merge exactly
                # (top-k of a union == top-k of per-shard top-k's)
                out = routing_mod.emit_topk(
                    state, feats, attrs, qv, qa, metric_cfg, cfg, m
                )
                gids = jnp.where(out.ids >= 0, out.ids + lo, INVALID)
                all_ids = jax.lax.all_gather(gids, "model", axis=0)
                all_d = jax.lax.all_gather(out.sqdists, "model", axis=0)
                all_ids = jnp.moveaxis(all_ids, 0, 1).reshape(b_loc, -1)
                all_d = jnp.moveaxis(all_d, 0, 1).reshape(b_loc, -1)
                neg, take = jax.lax.top_k(-all_d, k)
                out_ids = jnp.take_along_axis(all_ids, take, axis=1)
                out_sq = -neg
                evals = jax.lax.psum(out.n_dist_evals, "model")
                code_evals = jax.lax.psum(out.n_code_evals, "model")
                hops = jax.lax.psum(out.n_hops, ("data", "model"))
                return out_ids, out_sq, evals, code_evals, hops[None]

            # quantized sharded rerank: pool per-shard *code* top-k across
            # `model` first, rerank once globally at full precision.
            r = min(cfg.effective_rerank, cfg.pool_size)
            loc_ids = state.r_ids[:, :r]
            loc_d = jnp.where(loc_ids < 0, INF, state.r_d[:, :r])
            gids = jnp.where(loc_ids >= 0, loc_ids + lo, INVALID)
            all_ids = jax.lax.all_gather(gids, "model", axis=0)  # (S, b, r)
            all_d = jax.lax.all_gather(loc_d, "model", axis=0)
            all_ids = jnp.moveaxis(all_ids, 0, 1).reshape(b_loc, -1)
            all_d = jnp.moveaxis(all_d, 0, 1).reshape(b_loc, -1)
            neg, take = jax.lax.top_k(-all_d, r)  # global code top-k
            cand = jnp.take_along_axis(all_ids, take, axis=1)  # global ids
            cand = jnp.where(-neg < INF / 2, cand, INVALID)
            # each shard exactly re-scores only the candidates it owns; the
            # pmin over `model` assembles the full (B, r) exact distances
            # (every non-owner holds INF)
            mine = (cand >= lo) & (cand < lo + rows)
            loc = jnp.where(mine, cand - lo, INVALID)
            rd = routing_mod.score_exact(
                feats, attrs, loc, qv, qa, metric_cfg, m
            )
            rd = jnp.where(mine, rd, INF)
            if cfg.enforce_equality:
                # owner shards flag violating candidates; the verdict is
                # applied AFTER the final top-k (INVALID holes in place),
                # matching emit_topk's single-host ordering exactly
                ids_f, _ = routing_mod.enforce_filter(
                    loc, rd, attrs, qa, m
                )
                viol = jax.lax.pmax(
                    (mine & (ids_f < 0)).astype(jnp.int32), "model"
                )
            exact = jax.lax.pmin(rd, "model")
            neg2, take2 = jax.lax.top_k(-exact, k)
            out_sq = -neg2
            out_ids = jnp.take_along_axis(cand, take2, axis=1)
            out_ids = jnp.where(out_sq < INF / 2, out_ids, INVALID)
            if cfg.enforce_equality:
                bad = jnp.take_along_axis(viol, take2, axis=1).astype(bool)
                out_ids = jnp.where(bad, INVALID, out_ids)
                out_sq = jnp.where(bad, INF, out_sq)
            evals = jax.lax.psum(
                mine.sum(axis=1).astype(jnp.int32), "model"
            )  # fp rerank cost: one global pool, not one per shard
            code_evals = jax.lax.psum(state.evals, "model")
            hops = jax.lax.psum(state.hops, ("data", "model"))
            return out_ids, out_sq, evals, code_evals, hops[None]

        extra_specs: tuple = ()
        if has_mask:
            extra_specs = (P("data", None),)
        if qmode == "sq8":
            extra_specs += (P("model", None), P(None), P(None))
        elif is_pq_mode(qmode):
            extra_specs += (P("model", None), P(None, None, None))
            if has_rotation(qmode):
                extra_specs += (P(None, None),)
        # interval targets carry a trailing replicated [lo, hi] axis
        qa_spec = P("data", None, None) if qa_ndim == 3 else P("data", None)
        fn = jax.shard_map(
            local_search,
            mesh=mesh,
            in_specs=(
                P("model", None), P("model", None), P("model", None),
                P("data", None), qa_spec, P("data", None),
            ) + extra_specs,
            out_specs=(
                P("data", None), P("data", None), P("data"), P("data"), P(None)
            ),
            check_vma=False,
        )
        return jax.jit(fn)

    def search(
        self,
        qv: Array,
        qa: Array,
        k: int = 10,
        routing_cfg: Optional[RoutingConfig] = None,
        mask: Optional[Array] = None,
        seed: int = 0,
    ) -> routing_mod.SearchResult:
        """Sharded hybrid search; returns the same ``SearchResult`` shape as
        the single-host path (``n_dist_evals``/``n_code_evals`` are per-query
        totals summed over model shards; ``n_hops`` sums shard iterations).

        ``qa`` is (B, L) point targets or (B, L, 2) [lo, hi] interval
        targets (value-set / range predicates) — intervals shard over
        ``data`` exactly like points, with the trailing bound axis
        replicated.

        Prefer ``repro.api.Engine`` — this remains as the backend
        implementation behind the ``Searcher`` protocol."""
        cfg = routing_cfg or RoutingConfig(k=k, pool_size=max(4 * k, 32))
        if cfg.k != k:
            cfg = dataclasses.replace(cfg, k=k)
        if self.quant_mode != "none" and cfg.quant_mode == "none":
            cfg = dataclasses.replace(cfg, quant_mode=self.quant_mode)
        if cfg.quant_mode != self.quant_mode:
            raise ValueError(
                f"routing_cfg.quant_mode={cfg.quant_mode!r} but this index "
                f"was built with quant mode {self.quant_mode!r}"
            )
        qv = jnp.asarray(qv, jnp.float32)
        qa = jnp.asarray(qa, jnp.int32)
        has_mask = mask is not None
        entry = self._entry_ids(qv.shape[0], cfg.pool_size, seed)

        fn, _ = lru_get(
            self._fn_cache, (cfg, k, has_mask, qa.ndim),
            lambda: self._compile_search(cfg, k, has_mask, qa.ndim),
            CACHE_SIZE,
        )

        extra_args: tuple = ()
        if has_mask:
            extra_args = (jnp.asarray(mask, jnp.int32),)
        if cfg.quant_mode == "sq8":
            extra_args += (self.codes, self.sq_scale, self.sq_zero)
        elif is_pq_mode(cfg.quant_mode):
            extra_args += (self.codes, self.pq_centroids)
            if self.pq_rotation is not None:
                extra_args += (self.pq_rotation,)

        ids, sqd, evals, code_evals, hops = fn(
            self.features, self.attrs, self.graphs, qv, qa, entry, *extra_args
        )
        return routing_mod.SearchResult(
            ids=ids,
            dists=jnp.sqrt(jnp.maximum(sqd, 0.0)),
            sqdists=sqd,
            n_dist_evals=evals,
            n_hops=hops[0],
            n_code_evals=code_evals,
        )

    # -- persistence ----------------------------------------------------------

    def save(self, path: str, extra_meta: Optional[dict] = None) -> None:
        """Write one subdirectory per model shard (its feature/attr/code
        rows + *local* HELP graph), replicated codec arrays, and mesh/codec
        metadata. Arrays round-trip bit-exactly through ``np.save``; at
        fleet scale each host writes only its own ``shard_*`` directory —
        this single-host implementation loops over shards. ``extra_meta``
        persists engine-level state (e.g. an injected planner cost model)
        inside the sharded meta; unknown keys are ignored by ``load``."""
        os.makedirs(path, exist_ok=True)
        n_shards = int(self.mesh.shape["model"])
        rows = self.shard_rows
        feats = np.asarray(self.features)
        attrs = np.asarray(self.attrs)
        graphs = np.asarray(self.graphs)
        codes = None if self.codes is None else np.asarray(self.codes)
        for s in range(n_shards):
            d = os.path.join(path, f"shard_{s:05d}")
            os.makedirs(d, exist_ok=True)
            sl = slice(s * rows, (s + 1) * rows)
            np.save(os.path.join(d, "features.npy"), feats[sl])
            np.save(os.path.join(d, "attrs.npy"), attrs[sl])
            np.save(os.path.join(d, "graph.npy"), graphs[sl])
            if codes is not None:
                np.save(os.path.join(d, "codes.npy"), codes[sl])
        if self.sq_scale is not None:
            np.save(os.path.join(path, "sq_scale.npy"),
                    np.asarray(self.sq_scale))
            np.save(os.path.join(path, "sq_zero.npy"),
                    np.asarray(self.sq_zero))
        if self.pq_centroids is not None:
            np.save(os.path.join(path, "pq_centroids.npy"),
                    np.asarray(self.pq_centroids))
        if self.pq_rotation is not None:
            np.save(os.path.join(path, "pq_rotation.npy"),
                    np.asarray(self.pq_rotation))
        meta = {
            "format": SHARDED_FORMAT,
            "n_shards": n_shards,
            "shard_rows": rows,
            "metric_cfg": dataclasses.asdict(self.metric_cfg),
            "quant_mode": self.quant_mode,
            "pq_dim": self.pq_dim,
            "mesh_shape": {k: int(v) for k, v in self.mesh.shape.items()},
            **(extra_meta or {}),
        }
        tmp = os.path.join(path, SHARDED_META + ".tmp")
        with open(tmp, "w") as f:
            json.dump(meta, f, indent=2)
        os.replace(tmp, os.path.join(path, SHARDED_META))

    @classmethod
    def load(cls, path: str, mesh: Optional[Mesh] = None) -> "ShardedStableIndex":
        """Reload a saved sharded index onto ``mesh`` (default: a fresh
        local mesh with the saved model-shard count). The model axis must
        match the saved shard count — per-shard HELP graphs hold ids local
        to those boundaries — while the data axis is free to differ from
        save time (that is the reshard)."""
        with open(os.path.join(path, SHARDED_META)) as f:
            meta = json.load(f)
        if meta.get("format") != SHARDED_FORMAT:
            raise ValueError(
                f"{path} is not a {SHARDED_FORMAT} layout "
                f"(found {meta.get('format')!r})"
            )
        n_shards = int(meta["n_shards"])
        if mesh is None:
            from repro.launch.mesh import make_local_mesh

            nd = jax.device_count()
            if nd % n_shards:
                raise ValueError(
                    f"cannot build a default mesh: {nd} devices do not "
                    f"divide into {n_shards} saved model shards — pass mesh="
                )
            mesh = make_local_mesh(data=nd // n_shards, model=n_shards)
        if int(mesh.shape["model"]) != n_shards:
            raise ValueError(
                f"mesh has {mesh.shape['model']} model shards but {path} "
                f"was saved with {n_shards}: per-shard HELP graphs are "
                "local to the saved shard boundaries (rebuild to change "
                "the model-axis size; the data axis may differ freely)"
            )

        def stack(name):
            return np.concatenate([
                np.load(os.path.join(path, f"shard_{s:05d}", name))
                for s in range(n_shards)
            ])

        fsh = NamedSharding(mesh, P("model", None))
        rep = NamedSharding(mesh, P())
        kw: dict = {}
        if meta["quant_mode"] != "none":
            kw["quant_mode"] = meta["quant_mode"]
            kw["codes"] = jax.device_put(jnp.asarray(stack("codes.npy")), fsh)
            sq_scale = os.path.join(path, "sq_scale.npy")
            if os.path.exists(sq_scale):
                kw["sq_scale"] = jax.device_put(
                    jnp.asarray(np.load(sq_scale)), rep)
                kw["sq_zero"] = jax.device_put(
                    jnp.asarray(np.load(os.path.join(path, "sq_zero.npy"))),
                    rep)
            pq_c = os.path.join(path, "pq_centroids.npy")
            if os.path.exists(pq_c):
                kw["pq_centroids"] = jax.device_put(
                    jnp.asarray(np.load(pq_c)), rep)
                kw["pq_dim"] = int(meta["pq_dim"])
            pq_r = os.path.join(path, "pq_rotation.npy")
            if os.path.exists(pq_r):
                kw["pq_rotation"] = jax.device_put(
                    jnp.asarray(np.load(pq_r)), rep)
        return cls(
            mesh=mesh,
            features=jax.device_put(
                jnp.asarray(stack("features.npy"), jnp.float32), fsh),
            attrs=jax.device_put(
                jnp.asarray(stack("attrs.npy"), jnp.int32), fsh),
            graphs=jax.device_put(jnp.asarray(stack("graph.npy")), fsh),
            metric_cfg=MetricConfig(**meta["metric_cfg"]),
            shard_rows=int(meta["shard_rows"]),
            **kw,
        )
