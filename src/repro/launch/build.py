"""Cell builder: (arch × shape × mesh) → jit-able step + abstract inputs +
shardings. Shared by the dry-run, the launcher and the distributed tests.

``input_specs()`` returns ShapeDtypeStruct stand-ins for every input (weak-
type-correct, shardable, zero allocation) — params, optimizer state, KV
caches and data batches alike.

Also the offline STABLE index builder CLI —
``python -m repro.launch.build --n 20000 --quant pq --out DIR`` builds (and
optionally quantizes) an index over a synthetic hybrid dataset and saves it
for ``repro.launch.serve --index-dir DIR``. With ``--shards S`` the build
produces a mesh-sharded engine (one HELP sub-index per model shard) and
saves it in the per-shard sharded layout that ``Engine.load`` reshards onto
the serving mesh.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchSpec, ShapeCell
from repro.distributed import sharding as shard
from repro.models import gnn as gnn_mod
from repro.models import recsys as recsys_mod
from repro.models import transformer as tfm
from repro.train import optim as optim_mod
from repro.train import step as step_mod

SDS = jax.ShapeDtypeStruct


class CellBuild(NamedTuple):
    step: Callable  # positional-args step function
    abstract_args: tuple  # ShapeDtypeStruct pytrees
    in_specs: tuple  # PartitionSpec pytrees (same structure)
    out_specs: Any  # PartitionSpec pytree or None (compiler-chosen)
    meta: dict  # param counts, notes — feeds the roofline report
    donate: tuple = ()  # argnums donated (params/opt for train, cache for decode)


def _sds_tree(tree, sharding_tree=None):
    return jax.tree.map(lambda l: SDS(l.shape, l.dtype), tree)


def _batch_sds(spec_tree: dict, shapes: dict, dtypes: dict) -> dict:
    return {k: SDS(shapes[k], dtypes[k]) for k in shapes}


# ---------------------------------------------------------------------------
# LM cells
# ---------------------------------------------------------------------------


def _build_lm(spec: ArchSpec, cell: ShapeCell, mesh: Mesh,
              overrides: Optional[dict] = None) -> CellBuild:
    overrides = dict(overrides or {})
    micro_batches = overrides.pop("micro_batches", spec.micro_batches)
    unroll_micro = overrides.pop("unroll_micro", False)
    bx = shard.batch_axes(mesh)
    train_like = cell.kind in ("train", "prefill")
    cfg_kw = {}
    if train_like and overrides.pop("seq_shard_acts", True):
        cfg_kw = {"act_dp_axes": bx, "act_seq_axis": "model"}
    cfg: tfm.TransformerConfig = spec.make_config(**cfg_kw)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    if cfg.moe:
        ep = cfg.moe.n_experts % mesh.shape["model"] == 0
        cfg = dataclasses.replace(
            cfg,
            moe_expert_axis="model" if ep else None,
            moe_capacity_axes=bx,
            moe_ff_axis=None if ep else "model",
        )
    if cell.kind in ("prefill", "decode"):
        # serving checkpoints are bf16 (halves resident weight bytes)
        from repro.models.common import Precision
        import jax.numpy as _jnp

        cfg = dataclasses.replace(
            cfg, precision=Precision(param_dtype=_jnp.bfloat16)
        )

    params = tfm.abstract_params(cfg)
    p_specs = shard.lm_param_specs(cfg, mesh, fsdp=(cell.kind == "train"))
    b, s = cell.global_batch, cell.seq_len

    tokens = b * s if cell.kind != "decode" else b
    passes = 6.0 if cell.kind == "train" else 2.0
    meta = {
        "params": cfg.param_count,
        "active_params": cfg.active_param_count,
        "seq_len": s,
        "global_batch": b,
        # 6·N_active·D (train) / 2·N_active·D (inference) — lm_head+embed
        # included in active_param_count; attention quadratic term excluded
        # by the standard convention.
        "model_flops": passes * cfg.active_param_count * tokens,
    }

    if cell.kind == "train":
        opt_state = optim_mod.abstract_state(spec.optim, params)
        o_specs = shard.opt_state_specs(spec.optim, p_specs, params)
        batch = {
            "tokens": SDS((b, s), jnp.int32),
            "labels": SDS((b, s), jnp.int32),
        }
        b_specs = shard.lm_batch_specs(mesh, b)
        step = step_mod.make_lm_train_step(
            cfg, spec.optim, micro_batches, unroll_micro=unroll_micro
        )
        metric_specs = {"loss": P(), "grad_norm": P()}
        return CellBuild(
            step=step,
            abstract_args=(params, opt_state, batch),
            in_specs=(p_specs, o_specs, b_specs),
            out_specs=(p_specs, o_specs, metric_specs),
            meta=meta | {"micro_batches": micro_batches},
            donate=(0, 1),
        )

    if cell.kind == "prefill":
        batch = {"tokens": SDS((b, s), jnp.int32)}
        b_specs = {"tokens": P(shard.maybe(mesh, b, bx), None)}
        step = step_mod.make_lm_prefill_step(cfg)
        return CellBuild(
            step=step, abstract_args=(params, batch),
            in_specs=(p_specs, b_specs), out_specs=None, meta=meta,
        )

    # decode: one new token against a seq_len KV cache
    cache = tfm.abstract_cache(cfg, b, s)
    c_specs = shard.lm_cache_specs(cfg, mesh, b, seq_shard=True)
    batch = {"tokens": SDS((b, 1), jnp.int32)}
    b_specs = {"tokens": P(shard.maybe(mesh, b, bx), None)}
    step = step_mod.make_lm_decode_step(cfg)
    cache_bytes = sum(
        np.prod(x.shape) * x.dtype.itemsize for x in jax.tree.leaves(cache)
    )
    return CellBuild(
        step=step, abstract_args=(params, cache, batch),
        in_specs=(p_specs, c_specs, b_specs),
        out_specs=(c_specs, None), meta=meta | {"kv_cache_bytes": int(cache_bytes)},
        donate=(1,),
    )


# ---------------------------------------------------------------------------
# GNN cells
# ---------------------------------------------------------------------------


def _build_gnn(spec: ArchSpec, cell: ShapeCell, mesh: Mesh,
               overrides: Optional[dict] = None) -> CellBuild:
    overrides = dict(overrides or {})
    all_axes = tuple(mesh.axis_names)
    shard_acts = overrides.pop("shard_activations", True)
    if shard_acts:
        overrides.setdefault("edge_shard_axes", all_axes)
        n_devices = int(np.prod(list(mesh.shape.values())))
        if cell.n_nodes % n_devices == 0:
            overrides.setdefault("node_shard_axes", all_axes)
    cfg: gnn_mod.GNNConfig = spec.make_config(cell, **overrides)
    params = gnn_mod.abstract_params(cfg)
    p_specs = shard.gnn_param_specs(cfg, mesh)
    opt_state = optim_mod.abstract_state(spec.optim, params)
    o_specs = shard.opt_state_specs(spec.optim, p_specs, params)
    n, e = cell.n_nodes, cell.n_edges
    batch = {
        "node_feats": SDS((n, cell.d_feat), jnp.float32),
        "src": SDS((e,), jnp.int32),
        "dst": SDS((e,), jnp.int32),
        "edge_mask": SDS((e,), jnp.bool_),
        "targets": SDS((n, cell.d_out), jnp.float32),
        "node_mask": SDS((n,), jnp.float32),
    }
    b_specs = shard.gnn_batch_specs(mesh, e)
    step = step_mod.make_gnn_train_step(cfg, spec.optim)
    d = cfg.d_hidden
    per_layer = 6 * d * d * e + 4 * d * d * n  # edge MLP (2d→d→d) + node MLP
    enc_dec = 2 * cell.d_feat * d * n + 2 * d * cell.d_out * n
    meta = {
        "params": cfg.param_count, "n_nodes": n, "n_edges": e,
        "model_flops": 3.0 * (cfg.n_layers * per_layer + enc_dec),  # ×3 train
    }
    return CellBuild(
        step=step, abstract_args=(params, opt_state, batch),
        in_specs=(p_specs, o_specs, b_specs),
        out_specs=(p_specs, o_specs, {"loss": P(), "grad_norm": P()}),
        meta=meta,
        donate=(0, 1),
    )


# ---------------------------------------------------------------------------
# RecSys cells
# ---------------------------------------------------------------------------

N_MASKED = 20  # bert4rec masked positions per sequence
N_NEG = 8192  # shared sampled-softmax negatives


def _recsys_batch_sds(cfg: recsys_mod.RecsysConfig, b: int, train: bool) -> dict:
    if cfg.kind == "bert4rec":
        base = {"items": SDS((b, cfg.seq_len), jnp.int32)}
        if train:
            base |= {
                "masked_pos": SDS((b, N_MASKED), jnp.int32),
                "labels": SDS((b, N_MASKED), jnp.int32),
                "neg_ids": SDS((N_NEG,), jnp.int32),
            }
        return base
    base = {"sparse": SDS((b, cfg.n_sparse), jnp.int32)}
    if cfg.n_dense:
        base["dense"] = SDS((b, cfg.n_dense), jnp.float32)
    if train:
        base["labels"] = SDS((b,), jnp.float32)
    return base


def _build_recsys(spec: ArchSpec, cell: ShapeCell, mesh: Mesh,
                  overrides: Optional[dict] = None) -> CellBuild:
    overrides = dict(overrides or {})
    serve_chunk = overrides.pop("serve_chunk", 4096)
    score_chunk = overrides.pop("score_chunk", 16384)
    cfg: recsys_mod.RecsysConfig = spec.make_config(**overrides)
    params = recsys_mod.abstract_params(cfg)
    p_specs = shard.recsys_param_specs(cfg, mesh, params)
    b = cell.global_batch
    meta = {"params": sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))}
    # analytic per-example dense compute (embedding rows are lookups, not
    # matmuls — they contribute bytes, not MODEL_FLOPS)
    if cfg.kind == "bert4rec":
        per_ex = 2 * (cfg.seq_len * (12 * cfg.embed_dim**2 * cfg.n_blocks)
                      + cfg.seq_len**2 * cfg.embed_dim * 2 * cfg.n_blocks)
    elif cfg.kind == "dlrm":
        mlps = 0
        dims = [cfg.n_dense, *cfg.bot_mlp]
        mlps += sum(2 * dims[i] * dims[i + 1] for i in range(len(dims) - 1))
        n_int = (cfg.n_sparse + 1) * cfg.n_sparse // 2
        dims = [cfg.bot_mlp[-1] + n_int, *cfg.top_mlp]
        mlps += sum(2 * dims[i] * dims[i + 1] for i in range(len(dims) - 1))
        inter = 2 * (cfg.n_sparse + 1) ** 2 * cfg.embed_dim
        per_ex = mlps + inter
    elif cfg.kind == "xdeepfm":
        f0, d0 = cfg.n_sparse, cfg.embed_dim
        hs = [f0, *cfg.cin_layers]
        cin = sum(2 * hs[i] * f0 * hs[i + 1] * d0 for i in range(len(cfg.cin_layers)))
        dims = [f0 * d0, *cfg.mlp, 1]
        dnn = sum(2 * dims[i] * dims[i + 1] for i in range(len(dims) - 1))
        per_ex = cin + dnn
    else:  # fm: sum-square trick
        per_ex = 4 * cfg.n_sparse * cfg.embed_dim
    passes = 3.0 if cell.kind == "train" else 1.0
    if cell.kind == "retrieval":
        meta["model_flops"] = 2.0 * cell.n_candidates * (
            cfg.embed_dim + cfg.n_attr_dims)
    else:
        meta["model_flops"] = passes * per_ex * b

    if cell.kind == "train":
        opt_state = optim_mod.abstract_state(spec.optim, params)
        o_specs = shard.opt_state_specs(spec.optim, p_specs, params)
        batch = _recsys_batch_sds(cfg, b, train=True)
        b_specs = shard.recsys_batch_specs(cfg, mesh, b, train=True)
        step = step_mod.make_recsys_train_step(cfg, spec.optim)
        return CellBuild(
            step=step, abstract_args=(params, opt_state, batch),
            in_specs=(p_specs, o_specs, b_specs),
            out_specs=(p_specs, o_specs, {"loss": P(), "grad_norm": P()}),
            meta=meta,
            donate=(0, 1),
        )

    if cell.kind == "serve":
        batch = _recsys_batch_sds(cfg, b, train=False)
        b_specs = shard.recsys_batch_specs(cfg, mesh, b, train=False)
        if cfg.kind == "bert4rec":
            def step(params, batch):
                return recsys_mod.bert4rec_serve_topk(
                    cfg, params, batch["items"], batch_chunk=serve_chunk
                )
        else:
            step = step_mod.make_recsys_serve_step(cfg)
        return CellBuild(
            step=step, abstract_args=(params, batch),
            in_specs=(p_specs, b_specs), out_specs=None, meta=meta,
        )

    # retrieval_cand: STABLE hybrid scoring of n_candidates (paper technique)
    n_cand = cell.n_candidates
    d = cfg.embed_dim
    l_attr = cfg.n_attr_dims
    batch = _recsys_batch_sds(cfg, b, train=False) | {
        "query_attrs": SDS((b, l_attr), jnp.int32),
        "item_embs": SDS((n_cand, d), jnp.float32),
        "item_attrs": SDS((n_cand, l_attr), jnp.int32),
    }
    b_specs = shard.recsys_batch_specs(cfg, mesh, b, train=False) | {
        "query_attrs": P(None, None),
        "item_embs": P(shard.maybe(mesh, n_cand, "model"), None),
        "item_attrs": P(shard.maybe(mesh, n_cand, "model"), None),
    }
    step = step_mod.make_recsys_retrieval_step(
        cfg, k=100, score_chunk=score_chunk,
        topk_shards=mesh.shape["model"] if n_cand % mesh.shape["model"] == 0 else 1,
    )
    return CellBuild(
        step=step, abstract_args=(params, batch),
        in_specs=(p_specs, b_specs), out_specs=None,
        meta=meta | {"n_candidates": n_cand},
    )


def build_cell(spec: ArchSpec, cell: ShapeCell, mesh: Mesh,
               overrides: Optional[dict] = None) -> CellBuild:
    if cell.skipped:
        raise ValueError(f"cell {spec.arch_id}×{cell.name} is skipped: {cell.skip_reason}")
    if spec.family == "lm":
        return _build_lm(spec, cell, mesh, overrides)
    if spec.family == "gnn":
        return _build_gnn(spec, cell, mesh, overrides)
    if spec.family == "recsys":
        return _build_recsys(spec, cell, mesh, overrides)
    raise ValueError(spec.family)


# ---------------------------------------------------------------------------
# Offline STABLE index builder CLI
# ---------------------------------------------------------------------------


def main() -> None:
    import argparse
    import time

    from repro.api import Engine
    from repro.core.help_graph import HelpConfig
    from repro.data.synthetic import make_hybrid_dataset
    from repro.launch.compile_cache import enable_compile_cache
    from repro.quant import QUANT_MODES, QuantConfig

    enable_compile_cache()
    ap = argparse.ArgumentParser(description="build + save a STABLE engine")
    ap.add_argument("--out", required=True, help="output index directory")
    ap.add_argument("--n", type=int, default=20_000)
    ap.add_argument("--profile", default="sift")
    ap.add_argument("--attr-dim", type=int, default=5)
    ap.add_argument("--gamma", type=int, default=24)
    ap.add_argument("--max-rounds", type=int, default=8)
    ap.add_argument("--quant", default="none", choices=QUANT_MODES,
                    help="attach a quantized code store to the index")
    ap.add_argument("--pq-subspaces", type=int, default=32)
    ap.add_argument("--no-graph", action="store_true",
                    help="scan-only corpus: skip the HELP graph build "
                         "(the engine planner will use brute force)")
    ap.add_argument("--shards", type=int, default=0,
                    help="build a mesh-sharded engine over this many model "
                         "shards and save the per-shard layout (0 = "
                         "single-host)")
    ap.add_argument("--partitions", type=int, default=0,
                    help="build an IVF-partitioned engine: coarse k-means "
                         "over this many partitions, each with its own "
                         "HELP subgraph, saved one-subdirectory-per-"
                         "partition for streaming residency (0 = flat)")
    ap.add_argument("--residency-rows", type=int, default=0,
                    help="partitioned only: device-resident row cap of the "
                         "built engine's segment store (0 = hold all)")
    args = ap.parse_args()
    if args.partitions and args.shards:
        raise SystemExit("--partitions and --shards are mutually exclusive")

    ds = make_hybrid_dataset(
        n=args.n, n_queries=1, profile=args.profile, attr_dim=args.attr_dim,
        labels_per_dim=3, n_clusters=16, attr_cluster_corr=0.6, seed=0,
    )
    t0 = time.time()
    help_cfg = HelpConfig(gamma=args.gamma, gamma_new=6,
                          max_rounds=args.max_rounds)
    quant_cfg = QuantConfig(mode=args.quant, pq_subspaces=args.pq_subspaces)
    if args.shards:
        from repro.core import auto as auto_mod
        from repro.core.auto import MetricConfig
        from repro.distributed.search import ShardedStableIndex
        from repro.launch.mesh import make_local_mesh

        nd = jax.device_count()
        if nd % args.shards:
            raise SystemExit(
                f"--shards {args.shards} does not divide {nd} devices"
            )
        mesh = make_local_mesh(data=nd // args.shards, model=args.shards)
        stats = auto_mod.sample_stats(ds.features, ds.attrs)
        eng = Engine(ShardedStableIndex.build(
            mesh, ds.features, ds.attrs,
            MetricConfig(mode="auto", alpha=stats.alpha),
            help_cfg=help_cfg, quant_cfg=quant_cfg,
        ))
        eng.save(args.out)
        print(f"built {args.shards}-shard {args.n}×{ds.features.shape[1]} "
              f"engine in {time.time()-t0:.1f}s → {args.out} "
              f"(per-shard layout; Engine.load reshards onto the serving "
              f"mesh)")
        return
    if args.partitions:
        eng = Engine.build_partitioned(
            ds.features, ds.attrs, n_partitions=args.partitions,
            help_cfg=help_cfg, quant_cfg=quant_cfg,
            build_graph=not args.no_graph,
            residency_rows=args.residency_rows or None,
        )
        eng.save(args.out)
        pidx = eng.index
        print(f"built {args.n}×{ds.features.shape[1]} index over "
              f"{pidx.n_partitions} partitions in {time.time()-t0:.1f}s "
              f"(α={pidx.metric_cfg.alpha:.3f}, quant={args.quant}) → "
              f"{args.out} (per-partition layout; Engine.load streams "
              f"partitions under --residency-rows)")
        return
    eng = Engine.build(
        ds.features, ds.attrs, help_cfg,
        quant_cfg=quant_cfg,
        build_graph=not args.no_graph,
    )
    eng.save(args.out)
    idx = eng.index
    quant_note = (
        f", {idx.quant.code_bytes / 2**20:.1f} MiB codes ({args.quant})"
        if idx.quant is not None else ""
    )
    print(f"built {args.n}×{ds.features.shape[1]} index in {time.time()-t0:.1f}s"
          f" (α={idx.metric_cfg.alpha:.3f}{quant_note}) → {args.out}")


if __name__ == "__main__":
    main()
