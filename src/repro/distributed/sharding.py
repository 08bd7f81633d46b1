"""Sharding rules: parameter / optimizer / batch PartitionSpecs per family.

Axis conventions (DESIGN.md §4):
  * ``model``: tensor parallel (attention heads, d_ff, vocab, experts,
    embedding-table rows, candidate shards, decode-cache sequence);
  * ``data`` (+ leading ``pod`` on the multi-pod mesh): batch data-parallel
    and FSDP/ZeRO-3 weight+optimizer sharding (the second weight dim is
    sharded over the fsdp axes; XLA inserts the all-gathers at use and
    reduce-scatters on the gradients);
  * GNN edge lists are sharded over *all* axes (edge-parallel).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.gnn import GNNConfig
from repro.models.recsys import RecsysConfig
from repro.models.transformer import TransformerConfig
from repro.train.optim import AdafactorState, AdamWState, OptimConfig, SGDState

PyTree = Any


def _div(n: int, k: int) -> bool:
    return n % k == 0


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        return mesh.shape[axes]
    return int(np.prod([mesh.shape[a] for a in axes]))


def batch_axes(mesh: Mesh):
    """("pod","data") on the multi-pod mesh, "data" on the single-pod one."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def maybe(mesh: Mesh, dim_size: int, axes):
    """Axes if the dim divides evenly over them, else replicate."""
    if axes is None:
        return None
    size = _axis_size(mesh, axes if not isinstance(axes, str) else (axes,))
    if dim_size % size != 0:
        return None
    return axes


# ---------------------------------------------------------------------------
# LM transformer
# ---------------------------------------------------------------------------


def lm_param_specs(cfg: TransformerConfig, mesh: Mesh, fsdp: bool = True) -> dict:
    bx = batch_axes(mesh)
    dp = bx if fsdp else None
    d = cfg.d_model
    dp_d = maybe(mesh, d, dp)

    attn = {
        "wq": P(None, dp_d, maybe(mesh, cfg.n_heads * cfg.d_head, "model")),
        "wk": P(None, dp_d, maybe(mesh, cfg.n_kv_heads * cfg.d_head, "model")),
        "wv": P(None, dp_d, maybe(mesh, cfg.n_kv_heads * cfg.d_head, "model")),
        "wo": P(None, maybe(mesh, cfg.n_heads * cfg.d_head, "model"), dp_d),
    }
    if cfg.moe:
        e, ffe = cfg.moe.n_experts, cfg.moe.d_ff_expert
        if _div(e, mesh.shape["model"]):  # expert parallel
            ffn = {
                "router": P(None, dp_d, None),
                "w1": P(None, "model", dp_d, None),
                "w3": P(None, "model", dp_d, None),
                "w2": P(None, "model", None, dp_d),
            }
        else:  # tensor parallel inside each expert (e.g. Mixtral 8e on 16)
            ffn = {
                "router": P(None, dp_d, None),
                "w1": P(None, None, dp_d, maybe(mesh, ffe, "model")),
                "w3": P(None, None, dp_d, maybe(mesh, ffe, "model")),
                "w2": P(None, None, maybe(mesh, ffe, "model"), dp_d),
            }
    else:
        ffn = {
            "w1": P(None, dp_d, maybe(mesh, cfg.d_ff, "model")),
            "w3": P(None, dp_d, maybe(mesh, cfg.d_ff, "model")),
            "w2": P(None, maybe(mesh, cfg.d_ff, "model"), dp_d),
        }
    return {
        "embed": P(maybe(mesh, cfg.vocab, "model"), dp_d),
        "layers": {"ln1": P(None, None), "ln2": P(None, None), "attn": attn, "ffn": ffn},
        "final_ln": P(None),
        "lm_head": P(dp_d, maybe(mesh, cfg.vocab, "model")),
    }


def lm_batch_specs(mesh: Mesh, global_batch: int) -> dict:
    bx = maybe(mesh, global_batch, batch_axes(mesh))
    return {"tokens": P(bx, None), "labels": P(bx, None)}


def lm_cache_specs(
    cfg: TransformerConfig, mesh: Mesh, batch: int, seq_shard: bool = True
) -> dict:
    """KV cache (L, B, S, KV, dh): batch over dp, sequence over model
    (flash-decoding layout) — the layout that makes 32k-decode fit."""
    bx = maybe(mesh, batch, batch_axes(mesh))
    sx = "model" if seq_shard else None
    return {
        "k": P(None, bx, sx, None, None),
        "v": P(None, bx, sx, None, None),
        "len": P(),
    }


# ---------------------------------------------------------------------------
# GNN
# ---------------------------------------------------------------------------


def gnn_param_specs(cfg: GNNConfig, mesh: Mesh, fsdp: bool = True) -> dict:
    bx = batch_axes(mesh) if fsdp else None
    d = cfg.d_hidden
    dd = maybe(mesh, d, bx)
    d2 = maybe(mesh, 2 * d, bx)
    return {
        "encoder": {"w": P(None, maybe(mesh, d, "model")), "b": P(None)},
        "layers": {
            "we1": P(None, d2, maybe(mesh, d, "model")),
            "be1": P(None, None),
            "we2": P(None, dd, maybe(mesh, d, "model")),
            "be2": P(None, None),
            "wn1": P(None, d2, maybe(mesh, d, "model")),
            "bn1": P(None, None),
            "ln": P(None, None),
        },
        "decoder": {"w": P(dd, None), "b": P(None)},
    }


def gnn_batch_specs(mesh: Mesh, n_edges: int) -> dict:
    all_axes = tuple(mesh.axis_names)
    ex = maybe(mesh, n_edges, all_axes)
    return {
        "node_feats": P(None, None),  # replicated node state (edge-parallel)
        "src": P(ex),
        "dst": P(ex),
        "edge_mask": P(ex),
        "targets": P(None, None),
        "node_mask": P(None),
    }


# ---------------------------------------------------------------------------
# RecSys
# ---------------------------------------------------------------------------


def recsys_param_specs(
    cfg: RecsysConfig, mesh: Mesh, abstract_params: Optional[PyTree] = None,
) -> PyTree:
    """Replicate small dense weights; row-shard the huge embedding tables
    (and the per-field linear weights) over ``model``."""
    if abstract_params is None:
        from repro.models import recsys as recsys_mod

        abstract_params = recsys_mod.abstract_params(cfg)

    def spec(path, leaf):
        names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        if "tables" in names:
            return P(None, maybe(mesh, cfg.vocab_per_field, "model"), None)
        if "linear" in names:
            return P(None, maybe(mesh, cfg.vocab_per_field, "model"))
        if "item_embed" in names:
            return P(maybe(mesh, cfg.n_items, "model"), None)
        return P(*([None] * leaf.ndim))

    return jax.tree_util.tree_map_with_path(spec, abstract_params)


def recsys_batch_specs(
    cfg: RecsysConfig, mesh: Mesh, batch: int, train: bool = True
) -> dict:
    bx = maybe(mesh, batch, batch_axes(mesh))
    if cfg.kind == "bert4rec":
        if not train:
            return {"items": P(bx, None)}
        return {
            "items": P(bx, None),
            "masked_pos": P(bx, None),
            "labels": P(bx, None),
            "neg_ids": P(None),
        }
    out = {"sparse": P(bx, None)}
    if train:
        out["labels"] = P(bx)
    if cfg.n_dense:
        out["dense"] = P(bx, None)
    return out


def retrieval_batch_specs(cfg: RecsysConfig, mesh: Mesh, n_candidates: int) -> dict:
    cx = maybe(mesh, n_candidates, "model")
    base = (
        {"items": P(None, None)}
        if cfg.kind == "bert4rec"
        else {"sparse": P(None, None)}
        | ({"dense": P(None, None)} if cfg.n_dense else {})
    )
    return base | {
        "query_attrs": P(None, None),
        "item_embs": P(cx, None),
        "item_attrs": P(cx, None),
    }


# ---------------------------------------------------------------------------
# Optimizer-state specs follow the parameter specs
# ---------------------------------------------------------------------------


def opt_state_specs(opt_cfg: OptimConfig, param_specs: PyTree, abstract_params: PyTree):
    if opt_cfg.kind == "adamw":
        return AdamWState(step=P(), mu=param_specs, nu=param_specs)
    if opt_cfg.kind == "sgd":
        return SGDState(step=P())
    if opt_cfg.kind == "adafactor":
        from repro.train.optim import _factored

        def vr_spec(spec, p):
            if _factored(p.shape):
                return P(*spec[:-1]) if isinstance(spec, P) else P()
            return spec

        def vc_spec(spec, p):
            if _factored(p.shape):
                parts = tuple(spec[:-2]) + (spec[-1],) if isinstance(spec, P) else ()
                return P(*parts)
            return P(None)

        return AdafactorState(
            step=P(),
            vr=jax.tree.map(vr_spec, param_specs, abstract_params,
                            is_leaf=lambda x: isinstance(x, P)),
            vc=jax.tree.map(vc_spec, param_specs, abstract_params,
                            is_leaf=lambda x: isinstance(x, P)),
        )
    raise ValueError(opt_cfg.kind)


def to_shardings(mesh: Mesh, specs: PyTree) -> PyTree:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P),
    )
