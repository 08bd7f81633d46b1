"""HELP index construction (paper §III-C, Alg. 1–2), TPU-adapted.

The paper's incremental NN-descent with per-edge locks becomes a
*bulk-synchronous* NN-descent: every round, each node gathers a fixed-width
candidate set (neighbors-of-new-neighbors ∪ reverse neighbors ∪
neighbors-of-reverse-neighbors), scores it under the AUTO metric in one
batched pass and merges with `top_k` — no data-dependent shapes, no locks.
Convergence is monitored with the paper's sampled graph quality ψ (Eq. 7)
against the brute-force AUTO ground truth, stopping at Ψ (default 0.8).

Heterogeneous Semantic Pruning (Alg. 2) is vectorized: per node the Γ×Γ
edge-direction cosine matrix is computed with one einsum, and the sequential
"Select" scan becomes a `fori_loop` over neighbor slots. The in-degree guard
(protect nodes whose in-degree is 1) and a post-prune orphan-repair pass keep
the graph navigable — the property the paper's C2 robustness rests on.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import auto as auto_mod
from repro.core import graph_ops as gops
from repro.core.auto import MetricConfig
from repro.core.graph_ops import INF, INVALID

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class HelpConfig:
    """Index-construction hyper-parameters (paper notation in comments)."""

    gamma: int = 32  # Γ: max neighbors per node
    gamma_new: int = 8  # Γ_new: expansion width per NN-descent round
    reverse_capacity: int = 8  # reverse-neighbor sample slots per node
    sigma: float = 0.44  # σ: cosine prune threshold (HSP)
    psi_target: float = 0.80  # Ψ: graph-quality stop threshold
    max_rounds: int = 15  # Ǐ: NN-descent round cap
    quality_sample: int = 256  # |S| in Eq. 7
    node_block: int = 2048  # rows processed per vectorized block
    prune: bool = True  # heterogeneous semantic prune on/off (ablation)
    reverse_insert: bool = True  # Alg. 2 lines 14-19 reverse densification
    seed: int = 0


@dataclasses.dataclass
class BuildReport:
    psi_history: list[float]
    rounds: int
    pruned_edge_fraction: float
    build_seconds: float = 0.0


# ---------------------------------------------------------------------------
# Candidate scoring helper (blocked over nodes)
# ---------------------------------------------------------------------------


def _score_candidates(
    features: Array,
    attrs: Array,
    node_ids: Array,  # (B,)
    cand_ids: Array,  # (B, C)
    cfg: MetricConfig,
) -> Array:
    """Fused sq-distances from each node to its candidate list; INVALID→INF."""
    qv = features[node_ids]  # (B, M)
    qa = attrs[node_ids]
    cv = gops.gather_rows(features, cand_ids)  # (B, C, M)
    ca = gops.gather_rows(attrs, cand_ids)
    d = auto_mod.fused_sqdist(qv[:, None, :], qa[:, None, :], cv, ca, cfg)
    bad = (cand_ids < 0) | (cand_ids == node_ids[:, None])
    return jnp.where(bad, INF, d)


# ---------------------------------------------------------------------------
# One bulk-synchronous NN-descent round
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("cfg", "help_cfg"))
def _descent_round(
    features: Array,
    attrs: Array,
    nbr_ids: Array,  # (N, Γ) sorted ascending by dist
    nbr_d: Array,  # (N, Γ)
    is_old: Array,  # (N, Γ) int8: 1 ⇒ already expanded in a previous round
    cfg: MetricConfig,
    help_cfg: HelpConfig,
) -> tuple[Array, Array, Array]:
    n, gamma = nbr_ids.shape
    g_new = help_cfg.gamma_new
    rev_cap = help_cfg.reverse_capacity

    # --- expansion set: the Γ_new closest *new* neighbors of each node ------
    newness = (is_old == 0) & (nbr_ids >= 0)
    # Prefer new entries; among them prefer closer ones (rows sorted by dist).
    rank_score = newness.astype(jnp.int32) * (2 * gamma) - jnp.arange(gamma)
    _, sel_slots = jax.lax.top_k(rank_score, g_new)  # (N, Γ_new)
    # Pick the slots and mark them old through a (N, Γ_new, Γ) one-hot: the
    # equivalent per-row take_along_axis and scatter take the TPU compiler
    # minutes at 1M nodes, the one-hot about a second.
    pick = sel_slots[:, :, None] == jnp.arange(gamma)
    sel_valid = (pick & newness[:, None, :]).any(axis=2)
    sel_ids = jnp.where(pick, nbr_ids[:, None, :], 0).sum(axis=2)
    sel_ids = jnp.where(sel_valid, sel_ids, INVALID)
    hit = pick & sel_valid[:, :, None]
    is_old = is_old | hit.any(axis=1).astype(jnp.int8)

    # --- candidate generation ------------------------------------------------
    # (a) neighbors of the selected new neighbors: (N, Γ_new·Γ)
    cand_a = gops.gather_rows(nbr_ids, sel_ids).reshape(n, g_new * gamma)
    cand_a = jnp.where((sel_ids < 0)[:, :, None].repeat(gamma, 2).reshape(n, -1),
                       INVALID, cand_a)
    # (b) reverse neighbors: (N, R)
    rev = gops.reverse_neighbors(nbr_ids, n, rev_cap)
    # (c) neighbors of reverse neighbors: (N, R·Γ)
    cand_c = gops.gather_rows(nbr_ids, rev).reshape(n, rev_cap * gamma)
    cand_c = jnp.where((rev < 0)[:, :, None].repeat(gamma, 2).reshape(n, -1),
                       INVALID, cand_c)
    cands = jnp.concatenate([cand_a, rev, cand_c], axis=1)  # (N, C)

    # --- blocked scoring + merge ---------------------------------------------
    block = help_cfg.node_block
    n_blocks = (n + block - 1) // block
    pad = n_blocks * block - n

    def pad0(x, fill):
        return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1),
                       constant_values=fill)

    cands_p = pad0(cands, INVALID).reshape(n_blocks, block, -1)
    ids_p = pad0(nbr_ids, INVALID).reshape(n_blocks, block, gamma)
    d_p = pad0(nbr_d, INF).reshape(n_blocks, block, gamma)
    old_p = pad0(is_old, jnp.int8(1)).reshape(n_blocks, block, gamma)
    node_p = jnp.arange(n_blocks * block, dtype=jnp.int32).reshape(n_blocks, block)

    def body(carry, xs):
        cand_b, ids_b, d_b, old_b, node_b = xs
        cd = _score_candidates(features, attrs, node_b, cand_b, cfg)
        new_ids, new_d, new_old = gops.merge_pools(
            ids_b, d_b, cand_b, cd, gamma,
            pool_flags=old_b, cand_flags=jnp.zeros_like(cand_b, dtype=jnp.int8),
        )
        return carry, (new_ids, new_d, new_old)

    _, (ids_o, d_o, old_o) = jax.lax.scan(
        body, None, (cands_p, ids_p, d_p, old_p, node_p)
    )
    nbr_ids = ids_o.reshape(-1, gamma)[:n]
    nbr_d = d_o.reshape(-1, gamma)[:n]
    is_old = old_o.reshape(-1, gamma)[:n]
    return nbr_ids, nbr_d, is_old


# ---------------------------------------------------------------------------
# Graph quality ψ (Eq. 7)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("cfg", "k"))
def _graph_quality(
    features: Array,
    attrs: Array,
    nbr_ids: Array,
    sample_ids: Array,
    cfg: MetricConfig,
    k: int,
) -> Array:
    qv, qa = features[sample_ids], attrs[sample_ids]
    d = auto_mod.brute_fused_sqdist(qv, qa, features, attrs, cfg)
    # exclude self
    d = d.at[jnp.arange(sample_ids.shape[0]), sample_ids].set(INF)
    _, gt = jax.lax.top_k(-d, k)  # (S, k)
    rows = nbr_ids[sample_ids][:, :k]  # current best-k in-graph
    hit = (rows[:, :, None] == gt[:, None, :]) & (rows[:, :, None] >= 0)
    return hit.any(axis=2).sum(axis=1).astype(jnp.float32).mean() / k


# ---------------------------------------------------------------------------
# Heterogeneous semantic prune (Alg. 2)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("sigma", "gamma"))
def _prune_block(
    features: Array,
    attrs: Array,
    node_ids: Array,  # (B,)
    nbr_ids: Array,  # (B, Γ) sorted ascending
    nbr_d: Array,
    in_deg: Array,  # (N,)
    sigma: float,
    gamma: int,
) -> tuple[Array, Array]:
    b = node_ids.shape[0]
    sv = features[node_ids]  # (B, M)
    cv = gops.gather_rows(features, nbr_ids)  # (B, Γ, M)
    ca = gops.gather_rows(attrs, nbr_ids)  # (B, Γ, L)
    edges = cv - sv[:, None, :]
    norm = jnp.linalg.norm(edges, axis=-1, keepdims=True)
    unit = edges / jnp.maximum(norm, 1e-12)
    cos = jnp.einsum("bgm,bhm->bgh", unit, unit)  # (B, Γ, Γ)
    same_attr = (ca[:, :, None, :] == ca[:, None, :, :]).all(-1)  # (B, Γ, Γ)
    valid = nbr_ids >= 0
    protected = (in_deg[jnp.maximum(nbr_ids, 0)] <= 1) & valid  # island guard

    redundant_with = (cos > sigma) & same_attr  # (B, Γ, Γ)

    def step(t, selected):
        # prune slot t iff some already-selected same-attr neighbor is too
        # cosine-aligned — unless t is the last in-edge of its target.
        conflict = (redundant_with[:, t, :] & selected).any(axis=1)
        admit = valid[:, t] & (~conflict | protected[:, t])
        return selected.at[:, t].set(admit)

    selected = jax.lax.fori_loop(
        0, gamma, step, jnp.zeros((b, gamma), dtype=bool)
    )
    out_ids = jnp.where(selected, nbr_ids, INVALID)
    out_d = jnp.where(selected, nbr_d, INF)
    # compact: sort by distance so INVALID pads trail
    order = jnp.argsort(out_d, axis=1)
    return (
        jnp.take_along_axis(out_ids, order, axis=1),
        jnp.take_along_axis(out_d, order, axis=1),
    )


def _prune_all(
    features: Array,
    attrs: Array,
    nbr_ids: Array,
    nbr_d: Array,
    sigma: float,
    node_block: int,
) -> tuple[Array, Array]:
    n, gamma = nbr_ids.shape
    in_deg = gops.in_degrees(nbr_ids, n)
    out_i = np.empty((n, gamma), np.int32)
    out_d = np.empty((n, gamma), np.float32)
    for s in range(0, n, node_block):
        e = min(s + node_block, n)
        ids_b, d_b = _prune_block(
            features, attrs, jnp.arange(s, e, dtype=jnp.int32),
            nbr_ids[s:e], nbr_d[s:e], in_deg, float(sigma), gamma,
        )
        out_i[s:e] = np.asarray(ids_b)
        out_d[s:e] = np.asarray(d_b)
    return jnp.asarray(out_i), jnp.asarray(out_d)


def _repair_orphans(
    nbr_ids: Array, nbr_d: Array, pre_ids: Array, pre_d: Array
) -> tuple[Array, Array]:
    """Restore the closest pre-prune in-edge of any in-degree-0 node."""
    n, gamma = nbr_ids.shape
    for _ in range(3):
        deg = np.asarray(gops.in_degrees(nbr_ids, n))
        orphans = np.nonzero(deg == 0)[0]
        if orphans.size == 0:
            break
        pre_ids_np = np.asarray(pre_ids)
        pre_d_np = np.asarray(pre_d)
        nbr_ids_np = np.asarray(nbr_ids).copy()
        nbr_d_np = np.asarray(nbr_d).copy()
        orphan_set = set(orphans.tolist())
        # scan pre-prune edges (src-major) and give each orphan its best
        # in-edge; only edges into orphans reach the Python loop
        flat_ids, flat_d = pre_ids_np.reshape(-1), pre_d_np.reshape(-1)
        src_of = {}
        for e in np.flatnonzero(np.isin(flat_ids, orphans)).tolist():
            dst, d = int(flat_ids[e]), float(flat_d[e])
            if dst not in src_of or d < src_of[dst][1]:
                src_of[dst] = (e // gamma, d)
        # fallback: an orphan with no pre-prune in-edge gets the reverse of
        # its own best out-edge (the AUTO metric is symmetric).
        for dst in orphan_set - set(src_of):
            for t in range(gamma):
                s = int(nbr_ids_np[dst, t])
                if s >= 0 and s != dst:
                    src_of[dst] = (s, float(nbr_d_np[dst, t]))
                    break
        touched = set()
        for dst, (src, d) in src_of.items():
            # overwrite the worst slot of src
            worst = int(np.argmax(nbr_d_np[src]))
            nbr_ids_np[src, worst] = dst
            nbr_d_np[src, worst] = d
            touched.add(src)
        for src in touched:  # restore ascending row order
            order = np.argsort(nbr_d_np[src], kind="stable")
            nbr_ids_np[src] = nbr_ids_np[src][order]
            nbr_d_np[src] = nbr_d_np[src][order]
        nbr_ids = jnp.asarray(nbr_ids_np)
        nbr_d = jnp.asarray(nbr_d_np)
    return nbr_ids, nbr_d


def _reverse_insert(
    features: Array,
    attrs: Array,
    nbr_ids: Array,
    nbr_d: Array,
    cfg: MetricConfig,
    help_cfg: HelpConfig,
) -> tuple[Array, Array]:
    """Alg. 2 lines 14-19 (bulk): offer each edge's reverse to its target."""
    n, gamma = nbr_ids.shape
    rev = gops.reverse_neighbors(nbr_ids, n, gamma)  # (N, Γ) candidate sources
    block = help_cfg.node_block
    out_i = np.empty((n, gamma), np.int32)
    out_d = np.empty((n, gamma), np.float32)
    for s in range(0, n, block):
        e = min(s + block, n)
        node_b = jnp.arange(s, e, dtype=jnp.int32)
        cd = _score_candidates(features, attrs, node_b, rev[s:e], cfg)
        ids_b, d_b, _ = gops.merge_pools(
            nbr_ids[s:e], nbr_d[s:e], rev[s:e], cd, gamma
        )
        out_i[s:e] = np.asarray(ids_b)
        out_d[s:e] = np.asarray(d_b)
    return jnp.asarray(out_i), jnp.asarray(out_d)


# ---------------------------------------------------------------------------
# Incremental link/repair (streaming mutability — no full rebuild)
# ---------------------------------------------------------------------------


def link_nodes(
    features: Array,
    attrs: Array,
    graph: Array,
    node_ids: np.ndarray,  # (D,) rows to (re-)link into the adjacency
    metric_cfg: MetricConfig,
    cfg: HelpConfig,
    banned_ids: Optional[np.ndarray] = None,  # dead rows: never linked to
    seed: int = 0,
) -> tuple[Array, int]:
    """Insert/re-link ``node_ids`` into an existing HELP adjacency without a
    full rebuild — the merge path of the LSM delta segment.

    Per node: (1) a routed candidate search over the *current* graph finds
    its neighborhood under the AUTO metric (the same traversal serving
    uses, so link quality tracks search quality); (2) an all-pairs scan
    over the linked set supplies new↔new candidates the frozen graph cannot
    reach yet; (3) the node's row becomes the Γ best candidates; (4)
    mutual-neighbor repair offers every new edge's reverse to its target,
    which keeps new nodes *reachable* (a row with out-edges only would be
    invisible to traversal). Rows in ``banned_ids`` (tombstoned) are never
    linked to. Returns (new adjacency, number of repaired existing rows).
    """
    from repro.core import routing as routing_mod
    from repro.core.routing import RoutingConfig

    node_ids = np.asarray(node_ids, np.int64)
    n, gamma = int(features.shape[0]), int(graph.shape[1])
    d = int(node_ids.shape[0])
    if d == 0 or gamma == 0:
        return graph, 0
    banned = (
        np.zeros(0, np.int64) if banned_ids is None
        else np.unique(np.asarray(banned_ids, np.int64))
    )

    qv = jnp.take(features, jnp.asarray(node_ids, jnp.int32), axis=0)
    qa = jnp.take(attrs, jnp.asarray(node_ids, jnp.int32), axis=0)

    # (1) routed candidate search over the current graph (soft AUTO metric,
    # the node's own attributes as targets — exactly how build scores edges)
    pool = int(min(max(4 * gamma, 64), n))
    rcfg = RoutingConfig(
        k=pool, pool_size=pool, pioneer_size=min(8, pool),
        coarse_max_iters=16, refine_max_iters=64,
    )
    res = routing_mod.search(
        features, attrs, graph, qv, qa, metric_cfg, rcfg, seed=seed
    )
    cand_ids = np.asarray(res.ids)  # (D, pool)
    cand_d = np.asarray(res.sqdists)

    # (1b) one-hop expansion — the candidates' own neighbors, NN-descent's
    # core move: the routed pool localizes the neighborhood, the expansion
    # recovers edges the capped traversal cut off
    node_dev = jnp.asarray(node_ids, jnp.int32)
    graph_np0 = np.asarray(graph)
    hop_ids = graph_np0[np.maximum(cand_ids, 0)].reshape(d, -1)  # (D, pool·Γ)
    hop_ids = np.where(cand_ids.repeat(gamma, axis=1) < 0, INVALID, hop_ids)
    hop_d = np.asarray(
        _score_candidates(features, attrs, node_dev, jnp.asarray(hop_ids),
                          metric_cfg)
    )

    # (2) new↔new candidates: the frozen graph has no edges into the linked
    # set yet, so a routed search cannot discover co-inserted neighbors
    d_nn = np.asarray(auto_mod.brute_fused_sqdist(
        qv, qa, qv, qa, metric_cfg
    ))  # (D, D)
    nn_ids = np.broadcast_to(node_ids[None, :], (d, d))

    all_ids = np.concatenate([cand_ids, hop_ids, nn_ids], axis=1)
    all_d = np.concatenate([cand_d, hop_d, d_nn], axis=1).astype(np.float32)
    bad = (all_ids == node_ids[:, None]) | (all_ids < 0)
    if banned.size:
        bad |= np.isin(all_ids, banned)
    all_d = np.where(bad, INF, all_d)
    all_ids = np.where(bad, INVALID, all_ids).astype(np.int32)

    # (3) each linked node's row = Γ best candidates (deduped, ascending)
    new_rows, new_d, _ = gops.merge_pools(
        jnp.full((d, gamma), INVALID), jnp.full((d, gamma), INF),
        jnp.asarray(all_ids), jnp.asarray(all_d), gamma,
    )
    new_rows_np = np.asarray(new_rows)
    new_d_np = np.asarray(new_d)
    graph_np = np.asarray(graph).copy()
    graph_np[node_ids] = new_rows_np

    # (4) mutual-neighbor repair: offer v to each existing neighbor u — the
    # reverse edges are what make freshly inserted rows reachable
    linked = set(node_ids.tolist())
    offers: dict[int, list[int]] = {}
    for i, v in enumerate(node_ids.tolist()):
        for u in new_rows_np[i].tolist():
            if u >= 0 and u not in linked:
                offers.setdefault(u, []).append(v)
    if not offers:
        return jnp.asarray(graph_np), 0
    u_ids = np.fromiter(offers, np.int32, len(offers))
    width = max(len(vs) for vs in offers.values())
    off = np.full((len(offers), width), INVALID, np.int32)
    for r, vs in enumerate(offers.values()):
        off[r, : len(vs)] = vs
    u_dev = jnp.asarray(u_ids)
    # existing rows carry no stored distances — rescore them once, merge the
    # offered reverse edges in, and write the repaired rows back
    cur_d = _score_candidates(features, attrs, u_dev, graph_np[u_ids], metric_cfg)
    off_d = _score_candidates(features, attrs, u_dev, jnp.asarray(off), metric_cfg)
    rep_ids, _, _ = gops.merge_pools(
        jnp.asarray(graph_np[u_ids]), cur_d, jnp.asarray(off), off_d, gamma
    )
    graph_np[u_ids] = np.asarray(rep_ids)
    return jnp.asarray(graph_np), len(offers)


# ---------------------------------------------------------------------------
# Public build entry point (Alg. 1)
# ---------------------------------------------------------------------------


def build_help_graph(
    features: Array,
    attrs: Array,
    metric_cfg: MetricConfig,
    cfg: HelpConfig = HelpConfig(),
) -> tuple[Array, Array, BuildReport]:
    """Build the HELP adjacency table: returns (ids (N,Γ), sqdists, report)."""
    import time

    t0 = time.perf_counter()
    features = jnp.asarray(features, jnp.float32)
    attrs = jnp.asarray(attrs, jnp.int32)
    n = features.shape[0]
    gamma = cfg.gamma
    rng = np.random.default_rng(cfg.seed)

    # (1) Initialization: Γ random neighbors per node.
    init = rng.integers(0, n, size=(n, gamma), dtype=np.int32)
    nbr_ids = jnp.asarray(init)
    # score + dedup + sort the random rows
    block = cfg.node_block
    d0 = np.empty((n, gamma), np.float32)
    i0 = np.empty((n, gamma), np.int32)
    for s in range(0, n, block):
        e = min(s + block, n)
        node_b = jnp.arange(s, e, dtype=jnp.int32)
        cd = _score_candidates(features, attrs, node_b, nbr_ids[s:e], metric_cfg)
        ids_b, d_b, _ = gops.merge_pools(
            jnp.full((e - s, gamma), INVALID), jnp.full((e - s, gamma), INF),
            nbr_ids[s:e], cd, gamma,
        )
        i0[s:e] = np.asarray(ids_b)
        d0[s:e] = np.asarray(d_b)
    nbr_ids, nbr_d = jnp.asarray(i0), jnp.asarray(d0)
    is_old = jnp.zeros((n, gamma), jnp.int8)

    sample_ids = jnp.asarray(
        rng.choice(n, size=min(cfg.quality_sample, n), replace=False).astype(np.int32)
    )

    # (2)-(3) iterate until ψ ≥ Ψ or round cap.
    psi_history: list[float] = []
    rounds = 0
    for rounds in range(1, cfg.max_rounds + 1):
        nbr_ids, nbr_d, is_old = _descent_round(
            features, attrs, nbr_ids, nbr_d, is_old, metric_cfg, cfg
        )
        psi = float(
            _graph_quality(features, attrs, nbr_ids, sample_ids, metric_cfg, gamma)
        )
        psi_history.append(psi)
        if psi >= cfg.psi_target:
            break

    edges_before = int((np.asarray(nbr_ids) >= 0).sum())

    # (4) heterogeneous semantic prune + reverse densification + island repair.
    if cfg.prune:
        pre_ids, pre_d = nbr_ids, nbr_d
        nbr_ids, nbr_d = _prune_all(
            features, attrs, nbr_ids, nbr_d, cfg.sigma, cfg.node_block
        )
        if cfg.reverse_insert:
            nbr_ids, nbr_d = _reverse_insert(
                features, attrs, nbr_ids, nbr_d, metric_cfg, cfg
            )
            nbr_ids, nbr_d = _prune_all(
                features, attrs, nbr_ids, nbr_d, cfg.sigma, cfg.node_block
            )
        nbr_ids, nbr_d = _repair_orphans(nbr_ids, nbr_d, pre_ids, pre_d)

    edges_after = int((np.asarray(nbr_ids) >= 0).sum())
    report = BuildReport(
        psi_history=psi_history,
        rounds=rounds,
        pruned_edge_fraction=1.0 - edges_after / max(edges_before, 1),
        build_seconds=time.perf_counter() - t0,
    )
    return nbr_ids, nbr_d, report
