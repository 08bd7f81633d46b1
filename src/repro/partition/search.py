"""PartitionedSearcher: probe → per-partition search → merge → rerank.

Executes a ``Plan(backend="partitioned")`` over a ``PartitionedStableIndex``:
score the P coarse centroids, prune partitions whose attribute summaries
cannot contain a survivor, group the batch's queries by probed partition
(sub-batches padded up a power-of-two ladder so partitions of one row-bucket
share compiled shapes), search each resident partition, and merge the
per-partition pools into one global top-k.

Bit-exact parity with the unpartitioned brute oracle (``nprobe = P``) comes
from three properties, preserved deliberately:

* every scoring call is the *same eager op sequence* the unpartitioned
  ``BruteForceSearcher`` runs (``brute_fused_sqdist`` / ``adc_scan`` /
  ``feature_sqdist``) on the partition's row slice — per-row results are
  row-independent, so slicing cannot change them;
* per-partition selection and the global merge both order candidates by the
  lexicographic key (score, global id) via ``jax.lax.sort`` — exactly the
  tie order ``jax.lax.top_k`` yields over the unpartitioned array, where
  position == global id;
* the PQ path merges *raw ADC pools* globally and runs ONE global exact
  rerank of the merged pool head, mirroring ``_adc_two_stage`` (a
  per-partition rerank would rank in a different currency).

The graph sub-backend traverses each partition's HELP subgraph with the
global metric calibration and merges fused sqdists (approximate across
partitions, like any IVF layer); partitions too small to carry a subgraph
are scanned with the same fused metric so the merge currency matches.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import auto as auto_mod
from repro.core import lru_get
from repro.core import routing as routing_mod
from repro.core.auto import MetricConfig
from repro.core.graph_ops import INF, INVALID
from repro.core.routing import SearchResult
from repro.obs import trace as obs_trace
from repro.quant import adc_scan
from repro.quant.store import is_pq_mode

Array = jax.Array

__all__ = ["PartitionedSearcher"]

#: Bound on cached per-partition entry pools (pid × batch-bucket × seed).
ENTRY_CACHE_SIZE = 512


def _batch_bucket(b: int, cap: int) -> int:
    """Next power-of-two sub-batch size, capped at the full batch."""
    s = 1
    while s < b:
        s *= 2
    return min(s, cap)


def _iter_groups(store, groups: dict[int, np.ndarray]):
    """Yield (pid, qidx) while double-buffering: stage pid[i+1] on the
    store's background worker before pid[i] is scored, so the next
    partition's disk read + device put overlaps the current probe."""
    order = list(groups.items())
    for i, (pid, qidx) in enumerate(order):
        if i + 1 < len(order):
            store.prefetch(order[i + 1][0])
        yield pid, qidx


def _groups(probes: np.ndarray) -> dict[int, np.ndarray]:
    """pid → ascending query indices probing it (-1 slots are pruned)."""
    out: dict[int, np.ndarray] = {}
    for pid in np.unique(probes):
        if pid < 0:
            continue
        out[int(pid)] = np.where((probes == pid).any(axis=1))[0]
    return out


def _pad_idx(qidx: np.ndarray, bucket: int) -> np.ndarray:
    if qidx.size == bucket:
        return qidx
    return np.concatenate([qidx, np.full(bucket - qidx.size, qidx[0])])


def _ok_local(part, sub) -> Array:
    """(b, n_pad) hard admissibility on one partition — the same semantics
    as the engine's ``_ok_matrix`` (containment × ONE_OF membership ×
    wildcard), plus the pad-row mask."""
    attrs_p = part.attrs
    lo, hi = sub._bounds()
    lo = jnp.asarray(lo, jnp.int32)[:, None, :]
    hi = jnp.asarray(hi, jnp.int32)[:, None, :]
    okl = (attrs_p[None, :, :] >= lo) & (attrs_p[None, :, :] <= hi)
    if sub.allowed is not None:
        member = (
            attrs_p[None, :, :, None]
            == jnp.asarray(sub.allowed, jnp.int32)[:, None, :, :]
        ).any(-1)
        okl = okl & (member | ~jnp.asarray(sub.hard)[:, None, :])
    if sub.mask is not None:
        okl = okl | (jnp.asarray(sub.mask, jnp.int32)[:, None, :] == 0)
    return okl.all(-1) & (part.row_ids[None, :] >= 0)


def _select(scores: Array, gids: Array, k_sel: int):
    """Ascending lexicographic (score, gid) head — the top_k tie order."""
    s, g = jax.lax.sort((scores, gids), dimension=-1, num_keys=2)
    return s[:, :k_sel], g[:, :k_sel]


def _select_perm(scores: Array, gids: Array, k_sel: int):
    iota = jnp.broadcast_to(
        jnp.arange(scores.shape[1], dtype=jnp.int32), scores.shape
    )
    s, g, p = jax.lax.sort((scores, gids, iota), dimension=-1, num_keys=2)
    return s[:, :k_sel], g[:, :k_sel], p[:, :k_sel]


def _result_from_pools(
    scores: np.ndarray, gids: np.ndarray, k: int,
    evals: np.ndarray, code_evals: np.ndarray, hops: int = 0,
) -> SearchResult:
    """Global merge of accumulated (score, gid) pools → SearchResult with
    the brute oracle's INVALID/INF conventions."""
    sq, gid = _select(
        jnp.asarray(scores, jnp.float32), jnp.asarray(gids, jnp.int32), k
    )
    out = jnp.where(jnp.isfinite(sq) & (sq < INF / 2), gid, INVALID)
    sq = jnp.where(out >= 0, sq, INF)
    return SearchResult(
        ids=out,
        dists=jnp.sqrt(jnp.maximum(sq, 0.0)),
        sqdists=sq,
        n_dist_evals=jnp.asarray(evals, jnp.int32),
        n_hops=jnp.asarray(hops, jnp.int32),
        n_code_evals=jnp.asarray(code_evals, jnp.int32),
    )


class _PoolBuffer:
    """Host accumulator: per-query candidate pools scattered from grouped
    per-partition results (widths vary per query with pruning)."""

    def __init__(self, b: int, width: int, with_feats: Optional[int] = None):
        self.scores = np.full((b, width), INF, np.float32)
        self.gids = np.full((b, width), -1, np.int32)
        self.feats = (
            None if with_feats is None
            else np.zeros((b, width, with_feats), np.float32)
        )
        self._fill = np.zeros(b, np.int64)

    def scatter(self, qidx: np.ndarray, scores, gids, feats=None) -> None:
        k = scores.shape[1]
        cols = self._fill[qidx][:, None] + np.arange(k)[None, :]
        rows = qidx[:, None]
        self.scores[rows, cols] = np.asarray(scores)
        self.gids[rows, cols] = np.asarray(gids)
        if feats is not None:
            self.feats[rows, cols] = np.asarray(feats)
        self._fill[qidx] += k


class PartitionedSearcher:
    """IVF probe/merge execution over ``PartitionedStableIndex``."""

    name = "partitioned"

    def search(self, engine, queries, params, plan, entry_ids=None):
        pidx = engine.index
        hard_all = plan.sub_backend == "brute" or params.enforce_equality
        probes = pidx.probe(queries, plan.nprobe, hard_all)  # (B, nprobe)
        sp = obs_trace.current()  # "engine.dispatch" when sampled
        if sp:
            # host-side probe attribution: -1 slots are summary-pruned
            sp.set("partitions_scored", int(pidx.n_partitions))
            sp.set("partitions_probed", int((probes >= 0).sum()))
            sp.set("partitions_pruned", int((probes < 0).sum()))
            sp.set("nprobe", int(probes.shape[1]))
        if plan.sub_backend == "brute":
            if is_pq_mode(plan.quant_mode):
                return self._probe_pq(engine, queries, params, plan, probes)
            return self._probe_exact(engine, queries, params, plan, probes)
        return self._probe_graph(engine, queries, params, plan, probes)

    # -- oracle sub-backend (exact scan) ----------------------------------

    def _probe_exact(self, engine, queries, params, plan, probes):
        pidx = engine.index
        b, k = queries.batch_size, params.k
        buf = _PoolBuffer(b, probes.shape[1] * k)
        for pid, qidx in _iter_groups(pidx.store, _groups(probes)):
            part = pidx.store.get(pid)
            pad = _pad_idx(qidx, _batch_bucket(qidx.size, b))
            sub = queries.take(pad)
            # same eager scorer as BruteForceSearcher: pure-L2 fused sqdist
            sv2 = auto_mod.brute_fused_sqdist(
                jnp.asarray(sub.vectors, jnp.float32),
                jnp.asarray(sub.targets, jnp.int32),
                part.features, part.attrs, MetricConfig(mode="l2"),
            )
            ok = _ok_local(part, sub)
            scores = jnp.where(ok, sv2, INF)
            k_sel = min(k, int(scores.shape[1]))
            gids = jnp.broadcast_to(part.row_ids[None, :], scores.shape)
            s, g = _select(scores, gids, k_sel)
            buf.scatter(qidx, s[: qidx.size], g[: qidx.size])
        evals = self._probe_rows(pidx, probes)
        return _result_from_pools(
            buf.scores, buf.gids, k, evals, np.zeros(b, np.int32)
        )

    # -- oracle sub-backend, PQ codes (ADC scan + global exact rerank) ----

    def _probe_pq(self, engine, queries, params, plan, probes):
        pidx = engine.index
        b, k = queries.batch_size, params.k
        pool = min(params.effective_pool, pidx.n_items)
        pool = min(max(params.rerank_size or pool, k), pool)
        m = pidx.feat_dim
        buf = _PoolBuffer(b, probes.shape[1] * pool, with_feats=m)
        for pid, qidx in _iter_groups(pidx.store, _groups(probes)):
            part = pidx.store.get(pid)
            pad = _pad_idx(qidx, _batch_bucket(qidx.size, b))
            sub = queries.take(pad)
            qv = jnp.asarray(sub.vectors, jnp.float32)
            lut = pidx.query_lut(qv)
            scores = adc_scan(
                lut, part.quant.scan_codes, jnp.asarray(sub.attrs, jnp.int32),
                part.attrs, mode="l2", packed=part.quant.packed,
            )
            ok = _ok_local(part, sub)
            scores = jnp.where(ok, scores, INF)
            k_sel = min(pool, int(scores.shape[1]))
            gids = jnp.broadcast_to(part.row_ids[None, :], scores.shape)
            s, g, perm = _select_perm(scores, gids, k_sel)
            feats = jnp.take_along_axis(
                jnp.broadcast_to(
                    part.features[None], (s.shape[0],) + part.features.shape
                ),
                perm[..., None], axis=1,
            )
            buf.scatter(
                qidx, s[: qidx.size], g[: qidx.size], feats[: qidx.size]
            )
        # global merge of raw ADC pools, then ONE exact rerank of the head —
        # the same two-stage split (and tie order) as _adc_two_stage
        sq, gid, perm = _select_perm(
            jnp.asarray(buf.scores), jnp.asarray(buf.gids), pool
        )
        cand_feats = jnp.take_along_axis(
            jnp.asarray(buf.feats), perm[..., None], axis=1
        )
        qv = jnp.asarray(queries.vectors, jnp.float32)
        rd = auto_mod.feature_sqdist(qv[:, None, :], cand_feats)
        rd = jnp.where(sq < INF / 2, rd, INF)
        neg, take = jax.lax.top_k(-rd, k)
        out_sq = -neg
        out = jnp.take_along_axis(gid, take, axis=1)
        out = jnp.where(
            jnp.isfinite(out_sq) & (out_sq < INF / 2), out, INVALID
        )
        out_sq = jnp.where(out >= 0, out_sq, INF)
        return SearchResult(
            ids=out,
            dists=jnp.sqrt(jnp.maximum(out_sq, 0.0)),
            sqdists=out_sq,
            n_dist_evals=jnp.full((b,), pool, jnp.int32),
            n_hops=jnp.zeros((), jnp.int32),
            n_code_evals=jnp.asarray(self._probe_rows(pidx, probes)),
        )

    # -- traversal sub-backend (HELP subgraphs) ---------------------------

    def _probe_graph(self, engine, queries, params, plan, probes):
        pidx = engine.index
        cfg = plan.routing_cfg
        b, k_exec = queries.batch_size, cfg.k
        buf = _PoolBuffer(b, probes.shape[1] * k_exec)
        evals = np.zeros(b, np.int64)
        code_evals = np.zeros(b, np.int64)
        hops = 0
        quant_on = plan.quant_mode != "none"
        for pid, qidx in _iter_groups(pidx.store, _groups(probes)):
            part = pidx.store.get(pid)
            bucket = _batch_bucket(qidx.size, b)
            pad = _pad_idx(qidx, bucket)
            sub = queries.take(pad)
            qv = jnp.asarray(sub.vectors, jnp.float32)
            targets = jnp.asarray(sub.targets, jnp.int32)
            maskq = None if sub.mask is None else jnp.asarray(sub.mask)
            if part.graph.shape[1] == 0:
                # scan-only partition (too small for a subgraph): fused
                # metric scan keeps the merge currency identical
                sv2 = auto_mod.brute_fused_sqdist(
                    qv, targets, part.features, part.attrs,
                    pidx.metric_cfg, mask=maskq,
                )
                ok = part.row_ids[None, :] >= 0
                if cfg.enforce_equality:
                    ok = ok & _ok_local(part, sub)
                scores = jnp.where(ok, sv2, INF)
                k_sel = min(k_exec, int(scores.shape[1]))
                gids = jnp.broadcast_to(part.row_ids[None, :], scores.shape)
                s, g = _select(scores, gids, k_sel)
                buf.scatter(qidx, s[: qidx.size], g[: qidx.size])
                evals[qidx] += part.n_real
                continue
            eids = self._entry_ids(
                pidx, pid, part.n_real, bucket, cfg.pool_size, params.seed
            )
            res = routing_mod.search(
                part.features, part.attrs, part.graph, qv, targets,
                pidx.metric_cfg, cfg, mask=maskq, entry_ids=eids,
                seed=params.seed,
                quant=part.quant if quant_on else None,
            )
            gid = jnp.where(
                res.ids >= 0,
                jnp.take(part.row_ids, jnp.maximum(res.ids, 0)),
                INVALID,
            )
            sq = jnp.where(gid >= 0, res.sqdists, INF)
            buf.scatter(qidx, sq[: qidx.size], gid[: qidx.size])
            evals[qidx] += np.asarray(res.n_dist_evals)[: qidx.size]
            code_evals[qidx] += np.asarray(res.n_code_evals)[: qidx.size]
            hops += int(res.n_hops)
        return _result_from_pools(
            buf.scores, buf.gids, k_exec, evals, code_evals, hops
        )

    # -- helpers ----------------------------------------------------------

    @staticmethod
    def _probe_rows(pidx, probes: np.ndarray) -> np.ndarray:
        """(B,) true rows scanned: Σ n_rows over each query's probe set."""
        rows = np.concatenate([pidx.summaries.n_rows, [0]])  # -1 → 0
        return rows[probes].sum(axis=1).astype(np.int64)

    @staticmethod
    def _entry_ids(pidx, pid, n_real, bucket, pool, seed):
        """Per-partition entry pools, LRU-cached on the index (value arrays
        depend only on (n_real, bucket, pool, seed) — residency-independent)."""
        key = (pid, n_real, bucket, pool, seed)
        out, _ = lru_get(
            pidx._entry_cache, key,
            lambda: routing_mod.make_entry_ids(n_real, bucket, pool, seed),
            ENTRY_CACHE_SIZE,
        )
        return out
