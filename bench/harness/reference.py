"""The plain reference and the comparison that decides ``correct``.

The reference is exact filtered top-k: the rows that meet the query's
predicate, ranked by float64 squared L2, ties by id. The predicate is the
traffic mix's file under ``bench/predicates/``, whose ``meets`` states it in
NumPy. The reference is NumPy alone and takes nothing the program made.
Queries with the same attribute row share one predicate, so each such group
finds its rows once and scores only those (about N / labels**L rows for
MATCH). ``Reference.topk`` and ``recall`` follow ``chip_smoke.py``'s pair.

``compare`` reads three numbers from what a run returned, each beside a
limit that the configuration states under ``checks``:

  recall_at_10  mean |returned ∩ exact| / |exact| over answered requests
                (``min``: the recall the deployment promises);
  dist_gap      the widest relative gap between a returned distance and
                the float64 distance of the row it names. A row that meets
                the predicate must carry its L2 distance; one that does not
                (the graph's soft AUTO penalty admits some) must carry one
                no smaller than its L2 distance, since the penalty factor
                is at least 1 (``max``);
  missing       requests due that got no answer, or fewer valid ids than
                the reference has (``max``: 0).
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np

INVALID = -1


def to_bf16(x: np.ndarray) -> np.ndarray:
    """Round float32 to bfloat16 (nearest, ties to even), kept as float32."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> 16) & np.uint32(1))) & np.uint32(
        0xFFFF0000)
    return u.view(np.float32)


class Reference:
    """Exact filtered top-k over a host copy of the corpus.

    ``predicate`` is a predicate module (``meets``). ``precision="bfloat16"``
    is the control: features and queries rounded to bfloat16 and scored in
    float32, as a bf16 store with f32 accumulation would; it must come out
    as not correct."""

    def __init__(self, features: np.ndarray, attrs: np.ndarray, predicate,
                 precision: str = "float64"):
        if precision not in ("float64", "bfloat16"):
            raise ValueError(precision)
        self.precision = precision
        self.features = features
        self.attrs = attrs
        self.predicate = predicate

    def _scored(self, x: np.ndarray) -> np.ndarray:
        if self.precision == "bfloat16":
            return to_bf16(x)
        return x.astype(np.float64)

    def topk(self, qf: np.ndarray, qa: np.ndarray, k: int):
        """(Q, k) ids (INVALID-padded) and distances (inf-padded)."""
        ids = np.full((len(qf), k), INVALID, np.int64)
        dists = np.full((len(qf), k), np.inf)
        groups, inv = np.unique(qa, axis=0, return_inverse=True)
        for g, qa_row in enumerate(groups):
            qs = np.flatnonzero(inv.reshape(-1) == g)
            rows = np.flatnonzero(self.predicate.meets(self.attrs, qa_row))
            if len(rows) == 0:
                continue
            x = self._scored(self.features[rows])
            q = self._scored(qf[qs])
            d2 = ((q * q).sum(1)[:, None] + (x * x).sum(1)[None, :]
                  - 2.0 * q @ x.T)
            d2 = np.maximum(d2, 0.0)
            m = min(k, len(rows))
            for j, qi in enumerate(qs):
                part = np.argpartition(d2[j], m - 1)[:m] if m < len(rows) \
                    else np.arange(len(rows))
                top = part[np.lexsort((rows[part], d2[j, part]))][:m]
                ids[qi, :m] = rows[top]
                dists[qi, :m] = np.sqrt(d2[j, top])
        return ids, dists

    def pair_dists(self, qf: np.ndarray, ids: np.ndarray) -> np.ndarray:
        """float64 L2 distance of each query to each row it names."""
        x = self.features[np.maximum(ids, 0)].astype(np.float64)
        diff = x - qf.astype(np.float64)[:, None, :]
        return np.sqrt(np.einsum("qkm,qkm->qk", diff, diff))


def recall(ids: np.ndarray, truth: np.ndarray) -> float:
    """Mean |returned ∩ truth| / |truth| (paper §IV-A) over the queries
    that match at least one row."""
    per = []
    for r, t in zip(np.asarray(ids), truth):
        t = set(t[t >= 0].tolist())
        if t:
            per.append(len(t & set(r[r >= 0].tolist())) / len(t))
    return float(np.mean(per)) if per else 0.0


@dataclasses.dataclass
class Answers:
    """What a run returned: one row per request due in the window."""

    pool_idx: np.ndarray  # (R,) index into the query pool
    ids: np.ndarray  # (R, k) returned ids, INVALID where none
    dists: np.ndarray  # (R, k) returned distances
    answered: np.ndarray  # (R,) bool: a Completed answer came

    @classmethod
    def collect(cls, pool_idx: Sequence[int], results: Sequence, k: int):
        """``results[i]`` is (ids, dists) or None for a request that got no
        answer (shed, failed or never resolved)."""
        r = len(pool_idx)
        ids = np.full((r, k), INVALID, np.int64)
        dists = np.full((r, k), np.inf)
        answered = np.zeros(r, bool)
        for i, res in enumerate(results):
            if res is None:
                continue
            got_ids, got_d = res
            n = min(k, len(got_ids))
            ids[i, :n] = got_ids[:n]
            dists[i, :n] = got_d[:n]
            answered[i] = True
        return cls(np.asarray(pool_idx, np.int64), ids, dists, answered)


def compare(ans: Answers, ref: Reference, qf: np.ndarray, qa: np.ndarray,
            k: int) -> dict:
    """The three compared numbers (see the module docstring). ``qf``/``qa``
    are the query pool; ``ref`` must be the float64 reference."""
    uniq, inv = np.unique(ans.pool_idx, return_inverse=True)
    t_ids, _ = ref.topk(qf[uniq], qa[uniq], k)
    truth = t_ids[inv]
    have = (truth >= 0).sum(1)
    got = (ans.ids >= 0).sum(1)
    missing = int((~ans.answered | (got < have)).sum())

    a = ans.answered
    rec = recall(ans.ids[a], truth[a]) if a.any() else 0.0
    gap = _dist_gap(ans.ids[a], ans.dists[a], ref, qf[ans.pool_idx[a]],
                    qa[ans.pool_idx[a]])
    return {"recall_at_10": rec, "dist_gap": gap, "missing": missing}


def _dist_gap(ids, dists, ref: Reference, qf, qa) -> float:
    valid = ids >= 0
    if not valid.any():
        return 0.0
    exact = ref.pair_dists(qf, ids)
    meets = ref.predicate.meets(ref.attrs[np.maximum(ids, 0)], qa[:, None, :])
    scale = np.maximum(exact, 1e-30)
    gap = np.where(meets, np.abs(dists - exact), np.maximum(exact - dists, 0))
    return float(np.max(np.where(valid, gap / scale, 0.0)))


def verdict(numbers: dict, checks: dict) -> tuple[bool, list]:
    """(correct, rows of (name, value, relation, limit)) against the
    configuration's ``checks`` block: each name has a ``min`` or a ``max``."""
    rows, ok = [], True
    for name, lim in checks.items():
        v = numbers[name]
        if "min" in lim:
            good, rel, bound = v >= lim["min"], ">=", lim["min"]
        else:
            good, rel, bound = v <= lim["max"], "<=", lim["max"]
        ok &= bool(good)
        rows.append((name, v, rel, bound, bool(good)))
    return ok, rows


def control_answers(ans_idx: np.ndarray, qf, qa, features, attrs, predicate,
                    k: int) -> Answers:
    """The control in the program's place: every request answered by the
    bfloat16 reference."""
    ctl = Reference(features, attrs, predicate, precision="bfloat16")
    uniq, inv = np.unique(ans_idx, return_inverse=True)
    ids, d = ctl.topk(qf[uniq], qa[uniq], k)
    return Answers(np.asarray(ans_idx), ids[inv], d[inv],
                   np.ones(len(ans_idx), bool))
