"""The plain reference and the comparison that decides ``correct``, against
a brute NumPy check at tiny N."""
import types

import numpy as np
import pytest

from conftest import BENCH
from harness import corpus as corpus_mod
from harness import reference
from harness.spec import load_module

MATCH = load_module(f"{BENCH}/predicates/match.py", "predicate_match")

SPEC = {"rows": 1500, "dim": 16, "clusters": 4, "noise_scale": 10.0,
        "cluster_spread": 1.5, "attr_dims": 3, "labels_per_dim": 2,
        "attr_cluster_corr": 0.5}
K = 10


@pytest.fixture(scope="module")
def data():
    return corpus_mod.make_corpus(SPEC, 40, seed=11)


def brute(features, attrs, qf, qa, k):
    """Every row, one query at a time: the loop the reference must match."""
    ids = np.full((len(qf), k), -1)
    for i in range(len(qf)):
        rows = [j for j in range(len(features)) if (attrs[j] == qa[i]).all()]
        d = [float(np.sum((features[j].astype(np.float64) - qf[i]) ** 2))
             for j in rows]
        top = sorted(zip(d, rows))[:k]
        ids[i, :len(top)] = [j for _, j in top]
    return ids


def test_reference_matches_brute_loop(data):
    ids, dists = reference.Reference(data.features, data.attrs, MATCH).topk(
        data.query_features, data.query_attrs, K)
    want = brute(data.features, data.attrs, data.query_features,
                 data.query_attrs, K)
    np.testing.assert_array_equal(ids, want)
    exact = np.linalg.norm(data.features[ids].astype(np.float64)
                           - data.query_features[:, None], axis=-1)
    np.testing.assert_allclose(dists, exact, rtol=1e-12)


def test_reference_follows_the_predicate_file(data):
    """Another predicate file gives another reference: here a row meets
    the query when its first attribute matches."""
    first = types.SimpleNamespace(
        meets=lambda rows, q: rows[..., 0] == q[..., 0])
    ids, _ = reference.Reference(data.features, data.attrs, first).topk(
        data.query_features, data.query_attrs, K)
    want = brute(data.features, data.attrs[:, :1], data.query_features,
                 data.query_attrs[:, :1], K)
    np.testing.assert_array_equal(ids, want)


def test_a_query_with_no_matching_row_gets_nothing(data):
    qa = np.full((1, 3), 7, np.int32)  # label 7 never occurs
    ids, dists = reference.Reference(data.features, data.attrs, MATCH).topk(
        data.query_features[:1], qa, K)
    assert (ids == -1).all() and np.isinf(dists).all()


def test_recall_arithmetic():
    truth = np.array([[1, 2, 3, -1], [4, 5, -1, -1], [-1, -1, -1, -1]])
    got = np.array([[3, 2, 9, 8], [4, -1, -1, -1], [1, 2, 3, 4]])
    # (2/3 + 1/2) / 2; the third query matches no row and is skipped
    assert reference.recall(got, truth) == pytest.approx((2 / 3 + 1 / 2) / 2)


def test_bf16_rounding():
    x = np.array([1.0, 1.00390625, 1.005859375, 3.14159265, -2.5e-3],
                 np.float32)
    r = reference.to_bf16(x)
    assert r[0] == 1.0 and r[1] == 1.0  # a tie rounds to even
    assert r[2] == np.float32(1.0078125)
    u = r.view(np.uint32)
    assert (u & 0xFFFF == 0).all()
    assert np.all(np.abs(r - x) <= np.abs(x) * 2.0 ** -8)


def _answers(ids, dists):
    n = len(ids)
    return reference.Answers(np.arange(n), ids.copy(), dists.copy(),
                             np.ones(n, bool))


def test_compare_exact_answers(data):
    ref = reference.Reference(data.features, data.attrs, MATCH)
    ids, d = ref.topk(data.query_features, data.query_attrs, K)
    nums = reference.compare(_answers(ids, d.astype(np.float32)), ref,
                             data.query_features, data.query_attrs, K)
    assert nums["recall_at_10"] == 1.0 and nums["missing"] == 0
    assert nums["dist_gap"] < 1e-6


def test_compare_catches_what_is_wrong(data):
    ref = reference.Reference(data.features, data.attrs, MATCH)
    ids, d = ref.topk(data.query_features, data.query_attrs, K)
    checks = {"recall_at_10": {"min": 0.5}, "dist_gap": {"max": 1e-4},
              "missing": {"max": 0}}

    def judged(ans):
        nums = reference.compare(ans, ref, data.query_features,
                                 data.query_attrs, K)
        return nums, reference.verdict(nums, checks)[0]

    nums, ok = judged(_answers(ids, d))
    assert ok
    # an id altered where it is produced: its distance no longer fits it
    bad = _answers(ids, d)
    bad.ids[:, 0] = (bad.ids[:, 0] + 1) % len(data.features)
    nums, ok = judged(bad)
    assert not ok and nums["dist_gap"] > 1e-2
    # answers handed to the wrong requests
    nums, ok = judged(_answers(np.roll(ids, 1, 0), np.roll(d, 1, 0)))
    assert not ok and nums["dist_gap"] > 1e-2
    # half of the requests left without an answer
    half = _answers(ids, d)
    half.answered[::2] = False
    nums, ok = judged(half)
    assert not ok and nums["missing"] == 20
    # an answer cut short
    short = _answers(ids, d)
    short.ids[3, 5:] = -1
    assert judged(short)[0]["missing"] == 1
    # a row that breaks the predicate may carry a larger (penalised)
    # distance, never a smaller one
    other = np.flatnonzero((data.attrs != data.query_attrs[0]).any(1))[0]
    exact = np.linalg.norm(data.features[other].astype(np.float64)
                           - data.query_features[0])
    soft = _answers(ids, d)
    soft.ids[0, -1], soft.dists[0, -1] = other, exact * 1.5
    assert judged(soft)[0]["dist_gap"] < 1e-9
    soft.dists[0, -1] = exact * 0.5
    assert judged(soft)[0]["dist_gap"] == pytest.approx(0.5)


def test_control_is_not_correct(data):
    """The bfloat16 reference in the program's place fails the limit."""
    ref = reference.Reference(data.features, data.attrs, MATCH)
    idx = np.arange(len(data.query_features))
    ctl = reference.control_answers(idx, data.query_features,
                                    data.query_attrs, data.features,
                                    data.attrs, MATCH, K)
    nums = reference.compare(ctl, ref, data.query_features,
                             data.query_attrs, K)
    ok, rows = reference.verdict(nums, {"recall_at_10": {"min": 0.6},
                                        "dist_gap": {"max": 1e-4},
                                        "missing": {"max": 0}})
    assert not ok and nums["dist_gap"] > 1e-4 and nums["recall_at_10"] > 0.9
    assert [r[0] for r in rows if not r[-1]] == ["dist_gap"]
