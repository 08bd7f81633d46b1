"""The seeded generators: corpus and query pool, Poisson schedules, the
closed loop's clients."""
import numpy as np
import pytest

from conftest import REPO
from harness import corpus as corpus_mod
from harness.spec import Spec

CLOSED = {"generator": "schedule", "loop": "closed", "clients": 128,
          "predicate": "match", "query_pool": 4096}
OPEN = {"generator": "schedule", "loop": "open", "arrivals": "poisson",
        "rate_qps": 200, "predicate": "match", "query_pool": 4096}


@pytest.fixture(scope="module")
def gen():
    return Spec(REPO).generator(OPEN)


def test_poisson_schedule_is_fixed_work_in_seeded_order(gen):
    big = 2**31 + 12345  # seeds past 32 bits are whole numbers too
    a = gen.make_schedule(OPEN, corpus_mod.rng(big, 1), 30.0)
    b = gen.make_schedule(OPEN, corpus_mod.rng(big, 1), 30.0)
    c = gen.make_schedule(OPEN, corpus_mod.rng(big + 1, 1), 30.0)
    assert a.loop == "open" and len(a.due) == len(a.queries) == 6000
    np.testing.assert_array_equal(a.due, b.due)
    np.testing.assert_array_equal(a.queries, b.queries)
    assert len(c.due) == len(a.due) and not np.array_equal(c.due, a.due)
    assert np.all(np.diff(a.due) >= 0) and 0 <= a.due[0] and a.due[-1] < 30
    assert a.queries.min() >= 0 and a.queries.max() < 4096
    # Poisson: exponential gaps, mean 1/rate, coefficient of variation ≈ 1
    gaps = np.diff(a.due)
    assert abs(gaps.mean() * 200 - 1) < 0.05
    assert abs(gaps.std() / gaps.mean() - 1) < 0.1


def test_rate_override_for_the_sweep(gen):
    s = gen.make_schedule(OPEN, corpus_mod.rng(3, 1), 10.0, rate_qps=37.5)
    assert len(s.due) == 375


def test_closed_loop_keeps_its_clients(gen):
    s = gen.make_schedule(CLOSED, corpus_mod.rng(7, 1), 30.0)
    assert s.loop == "closed" and s.clients == 128 and len(s.due) == 0
    assert len(s.queries) >= 100_000 and s.queries.max() < 4096


@pytest.mark.parametrize("mix", [
    {**OPEN, "rate_qps": 0},
    {**OPEN, "arrivals": "bursty"},
    {**CLOSED, "loop": "replay"},
])
def test_unsupported_mixes_are_refused(gen, mix):
    with pytest.raises(ValueError):
        gen.make_schedule(mix, corpus_mod.rng(0, 1), 1.0)


def test_corpus_is_the_seeds_and_the_configs():
    spec = {"rows": 3000, "dim": 128, "clusters": 16, "noise_scale": 33.5,
            "cluster_spread": 1.5, "attr_dims": 5, "labels_per_dim": 3,
            "attr_cluster_corr": 0.6}
    a = corpus_mod.make_corpus(spec, 64, seed=-5)
    b = corpus_mod.make_corpus(spec, 64, seed=-5)
    c = corpus_mod.make_corpus(spec, 64, seed=6)
    np.testing.assert_array_equal(a.features, b.features)
    assert not np.array_equal(a.features, c.features)
    assert a.features.shape == (3000, 128) and a.features.dtype == np.float32
    assert a.attrs.shape == (3000, 5) and a.attrs.dtype == np.int32
    assert a.query_features.shape == (64, 128)
    assert set(np.unique(a.attrs)) == {0, 1, 2}
    # the program's make_hybrid_dataset at this profile and 16 clusters
    # gives a mean pairwise distance of about 935
    d = np.linalg.norm(a.features[:200, None] - a.features[None, 200:400],
                       axis=-1)
    assert 800 < d.mean() < 1100
