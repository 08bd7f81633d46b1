"""Unit + property tests for the AUTO metric (paper §III-B)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container has no hypothesis — deterministic fallback
    from _hypothesis_compat import given, settings, st

from repro.core import auto as A
from repro.core.auto import MetricConfig


def rand_case(seed, b=4, n=64, m=16, l=5, labels=3):
    rng = np.random.default_rng(seed)
    return (
        rng.normal(size=(b, m)).astype(np.float32),
        rng.integers(0, labels, size=(b, l)).astype(np.int32),
        rng.normal(size=(n, m)).astype(np.float32),
        rng.integers(0, labels, size=(n, l)).astype(np.int32),
    )


class TestNumericalMapping:
    def test_roundtrip_preserves_equality(self):
        rng = np.random.default_rng(0)
        raw = rng.choice(["red", "blue", "green"], size=(100, 4))
        mapped, tables = A.numerical_map(raw)
        # Remark 1: full-match checks are preserved by the mapping.
        for i in range(0, 50):
            for j in range(50, 60):
                assert (raw[i] == raw[j]).all() == (mapped[i] == mapped[j]).all()

    def test_query_mapping_consistent(self):
        rng = np.random.default_rng(1)
        raw = rng.integers(10, 20, size=(50, 3))
        mapped, tables = A.numerical_map(raw)
        q = A.map_query_attrs(raw[:5], tables)
        np.testing.assert_array_equal(q, mapped[:5])


class TestRemark2:
    """Manhattan ≥ Euclidean ≥ 1 and Manhattan ≥ Hamming ≥ 1 on mismatch."""

    @given(st.integers(0, 10_000))
    @settings(max_examples=50, deadline=None)
    def test_inequality_chain(self, seed):
        rng = np.random.default_rng(seed)
        l = int(rng.integers(1, 8))
        a = rng.integers(0, 5, size=(l,)).astype(np.int32)
        b = a.copy()
        # force at least one mismatch
        j = int(rng.integers(0, l))
        b[j] = (b[j] + 1 + int(rng.integers(0, 3))) % 7
        man = np.abs(a - b).sum()
        euc = np.sqrt(((a - b) ** 2).sum())
        ham = (a != b).sum()
        assert man >= euc >= 1
        assert man >= ham >= 1


class TestAlphaCalibration:
    def test_norm_maps_into_unit_interval(self):
        for x in [1e-9, 0.05, 0.1, 0.1001, 0.5, 1.0, 3.7, 99.0, 1e8]:
            y = A.norm_to_unit(x)
            assert 0.1 < y <= 1.0, (x, y)

    @given(
        st.integers(1000, 10_000_000),
        st.floats(0.01, 1e4),
        st.floats(0.01, 30.0),
        st.integers(1, 16),
    )
    @settings(max_examples=100, deadline=None)
    def test_alpha_bounded(self, n, sv, sa, l):
        # α = Norm(·) + Norm(·) ∈ (0.2, 2]
        alpha = A.compute_alpha(n, sv, sa, l)
        assert 0.2 < alpha <= 2.0

    def test_sample_stats_match_direct_computation(self):
        rng = np.random.default_rng(0)
        f = rng.normal(size=(64, 8)).astype(np.float32)
        a = rng.integers(0, 3, size=(64, 4))
        stats = A.sample_stats(f, a, n_samples=64, seed=0)
        # direct O(n²) reference
        fd, ad = [], []
        for i in range(64):
            for j in range(i + 1, 64):
                fd.append(np.linalg.norm(f[i] - f[j]))
                ad.append(np.abs(a[i] - a[j]).sum())
        assert np.isclose(stats.mean_feature_dist, np.mean(fd), rtol=1e-5)
        assert np.isclose(stats.mean_attribute_dist, np.mean(ad), rtol=1e-5)
        assert np.isclose(stats.max_feature_dist, np.max(fd), rtol=1e-5)


class TestFusedMetric:
    def test_auto_matches_definition(self):
        qv, qa, xv, xa = rand_case(0)
        cfg = MetricConfig(mode="auto", alpha=0.8)
        got = A.fused_sqdist(qv[:, None, :], qa[:, None, :], xv[None], xa[None], cfg)
        sv = np.linalg.norm(qv[:, None, :] - xv[None], axis=-1)
        sa = np.abs(qa[:, None, :].astype(np.float32) - xa[None].astype(np.float32)).sum(-1)
        want = (sv * (1 + sa / 0.8)) ** 2
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4)

    def test_matched_attrs_reduce_to_l2(self):
        qv, qa, xv, xa = rand_case(1)
        cfg = MetricConfig(mode="auto", alpha=1.0)
        got = A.fused_sqdist(qv, qa, xv[: qv.shape[0]], qa, cfg)  # same attrs
        want = ((qv - xv[: qv.shape[0]]) ** 2).sum(-1)
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4)

    @given(st.integers(0, 1000))
    @settings(max_examples=30, deadline=None)
    def test_eq6_selection_correctness(self, seed):
        """Paper Eq. 6: mismatched node wins iff S_V ratio beats 1+λ."""
        rng = np.random.default_rng(seed)
        alpha = float(rng.uniform(0.3, 2.0))
        sv_match = float(rng.uniform(0.1, 10.0))
        sv_mism = float(rng.uniform(0.01, 10.0))
        sa = float(rng.integers(1, 8))
        u_match = sv_match
        u_mism = sv_mism * (1 + sa / alpha)
        wins = u_mism < u_match
        margin = sv_mism < sv_match / (1 + sa / alpha)
        assert wins == margin

    def test_brute_fused_matches_pointwise(self):
        qv, qa, xv, xa = rand_case(2, b=3, n=50)
        for mode in A.METRIC_MODES:
            cfg = MetricConfig(mode=mode, alpha=0.7, nhq_weight=2.0)
            brute = A.brute_fused_sqdist(qv, qa, xv, xa, cfg)
            point = A.fused_sqdist(
                qv[:, None, :], qa[:, None, :], xv[None], xa[None], cfg
            )
            np.testing.assert_allclose(
                np.asarray(brute), np.asarray(point), rtol=1e-3, atol=1e-3
            )

    def test_brute_fused_chunked_equals_unchunked(self):
        qv, qa, xv, xa = rand_case(3, b=2, n=100)
        cfg = MetricConfig(mode="auto", alpha=1.0)
        a1 = A.brute_fused_sqdist(qv, qa, xv, xa, cfg, chunk=16)
        a2 = A.brute_fused_sqdist(qv, qa, xv, xa, cfg, chunk=4096)
        np.testing.assert_allclose(np.asarray(a1), np.asarray(a2), rtol=1e-5)

    @pytest.mark.parametrize("target", ["point", "interval", "masked"])
    def test_brute_fused_solo_query_bit_identical_to_batch(self, target):
        """A query scored alone gets the same bits as its row of a batch
        (a serving bucket pads a solo query up to a batch)."""
        qv, qa, xv, xa = rand_case(4, b=8, n=2000, m=128)
        mask = None
        if target == "interval":
            qa = np.stack([qa, qa + 1], axis=-1)
        elif target == "masked":
            mask = (np.arange(8 * 5).reshape(8, 5) % 3 > 0).astype(np.int32)
        cfg = MetricConfig(mode="auto", alpha=1.0)
        batch = np.asarray(A.brute_fused_sqdist(qv, qa, xv, xa, cfg, mask))
        for i in range(8):
            solo = A.brute_fused_sqdist(
                qv[i:i + 1], qa[i:i + 1], xv, xa, cfg,
                None if mask is None else mask[i:i + 1],
            )
            np.testing.assert_array_equal(np.asarray(solo)[0], batch[i])

    def test_triangle_inequality_within_uniform_attrs(self):
        """§III-B3[c]: within an attribute-uniform subspace U is a scaled
        Euclidean metric, so the triangle inequality holds."""
        rng = np.random.default_rng(7)
        v = rng.normal(size=(3, 16)).astype(np.float32)
        a = np.tile(rng.integers(0, 3, size=(1, 5)), (3, 1)).astype(np.int32)
        qa_const = rng.integers(0, 3, size=(5,)).astype(np.int32)
        cfg = MetricConfig(mode="auto", alpha=0.9)
        # distance of each node pair under AUTO w.r.t. a fixed query attr:
        # all three nodes share attrs ⇒ same penalty c ⇒ scaled L2.
        sa = np.abs(a[0] - qa_const).sum()
        scale = 1 + sa / 0.9
        d01 = np.linalg.norm(v[0] - v[1]) * scale
        d12 = np.linalg.norm(v[1] - v[2]) * scale
        d02 = np.linalg.norm(v[0] - v[2]) * scale
        assert d02 <= d01 + d12 + 1e-5


class TestMasking:
    def test_full_mask_equals_unmasked(self):
        qv, qa, xv, xa = rand_case(4)
        cfg = MetricConfig(mode="auto", alpha=1.0)
        m = np.ones_like(qa)
        a1 = A.brute_fused_sqdist(qv, qa, xv, xa, cfg, mask=jnp.asarray(m))
        a2 = A.brute_fused_sqdist(qv, qa, xv, xa, cfg)
        np.testing.assert_allclose(np.asarray(a1), np.asarray(a2))

    def test_zero_mask_ignores_attributes(self):
        qv, qa, xv, xa = rand_case(5)
        cfg = MetricConfig(mode="auto", alpha=1.0)
        m = np.zeros_like(qa)
        a1 = A.brute_fused_sqdist(qv, qa, xv, xa, cfg, mask=jnp.asarray(m))
        l2 = A.brute_fused_sqdist(qv, qa, xv, xa, MetricConfig(mode="l2"))
        np.testing.assert_allclose(np.asarray(a1), np.asarray(l2), rtol=1e-5)

    def test_partial_mask_eq8(self):
        qv, qa, xv, xa = rand_case(6, l=4)
        cfg = MetricConfig(mode="auto", alpha=0.5)
        m = np.array([[1, 0, 1, 0]] * qa.shape[0], np.int32)
        got = A.fused_sqdist(
            qv[:, None, :], qa[:, None, :], xv[None], xa[None], cfg,
            mask=jnp.asarray(m)[:, None, :],
        )
        sv = np.linalg.norm(qv[:, None, :] - xv[None], axis=-1)
        sa = (np.abs(qa[:, None, :] - xa[None]) * m[:, None, :]).sum(-1)
        want = (sv * (1 + sa / 0.5)) ** 2
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4)


class TestIntervalTargets:
    """Per-dimension [lo, hi] interval targets (max(lo−a, a−hi, 0) penalty)
    generalizing the point Manhattan term across every scorer."""

    @given(st.integers(0, 10_000))
    @settings(max_examples=30, deadline=None)
    def test_degenerate_interval_bit_exact_to_point(self, seed):
        """lo = hi = q must reduce to |a − q| *bit-exactly* in every metric
        mode — the all-MATCH legacy-path guarantee."""
        qv, qa, xv, xa = rand_case(seed)
        deg = jnp.stack([jnp.asarray(qa), jnp.asarray(qa)], axis=-1)
        for mode in A.METRIC_MODES:
            cfg = MetricConfig(mode=mode, alpha=0.7, nhq_weight=2.0)
            point = A.brute_fused_sqdist(qv, qa, xv, xa, cfg)
            interval = A.brute_fused_sqdist(qv, deg, xv, xa, cfg)
            np.testing.assert_array_equal(
                np.asarray(point), np.asarray(interval), err_msg=mode
            )

    @given(st.integers(0, 10_000))
    @settings(max_examples=30, deadline=None)
    def test_zero_penalty_inside_interval(self, seed):
        """Any value inside [lo, hi] contributes nothing: an all-covering
        interval batch scores identically to pure L2."""
        qv, qa, xv, xa = rand_case(seed, labels=4)
        wide = jnp.stack(
            [jnp.zeros_like(jnp.asarray(qa)),
             jnp.full_like(jnp.asarray(qa), 3)], axis=-1
        )  # covers the whole label range [0, 3]
        cfg = MetricConfig(mode="auto", alpha=0.8)
        got = A.brute_fused_sqdist(qv, wide, xv, xa, cfg)
        l2 = A.brute_fused_sqdist(qv, qa, xv, xa, MetricConfig(mode="l2"))
        np.testing.assert_allclose(np.asarray(got), np.asarray(l2), rtol=1e-6)

    @given(st.integers(0, 10_000))
    @settings(max_examples=30, deadline=None)
    def test_gap_is_distance_to_nearest_bound(self, seed):
        rng = np.random.default_rng(seed)
        a = rng.integers(0, 10, size=(32, 4)).astype(np.int32)
        lo = rng.integers(0, 10, size=(1, 4)).astype(np.int32)
        hi = lo + rng.integers(0, 5, size=(1, 4)).astype(np.int32)
        iv = jnp.asarray(np.stack([lo, hi], axis=-1))
        got = np.asarray(A.attribute_distance(iv, jnp.asarray(a)))
        want = (np.maximum(lo - a, 0) + np.maximum(a - hi, 0)).sum(-1)
        np.testing.assert_allclose(got, want)

    def test_interval_is_lower_bound_of_member_distance(self):
        """The ONE_OF guidance guarantee: the covering-hull gap never
        exceeds min_j |a − v_j| for any member set within the hull."""
        rng = np.random.default_rng(0)
        values = np.array([1, 4, 7])
        iv = jnp.asarray([[[1, 7]]], jnp.int32)  # (1, 1, 2) hull
        a = rng.integers(-3, 12, size=(64, 1)).astype(np.int32)
        gap = np.asarray(A.attribute_distance(iv, jnp.asarray(a)))
        exact = np.abs(a[:, 0:1] - values[None, :]).min(-1)
        assert (gap <= exact + 1e-6).all()

    def test_extra_rank_without_bound_axis_rejected(self):
        """An extra-rank target whose trailing axis isn't the two [lo, hi]
        bounds must fail loudly, not be mis-sliced into lo/hi views."""
        bad = jnp.zeros((2, 1, 3), jnp.int32)  # rank 3 vs rank-2 attrs
        xa = jnp.zeros((5, 3), jnp.int32)
        with pytest.raises(ValueError, match="lo, hi"):
            A.attribute_distance(bad, xa)
        with pytest.raises(ValueError):
            from repro.kernels.common import split_targets

            split_targets(jnp.zeros((2, 3, 4), jnp.int32))

    def test_interval_violation_hamming(self):
        iv = jnp.asarray([[[1, 3], [2, 2]]], jnp.int32)  # (1, 2, 2)
        xa = jnp.asarray([[0, 2], [2, 1], [3, 2], [4, 2]], jnp.int32)
        got = np.asarray(A.attribute_violation(iv, xa))
        want = np.array(
            [[True, False], [False, True], [False, False], [True, False]]
        )
        np.testing.assert_array_equal(got, want)


class TestBruteTopK:
    def test_topk_sorted_and_correct(self):
        qv, qa, xv, xa = rand_case(8, b=5, n=200)
        cfg = MetricConfig(mode="auto", alpha=1.0)
        d, idx = A.brute_topk(qv, qa, xv, xa, 10, cfg)
        d, idx = np.asarray(d), np.asarray(idx)
        assert (np.diff(d, axis=1) >= -1e-6).all()
        full = np.asarray(A.brute_fused_sqdist(qv, qa, xv, xa, cfg))
        want = np.sort(full, axis=1)[:, :10]
        np.testing.assert_allclose(np.sort(d, 1), want, rtol=1e-4)
