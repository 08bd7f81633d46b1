"""Quantized search subsystem: codec roundtrips (SQ8 / PQ / packed 4-bit
PQ / OPQ rotation), codec meta versioning, quantized index persistence,
end-to-end recall. Kernel parity lives in tests/test_adc_scan.py (the CI
kernel-parity gate)."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Engine, QueryBatch, SearchParams
from repro.core.baselines import brute_force_hybrid, recall_at_k
from repro.core.help_graph import HelpConfig
from repro.core.index import StableIndex
from repro.core.routing import RoutingConfig
from repro.data.synthetic import make_hybrid_dataset
from repro.quant import (
    CODEC_VERSION,
    QuantConfig,
    QuantizedVectors,
    adc_gathered_sqdist,
    adc_lut,
    opq_reconstruct,
    opq_train,
    pack_nibbles,
    pq_decode,
    pq_encode,
    pq_train,
    rotate,
    sq8_decode,
    sq8_encode,
    unpack_nibbles,
)
from repro.kernels.adc_scan.adc_scan import layout_codes
from repro.kernels.adc_scan.ref import adc_scan4_ref, adc_scan_ref
from repro.quant import adc_scan
from repro.quant.store import check_codec_spec, codec_spec

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container has no hypothesis — deterministic fallback
    from _hypothesis_compat import given, settings, st


@pytest.fixture(scope="module")
def small_ds():
    return make_hybrid_dataset(
        n=4000, n_queries=32, profile="sift", attr_dim=5, labels_per_dim=3,
        n_clusters=16, attr_cluster_corr=0.6, seed=0,
    )


@pytest.fixture(scope="module")
def small_index(small_ds):
    return StableIndex.build(
        small_ds.features, small_ds.attrs,
        HelpConfig(gamma=16, gamma_new=4, max_rounds=4,
                   quality_sample=64, node_block=1024),
    )


class TestSQ8Codec:
    def test_roundtrip_error_bounded_by_step(self):
        rng = np.random.default_rng(0)
        x = (rng.normal(size=(512, 32)) * rng.uniform(0.1, 30, 32)).astype(
            np.float32
        )
        codes, params = sq8_encode(x)
        assert codes.dtype == jnp.int8
        dec = np.asarray(sq8_decode(codes, params))
        # affine rounding: per-dim error ≤ half a quantization step
        step = np.asarray(params.scale)
        assert (np.abs(dec - x) <= 0.5 * step[None, :] + 1e-6).all()

    def test_range_endpoints_exact(self):
        x = np.array([[0.0], [255.0]], np.float32)
        codes, params = sq8_encode(x)
        dec = np.asarray(sq8_decode(codes, params))
        np.testing.assert_allclose(dec[:, 0], [0.0, 255.0], atol=1e-4)


class TestPQCodec:
    def test_encode_shapes_and_reconstruction(self):
        rng = np.random.default_rng(1)
        x = rng.normal(size=(2000, 48)).astype(np.float32)
        cb = pq_train(x, n_subspaces=8, n_iters=8, n_samples=1000, seed=0)
        codes = pq_encode(x, cb)
        assert codes.shape == (2000, 8)
        assert int(codes.max()) < 256 and int(codes.min()) >= 0
        dec = np.asarray(pq_decode(codes, cb))
        assert dec.shape == x.shape
        # reconstruction must beat the trivial zero codebook by a wide margin
        rel_mse = np.mean((dec - x) ** 2) / np.mean(x**2)
        assert rel_mse < 0.5, rel_mse

    def test_ragged_dim_zero_padded(self):
        """M not divisible by S: padding dims must not perturb distances."""
        rng = np.random.default_rng(2)
        x = rng.normal(size=(500, 30)).astype(np.float32)  # 30 / 8 ragged
        cb = pq_train(x, n_subspaces=8, n_iters=5, n_samples=500, seed=0)
        codes = pq_encode(x, cb)
        q = rng.normal(size=(3, 30)).astype(np.float32)
        lut = adc_lut(q, cb)
        d_adc = np.asarray(
            adc_gathered_sqdist(lut, jnp.broadcast_to(codes[None], (3,) + codes.shape))
        )
        dec = np.asarray(pq_decode(codes, cb))
        d_exact = ((q[:, None, :] - dec[None]) ** 2).sum(-1)
        np.testing.assert_allclose(d_adc, d_exact, rtol=1e-4, atol=1e-3)


class TestQuantizedIndex:
    @pytest.mark.parametrize("mode", ["sq8", "pq", "pq4", "opq-pq4"])
    def test_save_load_roundtrip(self, small_ds, tmp_path, mode):
        idx = StableIndex.build(
            small_ds.features[:1000], small_ds.attrs[:1000],
            HelpConfig(gamma=12, gamma_new=4, max_rounds=2,
                       quality_sample=64, node_block=512),
            quant_cfg=QuantConfig(mode=mode, pq_subspaces=8, pq_train_iters=5,
                                  opq_iters=2),
        )
        path = os.path.join(tmp_path, f"idx_{mode}")
        idx.save(path)
        idx2 = StableIndex.load(path)
        assert idx2.quant is not None and idx2.quant.cfg.mode == mode
        np.testing.assert_array_equal(
            np.asarray(idx.quant.codes), np.asarray(idx2.quant.codes)
        )
        if mode == "sq8":
            np.testing.assert_allclose(
                np.asarray(idx.quant.sq_params.scale),
                np.asarray(idx2.quant.sq_params.scale),
            )
        else:
            np.testing.assert_allclose(
                np.asarray(idx.quant.codebook.centroids),
                np.asarray(idx2.quant.codebook.centroids),
            )
        if idx.quant.rotation is not None:
            np.testing.assert_array_equal(
                np.asarray(idx.quant.rotation), np.asarray(idx2.quant.rotation)
            )
        # loaded index must search identically to the in-memory one
        r1 = idx.search(small_ds.query_features, small_ds.query_attrs, 10)
        r2 = idx2.search(small_ds.query_features, small_ds.query_attrs, 10)
        np.testing.assert_array_equal(np.asarray(r1.ids), np.asarray(r2.ids))

    def test_unquantized_save_load_unaffected(self, small_index, tmp_path):
        path = os.path.join(tmp_path, "idx_plain")
        small_index.save(path)
        idx2 = StableIndex.load(path)
        assert idx2.quant is None

    @pytest.mark.parametrize("mode", ["sq8", "pq", "pq4", "opq-pq"])
    def test_recall_within_3_points_and_fewer_fp_evals(self, small_ds,
                                                       small_index, mode):
        ds = small_ds
        truth = brute_force_hybrid(
            ds.features, ds.attrs, ds.query_features, ds.query_attrs, 10
        )
        cfg = RoutingConfig(k=10, pool_size=64, pioneer_size=8)
        exact = small_index.search(ds.query_features, ds.query_attrs, 10, cfg)
        r_exact = recall_at_k(exact.ids, truth.ids, 10)

        quant = QuantizedVectors.build(
            ds.features, QuantConfig(mode=mode, pq_subspaces=16)
        )
        idx_q = dataclasses.replace(small_index, quant=quant)
        qcfg = dataclasses.replace(cfg, quant_mode=mode)
        res = idx_q.search(ds.query_features, ds.query_attrs, 10, qcfg)
        r_quant = recall_at_k(res.ids, truth.ids, 10)

        assert r_quant >= r_exact - 0.03, (mode, r_exact, r_quant)
        assert res.n_dist_evals.shape == (ds.query_features.shape[0],)
        assert res.total_dist_evals < exact.total_dist_evals
        assert res.total_code_evals > 0
        assert exact.total_code_evals == 0

    def test_rerank_size_bounds_fp_evals(self, small_ds, small_index):
        quant = QuantizedVectors.build(small_ds.features, QuantConfig(mode="sq8"))
        idx_q = dataclasses.replace(small_index, quant=quant)
        nq = small_ds.query_features.shape[0]
        cfg = RoutingConfig(k=10, pool_size=64, pioneer_size=8,
                            quant_mode="sq8", rerank_size=16)
        res = idx_q.search(small_ds.query_features, small_ds.query_attrs, 10, cfg)
        assert (np.asarray(res.n_dist_evals) <= 16).all()
        assert res.total_dist_evals <= 16 * nq

    def test_bad_configs_rejected(self):
        with pytest.raises(ValueError):
            RoutingConfig(quant_mode="fp4")
        with pytest.raises(ValueError):
            RoutingConfig(k=10, pool_size=64, rerank_size=4)  # < k
        with pytest.raises(ValueError):
            QuantConfig(mode="int2")


class TestNibblePacking:
    @given(st.integers(1, 40), st.integers(1, 33), st.integers(0, 10**6))
    @settings(max_examples=25, deadline=None)
    def test_pack_unpack_roundtrip(self, n, s, seed):
        """Property: unpack(pack(c)) == c for any S, including odd S where
        the last byte carries a zero pad nibble."""
        rng = np.random.default_rng(seed)
        codes = rng.integers(0, 16, size=(n, s))
        packed = pack_nibbles(jnp.asarray(codes, jnp.int32))
        assert packed.dtype == jnp.uint8
        assert packed.shape == (n, (s + 1) // 2)
        np.testing.assert_array_equal(
            np.asarray(unpack_nibbles(packed, s)), codes
        )
        if s % 2:  # pad nibble must be zero so a zero-padded LUT ignores it
            assert (np.asarray(packed)[:, -1] >> 4 == 0).all()

    def test_packed_halves_code_bytes(self):
        rng = np.random.default_rng(0)
        x = rng.normal(size=(600, 32)).astype(np.float32)
        q8 = QuantizedVectors.build(x, QuantConfig(mode="pq", pq_subspaces=8,
                                                   pq_train_iters=3))
        q4 = QuantizedVectors.build(x, QuantConfig(mode="pq4", pq_subspaces=8,
                                                   pq_train_iters=3))
        assert q8.codes.dtype == jnp.uint8 and q4.codes.dtype == jnp.uint8
        assert q4.code_bytes * 2 == q8.code_bytes


@pytest.mark.parametrize("mode", ["pq", "pq4"])
class TestScanLayoutFreshness:
    """The ADC scan's laid-out codes are derived wherever a
    ``QuantizedVectors`` is made, so no path can scan stale codes."""

    @staticmethod
    def _cfg(mode):
        return QuantConfig(mode=mode, pq_subspaces=8, pq_centroids=16,
                           pq_train_iters=3)

    @staticmethod
    def _assert_fresh(q, attrs, qv):
        np.testing.assert_array_equal(
            np.asarray(q.scan_codes), np.asarray(layout_codes(q.codes))
        )
        lut = q.lut(jnp.asarray(qv))
        qa = jnp.zeros((qv.shape[0], attrs.shape[1]), jnp.int32)
        got = adc_scan(lut, q.scan_codes, qa, attrs, mode="l2",
                       packed=q.packed)
        ref = adc_scan4_ref if q.packed else adc_scan_ref
        want = ref(lut, q.codes, qa, attrs, 1.0, mode="l2")
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-5
        )

    def test_appended_rows_reach_the_brute_scan(self, small_ds, mode):
        x, a = small_ds.features, small_ds.attrs
        eng = Engine.build(x[:600], a[:600], build_graph=False,
                           quant_cfg=self._cfg(mode))
        new_ids = np.arange(600, 604)
        new_attrs = np.full((4, a.shape[1]), 3, np.int32)  # labels of their own
        grown = eng.index.apply_rows(new_ids, x[600:604], new_attrs)
        assert grown.quant.codes.shape[0] == 604
        self._assert_fresh(grown.quant, grown.attrs, x[600:604])
        res = Engine(grown).search(
            QueryBatch.match(x[600:604], new_attrs),
            SearchParams(k=1, backend="brute"),
        )
        np.testing.assert_array_equal(np.asarray(res.ids)[:, 0], new_ids)

    def test_partition_slices_scan_their_own_rows(self, small_ds, mode):
        x, a = small_ds.features[:600], small_ds.attrs[:600]
        eng = Engine.build_partitioned(x, a, n_partitions=3,
                                       build_graph=False,
                                       quant_cfg=self._cfg(mode))
        store = eng.index.store
        for pid in range(3):
            part = store.get(pid)
            assert part.quant.codes is part.codes
            self._assert_fresh(part.quant, part.attrs, x[:4])

    def test_loaded_store_lays_out_its_codes(self, small_ds, tmp_path, mode):
        q = QuantizedVectors.build(small_ds.features[:300], self._cfg(mode))
        meta = q.save(str(tmp_path))
        q2 = QuantizedVectors.load(str(tmp_path), meta)
        self._assert_fresh(q2, jnp.asarray(small_ds.attrs[:300]),
                           small_ds.features[:4])


class TestOPQ:
    @pytest.fixture(scope="class")
    def correlated(self):
        """Low-rank + noise: the regime where a learned rotation pays."""
        rng = np.random.default_rng(5)
        lat = rng.normal(size=(2000, 16)).astype(np.float32)
        mix = rng.normal(size=(16, 64)).astype(np.float32)
        return lat @ mix + 0.05 * rng.normal(size=(2000, 64)).astype(np.float32)

    @pytest.fixture(scope="class")
    def trained(self, correlated):
        return opq_train(correlated, n_subspaces=8, n_centroids=16,
                         n_iters=5, opq_iters=3, n_samples=2000, seed=0)

    def test_rotation_orthogonal(self, trained):
        rot, _ = trained
        r = np.asarray(rot)
        np.testing.assert_allclose(r.T @ r, np.eye(r.shape[0]), atol=1e-4)

    def test_rotation_preserves_distances(self, correlated, trained):
        rot, _ = trained
        x, y = correlated[:64], correlated[64:128]
        d0 = np.linalg.norm(x - y, axis=1)
        d1 = np.linalg.norm(
            np.asarray(rotate(x, rot)) - np.asarray(rotate(y, rot)), axis=1
        )
        np.testing.assert_allclose(d0, d1, rtol=1e-4, atol=1e-3)

    def test_opq_reconstruction_beats_plain_pq(self, correlated, trained):
        x = correlated
        rot, cb = trained
        codes = pq_encode(rotate(x, rot), cb)
        rec = np.asarray(opq_reconstruct(codes, cb, rot, x.shape[1]))
        mse_opq = float(np.mean((rec - x) ** 2))
        cb0 = pq_train(x, n_subspaces=8, n_centroids=16, n_iters=5,
                       n_samples=2000, seed=0)
        dec0 = np.asarray(pq_decode(pq_encode(x, cb0), cb0))[:, : x.shape[1]]
        mse_pq = float(np.mean((dec0 - x) ** 2))
        assert mse_opq <= mse_pq, (mse_opq, mse_pq)


class TestCodecMeta:
    def _spec(self, mode):
        return codec_spec(QuantConfig(mode=mode, pq_subspaces=8))

    def test_spec_versions(self):
        assert self._spec("pq")["version"] == 1
        for mode in ("pq4", "opq-pq", "opq-pq4"):
            assert self._spec(mode)["version"] == CODEC_VERSION

    def test_future_version_rejected(self):
        spec = dict(self._spec("pq4"), version=CODEC_VERSION + 1)
        with pytest.raises(ValueError, match="version"):
            check_codec_spec(spec, QuantConfig(mode="pq4"))

    def test_v2_store_without_spec_rejected(self):
        """An old writer can't have produced packed/rotated codes — a v2
        mode with no codec block means a corrupt or hand-edited store."""
        with pytest.raises(ValueError, match="codec"):
            check_codec_spec(None, QuantConfig(mode="opq-pq4"))

    def test_mismatched_spec_rejected(self):
        with pytest.raises(ValueError):
            check_codec_spec(self._spec("pq"), QuantConfig(mode="pq4"))

    def test_old_reader_rejects_unknown_mode_string(self):
        # an old QuantConfig (this one) fails loudly on future mode names
        with pytest.raises(ValueError):
            QuantConfig(mode="opq-pq2")

    def test_saved_store_roundtrips_spec(self, tmp_path):
        rng = np.random.default_rng(9)
        x = rng.normal(size=(400, 32)).astype(np.float32)
        qv = QuantizedVectors.build(
            x, QuantConfig(mode="opq-pq4", pq_subspaces=8, pq_train_iters=3,
                           opq_iters=2)
        )
        meta = qv.save(str(tmp_path))
        assert meta["codec"] == codec_spec(qv.cfg)
        q2 = QuantizedVectors.load(str(tmp_path), meta)
        np.testing.assert_array_equal(np.asarray(qv.codes), np.asarray(q2.codes))
        np.testing.assert_array_equal(
            np.asarray(qv.rotation), np.asarray(q2.rotation)
        )
        meta_bad = dict(meta, codec=dict(meta["codec"], version=99))
        with pytest.raises(ValueError, match="version"):
            QuantizedVectors.load(str(tmp_path), meta_bad)
