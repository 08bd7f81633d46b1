"""Fused ADC scan kernel parity (interpret mode): 8-bit and nibble-packed
4-bit variants vs the pure-jnp references, including bit-exactness of the
packed kernel against the unpacked one and degenerate interval targets,
the codes' lane-dense layout and the exact three-way LUT split. Both
kernels take only codes laid out by ``layout_codes``.

This module is the CI kernel-parity gate — it must stay runnable standalone
(``pytest tests/test_adc_scan.py``) without building any index.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import auto as auto_mod
from repro.core.auto import MetricConfig
from repro.kernels.adc_scan.adc_scan import (
    _split3, adc_scan4_scores, adc_scan_scores, layout_codes, padded_rows,
)
from repro.kernels.adc_scan.ref import adc_scan4_ref, adc_scan_ref
from repro.quant import adc_lut, pack_nibbles, pq_decode, pq_encode, pq_train
from repro.quant.pq import unpack_nibbles


class TestADCScanKernel:
    @pytest.mark.parametrize("b,n,s,l", [
        (4, 300, 8, 5),          # ragged N, everything padded
        (8, 256, 16, 7),         # exact blocks
        (1, 1, 4, 1),            # degenerate
        (9, 513, 8, 3),          # ragged in B and N
    ])
    def test_matches_ref(self, b, n, s, l):
        rng = np.random.default_rng(n + s)
        lut = jnp.asarray(rng.uniform(0, 4, size=(b, s, 256)), jnp.float32)
        codes = jnp.asarray(rng.integers(0, 256, size=(n, s)), jnp.int32)
        qa = jnp.asarray(rng.integers(0, 4, size=(b, l)), jnp.int32)
        xa = jnp.asarray(rng.integers(0, 4, size=(n, l)), jnp.int32)
        got = adc_scan_scores(lut, layout_codes(codes), qa, xa, alpha=0.8, interpret=True)
        want = adc_scan_ref(lut, codes, qa, xa, alpha=0.8)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-5
        )

    @pytest.mark.parametrize("n,s", [(300, 8), (300, 7), (9000, 16)])
    def test_layout_round_trips_to_codes(self, n, s):
        rng = np.random.default_rng(n + s)
        codes = rng.integers(0, 256, size=(n, s)).astype(np.uint8)
        words = np.asarray(layout_codes(jnp.asarray(codes)))
        assert words.dtype == np.int32
        assert words.shape == (-(-s // 4), padded_rows(n))
        got = np.stack([(words[j // 4] >> (8 * (j % 4))) & 0xFF
                        for j in range(4 * words.shape[0])])
        np.testing.assert_array_equal(got[:s, :n].T, codes)
        assert not got[s:].any() and not got[:, n:].any()  # zero padding

    def test_l2_mode_and_mask(self):
        rng = np.random.default_rng(3)
        lut = jnp.asarray(rng.uniform(0, 2, size=(5, 8, 256)), jnp.float32)
        codes = jnp.asarray(rng.integers(0, 256, size=(100, 8)), jnp.int32)
        qa = jnp.asarray(rng.integers(0, 3, size=(5, 4)), jnp.int32)
        xa = jnp.asarray(rng.integers(0, 3, size=(100, 4)), jnp.int32)
        mask = jnp.asarray(rng.integers(0, 2, size=(5, 4)), jnp.int32)
        for mode, m in (("l2", None), ("auto", mask)):
            got = adc_scan_scores(
                lut, layout_codes(codes), qa, xa, alpha=1.3, mode=mode, mask=m, interpret=True
            )
            want = adc_scan_ref(lut, codes, qa, xa, alpha=1.3, mode=mode, mask=m)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-5
            )

    def test_interval_targets_match_ref(self):
        """[lo, hi] interval targets through the fused ADC penalty: kernel
        == ref, degenerate intervals bit-exact to the point path."""
        rng = np.random.default_rng(7)
        b, n, s, l = 5, 300, 8, 4
        lut = jnp.asarray(rng.uniform(0, 4, size=(b, s, 256)), jnp.float32)
        codes = jnp.asarray(rng.integers(0, 256, size=(n, s)), jnp.int32)
        lo = jnp.asarray(rng.integers(0, 3, size=(b, l)), jnp.int32)
        iv = jnp.stack([lo, lo + 2], -1)
        xa = jnp.asarray(rng.integers(0, 5, size=(n, l)), jnp.int32)
        got = adc_scan_scores(lut, layout_codes(codes), iv, xa, alpha=0.8, interpret=True)
        want = adc_scan_ref(lut, codes, iv, xa, alpha=0.8)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-5
        )
        qa = jnp.asarray(rng.integers(0, 5, size=(b, l)), jnp.int32)
        deg = jnp.stack([qa, qa], -1)
        np.testing.assert_array_equal(
            np.asarray(adc_scan_scores(lut, layout_codes(codes), deg, xa, alpha=0.8,
                                       interpret=True)),
            np.asarray(adc_scan_scores(lut, layout_codes(codes), qa, xa, alpha=0.8,
                                       interpret=True)),
        )

    def test_consistent_with_exact_on_decoded_vectors(self):
        """ADC fused scores == exact fused scores of the reconstruction."""
        rng = np.random.default_rng(4)
        x = rng.normal(size=(400, 32)).astype(np.float32)
        cb = pq_train(x, n_subspaces=8, n_iters=8, n_samples=400, seed=0)
        codes = pq_encode(x, cb)
        dec = pq_decode(codes, cb)
        q = rng.normal(size=(6, 32)).astype(np.float32)
        qa = jnp.asarray(rng.integers(0, 3, size=(6, 5)), jnp.int32)
        xa = jnp.asarray(rng.integers(0, 3, size=(400, 5)), jnp.int32)
        lut = adc_lut(q, cb)
        got = adc_scan_scores(lut, layout_codes(codes), qa, xa, alpha=0.9, interpret=True)
        want = auto_mod.brute_fused_sqdist(
            jnp.asarray(q), qa, dec, xa, MetricConfig(mode="auto", alpha=0.9)
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-3, atol=1e-2
        )


def _packed_case(seed, b, n, s, l, lab=4):
    """Random (lut16, codes8, packed, qa, xa) tuple for the 4-bit tests."""
    rng = np.random.default_rng(seed)
    lut = jnp.asarray(rng.uniform(0, 4, size=(b, s, 16)), jnp.float32)
    codes = jnp.asarray(rng.integers(0, 16, size=(n, s)), jnp.int32)
    packed = pack_nibbles(codes)
    qa = jnp.asarray(rng.integers(0, lab, size=(b, l)), jnp.int32)
    xa = jnp.asarray(rng.integers(0, lab, size=(n, l)), jnp.int32)
    return lut, codes, packed, qa, xa


class TestADCScan4Kernel:
    @pytest.mark.parametrize("b,n,s,l", [
        (4, 300, 8, 5),          # even S, ragged N
        (3, 200, 7, 4),          # odd S → pad nibble in the last byte
        (8, 256, 32, 7),         # exact blocks, wide S
        (1, 1, 2, 1),            # degenerate
    ])
    def test_matches_ref_and_unpacked_kernel_bit_exact(self, b, n, s, l):
        lut, codes, packed, qa, xa = _packed_case(b * n + s, b, n, s, l)
        assert packed.dtype == jnp.uint8
        assert packed.shape == (n, (s + 1) // 2)
        got = adc_scan4_scores(lut, layout_codes(packed), qa, xa, alpha=0.8, interpret=True)
        want = adc_scan4_ref(lut, packed, qa, xa, alpha=0.8)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-5
        )
        # in-register nibble unpack must be BIT-EXACT vs the 8-bit kernel
        # run on the pre-unpacked codes (same one-hot → same dot_general)
        via8 = adc_scan_scores(
            lut, layout_codes(unpack_nibbles(packed, s)), qa, xa, alpha=0.8, interpret=True
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(via8))

    def test_l2_mode_and_mask(self):
        lut, _, packed, qa, xa = _packed_case(11, 5, 120, 8, 4, lab=3)
        rng = np.random.default_rng(12)
        mask = jnp.asarray(rng.integers(0, 2, size=(5, 4)), jnp.int32)
        for mode, m in (("l2", None), ("auto", mask)):
            got = adc_scan4_scores(
                lut, layout_codes(packed), qa, xa, alpha=1.3, mode=mode, mask=m,
                interpret=True,
            )
            want = adc_scan4_ref(
                lut, packed, qa, xa, alpha=1.3, mode=mode, mask=m
            )
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-5
            )

    def test_degenerate_intervals_bit_exact_to_points(self):
        lut, _, packed, qa, xa = _packed_case(21, 5, 150, 8, 4, lab=5)
        deg = jnp.stack([qa, qa], -1)
        np.testing.assert_array_equal(
            np.asarray(adc_scan4_scores(lut, layout_codes(packed), deg, xa, alpha=0.8,
                                        interpret=True)),
            np.asarray(adc_scan4_scores(lut, layout_codes(packed), qa, xa, alpha=0.8,
                                        interpret=True)),
        )

    def test_interval_targets_match_ref(self):
        lut, _, packed, qa, xa = _packed_case(31, 4, 200, 7, 3, lab=5)
        iv = jnp.stack([qa, qa + 2], -1)
        got = adc_scan4_scores(lut, layout_codes(packed), iv, xa, alpha=0.8, interpret=True)
        want = adc_scan4_ref(lut, packed, iv, xa, alpha=0.8)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-5
        )

    def test_rejects_non_16_centroid_lut(self):
        lut = jnp.zeros((2, 8, 256), jnp.float32)
        packed = jnp.zeros((10, 4), jnp.uint8)
        qa = xa = jnp.zeros((2, 1), jnp.int32)
        with pytest.raises(ValueError):
            adc_scan4_scores(lut, layout_codes(packed), qa,
                             jnp.zeros((10, 1), jnp.int32), interpret=True)

    @pytest.mark.parametrize("n,s", [
        (300, 8),                # even S, ragged N
        (300, 7),                # odd S → pad nibble in the last byte
        (9000, 32),              # past one row tile: N pads to 2 × 8192
        (1, 2),                  # degenerate
    ])
    def test_layout_round_trips_to_unpack(self, n, s):
        _, _, packed, _, _ = _packed_case(n + s, 1, n, s, 1)
        words = np.asarray(layout_codes(packed))
        assert words.dtype == np.int32
        assert words.shape == (-(-s // 8), padded_rows(n))
        nib = np.stack([(words[j // 8] >> (4 * (j % 8))) & 0xF
                        for j in range(8 * words.shape[0])])
        np.testing.assert_array_equal(
            nib[:s, :n].T, np.asarray(unpack_nibbles(packed, s))
        )
        assert not nib[s:].any() and not nib[:, n:].any()  # zero padding

    @pytest.mark.parametrize("targets", ["l2", "points", "intervals"])
    @pytest.mark.parametrize("b", [1, 8, 32, 128])  # the serving buckets
    def test_laid_out_codes_match_ref(self, b, targets):
        """The served operand: codes laid out once, the whole bucket in
        one block, ragged N, against the gather-sum reference."""
        lut, _, packed, qa, xa = _packed_case(b + 1, b, 1000, 32, 5, lab=3)
        mode = "l2" if targets == "l2" else "auto"
        if targets == "intervals":
            qa = jnp.stack([qa, qa + 1], -1)
        got = adc_scan4_scores(lut, layout_codes(packed), qa, xa, alpha=0.8,
                               mode=mode, interpret=True)
        want = adc_scan4_ref(lut, packed, qa, xa, alpha=0.8, mode=mode)
        assert got.shape == (b, 1000)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-5
        )

    def test_rejects_codes_that_do_not_fit(self):
        lut, codes, packed, qa, xa = _packed_case(41, 2, 300, 8, 2)
        with pytest.raises(ValueError):  # laid out for fewer rows than xa
            adc_scan4_scores(lut, layout_codes(packed[:100]), qa, xa,
                             interpret=True)
        with pytest.raises(ValueError):  # laid out for another S
            adc_scan4_scores(lut, layout_codes(codes), qa, xa,
                             interpret=True)
        with pytest.raises(ValueError):  # packed codes, not laid out
            adc_scan4_scores(lut, packed, qa, xa, interpret=True)
        with pytest.raises(ValueError):  # 8-bit codes, not laid out
            adc_scan_scores(lut, codes, qa, xa, interpret=True)

    @pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
    def test_lut_split_is_exact(self, jit):
        """hi + mid + lo carries every bit of the f32 LUT, so the one bf16
        MXU pass rounds no LUT value — also compiled, as the kernel's
        wrapper runs it, where a bf16 round trip could be folded away."""
        rng = np.random.default_rng(2)
        lut = (rng.uniform(-4, 4, size=(16, 512))
               * 10.0 ** rng.integers(-6, 7, size=(16, 512))
               ).astype(np.float32)
        split = jax.jit(_split3) if jit else _split3
        parts = np.asarray(
            split(jnp.asarray(lut)).astype(jnp.float32), np.float64
        ).reshape(3, 16, 512)
        np.testing.assert_array_equal(parts.sum(0), lut.astype(np.float64))
        assert np.abs(parts[1]).max() > 0 and np.abs(parts[2]).max() > 0
