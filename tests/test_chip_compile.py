"""Compile the served path's kernels and steps for a TPU v5e, without one.

The TPU compiler is installed with JAX, and it compiles for a chip that is
described rather than attached (``jax.experimental.topologies``). Interpret
mode runs a Pallas kernel on the CPU but cannot show what Mosaic refuses
(lane reshapes, unaligned tiles, too much VMEM), and a jitted step can fit
the CPU yet not the chip's 16 GB. These tests compile at serving widths:
B=128 queries, N=16384 rows (one brute-scan chunk), M=128 (sift); the
brute scan also at the other profiles' widths, 100 and 300 among them,
which are not multiples of the 128-lane tile.

The topology is described inside a module fixture, never at import, so
only the workers that run these tests load the TPU library, and they load
it with ``ALLOW_MULTIPLE_LIBTPU_LOAD`` set so that other processes on the
machine that hold it do not block them. The persistent compilation cache is off while these tests run:
an entry written for a described chip cannot be read back without one.
"""
import importlib.util
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

B, N, M, L = 128, 16384, 128, 5
HBM_BYTES = 16 * 2**30  # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no TPU compiler (libtpu) in this installation")
    # libtpu takes a machine-wide lock file unless told that several
    # processes may load it; a second test run or compile on the same
    # machine must not make these tests fail or skip
    saved = {k: os.environ.get(k)
             for k in ("TPU_LOG_DIR", "ALLOW_MULTIPLE_LIBTPU_LOAD")}
    os.environ["TPU_LOG_DIR"] = "disabled"  # no compiler logs outside
    os.environ["ALLOW_MULTIPLE_LIBTPU_LOAD"] = "1"
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    )
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits_one_chip(compiled) -> None:
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, used


@pytest.mark.parametrize("packed", [False, True], ids=["8bit", "4bit"])
def test_adc_scan_compiles_to_a_mosaic_kernel(one_chip, packed):
    from repro.kernels.adc_scan.adc_scan import (
        adc_scan4_scores, adc_scan_scores, padded_rows,
    )

    s = 32
    if packed:  # K=16 LUTs, two codes per byte, eight per laid-out word
        fn, lut, words = adc_scan4_scores, (B, s, 16), s // 8
    else:
        fn, lut, words = adc_scan_scores, (B, s, 256), s // 4
    codes = (words, padded_rows(N), jnp.int32)
    scan = jax.jit(lambda lut, codes, qa, xa: fn(
        lut, codes, qa, xa, alpha=1.0, mode="auto", interpret=False))
    compiled = scan.lower(
        _spec(one_chip, lut, jnp.float32),
        _spec(one_chip, codes[:2], codes[2]),
        _spec(one_chip, (B, L), jnp.int32),
        _spec(one_chip, (N, L), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits_one_chip(compiled)


@pytest.mark.parametrize("b", [8, 32, 128])  # the server's buckets
def test_served_4bit_scan_is_one_kernel_with_no_row_sized_layout_op(
        one_chip, b):
    """The brute path's scan at SIFT1M's N and S: one Mosaic kernel, and
    no op of N elements or more (a convert, pad or copy of the codes or
    the attributes) left in the program around it. The one exception is
    the scores' own relayout at B = 128, where the chip's default layout
    of a (128, N) f32 array puts the batch on the lanes. The LUT split
    masks bit patterns: the chip keeps a bf16 value made inside a fusion
    at f32, so a round trip through bf16 would cut nothing."""
    from repro.kernels.adc_scan.adc_scan import adc_scan4_scores, padded_rows

    n, s = 1_000_000, 32
    scan = jax.jit(lambda lut, codes, qa, xa: adc_scan4_scores(
        lut, codes, qa, xa, mode="l2", interpret=False))
    compiled = scan.lower(
        _spec(one_chip, (b, s, 16), jnp.float32),
        _spec(one_chip, (s // 8, padded_rows(n)), jnp.int32),
        _spec(one_chip, (b, L), jnp.int32),
        _spec(one_chip, (n, L), jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert text.count(" and(") >= 2  # hi and mid of the LUT, masked
    big = []
    for m in re.finditer(r"= \w+\[([\d,]*)\][^ ]* (\S+)\(", text):
        dims, op = m.group(1), m.group(2)
        size = math.prod(int(d) for d in dims.split(",") if d)
        relayout = op == "copy" and dims == f"{b},{n}"
        if size >= n and op not in ("parameter", "custom-call") \
                and not relayout:
            big.append(m.group(0))
    assert not big, big
    _fits_one_chip(compiled)


@pytest.mark.parametrize("m", [96, 100, 128, 300])  # the profiles' widths
def test_brute_scan_compiles_at_highest_precision(one_chip, m):
    from repro.core.auto import MetricConfig, brute_fused_sqdist

    scan = jax.jit(lambda qv, qa, xv, xa: brute_fused_sqdist(
        qv, qa, xv, xa, MetricConfig(mode="l2")))
    lowered = scan.lower(
        _spec(one_chip, (B, m), jnp.float32),
        _spec(one_chip, (B, L), jnp.int32),
        _spec(one_chip, (N, m), jnp.float32),
        _spec(one_chip, (N, L), jnp.int32),
    )
    # the exact oracle's q·x must not run as one bf16 pass on the MXU
    assert "HIGHEST" in lowered.as_text()
    _fits_one_chip(lowered.compile())


def test_graph_search_step_compiles(one_chip):
    from repro.core import routing
    from repro.core.auto import MetricConfig

    gamma, pool = 24, 64
    cfg = routing.RoutingConfig(k=10, pool_size=pool, pioneer_size=8)
    compiled = routing._search_jit.lower(
        _spec(one_chip, (N, M), jnp.float32),
        _spec(one_chip, (N, L), jnp.int32),
        _spec(one_chip, (N, gamma), jnp.int32),
        _spec(one_chip, (B, M), jnp.float32),
        _spec(one_chip, (B, L), jnp.int32),
        _spec(one_chip, (B, pool), jnp.int32),
        MetricConfig(mode="auto", alpha=1.0), cfg, N,
    ).compile()
    _fits_one_chip(compiled)
