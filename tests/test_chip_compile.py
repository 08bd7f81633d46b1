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
import os

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
        adc_scan4_scores, adc_scan_scores,
    )

    s = 32
    if packed:  # K=16 LUTs, two codes per byte: 16 packed bytes per row
        fn, lut, codes = adc_scan4_scores, (B, s, 16), (N, s // 2, jnp.uint8)
    else:
        fn, lut, codes = adc_scan_scores, (B, s, 256), (N, s, jnp.int32)
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
