"""Where JAX keeps its persistent compilation cache.

Every entry point (``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.build``, ``benchmarks.run``) calls ``enable_compile_cache``
before its first compile, so a second run of the same program on the same
machine loads its executables instead of compiling them again.

``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and this
module sets nothing. Otherwise the cache lives at ``<checkout>/.jax_cache``.
The path is fixed (never a temp dir, a pid or a timestamp) because it is
part of what a later run must find again.
"""
from __future__ import annotations

import os

import jax

#: <checkout>/.jax_cache — this file sits at <checkout>/src/repro/launch/
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
