"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``repro.launch.serve.main``,
``benchmarks.run.main``) call :func:`configure_compile_cache` once before
their first compile; importing this module changes nothing. A set
``JAX_COMPILATION_CACHE_DIR`` wins (JAX reads it itself). Otherwise the
cache goes to ``<checkout>/.jax_cache``: a fixed path, because the path
is part of each entry's key and a directory that moves never hits.
"""

from __future__ import annotations

import os

import jax

__all__ = ["configure_compile_cache", "CHECKOUT_CACHE_DIR"]

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def configure_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
