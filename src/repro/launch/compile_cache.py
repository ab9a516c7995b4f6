"""Where JAX keeps its persistent compilation cache for this checkout.

A cold run of the served path compiles one pipeline per padding bucket and
per repack rung, minutes in all on a TPU.  JAX can keep those executables
on disk, but its cache key includes the directory, so the directory must
not move between runs: ``use_compile_cache`` places it at a fixed path
inside the checkout unless the caller's environment already places it.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``benchmarks/serve_bench.py``) call it once, before their first compile.
Tests do not: they must not depend on, or write to, a cache.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache: src/repro/launch/ -> three levels up is <checkout>
IN_TREE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``$JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting: it is
    left alone and no other directory is configured.  Otherwise the cache
    goes to the fixed in-tree ``.jax_cache`` (listed in ``.gitignore``).
    """
    env = os.environ.get(ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", IN_TREE)
    return IN_TREE
