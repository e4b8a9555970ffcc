"""JAX's persistent compilation cache, placed from outside.

Entry points (``chip_smoke.py``, the ``repro.experiments`` CLI,
``repro.launch.train``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` under their ``__main__`` check. Nothing
calls it on import, so library users and the test suite keep JAX's
default (no persistent cache).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed, so a later run from the same checkout finds what this one wrote
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    no other directory is set here. Otherwise the cache lives in
    ``.jax_cache/`` at the checkout root (ignored by git). Every
    compile is cached, however short.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(CHECKOUT_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
