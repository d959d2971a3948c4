"""Persistent XLA compilation cache for this repository's entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/*``)
call :func:`enable_compile_cache` once, before their first compile.
Library modules never call it: importing ``repro`` changes no JAX config.

Where the cache lives:

* ``JAX_COMPILATION_CACHE_DIR`` set -- JAX reads that directory itself,
  and this helper sets no other;
* otherwise ``<checkout>/.jax_cache``, a fixed path inside the checkout
  (git-ignored).  The path is part of nothing that varies between runs,
  so a later run in the same checkout finds what an earlier one cached.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Every program is cached, however fast it compiled: this repository
    runs many small programs (kernels, bucketed samplers, round engines),
    and JAX's default threshold of one second of compile time would
    skip most of them."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
