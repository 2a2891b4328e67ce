"""Where JAX keeps its persistent compilation cache.

One rule for every entry point (``chip_smoke.py``, the examples, the
benchmarks and the replica subprocess ``python -m
repro.core.checkpoint``): a ``JAX_COMPILATION_CACHE_DIR`` set in the
environment wins and JAX reads it on its own; otherwise the cache lives
at the fixed path ``<checkout>/.jax_cache/``.  The path is part of
what makes a cache entry findable again, so it never depends on a temp
name, a pid or the time.
"""

from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory.  Sets nothing when ``JAX_COMPILATION_CACHE_DIR``
    is already set (JAX picks the variable up itself)."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
