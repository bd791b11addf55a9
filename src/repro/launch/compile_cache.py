"""JAX's persistent compilation cache, turned on by the entry points.

Nothing turns it on at import: ``chip_smoke.py``, ``launch/analytics.py``,
``launch/serve.py`` and ``benchmarks/run.py`` call
:func:`enable_compile_cache` first thing. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads the cache
directory from it and this sets no other path. Otherwise the cache goes
to ``.jax_cache/`` at the root of the checkout: a fixed path (listed in
``.gitignore``), so a later run of the same checkout finds what an
earlier one compiled.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent cache for every compiled program, however
    quick its compile, and return the directory it lives in."""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir
