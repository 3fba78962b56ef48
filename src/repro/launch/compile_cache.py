"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the examples) call
``enable_compile_cache()`` once, before their first compile.  Nothing calls
it at import time, and the tests never call it in their own process.
"""
from __future__ import annotations

import os

import jax


def checkout_cache_dir() -> str:
    """``<checkout>/.jax_cache``: fixed, because the directory is part of
    what lets a later run find an entry."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    return os.path.join(root, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets nothing; otherwise the cache goes to ``checkout_cache_dir()``.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = checkout_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
