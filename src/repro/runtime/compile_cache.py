"""JAX's persistent compilation cache, placed from outside or at a fixed
path in the checkout.

Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks.run``) call :func:`enable_compile_cache` once, before their
first compile; library code and tests never do. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this sets
nothing. Otherwise the cache lives at ``<checkout>/.jax_cache``: a fixed
path, because the path is part of what a later run must find again.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Optional[Path]:
    """Point JAX's persistent compilation cache at ``<checkout>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` already places it. Returns the
    directory this call set, or None when it set nothing."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return CHECKOUT_CACHE_DIR
