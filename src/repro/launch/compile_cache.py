"""JAX's persistent compilation cache, placed by the entry points.

The library never turns the cache on by itself: ``chip_smoke.py``,
``repro.launch.serve`` and ``benchmarks/run.py`` call
``enable_compile_cache`` once, at start-up.  The directory is part of
the cache's key, so it is never built from a temp name, a pid or the
time: a directory that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache (listed in .gitignore)
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory (JAX reads
    the variable itself, and no other is set here); otherwise the fixed
    ``REPO_CACHE_DIR`` inside the checkout.
    """
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
