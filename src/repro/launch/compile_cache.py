"""JAX's persistent compilation cache for the repo's entry points.

Scripts a user runs (``chip_smoke.py``, ``launch/serve.py``,
``benchmarks/run.py``, the examples) call :func:`enable_compile_cache`
before their first compile, so a second run finds its programs compiled.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and nothing is
set here.  Otherwise the cache lives at the fixed path
``<checkout>/.jax_cache``: the path is part of the cache's key, so a
directory that moved between runs would never hit.  Tests do not call it.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
