"""Persistent XLA compilation cache for the entry points.

Entry points (``chip_smoke.py``, ``repro.launch.serve``, the
``benchmarks/`` mains) call :func:`enable_compile_cache` once before
they compile anything; importing the package never does. A run then
reuses every program an earlier run on the same checkout compiled.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
helper changes nothing. Otherwise the cache lives at the fixed path
``<checkout>/.jax_cache`` (git-ignored): the directory is part of each
entry's key, so a path that moved between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
