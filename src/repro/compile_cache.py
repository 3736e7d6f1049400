"""JAX's persistent compilation cache, kept at one fixed path.

Entry points (``chip_smoke.py``, ``repro.launch.simulate``,
``repro.launch.serve``, ``benchmarks.run``) call :func:`enable` before their
first compile, so every process of a checkout shares compiled programs.
The cache directory is part of the cache key, so it never moves: it is
``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the variable
itself and nothing is changed), and ``<checkout>/.jax_cache`` otherwise.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
