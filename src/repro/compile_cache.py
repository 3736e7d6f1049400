"""JAX's persistent compilation cache, kept at one fixed path.

Entry points (``chip_smoke.py``, ``repro.launch.simulate``,
``repro.launch.serve``, ``benchmarks.run``) call :func:`enable` before their
first compile, so every process of a checkout shares compiled programs.
The cache directory is part of the cache key, so it never moves: it is
``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the variable
itself), and ``<checkout>/.jax_cache`` otherwise.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    # Metadata in the key: a program whose op names (its named scopes)
    # differ compiles anew, so a profile of it shows its own names and not
    # those of another program that compiled to the same code.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
