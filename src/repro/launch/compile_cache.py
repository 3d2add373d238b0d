"""Where the entry points keep JAX's persistent compilation cache."""
from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Call before the first compile.  A ``JAX_COMPILATION_CACHE_DIR`` in
    the environment is left for JAX to read, and nothing is set in code.
    Otherwise the cache is ``<checkout>/.jax_cache``: a fixed path,
    because a later process only finds entries in the same directory.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
