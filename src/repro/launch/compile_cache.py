"""Where JAX's persistent compilation cache lives.

The cache key includes the cache's path, so a directory that moves
between runs never hits.  The path is therefore either the one the
deployment names in ``JAX_COMPILATION_CACHE_DIR`` (JAX reads that
variable itself; this module then sets nothing) or a fixed
``<checkout>/.jax_cache`` resolved from this file's location.

Entry points call ``use_compile_cache()`` before their first compile;
importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the checkout root
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> Path:
    """Turn the persistent cache on and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``."""
    env = os.environ.get(ENV_VAR)
    if env:
        return Path(env)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return DEFAULT_DIR
