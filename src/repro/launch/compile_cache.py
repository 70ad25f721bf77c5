"""Where JAX's persistent compilation cache lives.

The cache key includes the directory, so a directory that moves between runs
never hits: the cache sits at one fixed path inside the checkout unless
``JAX_COMPILATION_CACHE_DIR`` (JAX's own setting, read when JAX is imported)
already names one. Only entry points call :func:`use_compile_cache`;
importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>/.jax_cache`` (this file is ``src/repro/launch/compile_cache.py``)
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already caches there and
    nothing is changed. Call before the first ``jit`` compiles.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
