"""Where the entry points keep JAX's persistent compilation cache.

The cache directory is part of each entry's key, so it must not move
between runs: it is ``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX
reads the variable itself), else a fixed directory the entry point names
inside its checkout.
"""
from __future__ import annotations

import os

import jax


def enable(default_dir) -> str:
    """Turn the persistent compilation cache on before the first compile and
    return its directory. Every program is cached, however quickly it
    compiled, so a second run of the same shapes compiles nothing."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(default_dir)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
