"""JAX's persistent compilation cache at a fixed path.

The cache key includes the directory, so the path never depends on a
temporary name, a process id or the time: a later run of the same
checkout finds what an earlier one compiled. Entry points call
:func:`enable_compile_cache` before they compile; tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
ENV = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``."""
    return os.environ.get(ENV) or str(CHECKOUT / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the cache on and return its directory. When the environment
    names one, JAX already reads it and nothing is set here."""
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    return compile_cache_dir()
