"""Persistent JAX compilation cache for the entry points.

Each entry point's ``main`` calls :func:`enable_compile_cache` — never an
import — so library users and tests keep JAX's own defaults.  The cache
lives where ``JAX_COMPILATION_CACHE_DIR`` says when it is set, and
otherwise at ``<checkout>/.jax_cache`` (git-ignored).  The path is part
of every cache key, so it is fixed: never a temporary name, a pid or a
time.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    import jax

    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    # keep every program: a cold call on the chip pays for each compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
