"""Persistent XLA compilation cache for the launchers and ``chip_smoke.py``.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it as its
cache directory and nothing here overrides it. Otherwise the cache lives at
``<checkout>/.jax_cache`` — a fixed path (the path is part of the cache
key, so a moving directory never hits), listed in ``.gitignore``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
