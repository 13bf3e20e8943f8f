"""Persistent XLA compile cache for the entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and
nothing here overrides it. Otherwise the cache lives at one fixed path
inside the checkout, ``<repo>/.jax_cache``: the path is part of the cache
key, so it must not move between runs. Tests never call this.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
