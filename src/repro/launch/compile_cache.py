"""JAX's persistent compilation cache, at one fixed place.

Every entry point that compiles for the chip (`chip_smoke.py`, the
train and serve launchers) calls `enable_compile_cache()` before its
first compile. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing else is set here. Otherwise the cache lives at
``<repo root>/.jax_cache`` (git-ignored): a fixed path, never one made
from a temp dir, a pid or the time, because the directory is part of
what a later run must find again. Tests never call this.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the repository root
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """Where the persistent cache lives: the environment's choice if it
    made one, else the fixed in-repo directory."""
    return os.environ.get(ENV_VAR) or str(REPO_CACHE_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at `compile_cache_dir()`
    and return that directory."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
