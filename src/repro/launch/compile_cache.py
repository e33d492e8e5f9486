"""Persistent XLA compilation cache, placed from outside or in the checkout.

Every entry point calls :func:`setup_compile_cache` before its first
compile. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and nothing is set here. Otherwise the cache lives at one fixed path inside
the checkout (``.jax_cache/``, git-ignored): the path is part of what a
later run must find again, so it is never temporary or named per process.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory used."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
