"""Persistent compilation cache location for the repo's entry points.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; when it is set it wins
and nothing is configured here. Otherwise the cache goes to a fixed
directory inside the checkout, ``<repo>/.jax_cache`` (git-ignored): the
path is part of the cache key, so a directory that moved between runs
(a temp dir, a pid or a timestamp in the name) would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.
    Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
