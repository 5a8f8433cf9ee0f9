"""Persistent XLA compile cache placed from outside the program.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
there and nothing here changes it. Otherwise the cache goes to the fixed
``<repo root>/.jax_cache`` (git ignores it): a fixed path, never one
named after a temp directory, pid or time, so a later run finds what an
earlier one wrote. Call :func:`enable` before the first compile.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable() -> str:
    """Point JAX's persistent compile cache at its directory; returns
    the directory in use."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return os.environ["JAX_COMPILATION_CACHE_DIR"]
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
