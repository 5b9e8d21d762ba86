"""JAX persistent compilation cache for the repo's entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it at start-up and
this module sets no other directory.  Otherwise the cache lives at the
fixed path ``<root>/.jax_cache``: the path is part of the cache key, so a
directory that moved between runs would never hit.
"""
from __future__ import annotations

import os

import jax


def enable_compile_cache(root: str) -> str:
    """Turn on the persistent compilation cache before the first compile
    and return the directory in use.  ``root`` is the checkout root."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(os.path.abspath(root), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
