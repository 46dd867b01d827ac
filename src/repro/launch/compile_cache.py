"""JAX's persistent compilation cache for this repo's entry points.

Called first thing in each entry point's ``main`` (never at import, so
tests and library users keep JAX's own defaults). A compile of the
full-width decode round takes tens of seconds on the TPU; with the cache
a second run in the same place reuses it.
"""
from __future__ import annotations

import os

import jax

#: The checkout's root: the cache path is part of JAX's cache key, so it
#: must not move between runs.
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``
    (listed in .gitignore)."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
