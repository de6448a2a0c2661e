"""THE compile-cache rule, in one place.

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; this repo sets no
  cache directory in code (so whoever placed the variable owns the path).
- unset: the cache goes to :data:`CACHE_DIR`, one fixed git-ignored
  directory at the root of the checkout.  The path is part of the cache's
  key, so nothing in it is derived from the host, a pid, a temp name or
  the time — a directory that moves never hits.

Failures are not swallowed: a cache that cannot be enabled is a broken
installation, not a best-effort nicety.
"""

from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Apply the rule; return the directory JAX's persistent cache uses."""
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
