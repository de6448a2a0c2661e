"""THE compile-cache rule, in one place.

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; this repo sets no
  cache directory in code (so whoever placed the variable owns the path).
- unset: the cache goes to :data:`CACHE_DIR`, one fixed git-ignored
  directory at the root of the checkout.  The path is part of the cache's
  key, so nothing in it is derived from the host, a pid, a temp name or
  the time — a directory that moves never hits.

- either way, MLIR locations carry ONE frame of the Python traceback, not
  ten (``jax_traceback_in_locations_limit``).  A Pallas kernel is
  serialized WITH its locations into the custom call that the cache key
  hashes, and a jitted entry point is traced once a process, by whichever
  program needs it first: with ten frames in, that caller's stack became
  part of every later program's key, and about a third of the engine's
  programs missed a warm cache in some runs and not in others (PERF.md
  section 6, PR 25).  One frame is the kernel's own line, the same from
  every caller.  (Switching tracebacks off altogether,
  ``jax_include_full_tracebacks_in_locations``, would do too, but JAX
  0.9.0 then drops the name stack from every operation's ``op_name``: the
  device trace loses its scopes.)

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
    import jax

    jax.config.update("jax_traceback_in_locations_limit", 1)
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
