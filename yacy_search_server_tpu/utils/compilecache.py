"""Placement of JAX's persistent compilation cache.

The serving store prewarms every kernel shape at start, so a cold
process is mostly compiling; a cache that survives the process makes
the second start cheap. The cache directory is part of the cache key,
so it must not move between runs:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this
  module sets nothing — whoever runs the node places the cache.
- unset: ``<checkout>/.jax_cache``, derived from the package location
  (never a temp dir, a pid or a clock), git-ignored.

Called lazily where a process first touches JAX on the serving path
(``Switchboard``, ``parallel/distributed.bootstrap_from_env``,
``chip_smoke.py``) — never at package import: ``utils/lint``,
``ingest`` and the crash-chaos children are jax-free by contract.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def ensure() -> str:
    """Make sure this process compiles against a persistent cache and
    return its directory. Idempotent."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


def entry_count(path: str) -> int:
    """Number of cache entries under `path` (0 when it does not exist)."""
    try:
        return sum(1 for n in os.listdir(path) if n.endswith("-cache"))
    except OSError:
        return 0
