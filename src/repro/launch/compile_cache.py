"""JAX's persistent compilation cache, at one fixed place.

A compile of the whole-model step at published widths takes tens of seconds
to minutes; the cache lets a second run of the same program skip it.  The
cache key includes the directory, so the directory must not move between
runs: no temp names, pids or times.
"""

from __future__ import annotations

import os

__all__ = ["enable_compile_cache"]

_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn the cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is what JAX already uses and
    nothing is changed; otherwise the cache goes to ``<repo>/.jax_cache``.
    Call it from a program's entry point, never at import.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    os.makedirs(_DEFAULT_DIR, exist_ok=True)  # JAX writes into, never creates, it
    jax.config.update("jax_compilation_cache_dir", _DEFAULT_DIR)
    return _DEFAULT_DIR
