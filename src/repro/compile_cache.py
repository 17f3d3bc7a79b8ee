"""Persistent XLA compilation cache for the entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX keeps its cache there and
nothing here overrides it.  Otherwise the cache lives at a fixed path inside
the checkout (``<repo>/.jax_cache``, listed in ``.gitignore``): the path is
part of what a later run looks up, so it is never built from a temporary
name, a process id or the time.
"""

from __future__ import annotations

import os

__all__ = ["enable_compile_cache", "CACHE_DIR"]

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
