"""JAX's persistent compilation cache, in one place for every entry point.

``run_sim``, ``bench.py`` and ``chip_smoke.py`` call :func:`use_compile_cache`
before their first compile. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
already reads it and nothing is set here. Otherwise the cache lives at the
fixed ``<repo>/.jax_cache`` (git-ignored): the directory is part of the
cache key, so a temporary or per-process path would never hit.
"""

from __future__ import annotations

import os

__all__ = ["CACHE_DIR", "use_compile_cache"]

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def use_compile_cache() -> str:
    """Point JAX's persistent cache at its directory; returns that path."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
