"""JAX persistent compilation cache placement for the launchers.

Called once at start-up by ``repro.launch.train``, ``repro.launch.serve``
and ``chip_smoke.py`` — never at import and never from tests, whose
compiles must not land in (or be served from) a shared cache.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
other directory is set in code.  Otherwise the cache lives at one fixed
directory inside the checkout (``<repo>/.jax_cache``, git-ignored): the
directory is part of the cache key, so a path that moved would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
