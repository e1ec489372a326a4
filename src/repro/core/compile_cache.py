"""JAX's persistent compilation cache, at one fixed place per checkout.

Call :func:`enable` at the start of a process, before anything compiles
(JAX decides whether a process uses the cache at its first compile).  The
cache key includes the directory, so the directory never moves:

  * ``$JAX_COMPILATION_CACHE_DIR`` when it is set — JAX reads the variable
    itself and no code here sets another directory;
  * otherwise ``<checkout>/.jax_cache/`` (git-ignored).
"""

from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def cache_dir() -> Path:
    """The directory :func:`enable` makes JAX use."""
    env = os.environ.get(ENV)
    return Path(env) if env else REPO_CACHE_DIR


def enable() -> Path:
    """Turn the persistent compilation cache on; returns its directory.

    Every compiled program is cached, however quick its compile: a search
    path is a handful of programs, each compiled once per process.
    """
    import jax

    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir()
