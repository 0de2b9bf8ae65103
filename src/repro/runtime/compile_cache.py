"""JAX's persistent compilation cache for the repo's entry points.

A process that compiles the serving chunk step or the training step pays
seconds of XLA compile time per program; JAX's persistent cache keeps the
compiled executables on disk so the next process with the same programs
loads them instead. Entry points (``chip_smoke.py``, ``benchmarks.run``)
call :func:`use_persistent_cache` in ``main`` — never at import — before
their first compile.
"""
from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIRNAME = ".jax_cache"


def use_persistent_cache(root: str) -> str:
    """Turn on the persistent compile cache and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed here. Otherwise the cache goes to the fixed
    ``<root>/.jax_cache`` (git-ignored): the directory is part of the
    cache's key, so a path built from a temp name, a PID or the time
    would never hit.
    """
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    import jax
    path = os.path.join(os.path.abspath(root), CACHE_DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
