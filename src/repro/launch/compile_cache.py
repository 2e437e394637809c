"""Where JAX keeps its persistent compilation cache for the launchers.

Call :func:`enable_compile_cache` from a program's ``main``, before the first
compile, never at import.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str | None:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is changed.  Otherwise the cache is ``<checkout>/.jax_cache``: a
    fixed path, since a later run only finds what was cached at the same
    place.  The CPU backend gets no default cache (returns None): CPU runs
    are tests, and XLA:CPU warns when it reloads its own cached programs.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() == "cpu":
        return None
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
