"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`enable` before their first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
is set here.  Otherwise the cache goes to ``<checkout>/.jax_cache``: a
fixed path, because the directory is part of what a later run must find
again, so a path made from a temp name, a pid or the time never hits.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = str(pathlib.Path(__file__).resolve().parents[3] / ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
