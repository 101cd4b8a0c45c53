"""Where JAX's persistent compile cache lives.

``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself at import,
so nothing is set here. Otherwise the cache goes to one fixed directory
inside the checkout (git-ignored). The path is part of the cache key, so it
never depends on a temporary name, a process id or the time.
"""
from __future__ import annotations

import os
import pathlib
from typing import Mapping, Tuple

import jax

__all__ = ["ENV_VAR", "REPO_CACHE_DIR", "compile_cache_dir",
           "enable_compile_cache"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache: this file sits at <checkout>/src/repro/launch/.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir(environ: Mapping[str, str] = os.environ
                      ) -> Tuple[str, bool]:
    """The cache directory and whether the environment chose it."""
    if environ.get(ENV_VAR):
        return environ[ENV_VAR], True
    return str(REPO_CACHE_DIR), False


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on for this process; returns its
    directory. Entry points call this; library code and tests do not."""
    path, from_env = compile_cache_dir()
    if not from_env:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
