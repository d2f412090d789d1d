"""Where JAX's persistent compilation cache lives for this repo's programs.

Entry points (the serve and train launchers, the benchmark runner and
``chip_smoke.py``) call :func:`enable_compile_cache` once, before their
first compile. Library code and tests never do.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: Fixed default: a cache under a moving (temp, pid- or time-named) path
#: would never be hit again.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting: it is
    used as it is and nothing else is set. Otherwise the cache goes to
    ``<repo>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
