"""JAX's persistent compilation cache, kept at one fixed place.

Every entry point (``launch.serve``, ``launch.train``, ``benchmarks.run``,
``chip_smoke.py``) calls ``enable_compile_cache`` before it compiles, so
processes that share a checkout reuse each other's compiled programs.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[2]
ENV_VAR = 'JAX_COMPILATION_CACHE_DIR'


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets nothing.  Otherwise the cache lives at ``<checkout>/.jax_cache``
    (git-ignored): a fixed path, never one built from a temp name, a pid or
    the time, because a later process finds an entry only where an earlier
    one left it.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    path = str(CHECKOUT / '.jax_cache')
    jax.config.update('jax_compilation_cache_dir', path)
    return path
