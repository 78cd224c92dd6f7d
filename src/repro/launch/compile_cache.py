"""JAX's persistent compilation cache, placed from outside the program.

Every entry point (``chip_smoke.py``, ``launch/serve.py``,
``launch/train.py``, ``benchmarks/``) calls :func:`enable_compile_cache`
first.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its
own and nothing is set in code.  Otherwise the cache lives at one fixed
path inside the checkout, ``<checkout>/.jax_cache`` (git ignores it): the
directory is part of a cached entry's key, so a path derived from a temp
name, a pid or the time would never hit.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the checkout root: src/repro/launch/ is three levels below it
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
