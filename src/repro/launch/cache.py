"""JAX's persistent compilation cache, placed from outside.

Call ``enable_compile_cache()`` from an entry point (a script's ``main``),
never at import: importing ``repro`` leaves JAX's cache settings alone.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# A fixed path, so that a later process of the same checkout finds what an
# earlier one compiled: the cache directory is part of what it is keyed by.
REPO_CACHE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory: the one
    ``JAX_COMPILATION_CACHE_DIR`` names when it is set, else ``.jax_cache/``
    at the root of the checkout."""
    path = os.environ.get(ENV_VAR) or REPO_CACHE_DIR
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
