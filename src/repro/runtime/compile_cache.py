"""Persistent XLA compilation cache, placed from outside the program.

The cache directory is part of what makes an entry findable again, so it
must not move between runs: ``JAX_COMPILATION_CACHE_DIR`` wins when the
environment sets it (JAX reads it itself), and otherwise the cache lives
at the fixed path ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory.  Call before the first compile to cache."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
