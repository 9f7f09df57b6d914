"""Persistent XLA compilation cache for the training entry points.

A full-width train step takes tens of seconds to compile; the cache lets
later processes on the same machine skip that.  Where the cache lives is
decided from outside the program: ``JAX_COMPILATION_CACHE_DIR``, when set,
is read by JAX itself and nothing here overrides it.  Otherwise the cache
goes to ``.jax_cache/`` at the checkout root — a fixed path, because the
path is part of what a later process must find again (git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    import jax

    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
