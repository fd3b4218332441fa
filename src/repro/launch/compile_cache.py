"""Where JAX keeps its persistent compilation cache.

Nothing here runs at import: a program that wants the cache calls
``use_compile_cache()`` before its first compile, so the tests (which never
call it) compile without one.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# a fixed path inside the checkout: the cache key includes nothing about
# the directory, but a directory that moves between runs never hits
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache: JAX reads it
    itself and this sets no other directory.  Otherwise the cache lives in
    ``<checkout>/.jax_cache`` (git-ignored).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
