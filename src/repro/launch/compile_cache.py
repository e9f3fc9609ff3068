"""JAX's persistent compilation cache for this repository's programs.

Each entry point calls ``enable_compile_cache()`` first thing in its
``main``, never at import, so a second run of the same program loads its
compiled steps instead of compiling them again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset. It is
#: a fixed path in the checkout, neither temporary nor per-process, so
#: every later run of any entry point finds what an earlier one compiled.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has read it already
    and no other directory is set here.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
