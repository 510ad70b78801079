"""JAX's persistent compilation cache: the one rule every entry point
(``chip_smoke.py``, ``benchmarks/run.py``) applies before it compiles.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
this module sets no other directory.  Otherwise the cache lives at a
fixed path inside the checkout, ``.jax_cache/`` (git-ignored): the path
is part of what makes a later run find the entries, so it never depends
on a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every compile, however short (the
    small kernel programs would otherwise fall under JAX's one-second
    floor).  Returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
