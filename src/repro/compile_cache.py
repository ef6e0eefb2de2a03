"""Where JAX keeps its persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and wins:
the deployment decides where compiled programs live.  Otherwise the cache
goes to ``.jax_cache`` at the root of this checkout — a fixed path, because
the directory is part of what a later process looks the cache up by.

Either way the cache key includes the program's metadata: a profile reads
its ``jax.named_scope`` names from the executable, and a key without them
would hand one program's executable, scope names and all, to another that
differs only in them.
"""
from __future__ import annotations

import os

import jax

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory.  Sets no directory when ``JAX_COMPILATION_CACHE_DIR``
    is set."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return DEFAULT_DIR
