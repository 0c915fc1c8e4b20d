"""JAX's persistent compilation cache, for entry points that run on a chip.

Call ``enable_compilation_cache()`` at the top of an entry point, before
anything compiles; library code never calls it at import.
"""
from __future__ import annotations

import os

import jax

#: the checkout's own cache directory (listed in ``.gitignore``)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compilation_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, names the directory and JAX
    reads it itself; otherwise the cache lives at ``.jax_cache/`` in the
    checkout, a fixed path, so a later process finds what an earlier one
    wrote.  Every program is persisted however small or quick to compile:
    the engine's histogram kernels compile in well under JAX's default
    one-second threshold."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
