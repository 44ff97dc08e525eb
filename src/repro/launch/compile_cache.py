"""Placement of JAX's persistent compilation cache for the entry points."""

from __future__ import annotations

import os
from pathlib import Path

#: Root of the checkout (``src/repro/launch`` → three levels up).
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache sits at the fixed
    ``<checkout>/.jax_cache``: the directory is part of what a later run
    must find again, so it never comes from a temporary name, a process
    id or the time. Call from an entry point's ``main()``, never at
    import.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
