"""Where JAX keeps its persistent compilation cache.

Call ``use_compile_cache()`` from a program's entry point before its first
compile; nothing calls it at import. If ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it itself and this sets nothing. Otherwise the cache goes to
``<checkout>/.jax_cache`` (gitignored): a fixed path, since the path is part
of what a later run must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
