"""Where JAX keeps its persistent compilation cache.

A serving process compiles a program per prefill chunk shape and decode
bucket; the persistent cache lets the next process on the same machine load
them instead.  The cache's key includes its path, so the path is fixed:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads the
variable itself and nothing here overrides it), else
``<checkout>/.jax_cache``.

Called from entry points' ``main`` (never at import), so a library user's
own cache settings are left alone.
"""
from __future__ import annotations

import os
from pathlib import Path

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
