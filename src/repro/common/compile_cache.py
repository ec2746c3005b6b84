"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`enable_compile_cache` once at start-up (never at
import).  A directory named by ``JAX_COMPILATION_CACHE_DIR`` wins, and the
code then sets no other; without one the cache lives at a fixed path
inside the checkout.  The path is part of every cache key, so it is never
built from a temporary name, a process id or the time.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache — this file is <checkout>/src/repro/common/
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        # JAX reads the variable itself; setting anything here could only
        # disagree with it
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
