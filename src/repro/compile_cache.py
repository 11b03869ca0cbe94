"""JAX persistent compilation cache, placed from outside the program.

Entry points (`chip_smoke.py`, `repro.sweep.cli`, `scripts/bench_step.py`)
call `enable()` before their first compile. Where
`JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and this module
sets no other path. Otherwise the cache lives at `<checkout>/.jax_cache`
(git-ignored): a fixed path, because the path is part of what a warm run
must find again. The fleet programs compile in one to a few seconds, so
the threshold below which JAX skips caching a compile is dropped to 0.
"""
from __future__ import annotations

import os

__all__ = ["enable", "DEFAULT_DIR"]

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable() -> str:
    """Give the persistent compilation cache a directory; returns it."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
