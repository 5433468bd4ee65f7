"""Persistent XLA compilation cache for the entry points.

`chip_smoke.py` calls `enable_compile_cache()` before it compiles
anything; library imports never do.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it.  Otherwise the cache lives at the fixed
``<checkout>/.jax_cache`` — a fixed path, because the path is part of
what a later run must find again.
"""

from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache for every compile, however
    short, and return the directory it writes to."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
