"""Where the launch entry points keep JAX's persistent compilation cache.

Call `use_compile_cache` first thing in an entry point, never at import.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# A fixed path inside the checkout: the cache directory is part of the
# cache key, so a path built from a temp name, a pid or the time never hits.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Keep compiled programs in ``$JAX_COMPILATION_CACHE_DIR`` when it is
    set (JAX reads it itself; nothing is set here), else in `CACHE_DIR`.
    Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
