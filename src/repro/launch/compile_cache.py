"""JAX's persistent compilation cache for entry points that run on the chip.

Tests never call this: a compile for a described (not attached) chip
cannot be read back from the cache.
"""

from __future__ import annotations

import os
from pathlib import Path

#: fixed cache directory inside the checkout (listed in .gitignore); the
#: path is part of the cache key, so it must not move between runs
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` when set, else ``DEFAULT_DIR``. Every
    program is cached, however quickly it compiled."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
