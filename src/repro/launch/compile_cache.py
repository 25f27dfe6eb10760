"""JAX's persistent compilation cache, placed by the entry points.

Only entry-point ``main()`` functions call :func:`enable_compile_cache`;
library imports and the test suite never do, because ahead-of-time
compiles for a described (unattached) TPU write entries that cannot be
read back.  The cache key includes the directory, so the default is a
fixed path inside the checkout, never a temporary name.
"""

from __future__ import annotations

import os
from pathlib import Path

# src/repro/launch/compile_cache.py -> the checkout root is parents[3]
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use:
    ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it on its own and
    no other directory is configured here), else ``<checkout>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
