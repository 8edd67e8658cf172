"""JAX's persistent compilation cache for the entry points.

`launch.serve`, `launch.train` and `chip_smoke.py` call
`enable_compile_cache()` at the top of `main()`; importing this module
changes nothing. A cold process otherwise compiles every serving shape
(3 buckets x 6 batch sizes) and the training step from scratch.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache (gitignored). The path is part of what a later
# process must find again, so it is fixed: never a temporary name.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and no
    other directory is set here; otherwise the cache lives at DEFAULT_DIR.
    Every compile is cached, however short: compiled for v5e, one serving
    shape takes 0.2 to 3 s, so most fall under JAX's default 1 s floor."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
