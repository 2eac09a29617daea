"""Where the program runs: the TPU gate and the compile cache.

Every Pallas kernel dispatch in the library asks :func:`on_tpu`; off a
TPU the XLA twin runs, and the Pallas interpreter runs only where a
caller passes ``interpret=True`` / ``pallas_interpret=True``.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <repo>/.jax_cache — fixed, because the cache key includes the path
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU (Mosaic kernels compile)."""
    return jax.default_backend() == "tpu"


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at
    ``$JAX_COMPILATION_CACHE_DIR`` when set, else at ``<repo>/.jax_cache``,
    and cache every compile worth half a second.  Returns the directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
