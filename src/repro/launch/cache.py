"""Where the entry points keep JAX's persistent compile cache.

Only entry points call ``use_compile_cache``, from ``main``: importing the
package never touches the cache setting.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> None:
    """Keep the cache in the checkout's ``.jax_cache/``, unless
    ``JAX_COMPILATION_CACHE_DIR`` is set: JAX then reads that itself. The
    path stays fixed from run to run so that later runs find the entries."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
