"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`setup_compile_cache` once at start; nothing here runs at import.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["REPO_CACHE_DIR", "setup_compile_cache"]

# Fixed in-checkout default (listed in .gitignore). The directory must not
# move between runs, or a warm cache is never found again.
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def setup_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    path is set here. Otherwise the cache lives in ``.jax_cache`` at the
    root of the checkout. Every program is cached however fast it
    compiled: refine issues many small sweep programs, one per shape.
    """
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir
