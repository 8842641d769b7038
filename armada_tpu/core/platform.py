"""The persistent XLA compilation cache, placed from outside the program.

JAX keeps its cache where ``JAX_COMPILATION_CACHE_DIR`` says.  Where the
operator (or the machine image) sets that variable, this module sets no
directory in code; otherwise the cache lives at the one fixed path
``<checkout>/.jax_cache`` (git-ignored).  The directory is part of the
cache key, so a path built from a data dir, a temp name, a pid or the time
would never hit.
"""

from __future__ import annotations

import os
from typing import Optional

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def compilation_cache_dir() -> Optional[str]:
    """The directory this program would set in code, or None when
    ``JAX_COMPILATION_CACHE_DIR`` already places the cache from outside."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    return os.path.join(_CHECKOUT, ".jax_cache")


def enable_compilation_cache() -> None:
    """Persist XLA compilations across process restarts (serve, the soak
    driver, bench.py and chip_smoke.py call this; idempotent).  The
    threshold floors keep tiny test jits from churning the directory."""
    import jax

    cache_dir = compilation_cache_dir()
    if cache_dir is not None:
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
