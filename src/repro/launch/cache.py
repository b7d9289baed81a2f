"""JAX's persistent compilation cache, as every entry point sets it up.

A process that holds the chip compiles every program it runs; with the cache
on, a second run of the same programs reads them back instead.  The
launchers, the examples and ``chip_smoke.py`` call :func:`enable_compile_cache`
first thing in ``main``; importing this module changes nothing.
"""

from __future__ import annotations

import os
from pathlib import Path

# a fixed directory at the root of the checkout (listed in .gitignore); never
# derived from a temporary name, a pid or the time, so runs find each other
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    it.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the
    cache there and no other directory is set; otherwise it goes to
    :data:`CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
