"""Where JAX keeps its persistent compilation cache.

Every launcher's ``main()`` (and ``chip_smoke.py``) calls
`use_compilation_cache` before it compiles anything; importing a module
never does.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads
it and this leaves it alone.  Otherwise the cache goes to one fixed
directory inside the checkout (gitignored), so each run of the same
checkout finds what the previous one compiled: the directory is part of
the cache key, so it must not be a temp name, a pid or a time.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE_DIR = (
    pathlib.Path(__file__).resolve().parents[3] / ".jax_compilation_cache"
)


def use_compilation_cache() -> str:
    """Point JAX's persistent cache at its one directory; return that path."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
