"""JAX persistent compilation cache, placed from outside or at one fixed
path inside the checkout.

JAX keys a cache entry on the cache directory too, so a directory that
moves between runs (a temp dir, a pid, a time stamp) never hits. Call
:func:`enable_compile_cache` from a driver's ``main()``, never at import.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> Optional[Path]:
    """Point JAX's persistent compilation cache at ``<checkout>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` is set, in which case JAX reads
    the variable itself and no directory is set here. Returns the
    directory this call set, or ``None``."""
    if os.environ.get(ENV_VAR):
        return None
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return DEFAULT_DIR
