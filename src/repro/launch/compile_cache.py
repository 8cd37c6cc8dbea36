"""JAX's persistent compilation cache, as the entry points configure it.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing else is set.  Otherwise the cache lives at a fixed path inside
the checkout (``.jax_cache``, listed in ``.gitignore``): the directory is
part of each entry's key, so a path that varied from run to run would
never hit.  The cache stays off wherever JAX's own switch
``jax_enable_compilation_cache`` is off (the test suite turns it off).
"""

from __future__ import annotations

import os
import pathlib
from typing import Optional

import jax

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/launch/``)
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent cache on; returns its directory (None = off)."""
    if not jax.config.jax_enable_compilation_cache:
        return None
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
