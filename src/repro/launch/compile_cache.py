"""JAX's persistent compilation cache, placeable from outside.

The entry points (``launch.train``, ``launch.serve``, ``chip_smoke.py``)
call ``enable_compile_cache()`` from their ``main()``; importing the
library never turns the cache on, so tests compile with it off.

The cache directory is ``JAX_COMPILATION_CACHE_DIR`` when that is set
(JAX reads the variable itself, so nothing is set in code), and
otherwise ``<checkout>/.jax_cache`` — a fixed path, because the
directory is part of what a later run must find again: a name drawn
from a temp dir, a pid or a clock never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: the checkout root is three levels above src/repro/launch/
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
