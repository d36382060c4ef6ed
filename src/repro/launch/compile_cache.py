"""JAX's persistent compilation cache, at one fixed place per checkout.

Entry points (``chip_smoke.py``, the ``launch`` CLIs, the bench scripts) call
:func:`enable` once at start-up; importing this module changes nothing.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, that directory is the cache and
no other is set.  Otherwise the cache is ``<checkout>/.jax_cache``: a fixed
path, because the cache directory is part of what a later process must find
again, so it is never built from a temp name, a pid or the time.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_ROOT = pathlib.Path(__file__).resolve().parents[3]


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV_VAR) or str(CHECKOUT_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
