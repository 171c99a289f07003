"""Where JAX keeps its persistent compilation cache for this checkout.

``JAX_COMPILATION_CACHE_DIR``, when set, is honored as JAX reads it, and no
path is set in code.  Otherwise the cache lives at ``<checkout>/.jax_cache``
(listed in ``.gitignore``), so repeated runs from one checkout reuse their
compiled programs.  Entry points call :func:`use_checkout_cache` once,
before their first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>/.jax_cache``: this file is ``<checkout>/src/repro/launch/``.
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_checkout_cache() -> str:
    """Apply the cache rule above; returns the cache directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
