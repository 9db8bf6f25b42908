"""Where the entry points keep JAX's persistent compilation cache.

Entry points (``launch/serve.py``, ``launch/pagerank.py``,
``chip_smoke.py``) call ``enable_compile_cache`` from ``main``; importing
a module never changes the cache.
"""
from __future__ import annotations

import os

import jax

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Leave the cache where ``JAX_COMPILATION_CACHE_DIR`` puts it when
    that is set, else keep it at ``<checkout>/.jax_cache``.  Returns the
    directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
