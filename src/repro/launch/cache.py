"""JAX persistent compilation cache for the entry points.

Called from ``main()`` of the launchers (and ``chip_smoke.py``), never
at import, so tests and library users keep whatever cache setting they
chose.  The cache key includes its directory, so the default lives at a
fixed path inside the checkout.
"""
from __future__ import annotations

import os

import jax

#: default cache directory: ``<checkout>/.jax_cache`` (in .gitignore)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache(default_dir: str = DEFAULT_CACHE_DIR) -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed here; otherwise the cache goes to ``default_dir``.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", default_dir)
    return default_dir
