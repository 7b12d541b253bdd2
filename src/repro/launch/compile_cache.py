"""Where JAX's persistent compilation cache lives.

Entry points (the trainer and server CLIs, ``chip_smoke.py``) call
``enable_compile_cache()`` once at start-up; nothing enables it at import.
The cache key includes the directory, so the directory is fixed: the one
``JAX_COMPILATION_CACHE_DIR`` names when the environment sets it (JAX reads
that variable itself), else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
