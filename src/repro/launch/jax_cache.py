"""JAX's persistent compilation cache for the entry points.

Every entry point (``chip_smoke.py``, ``launch/serve.py``,
``launch/train.py``) calls :func:`enable_compile_cache` at start-up, never at
import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing here overrides it; otherwise the cache lives at the fixed
``<checkout>/.jax_cache`` (listed in ``.gitignore``), since the directory is
part of what makes a later run find an entry again.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    import jax

    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
