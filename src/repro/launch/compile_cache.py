"""Where JAX keeps its persistent compilation cache for this checkout.

A cached executable is found again only under the same directory, so the
directory is fixed: ``JAX_COMPILATION_CACHE_DIR`` when the environment
sets it (JAX reads it itself, and nothing here overrides it), otherwise
``<checkout>/.jax_cache``, resolved from this file's location and listed
in ``.gitignore``. It never depends on a temp name, a PID or the clock.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
