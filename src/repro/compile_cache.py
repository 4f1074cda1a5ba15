"""JAX's persistent compilation cache, kept at one fixed place.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/``)
call :func:`enable` before they compile anything.  A later run finds
what an earlier one wrote only at the same path, so it never moves:
``JAX_COMPILATION_CACHE_DIR`` when
the environment sets it (JAX reads that variable itself, and nothing
else is set here), otherwise ``<repo>/.jax_cache`` (git-ignored).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: the cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
