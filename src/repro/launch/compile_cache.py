"""JAX's persistent compilation cache, kept at one fixed path.

Every entry point (``chip_smoke.py``, ``launch/serve.py``,
``launch/train.py``, ``benchmarks/run.py``) calls :func:`enable` before it
compiles anything, so a process that compiles what an earlier one already
compiled loads the executable from disk instead.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing. Otherwise the cache lives in ``.jax_cache`` at the root
of the checkout (git-ignored). The path is part of the cache key, so it is
never a temporary name, a pid or a time.
"""
from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
