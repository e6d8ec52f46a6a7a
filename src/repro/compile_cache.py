"""JAX's persistent compilation cache, placeable from outside.

Every entry point (``chip_smoke.py``, the ``scripts/launch_multihost.py``
worker, ``benchmarks/run.py``, the examples) calls :func:`enable` before
its first compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it itself and nothing here sets another directory.  Otherwise the cache
lives at ``.jax_cache/`` in the checkout root: a fixed path, so a run
finds what an earlier run from the same checkout compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax

    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return jax.config.jax_compilation_cache_dir
