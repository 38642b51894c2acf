"""JAX's persistent compilation cache, at one fixed place per checkout.

Entry points (``chip_smoke.py``, ``python -m repro``, ``launch/serve.py``,
``launch/train.py``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` first thing, before anything compiles.
Importing this module changes nothing.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
function sets no other directory.  Otherwise the cache lives in
``<checkout>/.jax_cache`` (git-ignored).  The path is part of the cache's
key, so it is fixed: never a temporary, per-process or per-run name.
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on for this process and
    return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
