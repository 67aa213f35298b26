"""JAX's persistent compilation cache at a fixed place.

Call ``enable_compile_cache`` once, before the first compile, from every
entry point (the launch mains and ``chip_smoke.py``).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that directory
    from the environment itself and nothing is set here. Otherwise the
    cache goes to ``<repo>/.jax_cache``: a fixed path, so that a later
    run from the same checkout finds what an earlier one compiled.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
