"""JAX's persistent compilation cache at a fixed place in the checkout."""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache():
    """Let later processes reuse this one's compiled programs.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and
    nothing is set here. Otherwise, on a TPU, the cache lives at
    ``<checkout>/.jax_cache`` (git-ignored): a fixed path, so that a later
    process finds what an earlier one wrote. CPU runs (the tests) compile
    small programs and keep no cache. Call before the first compile.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    if jax.default_backend() == "tpu":
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
