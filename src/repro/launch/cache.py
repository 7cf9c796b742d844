"""Where JAX's persistent compilation cache lives.

The cache's path is part of its key, so it sits in one fixed place: the
directory ``JAX_COMPILATION_CACHE_DIR`` names, when that variable is set
(JAX reads it itself and nothing here overrides it), and otherwise
``<checkout>/.jax_cache/``. Entry points (``chip_smoke.py``,
``benchmarks/run.py``) call :func:`enable_compilation_cache`; importing
the library never does, so test runs write nothing into the tree.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIRNAME = ".jax_cache"


def enable_compilation_cache(root) -> str:
    """Turn the persistent cache on for every compile of this process
    and return its directory; ``root`` is the checkout's root."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(Path(root).resolve() / CACHE_DIRNAME)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
