"""Where JAX keeps its persistent compilation cache.

The round step compiles once per budget rung and round variant, so a cold
start pays those compiles again unless they are cached on disk.  The cache
directory is part of an entry's key, so it stays at one fixed path.

Entry points (``chip_smoke.py``, the benchmark mains, the examples) call
:func:`use_compile_cache` once at start.  The library and the tests never
set a cache.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# the checkout this package is imported from: <checkout>/src/repro/
CHECKOUT = Path(__file__).resolve().parents[2]


def use_compile_cache() -> str:
    """Place the cache and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads that directory itself
    and nothing here overrides it.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``.
    """
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
