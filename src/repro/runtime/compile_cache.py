"""Where the entry points keep JAX's persistent compilation cache.

A TPU compile of the main path takes minutes; a cache that survives the
process turns a rerun's compiles into reads. The cache key includes the
directory, so the directory must not move between runs:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, nothing is set
    here;
  * otherwise: ``<checkout>/.jax_cache`` (git-ignored).

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/*.py``)
call ``enable_compile_cache()`` before their first compile. Importing the
package never does, so the test suite compiles without a cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: The fixed in-checkout cache directory (this file is
#: ``<checkout>/src/repro/runtime/compile_cache.py``).
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
