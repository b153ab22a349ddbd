"""Where JAX keeps its persistent compilation cache for this checkout.

Entry points (``chip_smoke.py``, the ``benchmarks/`` scripts) call
:func:`use_compile_cache` once, before their first jit.  Importing
``repro`` never calls it, and neither do the tests.

The path is part of each cache entry's key, so it is fixed: either the
directory ``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that variable
itself, and nothing here overrides it) or ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os
import pathlib

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory and
    return it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
