"""JAX's persistent compilation cache, at one fixed place per checkout.

Entry points that run on the chip (``chip_smoke.py``, ``benchmarks/
throughput.py --compiled``) call ``use_compile_cache()`` before anything
compiles, so a second run of the same programs loads them instead of
compiling again. The directory is part of each entry's key, so it never
moves: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX
reads that variable itself, and no other directory is set), else
``<checkout>/.jax_cache`` (gitignored). Tests leave the cache off.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CHECKOUT_CACHE", "use_compile_cache"]

#: ``<checkout>/.jax_cache``: this file is <checkout>/src/repro/...
CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
