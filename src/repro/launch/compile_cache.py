"""JAX's persistent compilation cache, placed from outside or at one fixed
directory of the checkout.

Entry points that run on the chip (`chip_smoke.py`) call
`enable_compile_cache()` once, before their first compile. Nothing calls it
on import, and the tests never do: a test run compiles in interpret mode on
the CPU and keeps no cache.

  * `JAX_COMPILATION_CACHE_DIR` set   -> JAX already reads it; the code sets
                                         no other directory.
  * unset                             -> `<checkout>/.jax_cache`, listed in
                                         .gitignore. The path is fixed, never
                                         a temporary name, a pid or a time,
                                         so a later run finds what an
                                         earlier one cached.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory. Every compile
    is cached, however short: the Pallas coding kernels compile in about a
    second each, under JAX's default threshold."""
    path = os.environ.get(ENV)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
