"""JAX's persistent compilation cache, switched on by the entry points.

The sampler's round programs take minutes to compile for a TPU (the x64
dedup sort dominates), so every process that drives the chip keeps its
compiled programs on disk.  The entry points (``chip_smoke.py``,
``repro.launch.serve`` and ``benchmarks.run``) call
:func:`enable_compile_cache` once, before their first compile; importing
the library never does.
"""

from __future__ import annotations

import os
import pathlib

import jax

# a fixed path, because the directory is part of the cache key: a cache that
# moves never hits
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads that
    directory from the environment and nothing is set here.  Otherwise the
    cache goes to ``<checkout>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
