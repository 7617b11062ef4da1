"""JAX's persistent compilation cache for the program's entry points.

A run on the chip compiles every serving bucket, training banding and
kernel it touches; the persistent cache lets the next process on the same
checkout load them instead.  Entry points (``chip_smoke.py``,
``launch/train.py``, ``benchmarks/run.py`` and each bench script) call
``enable_compile_cache()`` first thing in ``main()``.  Library code, imports
and tests never call it.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
MIN_COMPILE_TIME_ENV = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"
#: ``<checkout>/.jax_cache`` (git-ignored).  A fixed path: the directory is
#: part of what a later run must find again.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; return the directory it uses.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    directory is set here; otherwise the cache goes to ``CACHE_DIR``.
    Unless ``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`` says otherwise,
    programs of any compile time are kept: JAX's default keeps only those
    over one second, and the serving bucket ladder is a hundred programs
    under it.  Call it before the first compile of the process.
    """
    directory = os.environ.get(CACHE_ENV)
    if not directory:
        directory = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", directory)
    if MIN_COMPILE_TIME_ENV not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return directory
