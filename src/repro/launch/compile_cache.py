"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``chip_smoke.py``, :mod:`repro.launch.serve`,
``benchmarks/run.py``) call :func:`use_compile_cache` before they compile
anything, so a second run of the same shapes loads its executables
instead of compiling them again.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: Cache directory when ``JAX_COMPILATION_CACHE_DIR`` is not set: a fixed
#: path inside the checkout (listed in ``.gitignore``). Never a temporary,
#: per-process or per-run path — a cache directory that moves between
#: runs never hits.
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that variable
    itself and no directory is set here. Otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`. Either way every executable is cached,
    however short its compile: the engines compile many small executors
    (one per engine, batch bucket, sign bucket and M-bucket), most of them
    under JAX's default one-second floor.
    """
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
