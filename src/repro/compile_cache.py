"""Persistent compilation cache for the command-line entry points.

A cold TPU compile of the streaming route takes about a minute; the cache
lets the next process on the same machine skip it.  Library import sets
nothing: only entry points that run on a chip (``chip_smoke.py``, the
``benchmarks`` mains, ``repro.launch.train``) call :func:`use_compile_cache`.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: Fixed cache path inside the checkout (git-ignored).  Never derived from a
#: temp name, a pid or the time: the path is part of what a later process
#: must find again.
CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed.  Otherwise the cache goes to :data:`CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
