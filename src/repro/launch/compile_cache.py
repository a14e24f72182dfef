"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` before their first compile.  Importing
``repro`` never touches the cache.
"""

from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache(checkout: str | os.PathLike) -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed here.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``: a fixed path, because a run can only hit
    entries that an earlier run wrote to the same directory.
    """
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    path = os.path.join(os.path.abspath(checkout), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
