"""JAX's persistent compilation cache, switched on by each entry point.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the ``dbserver``,
``launch/train.py`` and ``launch/serve.py``) call :func:`enable_compile_cache`
before their first compile; importing this module sets nothing.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# a fixed path inside the checkout: the directory is part of the cache
# key, so a path built from a temp name, pid or time would never hit
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Keep compiled programs across processes; returns the cache directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and the
    directory is left alone; otherwise the cache lives in ``.jax_cache`` at
    the root of the checkout.  The minimum compile time and entry size are
    lowered so kernels that compile in under a second are kept too.
    """
    import jax
    path = os.environ.get(ENV_VAR)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
