"""JAX's persistent compilation cache, placed from outside the program.

Entry points that compile for the device (``chip_smoke.py``,
``benchmarks/run.py``) call :func:`enable` once before their first compile;
nothing calls it at import.  ``JAX_COMPILATION_CACHE_DIR``, when set, names
the directory and JAX reads it itself; otherwise the cache lives at the
fixed ``CACHE_DIR`` inside the checkout, so one checkout's runs find each
other's compiles.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

__all__ = ["CACHE_DIR", "enable"]


def enable() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Every compile is cached, however short: the Pallas kernels compile in
    about a second each, under JAX's default one-second threshold.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
