"""Where JAX keeps its persistent compilation cache for this checkout.

Entry points (``chip_smoke.py``, ``python -m repro.service``,
``benchmarks/run.py``) call :func:`enable` once before their first compile;
importing the library changes nothing.
"""
from __future__ import annotations

import os
import pathlib

#: Fixed in-checkout cache path (listed in ``.gitignore``).  The path is part
#: of what a later run must match to hit, so it never varies between runs.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and this
    sets nothing.  Otherwise the cache goes to :data:`DEFAULT_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
