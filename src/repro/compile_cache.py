"""JAX's persistent compilation cache, for the program's entry points.

Entry points (`chip_smoke.py`, `benchmarks/run.py`, `examples/*.py`)
call `enable_compile_cache()` once before they compile anything.  It is
not called on `import repro`: the tests compile kernels for a described
TPU, and such entries cannot be read back on the CPU.
"""
from __future__ import annotations

import os
import pathlib

#: Cache directory when `$JAX_COMPILATION_CACHE_DIR` is unset: fixed at
#: the checkout root, because the path is part of every entry's key.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where `$JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and
    nothing else is set here.  Otherwise the cache goes to
    `DEFAULT_DIR`.
    """
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
