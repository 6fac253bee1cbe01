"""JAX's persistent compilation cache, switched on by the entry points.

``chip_smoke.py``, ``repro.launch.serve``, ``repro.launch.train`` and
``benchmarks/run.py`` call :func:`enable_compile_cache` before their first
compile.  Importing the package never does, so the tests stay cache-free.
"""

from __future__ import annotations

import os
import pathlib

#: the checkout this module lives in (``<checkout>/src/repro/launch/``)
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that directory
    itself and nothing is set here.  Otherwise the cache is the fixed,
    git-ignored ``<checkout>/.jax_cache``.  The path has to stay fixed
    from run to run: it is where a later run looks entries up.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
