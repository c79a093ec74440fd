"""Where compiled programs are kept between runs.

Program entry points (``launch.train.run``, ``benchmarks/run.py``,
``chip_smoke.py``) call :func:`use_compile_cache` once, before their
first compile.  Importing this module changes nothing.
"""

from __future__ import annotations

import os

import jax

# <checkout>/src/repro/launch/cache.py -> <checkout>
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its path.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache is ``<checkout>/.jax_cache``
    (git-ignored).  The path is fixed on purpose: it is part of what the
    cache is keyed on, so a per-run directory would never be hit.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
