"""JAX's persistent compilation cache, placed from outside the program.

Entry points that compile large programs (``chip_smoke.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` once, at start-up;
importing this module changes nothing. Where ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX reads the variable itself and the call sets nothing. Otherwise the
cache lives at the fixed ``<checkout>/.jax_cache`` (git-ignored), so a later
run from the same checkout finds what an earlier one compiled.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the checkout this package was imported from (``src/repro/launch/..``)
_CHECKOUT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                         "..", ".."))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
