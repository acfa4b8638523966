"""JAX's persistent compilation cache for this repo's entry points.

A cold process on a TPU spends minutes compiling the engine's fused steps
and the solver's pass loops; the persistent cache lets the next process
load them instead. Only entry points call :func:`enable_compile_cache`
(``solve_server``, ``serve.worker`` and ``chip_smoke.py``, from their
``__main__`` paths) — never an import, so in-process tests, which count
real compiles, keep the cache off.

Where the cache lives: ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX
reads it itself, and it is left alone), otherwise the fixed
``<checkout>/.jax_cache``. The path is part of what makes entries hit, so
it must not move between runs.
"""
from __future__ import annotations

import os
import pathlib

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory
    (see the module docstring); returns that directory. Call before the
    first compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        import jax
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
