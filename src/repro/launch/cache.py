"""Persistent XLA compilation cache for the command-line entry points.

``qsim``, ``serve``, ``benchmarks.run`` and ``chip_smoke.py`` call
:func:`use_compile_cache` once at start-up (never at import): compiled
stage programs and kernels then survive the process, so a second run of
the same circuit skips most of its compilation.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "use_compile_cache"]

#: where the cache lives when ``JAX_COMPILATION_CACHE_DIR`` is unset —
#: one fixed directory of the checkout (listed in .gitignore), since the
#: path is part of what a later run must find again
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at
    ``$JAX_COMPILATION_CACHE_DIR``, or at :data:`CHECKOUT_CACHE_DIR` when
    that is unset.  Returns the directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
