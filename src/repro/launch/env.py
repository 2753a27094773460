"""Process set-up shared by the entry points: compile cache and XLA flags.

Every entry point (``chip_smoke.py``, ``repro.launch.recover``,
``repro.launch.serve``, ``benchmarks.run``) calls
:func:`configure_compile_cache` before its first compile:

* if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and the
  cache stays there — no other directory is configured in code;
* otherwise the cache goes to :data:`DEFAULT_CACHE_DIR`, one fixed,
  git-ignored directory inside the checkout.  The path is part of the
  cache key, so it is never built from a temp name, a pid or the time.

Flags for XLA or libtpu are appended to what the environment already
holds (:func:`append_flag`), never written over it.  This module imports
no JAX at top level, so launchers can call :func:`append_flag` before JAX
initialises its backends.
"""

from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache",
)


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory (see the module docstring)."""
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


def append_flag(var: str, flag: str) -> None:
    """Add ``flag`` to the space-separated flag list in env var ``var``
    (``XLA_FLAGS``, ``LIBTPU_INIT_ARGS``), keeping what is already there."""
    old = os.environ.get(var, "").strip()
    os.environ[var] = f"{old} {flag}" if old else flag
