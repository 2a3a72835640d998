"""Persistent XLA compilation cache for the entry points of this checkout.

:func:`enable` runs before a program's first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache there and
the directory is left alone.  Otherwise the cache goes to ``.jax_cache/`` at
the root of the checkout: a fixed path, because a later run finds an entry
again only under the same path.

Every device program of the simulator (the U-Net forward) compiles in well
under JAX's default one-second threshold for persisting an entry, so that
threshold is lowered to zero unless
``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`` sets it.
"""
from __future__ import annotations

import os

CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable() -> None:
    """Point JAX's persistent compilation cache at its directory (see the
    module docstring).  Must run before the process's first compile: JAX
    fixes the cache location when it first uses it."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
