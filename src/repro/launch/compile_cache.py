"""Persistent XLA compilation cache for the entry points.

A 32-layer model takes tens of seconds to a minute per step program to
compile; with the cache on, a second run of the same program on the same
checkout loads it instead. Entry points (``python -m repro.launch.serve``,
``python -m repro.launch.train``, ``chip_smoke.py``) call
``enable_compile_cache()`` before their first compile; importing this or
any other module never does, so tests and library users are unaffected.
"""

from __future__ import annotations

import os

import jax

# fixed, in-checkout, gitignored: the cache key includes nothing that
# moves between runs, so a later run of the same program hits
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` set in the environment: JAX already
    reads it, and no other directory is set. Unset: ``.jax_cache/`` at the
    root of the checkout.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.normpath(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
