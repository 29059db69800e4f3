"""Persistent XLA compilation cache, placed from outside the program.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
:func:`enable_compile_cache` sets nothing.  Otherwise the cache lives at
one fixed path inside the checkout, ``<repo>/.jax_cache`` (listed in
``.gitignore``).  The directory is part of what lets a later process find
an entry again, so it never carries a temp name, a pid or a time.

Entry points call :func:`enable_compile_cache` from ``main()``; importing
this module changes nothing, and the test process never turns it on.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
