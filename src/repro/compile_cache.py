"""JAX's persistent compilation cache, for the programs' entry points.

``enable()`` is called by ``chip_smoke.py``, ``launch/*.py`` and
``benchmarks/run.py`` before their first compile, never on import, so
tests run with whatever cache their environment sets. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads that directory and
no other is set here. Otherwise the cache lives at ``<repo>/.jax_cache``:
a fixed path, so that a later run finds what an earlier one wrote.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV) or DEFAULT_DIR
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
