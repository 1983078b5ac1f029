"""Where JAX keeps its persistent compilation cache for this repo's runs.

A chip run compiles the 2^14 EvalPlan programs (tens of seconds each);
the persistent cache lets a later process with the same programs skip
that.  The cache key includes the directory, so it must not move:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing
    here touches the configuration;
  * otherwise: ``<repo>/.jax_cache`` (listed in ``.gitignore``).

The library never calls this on import.  Entry points do —
``chip_smoke.py``, ``benchmarks/run.py`` and the examples — before
their first compile.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def enable() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    path = os.environ.get(ENV)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE)
    return REPO_CACHE
