"""Persistent XLA compilation cache for the command-line entry points.

``enable()`` is called from the ``__main__`` entry of the serving
launcher, the benchmark entry points and ``chip_smoke.py`` — never at import
and never by the tests. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
has already read it and the directory is left to JAX. Otherwise the cache
lives at the fixed path ``<checkout>/.jax_cache``: the path is part of the
cache key, so it must not move between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    import jax
    if os.environ.get(ENV):
        return jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
