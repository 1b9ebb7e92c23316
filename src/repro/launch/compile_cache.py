"""Where JAX's persistent compilation cache lives for this repo's programs.

Every entry point (``chip_smoke.py``, ``benchmarks/run.py``,
``examples/*.py``) calls :func:`enable_compile_cache` once, before its
first compile.  The library itself never touches the cache on import.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; nothing else
  is configured here.
* unset: the cache goes to ``<checkout>/.jax_cache`` (gitignored).  The
  path is fixed because it is part of the cache key: a directory named by
  a temp dir, pid or time would never be hit by the next run.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The directory the persistent compilation cache uses."""
    return os.environ.get(ENV) or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at :func:`compile_cache_dir`
    and return that directory."""
    path = compile_cache_dir()
    if not os.environ.get(ENV):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path
