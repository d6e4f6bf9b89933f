"""Where JAX's persistent compilation cache lives.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself at import, and
  this module sets no other directory.
* Unset: the cache goes to ``<checkout>/.jax_cache`` (git-ignored). The
  path is fixed - never built from a temp name, a pid or the time - so
  a later process in the same checkout finds what an earlier one
  compiled.

The launchers (``serve``, ``fleet``, ``train``) and ``chip_smoke.py``
call :func:`enable` at start-up; nothing calls it at import, and the
tests leave the cache off.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def cache_dir() -> Path:
    """The directory :func:`enable` puts the cache in."""
    env = os.environ.get(ENV)
    return Path(env) if env else DEFAULT_DIR


def enable() -> Path:
    """Turn the persistent compilation cache on; returns its directory."""
    path = cache_dir()
    if not os.environ.get(ENV):
        import jax
        jax.config.update("jax_compilation_cache_dir", str(path))
    return path
