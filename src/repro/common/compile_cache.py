"""JAX's persistent compilation cache, for the entry points.

A fresh process on the chip compiles every program from scratch; the
persistent cache lets a second identical run read them back. The entry
points (``chip_smoke.py``, ``repro.launch.train.main``,
``examples/quickstart.py``) call ``enable_compile_cache()`` once at
start-up — never at import, and never in the tests.

Where the cache lives:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing here
  touches the setting.
* otherwise: ``<checkout>/.jax_cache`` — one fixed path (the directory
  is part of the cache key, so a path built from a temp name, a pid or
  the time would never hit). It is git-ignored.
"""
from __future__ import annotations

import os
from typing import Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/common/compile_cache.py -> the checkout root
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def compile_cache_dir() -> Optional[str]:
    """The directory this module would set, or None when the environment
    already names one (JAX honours ``JAX_COMPILATION_CACHE_DIR`` on its
    own)."""
    if os.environ.get(ENV_VAR):
        return None
    return DEFAULT_DIR


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process; returns its
    directory."""
    path = compile_cache_dir()
    if path is None:
        return os.environ[ENV_VAR]
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    return path
