"""Where JAX's persistent compilation cache lives — set by entry points.

``use_compile_cache()`` is called by the programs a user runs
(``chip_smoke.py``, the ``benchmarks/`` mains), never when a library
module is imported.  The rule:

* if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
  sets nothing;
* otherwise the cache goes to ``<repo>/.jax_cache`` — a fixed path (the
  path is part of the cache key, so a directory that moves never hits),
  listed in ``.gitignore``.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["REPO_CACHE_DIR", "use_compile_cache"]

#: the in-checkout default (``src/repro/launch`` -> repo root)
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Enable the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
