"""Where JAX keeps its persistent compilation cache.

``configure()`` is called once by each entry point (``launch.serve``,
``launch.train``, ``chip_smoke.py``) before it compiles anything, never
when the library is imported:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself; nothing is
  set here, so the cache goes there and nowhere else.
* unset — the cache goes to ``<checkout>/.jax_cache`` (gitignored).  The
  path is fixed on purpose: it is part of the cache's key, so a path
  built from a temporary name, a pid or the time would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure() -> str:
    """Place the persistent compile cache; returns the directory used."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
