"""Where JAX keeps its persistent compilation cache for this checkout.

Entry points that compile (``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.train``, ``benchmarks.run``) call
:func:`place_compile_cache` before anything compiles.  Importing ``repro``
never places the cache: tests and library users keep JAX's own default.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, so nothing is
  set here.
* Otherwise the cache goes to ``.jax_cache/`` at the checkout root (git
  ignores it).  The path is fixed because it is part of every cache key:
  a directory that moved between runs would never hit.
"""

from __future__ import annotations

import os
import pathlib

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/launch/``)
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def place_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory
    unless ``JAX_COMPILATION_CACHE_DIR`` already does; returns the
    directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
