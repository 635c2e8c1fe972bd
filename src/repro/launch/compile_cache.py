"""Where JAX keeps its persistent compilation cache.

``configure()`` is called from the ``main()`` of each entry point
(``chip_smoke.py``, ``repro.launch.serve``, ``benchmarks/serve_bench.py``,
``benchmarks/run.py``), never when a module is imported:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX read it when it was imported, and
  nothing else is set, so whoever runs the program decides where the
  cache lives and whether it outlives the run.
* otherwise: ``<checkout>/.jax_cache`` (listed in ``.gitignore``). The
  path is fixed because it is part of what a cache entry is found by: a
  directory that moves never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure() -> str:
    """Point JAX's compilation cache at its directory; returns the path."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
