"""JAX persistent compilation cache for the launchers.

A cold start on the chip compiles the whole serving ladder (every decode
table-width bucket and every prefill chunk bucket of a 30-layer model);
the persistent cache lets the next process at the same path read those
executables back instead.  The cache key includes the directory, so it
must not move between runs:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, and nothing
    here overrides it.
  * otherwise: ``<checkout>/.jax_cache`` (listed in ``.gitignore``).

Only entry points call :func:`enable_compile_cache` (``launch.serve``,
``launch.train``, ``chip_smoke.py``); library code and tests never turn
the cache on.
"""

from __future__ import annotations

import os
import pathlib

#: the checkout root: src/repro/launch/compile_cache.py -> three levels up
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
