"""Where JAX keeps its persistent compile cache.

One rule for every entry point that compiles kernels (``chip_smoke.py``,
``bench.py``, ``vtpu-scheduler``):

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and nothing
  here sets anything.
* Otherwise the cache lives in ``<checkout>/.jax_cache`` (gitignored).
  The directory is part of the cache's key, so it is fixed — never a
  temp name, a pid or a time, which would never hit.
* The cache turned off (``JAX_ENABLE_COMPILATION_CACHE=false``, as
  ``tests/conftest.py`` does for the suite): nothing is set either.
"""

from __future__ import annotations

import os
from typing import Optional

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def configure_compile_cache() -> Optional[str]:
    """Apply the rule above; returns the directory in use, or None when
    the cache is off."""
    import jax

    if not jax.config.jax_enable_compilation_cache:
        return None
    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
