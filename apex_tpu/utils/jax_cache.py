"""The one place that points JAX's persistent compilation cache somewhere.

Entry points call :func:`enable_compile_cache` (``chip_smoke.py``,
``bench.py``, ``bench_kernels.py``, ``examples/*.py``, the cluster
worker's ``main``); importing the package never does.  The directory is
part of the cache key, so it must not move between runs:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX maps the variable onto
  ``jax_compilation_cache_dir`` itself — this module sets nothing;
- unset: ``<checkout>/.jax_cache`` (git-ignored), derived from this
  file's location — never a temp name, a pid or a time.
"""

from __future__ import annotations

import os
from typing import Optional

__all__ = ["enable_compile_cache", "default_cache_dir"]


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache`` — three levels up from this file."""
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.join(os.path.dirname(os.path.dirname(here)),
                        ".jax_cache")


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent compile cache on; returns the directory this
    call set, or ``None`` when ``JAX_COMPILATION_CACHE_DIR`` already
    decides it."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    import jax

    path = default_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
