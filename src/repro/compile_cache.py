"""JAX's persistent compilation cache for this repository's entry points.

Every replay shape compiles once per process; the persistent cache lets
the next process on the same machine load those executables instead of
recompiling them.  Call :func:`configure` before any JAX work:

  - if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this module sets no other directory;
  - otherwise the cache lives in ``<repo>/.jax_cache`` — a fixed path
    (the path is part of the cache's key, so it must not move between
    runs), listed in ``.gitignore``.

Replay shapes typically compile in well under JAX's default one-second
threshold for caching, so the threshold is set to zero.
"""

from __future__ import annotations

import os
from pathlib import Path

DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
