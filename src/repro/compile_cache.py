"""Where JAX keeps its persistent compilation cache for this repo's scripts.

JAX reads `JAX_COMPILATION_CACHE_DIR` itself; when it is set the cache is
left where it points.  Otherwise the cache goes to `<checkout>/.jax_cache`,
a fixed path: the directory is part of the cache key, so a path built from
a temp name, a pid or the time would never hit.  Importing the library
sets nothing; scripts call `configure()` before their first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure() -> str:
    """Turn the persistent cache on and return its directory."""
    import jax
    path = os.environ.get(ENV)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
