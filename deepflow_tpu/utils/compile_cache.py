"""JAX's persistent compilation cache, placed from outside.

Entry points call `configure()` once (chip_smoke.py, the server's
main, bench.py); nothing calls it at import, so tests never get a
cache. Where JAX_COMPILATION_CACHE_DIR is set, JAX already reads it and
no other directory is set here. Otherwise the cache lives at one fixed
path, `<repo>/.jax_cache` (git-ignored): the path is part of the cache
key, so a directory named after a temp dir, a pid or the time would
never hit.
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    import jax

    path = os.environ.get(ENV) or DEFAULT_DIR
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    # every program of the sketch path compiles in well under JAX's
    # default 1 s floor for caching; cache them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
