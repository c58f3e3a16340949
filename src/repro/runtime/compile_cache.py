"""Where JAX's persistent compilation cache lives for this checkout."""
from __future__ import annotations

import os
from typing import Optional

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/src/repro/runtime/compile_cache.py -> <checkout>/.jax_cache
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> Optional[str]:
    """Turn on the persistent compilation cache for this process and
    return its directory. Call before the first compile.

    ``$JAX_COMPILATION_CACHE_DIR``, when set, names the directory (JAX
    reads the variable itself; nothing here overrides it). Otherwise the
    cache is ``<checkout>/.jax_cache``: a fixed path, because the path
    is part of what lets a later process find an entry. Child processes
    inherit either choice: the variable through their environment, the
    default because they import the same checkout. Every program is
    cached, however short its compile: the serving programs compile in
    well under JAX's default one-second floor.

    A process held to the CPU (``JAX_PLATFORMS=cpu``) keeps no cache and
    gets ``None``: XLA:CPU programs recompile in moments, and loading
    them back logs machine-feature errors on every hit."""
    if jax.config.jax_platforms == "cpu":
        return None
    path = os.environ.get(ENV_VAR)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
