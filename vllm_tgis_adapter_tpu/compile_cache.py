"""Where JAX's persistent compilation cache lives: ONE place decides.

The cache directory is part of the cache key, so a path that moves (a
temporary name, a pid, the time) never hits.  Every process of this repo
that compiles — the server, the tools — calls
:func:`configure_compile_cache` before its first compile and sets no
cache path anywhere else.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the fixed default: ``<checkout>/.jax_cache`` beside the package
#: (git-ignored)
DEFAULT_DIR = Path(__file__).resolve().parent.parent / ".jax_cache"


def configure_compile_cache() -> str:
    """Return the cache directory in force.  A set
    ``JAX_COMPILATION_CACHE_DIR`` is JAX's own business (it reads the
    variable itself) and nothing is set in code; otherwise the cache
    goes to :data:`DEFAULT_DIR`."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
