"""JAX's persistent compilation cache for the repo's entry points.

Call :func:`enable_compile_cache` at the top of a ``main()``, before the
first compile -- never at import, so importing a module changes no global
JAX state.  The cache key includes the directory, so the directory is a
fixed path: a run finds what an earlier run on the same checkout compiled.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
#: the default cache directory: ``.jax_cache`` at the checkout's root
#: (listed in .gitignore)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it and this
    sets nothing; otherwise the cache goes to :data:`DEFAULT_DIR`."""
    env = os.environ.get(ENV)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
