"""Where the persistent XLA compile cache lives.

One policy for every entry point (``chip_smoke.py``, ``bench.py``,
``tests/conftest.py``, the model mains): ``JAX_COMPILATION_CACHE_DIR``
places the cache from outside — jax reads the variable itself, so no
directory is set in code when it is present; without it the cache goes
to ``<checkout>/.jax_cache`` (gitignored).  The path is part of the
cache key's world: a directory derived from a temporary name, a pid or
the time never hits, so the default is fixed by the package's location
and nothing else.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache`` — the parent of the package directory."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory.
    Call before the first compilation: jax initialises the cache once,
    at the first compile."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax
    path = default_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
