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
import re

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_SOURCE_REGEX = "jax_hlo_source_file_canonicalization_regex"


def checkout_root() -> str:
    """The directory that holds the package (the checkout's root)."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.dirname(pkg)


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache`` — the parent of the package directory."""
    return os.path.join(checkout_root(), ".jax_cache")


def canonical_source_paths() -> None:
    """Record source files relative to the checkout in what jax lowers.

    jax strips locations from a program before it hashes it, but not
    from a Pallas kernel: the Mosaic module travels as the custom call's
    payload with its debug locations, the innermost frames of the Python
    stack with ABSOLUTE file names.  So the same program lowered from two
    copies of this repository (a parent and a change side by side, an
    exported tree, a benchmark's second checkout) got two cache keys for
    exactly the programs that hold a kernel — the generation server's
    prefill rungs and its decode chunk, 80 s of compile — while every
    other program hit (found in PR 24; before, it read as "traced
    serving runs miss the cache").  With the checkout's root removed the
    names are ``bigdl_tpu/ops/attention.py`` wherever the tree lies.  A
    regex the user already set is left alone."""
    import jax
    if not getattr(jax.config, _SOURCE_REGEX, None):
        jax.config.update(_SOURCE_REGEX,
                          re.escape(checkout_root() + os.sep))


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory.
    Call before the first compilation: jax initialises the cache once,
    at the first compile."""
    canonical_source_paths()
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax
    path = default_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
