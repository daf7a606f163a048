"""The two jax names the tree imports from one place.

``shard_map`` is ``jax.shard_map`` (keywords ``mesh=``, ``in_specs=``,
``out_specs=``, ``check_vma=``); ``force_cpu_devices`` is the
``jax_num_cpu_devices`` config.  The code targets the one installation
there is (jax 0.9): a name this jax does not offer is an ImportError at
collection time, not a silent second path.  The module stays so the
import sites and graftlint's traced-region discovery
(``analysis/context.py``) keep one spelling.
"""

from __future__ import annotations

import jax
from jax import shard_map  # noqa: F401 — re-exported

__all__ = ["shard_map", "force_cpu_devices"]


def force_cpu_devices(n: int) -> None:
    """Ask for ``n`` virtual CPU devices (the local[N] test topology).
    Call before any ``jax.devices()``/array op instantiates the CPU
    backend."""
    jax.config.update("jax_num_cpu_devices", n)
