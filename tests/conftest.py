"""Test harness config.

Multi-chip logic is tested on CPU with a virtual 8-device mesh — the
TPU-native analogue of the reference's Spark local[N] + Engine.init(4,4)
trick (SURVEY.md section 4.6): fake the topology, exercise the real code
path.

The suite runs on the CPU whatever the machine holds: ``JAX_PLATFORMS``
defaults to ``cpu`` here and ``jax.config`` pins it before any backend is
initialised.  The chip is reached by ``python chip_smoke.py`` only.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax

from bigdl_tpu.compat import force_cpu_devices
from bigdl_tpu.utils.compile_cache import enable_compile_cache

jax.config.update("jax_platforms", "cpu")
force_cpu_devices(8)

# Persistent compilation cache: the fast tier is dominated by XLA:CPU
# compiles of programs that are byte-identical run to run; caching them
# (JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache) cuts repeat
# fast-tier wall time.  Correctness is fingerprint-keyed by jax
# (program + flags + versions), so a toolchain bump misses cleanly
# instead of reusing stale code.
enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)

# Kernel-tuning hermeticity (r14): a developer's warm ~/.cache tuning
# store must never reach the suite — tile lookups would serve that
# box's winners and make kernel tests depend on what was tuned before.
# Tests that exercise the store set their own dir (API > env wins).
os.environ.setdefault(
    "BIGDL_TPU_TUNE_DIR",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 ".tune_cache_test"))
