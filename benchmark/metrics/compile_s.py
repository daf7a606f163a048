"""Seconds of ``backend_compile_duration`` events (compiles and cache
loads, ``jax.monitoring``) before the window."""
UNIT, LAYER, MOVES = "s", "set-up", "setup_s"


def read(run):
    return run.compile["setup_s"]
