"""Compile events inside the window; should be 0."""
UNIT, LAYER, MOVES = "count", "set-up", "setup_s"


def read(run):
    return run.compile["window"]
