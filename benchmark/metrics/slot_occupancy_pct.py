"""Mean share of slots active per decode chunk inside the window, from
the difference of two ``stats()`` snapshots."""
UNIT, LAYER, MOVES = "%", "scheduler", "serve_tokens_per_s"


def read(run):
    return run.counters.get("occupancy_pct")
