"""Device busy time per training step in the traced slice."""
UNIT, LAYER, MOVES = "ms", "model", "train_samples_per_s"


def read(run):
    if run.trace is None:
        return None
    steps = len(run.trace.runs(run.cell.config["programs"]["step"]))
    return 1e3 * run.trace.busy_s() / steps if steps else None
