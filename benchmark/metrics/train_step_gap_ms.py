"""Device idle per step between one step's last operation and the next
one's first, mean over the traced steps."""
UNIT, LAYER, MOVES = "ms", "trainer loop", "train_samples_per_s"


def read(run):
    if run.trace is None:
        return None
    gaps = run.trace.idle_between_runs(run.cell.config["programs"]["step"])
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
