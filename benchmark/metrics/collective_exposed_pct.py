"""Time in collective operations while no other operation runs on that
device, over the traced window."""
UNIT, LAYER, MOVES = "%", "SPMD step", "train_samples_per_s"


def read(run):
    if run.trace is None or not run.trace.window_s():
        return None
    return 100.0 * run.trace.exposed_collective_s() / run.trace.window_s()
