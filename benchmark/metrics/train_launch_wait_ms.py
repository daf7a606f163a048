"""Device idle from the start of a step's ``train.dispatch`` span to the
first operation of that step's program, mean over the traced steps: the
input still in flight and the dispatch itself."""
from benchmark import loop_gaps, scope_events

UNIT, LAYER, MOVES = "ms", "trainer loop", "train_samples_per_s"


def read(run):
    scope_events.ops(run)       # prints the device seconds by scope
    gaps = loop_gaps.split_gaps(run)
    return 1e3 * sum(g[1] for g in gaps) / len(gaps) if gaps else None
