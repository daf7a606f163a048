"""Device idle from the last operation of a step's program to the start
of the next ``train.dispatch`` span, mean over the traced steps: the sync's
return, ``loop.bookkeeping``, ``data.next`` and the ``h2d`` enqueue.  With
``train_launch_wait_ms`` it sums to ``train_step_gap_ms``."""
from benchmark import loop_gaps

UNIT, LAYER, MOVES = "ms", "trainer loop", "train_samples_per_s"


def read(run):
    gaps = loop_gaps.split_gaps(run)
    return 1e3 * sum(g[0] for g in gaps) / len(gaps) if gaps else None
