"""Bytes the WHOLE traced decode steps of the looped model cannot avoid
(the stack's weights once a pass run, the head once a step, the keys and
values every layer of every pass read), over the chunks' device busy time
x the HBM bandwidth."""
from benchmark import costs_looped, looped_trace

UNIT, LAYER, MOVES = "%", "model", "serve_tokens_per_s"


def read(run):
    return looped_trace.floor_share(
        run, lambda c, d, peaks: costs_looped.step_min_bytes(
            c["steps"], c["latent_tokens"], c["passes"], d)
        / peaks["hbm_bytes_per_s"])
