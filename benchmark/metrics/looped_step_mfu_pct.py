"""Matmul FLOPs of the WHOLE traced decode steps of the looped model
(every layer's matrices in every pass a row ran, the head once, attention
over the keys every layer of every pass read), over the chunks' device
busy time x the bf16 peak."""
from benchmark import costs_looped, looped_trace

UNIT, LAYER, MOVES = "%", "model", "serve_tokens_per_s"


def read(run):
    return looped_trace.floor_share(
        run, lambda c, d, peaks: costs_looped.step_matmul_flops(
            c["state_rows"], c["latent_tokens"], c["passes"], d)
        / peaks["bf16_flops"])
