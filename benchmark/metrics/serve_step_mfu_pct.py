"""Matmul FLOPs of the traced decode steps (the matrices every row
multiplies by, the routed pairs the program counted, the absorbed latent
attention), over the chunks' device busy time x the bf16 peak."""
from benchmark import costs_hybrid, hybrid_trace

UNIT, LAYER, MOVES = "%", "model", "serve_tokens_per_s"


def read(run):
    return hybrid_trace.floor_share(
        run, lambda c, d, peaks: costs_hybrid.step_matmul_flops(
            c["state_rows"], c["expert_pairs"], c["latent_tokens"], d)
        / peaks["bf16_flops"])
