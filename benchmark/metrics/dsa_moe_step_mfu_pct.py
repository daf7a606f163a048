"""Matmul FLOPs of the WHOLE traced decode steps (the matrices every row
multiplies by, the routed pairs the program counted, the indexer over the
keys it scored, attention over the keys it selected), over the chunks'
device busy time x the bf16 peak."""
from benchmark import costs_dsa, dsa_trace

UNIT, LAYER, MOVES = "%", "model", "serve_tokens_per_s"


def read(run):
    return dsa_trace.floor_share(
        run, lambda c, d, peaks: costs_dsa.step_matmul_flops(c, d)
        / peaks["bf16_flops"])
