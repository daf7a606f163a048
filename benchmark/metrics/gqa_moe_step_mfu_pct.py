"""Matmul FLOPs of the WHOLE traced decode steps (the matrices every row
multiplies by, the routed pairs the program counted, attention over the
keys the full and the window layers read), over the chunks' device busy
time x the bf16 peak."""
from benchmark import costs_gqa_moe, gqa_trace

UNIT, LAYER, MOVES = "%", "model", "serve_tokens_per_s"


def read(run):
    return gqa_trace.floor_share(
        run, lambda c, d, peaks: costs_gqa_moe.step_matmul_flops(
            c["state_rows"], c["expert_pairs"], c["full_tokens"],
            c["window_tokens"], d) / peaks["bf16_flops"])
