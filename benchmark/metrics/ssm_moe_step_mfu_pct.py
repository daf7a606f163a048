"""Matmul FLOPs of the WHOLE traced decode steps (the matrices every row
multiplies by, the routed pairs the program counted, the recurrence, and
attention over the keys the attention block read), over the chunks' device
busy time x the bf16 peak: the share of the whole step's peak that bounds
any later claim in this cell."""
from benchmark import costs_ssm_moe, ssm_trace

UNIT, LAYER, MOVES = "%", "model", "serve_tokens_per_s"


def read(run):
    return ssm_trace.floor_share(
        run, lambda c, d, peaks: costs_ssm_moe.step_matmul_flops(
            c["state_rows"], c["expert_pairs"], c["latent_tokens"], d)
        / peaks["bf16_flops"])
