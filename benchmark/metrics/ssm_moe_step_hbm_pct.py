"""Bytes the WHOLE traced decode steps cannot avoid (the resident matrices
once a step, each hit expert once, each active row's state read and
written in every Mamba-2 block, the keys and values the attention block
read), over the chunks' device busy time x the HBM bandwidth."""
from benchmark import costs_ssm_moe, ssm_trace

UNIT, LAYER, MOVES = "%", "model", "serve_tokens_per_s"


def read(run):
    return ssm_trace.floor_share(
        run, lambda c, d, peaks: costs_ssm_moe.step_min_bytes(
            c["steps"], c["state_rows"], c["experts_hit"],
            c["latent_tokens"], d) / peaks["hbm_bytes_per_s"])
