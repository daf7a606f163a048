"""Bytes the WHOLE traced decode steps cannot avoid (the resident matrices
once a step, each hit expert once, the keys and values the full and the
window layers read), over the chunks' device busy time x the HBM
bandwidth."""
from benchmark import costs_gqa_moe, gqa_trace

UNIT, LAYER, MOVES = "%", "model", "serve_tokens_per_s"


def read(run):
    return gqa_trace.floor_share(
        run, lambda c, d, peaks: costs_gqa_moe.step_min_bytes(
            c["steps"], c["experts_hit"], c["full_tokens"],
            c["window_tokens"], d) / peaks["hbm_bytes_per_s"])
