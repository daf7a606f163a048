"""Bytes the WHOLE traced decode steps cannot avoid (the resident matrices
once a step, each hit expert once, the indexer keys scored, the keys and
values selected), over the chunks' device busy time x the HBM
bandwidth."""
from benchmark import costs_dsa, dsa_trace

UNIT, LAYER, MOVES = "%", "model", "serve_tokens_per_s"


def read(run):
    return dsa_trace.floor_share(
        run, lambda c, d, peaks: costs_dsa.step_min_bytes(c, d)
        / peaks["hbm_bytes_per_s"])
