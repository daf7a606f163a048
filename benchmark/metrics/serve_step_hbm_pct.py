"""Bytes the traced decode steps cannot avoid (the resident matrices once
a step, each hit expert once, each active row's state read and written,
the latents read), over the chunks' device busy time x the HBM
bandwidth."""
from benchmark import costs_hybrid, hybrid_trace

UNIT, LAYER, MOVES = "%", "model", "serve_tokens_per_s"


def read(run):
    return hybrid_trace.floor_share(
        run, lambda c, d, peaks: costs_hybrid.step_min_bytes(
            c["steps"], c["state_rows"], c["experts_hit"],
            c["latent_tokens"], d) / peaks["hbm_bytes_per_s"])
