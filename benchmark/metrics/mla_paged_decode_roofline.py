"""The latent-attention layer's paged read in decode: the latents of the
context tokens the active rows see (``latent_tokens`` of the
``serve.decode`` spans, 1,152 bytes each) read once, over the traced time
under ``mla/attn.paged``."""
from benchmark import costs_hybrid, hybrid_trace

UNIT, LAYER, MOVES = "%", "kernels", "serve_tokens_per_s"


def read(run):
    return hybrid_trace.floor_share(
        run, lambda c, d, peaks: costs_hybrid.mla_read_floor_s(
            c["latent_tokens"], d, peaks), ("mla", "attn.paged"))
