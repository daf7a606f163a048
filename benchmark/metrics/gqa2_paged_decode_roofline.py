"""The attention block's paged read in decode (``paged_attention`` at 2 KV
heads of 16 query heads each over 640-slot tables): the keys and values of
the context tokens the active rows see (``latent_tokens`` of the
``serve.decode`` spans, 1,024 bytes each) read once, over the traced time
under ``full/attn.paged``."""
from benchmark import costs_ssm_moe, ssm_trace

UNIT, LAYER, MOVES = "%", "kernels", "serve_tokens_per_s"


def read(run):
    return ssm_trace.floor_share(
        run, lambda c, d, peaks: costs_ssm_moe.gqa2_read_floor_s(
            c["latent_tokens"], d, peaks), ("full", "attn.paged"))
