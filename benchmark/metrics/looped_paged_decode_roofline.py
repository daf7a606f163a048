"""The looped model's paged read in decode (``paged_attention`` at 16 KV
heads of one query head each, on pool ``pass x 48 + layer`` of the stacked
pools): the keys and values of the context tokens the active rows see
(``latent_tokens`` of the ``serve.decode`` spans) read once in every layer
of every pass a step ran, 8,192 bytes a token a layer, over the traced
time under ``attn/attn.paged`` x the HBM bandwidth."""
from benchmark import costs_looped, looped_trace

UNIT, LAYER, MOVES = "%", "kernels", "serve_tokens_per_s"


def read(run):
    return looped_trace.floor_share(
        run, lambda c, d, peaks: costs_looped.kv_read_bytes(
            c["latent_tokens"], c["passes"], d) / peaks["hbm_bytes_per_s"],
        ("attn", "attn.paged"))
