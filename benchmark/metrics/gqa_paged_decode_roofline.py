"""The full layers' paged read in decode (``paged_attention`` at 8 KV heads
of 8 query heads each over 640-slot tables): the keys and values of the
context tokens the active rows see (``full_tokens`` of the ``serve.decode``
spans, 4,096 bytes each a layer) read once, over the traced time under
``full/attn.paged``."""
from benchmark import costs_gqa_moe, gqa_trace

UNIT, LAYER, MOVES = "%", "kernels", "serve_tokens_per_s"


def read(run):
    return gqa_trace.floor_share(
        run, lambda c, d, peaks: costs_gqa_moe.gqa_read_floor_s(
            c["full_tokens"], d, peaks), ("full", "attn.paged"))
