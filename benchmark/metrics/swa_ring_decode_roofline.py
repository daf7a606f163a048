"""The window layers' ring read in decode: the keys and values of the ring
rows the active rows see (``window_tokens`` of the ``serve.decode`` spans,
at most 128 a row, 4,096 bytes each a layer) read once, over the traced
time under ``swa/attn.ring``."""
from benchmark import costs_gqa_moe, gqa_trace

UNIT, LAYER, MOVES = "%", "kernels", "serve_tokens_per_s"


def read(run):
    return gqa_trace.floor_share(
        run, lambda c, d, peaks: costs_gqa_moe.ring_read_floor_s(
            c["window_tokens"], d, peaks), ("swa", "attn.ring"))
