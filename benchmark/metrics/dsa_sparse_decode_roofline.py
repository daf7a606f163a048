"""Attention over the SELECTED keys in decode (at most 2,048 a row and
layer, gathered at token granularity from the pools): the keys and values
of the ``keys_selected`` tokens (2 KB a token and layer) and each
row-step's query and output read once, over the traced time under
``dsa/attn.sparse``.  A read of more than the selected rows reads low."""
from benchmark import costs_dsa, dsa_trace

UNIT, LAYER, MOVES = "%", "kernels", "serve_tokens_per_s"


def read(run):
    return dsa_trace.floor_share(run, costs_dsa.sparse_read_floor_s,
                                 ("dsa", "attn.sparse"))
