"""The lightning indexer in decode (16 query heads of 64 against one key
head, over every visible key of a row's pages): the indexer keys of the
``keys_indexed`` tokens the active rows scored (one 128-lane bf16 row, 256
bytes, a token and layer) and each row-step's query read once, over the
traced time under ``dsa/indexer``."""
from benchmark import costs_dsa, dsa_trace

UNIT, LAYER, MOVES = "%", "kernels", "serve_tokens_per_s"


def read(run):
    return dsa_trace.floor_share(run, costs_dsa.indexer_read_floor_s,
                                 ("dsa", "indexer"))
