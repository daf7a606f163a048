"""Share of the traced decode chunks' device time spent under
``dsa/select``: the exact top 2,048 of each row's indexer scores, a
sort-like step that does no arithmetic the model needs; lower is better."""
from benchmark import dsa_trace

UNIT, LAYER, MOVES = "%", "kernels", "serve_tokens_per_s"


def read(run):
    return dsa_trace.scope_share(run, ("dsa", "select"))
