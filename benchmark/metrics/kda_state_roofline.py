"""The delta-rule state update in decode: every active row's float32
state read and written once a layer (``state_rows`` of the ``serve.decode``
spans), over the traced time under ``kda/state``."""
from benchmark import costs_hybrid, hybrid_trace

UNIT, LAYER, MOVES = "%", "kernels", "serve_tokens_per_s"


def read(run):
    return hybrid_trace.floor_share(
        run, lambda c, d, peaks: costs_hybrid.kda_state_floor_s(
            c["state_rows"], d, peaks), ("kda", "state"))
