"""Share of the measured window spent inside ``serve.prefill`` spans
(dispatch of a prompt's prefill to the read of its first token): the
generator runs one program at a time, so every decoding row waits through
each of them.  Over the whole window, not the traced slice, which holds
one or two prefills of whatever bucket fell into it."""
from benchmark import gqa_trace

UNIT, LAYER, MOVES = "%", "scheduler", "serve_tokens_per_s"


def read(run):
    got = gqa_trace.window_prefills(run)
    if got is None:
        return None
    return 100.0 * sum(s.get("dur_s", 0.0) for s in got["spans"]) / got["seconds"]
