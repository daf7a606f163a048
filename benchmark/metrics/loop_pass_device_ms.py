"""Device time of ONE pass of the looped model's stack inside a decode
step: the traced chunks' device seconds under the scope ``pass`` over
their steps x the passes a step ran (``loop_passes`` / ``state_rows``)."""
from benchmark import looped_trace

UNIT, LAYER, MOVES = "ms", "model", "serve_tokens_per_s"


def read(run):
    chunks = looped_trace.traced_chunks(run)
    if chunks is None or not chunks["steps"]:
        return None
    seconds = looped_trace.pass_seconds(run, chunks["runs"])
    if not seconds:
        return None
    return 1e3 * seconds / (chunks["steps"] * chunks["passes"])
