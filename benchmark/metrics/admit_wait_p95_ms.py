"""95th percentile of ``queue_s`` (the scheduler took the request out of
the queue, less its submission) over the ``serve.request`` records whose
``t_submit`` lies in the clean part of the window (in a traced run: before
the profiled slice opened)."""
from benchmark.stats import percentile

UNIT, LAYER, MOVES = "ms", "scheduler", "ttft_p95_ms"


def read(run):
    start = run.window[0]
    end = run.samples.get("clean_until", run.window[1])
    waits = [1e3 * r["queue_s"] for r in run.records
             if r.get("type") == "serve.request" and "queue_s" in r
             and start <= r.get("t_submit", -1.0) < end]
    return percentile(waits, 95)
