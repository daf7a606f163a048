"""How late the load generator sent a request: send time less due time,
95th percentile over the requests due in the window (in a traced run: those
due before the profiled slice opened in the middle of the window)."""
from benchmark.stats import percentile

UNIT, LAYER, MOVES = "ms", "load generator", "ttft_p95_ms"


def read(run):
    return percentile(run.samples.get("late_ms") or [], 95)
