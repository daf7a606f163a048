"""95th percentile of the time between two deliveries of tokens to one
stream (``gaps_s`` of the ``serve.request`` records; prefills of other
requests included), over the deliveries inside the window; in a traced
run only those after the profiled slice closed, since the profiler's
start and stop stall the process.  The forerunner of ``itl_p95_ms``."""
from benchmark import scope_events
from benchmark.stats import percentile

UNIT, LAYER, MOVES = "ms", "scheduler", "serve_tokens_per_s"
SETTLE_S = 2.0              # after the slice's last operation


def read(run):
    scope_events.ops(run)       # prints the device seconds by scope
    start, end = run.window
    tr = run.trace
    if tr is not None and tr.sync and tr.window():
        off = (tr.sync["mono_ns"] - tr.sync["trace_ns"]) / 1e9
        start = max(start, tr.window()[1] / 1e9 + off + SETTLE_S)
    gaps = []
    for r in run.records:
        if r.get("type") != "serve.request" or "gaps_s" not in r:
            continue
        t = r["t_submit"] + r["ttft_s"]     # the first delivery
        for g in r["gaps_s"]:
            t += g
            if start <= t - g and t <= end:
                gaps.append(1e3 * g)
    return percentile(gaps, 95)
