"""Start of a request's ``serve.prefill`` span less its due time, median
over the requests due in the window (in a traced run: those due before the
profiled slice opened in the middle of the window, since the profiler's
start and stop stall the process).  Requests and spans are both in
admission order (one sender, a FIFO queue), so the k-th span is the k-th
request sent."""
import statistics

from benchmark.spans import spans_named

UNIT, LAYER, MOVES = "ms", "scheduler", "ttft_p95_ms"


def read(run):
    sent = run.samples.get("sent")
    prefills = sorted(spans_named(run.records, "serve.prefill"),
                      key=lambda r: r["attrs"]["rid"])
    if not sent or len(prefills) != len(sent):
        return None
    start, end = run.window[0], run.samples["clean_until"]
    waits = [(span["mono"] - rec["t_due"]) * 1e3
             for span, rec in zip(prefills, sent)
             if start <= rec["t_due"] < end]
    return statistics.median(waits) if waits else None
