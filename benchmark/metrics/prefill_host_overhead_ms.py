"""What the host adds around the prefill program: per traced prefill, the
request's ``t_first - t_admit`` (``serve.request``: ``ttft_s - queue_s``)
less the device's busy time inside that request's run of the program,
joined by the clock pair of ``bench.sync``; median."""
import statistics

from benchmark import scope_events

UNIT, LAYER, MOVES = "ms", "scheduler", "ttft_p50_ms"


def read(run):
    scope_events.ops(run)       # prints the device seconds by scope
    tr = run.trace
    if tr is None or not tr.sync:
        return None
    off = (tr.sync["mono_ns"] - tr.sync["trace_ns"]) / 1e9
    placed = [(r["t_submit"] + r["queue_s"], r["t_submit"] + r["ttft_s"])
              for r in run.records
              if r.get("type") == "serve.request"
              and "queue_s" in r and "ttft_s" in r]
    over = []
    for iv in tr.runs(run.cell.config["programs"]["prefill"]):
        began = iv[0] / 1e9 + off           # on the program's clock
        mine = [(a, f) for a, f in placed if a <= began <= f]
        if len(mine) == 1:
            a, f = mine[0]
            over.append(1e3 * ((f - a) - tr.busy_in(iv)))
    return statistics.median(over) if over else None
