"""Device busy time per prefill call, median over the traced calls."""
import statistics

UNIT, LAYER, MOVES = "ms", "model", "ttft_p50_ms"


def read(run):
    if run.trace is None:
        return None
    busy = run.trace.busy_per_run(run.cell.config["programs"]["prefill"])
    return 1e3 * statistics.median(busy) if busy else None
