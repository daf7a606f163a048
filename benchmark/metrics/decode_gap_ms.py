"""Device idle between consecutive decode chunks, median."""
import statistics

UNIT, LAYER, MOVES = "ms", "scheduler", "serve_tokens_per_s"


def read(run):
    if run.trace is None:
        return None
    gaps = run.trace.idle_between_runs(run.cell.config["programs"]["decode"])
    return 1e3 * statistics.median(gaps) if gaps else None
