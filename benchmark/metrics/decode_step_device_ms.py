"""Device busy time per decode step: busy time inside the traced runs of
the decode-chunk program over chunks x steps a chunk."""
UNIT, LAYER, MOVES = "ms", "model", "serve_tokens_per_s"


def read(run):
    if run.trace is None:
        return None
    cfg = run.cell.config
    busy = run.trace.busy_per_run(cfg["programs"]["decode"])
    steps = len(busy) * int(cfg["server"]["steps_per_sync"])
    return 1e3 * sum(busy) / steps if steps else None
