"""1 - union of the device's operation intervals over the traced window
(an open loop below its knee idles by design: read it with the gaps'
attribution in the breakdown)."""
UNIT, LAYER, MOVES = "%", "device", "ttft_p50_ms"


def read(run):
    return run.trace.idle_pct() if run.trace is not None else None
