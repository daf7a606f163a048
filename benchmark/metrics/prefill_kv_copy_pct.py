"""Share of the device time inside the prefill runs spent in copy and
layout operations whose scope path holds ``attn.paged`` or ``kv.write``:
the relayout of the page pool around the cache's write and its paged
read."""
from benchmark import scope_events

UNIT, LAYER, MOVES = "%", "model", "ttft_p50_ms"


def read(run):
    return scope_events.copy_share_pct(
        run, run.cell.config["programs"]["prefill"], scope_events.KV_SCOPES)
