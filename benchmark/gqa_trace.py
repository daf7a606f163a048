"""What the readers of the window/full pattern model's metrics share
(``k_exaone_236b``): the traced decode chunks matched one to one with the
program's ``serve.decode`` spans that carry THIS model's counters, and
``floor_share`` over them; and the ``serve.prefill`` spans of the measured
window (``window_prefills``).  The matching is ``hybrid_trace``'s with other
counters (its tuple is its own: it asks for ``latent_tokens`` alone where
this model's spans say ``full_tokens`` and ``window_tokens`` too); the
device seconds under a pair of scopes are ``hybrid_trace.scope_seconds``
as it is.  A program without the counters or the scopes gives nothing, and
the readers report nothing."""

from __future__ import annotations

from typing import Optional, Tuple

from benchmark import costs_gqa_moe, hybrid_trace, spans

COUNTERS = ("expert_pairs", "experts_hit", "state_rows", "full_tokens",
            "window_tokens")
# the scopes this model adds, parent/child as ``jax.named_scope`` nests them
SCOPES = (("swa", "qkv"), ("swa", "kv.write"), ("swa", "attn.ring"),
          ("swa", "attn.prefill"), ("swa", "out"), ("full", "qkv"),
          ("full", "kv.write"), ("full", "attn.paged"),
          ("full", "attn.prefill"), ("full", "out"), ("moe", "router"),
          ("moe", "experts"), ("moe", "shared"))
SETTLE_S = 2.0      # after a profiled slice closed (``chunk_itl_p95_ms``)


def dims(run) -> dict:
    model = run.cell.config["model"]
    return costs_gqa_moe.dims(dict(model["kwargs"], vocab=model["args"][0]))


def traced_chunks(run) -> Optional[dict]:
    """The decode chunks inside the traced slice whose ``serve.decode``
    span carries the counters: their intervals (trace ns), their device
    busy seconds, and the counters summed (``steps`` too)."""
    tr = run.trace
    if tr is None or not tr.sync:
        return None
    program = run.cell.config["programs"]["decode"]
    off = (tr.sync["mono_ns"] - tr.sync["trace_ns"]) / 1e9
    decodes = [r for r in spans.spans_named(run.records, "serve.decode")
               if all(c in r.get("attrs", {}) for c in COUNTERS)]
    runs, total = [], dict.fromkeys(COUNTERS + ("steps",), 0)
    for a, b in tr.runs(program):
        # the span covers the chunk's dispatch and the read of its
        # results, so the run ends inside it
        t = b / 1e9 + off
        span = next((r for r in decodes
                     if r["mono"] <= t <= r["mono"] + r.get("dur_s", 0.0)),
                    None)
        if span is None:
            continue
        runs.append((a, b))
        for c in total:
            total[c] += int(span["attrs"][c])
    if not runs:
        return None
    return {"runs": runs, "busy_s": sum(tr.busy_in(iv) for iv in runs),
            **total}


def floor_share(run, floor_s, pair: Optional[Tuple[str, str]] = None
                ) -> Optional[float]:
    """100 x ``floor_s(chunks, dims, peaks)`` over the traced time under
    ``pair`` (or over the chunks' device busy time)."""
    chunks = traced_chunks(run)
    if chunks is None:
        return None
    traced = hybrid_trace.scope_seconds(run, chunks["runs"], pair) if pair \
        else chunks["busy_s"]
    if not traced:
        return None
    return 100.0 * floor_s(chunks, dims(run), run.peaks) / traced


def window_prefills(run) -> Optional[dict]:
    """The ``serve.prefill`` spans that lie whole inside the measured
    window: in a traced run only those that began ``SETTLE_S`` after the
    profiled slice closed, since the profiler's start and stop stall the
    process (``chunk_itl_p95_ms`` says the same of its gaps).  A span runs
    from the prefill's dispatch to the read of its first token, and the
    decoding rows wait all through it.  ``spans`` and the ``seconds`` of
    window they were taken from."""
    if not run.window:
        return None
    start, end = run.window
    tr = run.trace
    if tr is not None and tr.sync and tr.window():
        off = (tr.sync["mono_ns"] - tr.sync["trace_ns"]) / 1e9
        start = max(start, tr.window()[1] / 1e9 + off + SETTLE_S)
    inside = [r for r in spans.spans_named(run.records, "serve.prefill")
              if start <= r["mono"] and r["mono"] + r.get("dur_s", 0.0) <= end]
    if not inside or end <= start:
        return None
    return {"spans": inside, "seconds": end - start}
