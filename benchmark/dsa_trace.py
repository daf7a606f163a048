"""What the readers of the sparse-attention pattern model's metrics share
(``keye_vl2_30b_a3b``): the traced decode chunks matched one to one with
the program's ``serve.decode`` spans that carry THIS model's counters, and
``floor_share`` over them.  The matching is ``gqa_trace``'s with other
counters (``keys_indexed`` and ``keys_selected`` where that model's spans
say ``full_tokens`` and ``window_tokens``); the device seconds under a pair
of scopes are ``hybrid_trace.scope_seconds`` as it is, and the window's
prefills ``gqa_trace.window_prefills``.  A program without the counters or
the scopes gives nothing, and the readers report nothing."""

from __future__ import annotations

from typing import Optional, Tuple

from benchmark import costs_dsa, hybrid_trace, spans

COUNTERS = ("expert_pairs", "experts_hit", "state_rows", "keys_indexed",
            "keys_selected")
# the scopes this model adds, parent/child as ``jax.named_scope`` nests them
SCOPES = (("dsa", "qkv"), ("dsa", "index.qk"), ("dsa", "kv.write"),
          ("dsa", "indexer"), ("dsa", "select"), ("dsa", "attn.sparse"),
          ("dsa", "attn.prefill"), ("dsa", "out"), ("moe", "router"),
          ("moe", "experts"))


def dims(run) -> dict:
    model = run.cell.config["model"]
    return costs_dsa.dims(dict(model["kwargs"], vocab=model["args"][0]))


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


def scope_share(run, pair: Tuple[str, str]) -> Optional[float]:
    """100 x the traced decode chunks' device seconds under ``pair`` over
    their device busy seconds."""
    chunks = traced_chunks(run)
    if chunks is None or not chunks["busy_s"]:
        return None
    under = hybrid_trace.scope_seconds(run, chunks["runs"], pair)
    return None if under is None else 100.0 * under / chunks["busy_s"]
