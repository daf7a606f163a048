"""What the readers of the looped model's metrics share (``ouro_2_6b``): the
traced decode chunks matched one to one with the program's ``serve.decode``
spans that carry THIS model's counters (``loop_passes`` beside the
generator's ``state_rows`` and ``latent_tokens``), ``floor_share`` over
them, and the device seconds under the scope ``pass`` (``LoopedLM``: one
pass of the stack, ``pass/block/...`` and ``pass/norm_f``).  The matching
and the seconds under a pair of scopes are ``hybrid_trace``'s as they are.
A program without the counter or the scopes gives nothing, and the readers
report nothing."""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

from benchmark import costs_looped, hybrid_trace, scope_events, spans

COUNTERS = ("loop_passes", "state_rows", "latent_tokens")
PASS = "pass"
# the scopes this model adds, parent/child as ``jax.named_scope`` nests them
SCOPES = (("pass", "block"), ("pass", "norm_f"), ("block", "norm_in"),
          ("block", "attn"), ("attn", "qkv"), ("attn", "kv.write"),
          ("attn", "attn.paged"), ("attn", "attn.prefill"), ("attn", "out"),
          ("block", "norm_post"), ("block", "mlp"))


def dims(run) -> dict:
    model = run.cell.config["model"]
    return costs_looped.dims(dict(model["kwargs"], vocab=model["args"][0]))


def traced_chunks(run) -> Optional[dict]:
    """The decode chunks inside the traced slice whose ``serve.decode``
    span carries the counters: their intervals (trace ns), their device
    busy seconds, the counters summed (``steps`` too) and ``passes``, the
    passes a step ran (``loop_passes`` over ``state_rows``)."""
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
    if not runs or not total["state_rows"]:
        return None
    return {"runs": runs, "busy_s": sum(tr.busy_in(iv) for iv in runs),
            "passes": total["loop_passes"] / total["state_rows"], **total}


def pass_seconds(run, runs: Sequence[Tuple[float, float]]
                 ) -> Optional[float]:
    """Device seconds of the operations under the scope ``pass`` that start
    inside ``runs``; None where no operation of the slice is."""
    inside = [o for o in scope_events.ops(run)
              if PASS in scope_events.scopes(o[2])
              and any(a <= o[3] < b for a, b in runs)]
    return sum(o[4] for o in inside) / 1e9 if inside else None


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
