"""What the readers of the layer-pattern model's metrics share: the traced
decode chunks matched one to one with the program's ``serve.decode`` spans
(which carry the chunk's counters), and the device seconds under a pair of
nested scopes inside those chunks.  A program without the counters or the
scopes (the parent of the PR that added them) gives nothing, and the
readers report nothing."""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

from benchmark import costs_hybrid, scope_events, spans

COUNTERS = ("expert_pairs", "experts_hit", "state_rows", "latent_tokens")
# the scopes this model adds, parent/child as ``jax.named_scope`` nests them
SCOPES = (("kda", "conv"), ("kda", "gates"), ("kda", "state"),
          ("mla", "absorb"), ("mla", "kv.write"), ("mla", "attn.paged"),
          ("mla", "attn.expand"), ("moe", "router"), ("moe", "experts"),
          ("moe", "shared"))


def dims(run) -> dict:
    model = run.cell.config["model"]
    return costs_hybrid.dims(dict(model["kwargs"], vocab=model["args"][0]))


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


# A scope whose heaviest operations the compiler leaves WITHOUT the scope's
# path: ``lax.ragged_dot`` becomes custom calls whose path is their own
# name (``ragged-dot-none``, ``ragged-dot-metadata``: first chip run of PR
# 27, where they were 0.45 s of a 0.73 s slice and ``moe/experts`` read
# 0.005 s).  The configuration's ``kernels`` names the prefix.
KERNEL_OF = {("moe", "experts"): "moe_experts"}


def _under(path: str, pair: Tuple[str, str], prefix: Optional[str]) -> bool:
    if prefix and path.startswith(prefix):
        return True
    sc = scope_events.scopes(path)
    return any(sc[i] == pair[0] and sc[i + 1] == pair[1]
               for i in range(len(sc) - 1))


def scope_seconds(run, runs: Sequence[Tuple[float, float]],
                  pair: Tuple[str, str]) -> Optional[float]:
    """Device seconds of the operations under ``parent/child`` (and of the
    custom calls the configuration files under it) that start inside
    ``runs``; None where no operation of the slice is."""
    prefix = run.cell.config.get("kernels", {}).get(KERNEL_OF.get(pair))
    inside = [o for o in scope_events.ops(run)
              if _under(o[2], pair, prefix)
              and any(a <= o[3] < b for a, b in runs)]
    return sum(o[4] for o in inside) / 1e9 if inside else None


def floor_share(run, floor_s, pair: Optional[Tuple[str, str]] = None
                ) -> Optional[float]:
    """100 x ``floor_s(chunks, dims, peaks)`` over the traced time under
    ``pair`` (or over the chunks' device busy time)."""
    chunks = traced_chunks(run)
    if chunks is None:
        return None
    traced = scope_seconds(run, chunks["runs"], pair) if pair \
        else chunks["busy_s"]
    if not traced:
        return None
    return 100.0 * floor_s(chunks, dims(run), run.peaks) / traced
