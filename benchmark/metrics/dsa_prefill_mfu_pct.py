"""The SPARSE matmul FLOPs of the window's prefills (each over its prompt's
REAL tokens ``tp`` and the routed pairs its ``serve.prefill`` span
counted: projections, held pairs, the causal indexer and attention over
each query's selected keys only) over the spans' seconds x the bf16 peak.
A prefill that multiplies more than the selection (dense attention
masked to it) reads low; a span is host time from dispatch to the first
token's read, never less than the device's, so the share reads low by the
host's part and cannot pass 100."""
from benchmark import costs_dsa, dsa_trace, gqa_trace

UNIT, LAYER, MOVES = "%", "model", "serve_tokens_per_s"


def read(run):
    got = gqa_trace.window_prefills(run)
    counted = [s for s in (got or {}).get("spans", ())
               if "keys_selected" in s.get("attrs", {})]
    seconds = sum(s.get("dur_s", 0.0) for s in counted)
    if not seconds:
        return None
    d = dsa_trace.dims(run)
    flops = sum(costs_dsa.prefill_sparse_flops(
        int(s["attrs"]["tp"]), int(s["attrs"]["expert_pairs"]), d)
        for s in counted)
    return 100.0 * flops / (seconds * run.peaks["bf16_flops"])
