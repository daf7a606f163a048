"""Matmul FLOPs of the window's prefills (each over its prompt's REAL
tokens ``tp`` and the routed pairs its ``serve.prefill`` span counted: the
chunked scan, the flash path of the attention block and the grouped expert
product are all in it) over the spans' seconds x the bf16 peak.  A span is
host time from dispatch to the first token's read, never less than the
device's, so the share reads low by the host's part and cannot pass 100."""
from benchmark import costs_ssm_moe, gqa_trace, ssm_trace

UNIT, LAYER, MOVES = "%", "model", "serve_tokens_per_s"


def read(run):
    d = ssm_trace.dims(run)
    got = gqa_trace.window_prefills(run) if d else None
    counted = [s for s in (got or {}).get("spans", ())
               if "expert_pairs" in s.get("attrs", {})]
    seconds = sum(s.get("dur_s", 0.0) for s in counted)
    if not seconds:
        return None
    flops = sum(costs_ssm_moe.prefill_matmul_flops(
        int(s["attrs"]["tp"]), int(s["attrs"]["expert_pairs"]), d)
        for s in counted)
    return 100.0 * flops / (seconds * run.peaks["bf16_flops"])
