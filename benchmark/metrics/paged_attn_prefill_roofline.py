"""The paged-attention kernel in prefill: the same arithmetic for the
traced prefill calls, each over its prompt's real length (causal, no
cached prefix in a mix that shares none).  Long prompts make it compute
bound, short ones memory bound; the floor takes the larger per call."""
from benchmark import costs, spans
from benchmark.peaks import roofline_floor_s

UNIT, LAYER, MOVES = "%", "kernels", "ttft_p50_ms"


def read(run):
    if run.trace is None or not run.trace.sync:
        return None
    cfg = run.cell.config
    calls = run.trace.runs(cfg["programs"]["prefill"])
    traced = run.trace.op_seconds_in(cfg["kernels"]["paged_attention"],
                                     calls)
    if not calls or not traced:
        return None
    off = (run.trace.sync["mono_ns"] - run.trace.sync["trace_ns"]) / 1e9
    t0, t1 = calls[0][0] / 1e9 + off - 0.05, calls[-1][1] / 1e9 + off
    kw = cfg["model"]["kwargs"]
    floor = 0.0
    for s in spans.spans_named(run.records, "serve.prefill"):
        if not t0 <= s["mono"] <= t1:
            continue
        a = s["attrs"]
        c = costs.paged_attention_costs(
            [a["shared_tokens"]], a["tp"] - a["shared_tokens"],
            kw["embed_dim"], cfg["server"]["page_size"])
        floor += kw["num_layers"] * roofline_floor_s(
            c["flops"], c["bytes"], run.peaks)[0]
    return 100.0 * floor / traced if floor else None
