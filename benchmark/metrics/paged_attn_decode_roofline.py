"""The paged-attention kernel in decode: bytes of the K and V pages a row
really has to read and 4 x context x width FLOPs per row, from the
positions the traced chunks ran at, over the kernel's traced time inside
the decode-chunk program.  Decode reads a whole cache for one query row,
so the memory bound applies."""
from benchmark import costs, spans
from benchmark.peaks import roofline_floor_s

UNIT, LAYER, MOVES = "%", "kernels", "serve_tokens_per_s"


def read(run):
    if run.trace is None or not run.trace.sync:
        return None
    cfg = run.cell.config
    chunks = run.trace.runs(cfg["programs"]["decode"])
    traced = run.trace.op_seconds_in(cfg["kernels"]["paged_attention"],
                                     chunks)
    if not chunks or not traced:
        return None
    # the traced chunks, on the program's monotonic clock
    off = (run.trace.sync["mono_ns"] - run.trace.sync["trace_ns"]) / 1e9
    t0, t1 = chunks[0][0] / 1e9 + off, chunks[-1][0] / 1e9 + off
    steps = spans.decode_contexts(run.records, t0 - 0.05, t1 + 0.05)
    kw = cfg["model"]["kwargs"]
    floor = 0.0
    for ctxs in steps:
        c = costs.paged_attention_costs(ctxs, 1, kw["embed_dim"],
                                        cfg["server"]["page_size"])
        floor += kw["num_layers"] * roofline_floor_s(
            c["flops"], c["bytes"], run.peaks)[0]
    return 100.0 * floor / traced if floor else None
