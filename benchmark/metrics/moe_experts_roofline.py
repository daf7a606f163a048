"""The held experts' product in decode: per traced chunk the larger of
reading each hit expert's weights once and of multiplying the routed
pairs (the program's ``experts_hit`` and ``expert_pairs`` on its
``serve.decode`` spans: what ANY implementation must move), over the
traced time under ``moe/experts`` (a decode step takes every held expert
over its rows, so the share cannot pass hit / held)."""
from benchmark import costs_hybrid, hybrid_trace

UNIT, LAYER, MOVES = "%", "kernels", "serve_tokens_per_s"


def read(run):
    return hybrid_trace.floor_share(
        run, lambda c, d, peaks: costs_hybrid.moe_experts_floor_s(
            c["expert_pairs"], c["experts_hit"], d, peaks),
        ("moe", "experts"))
