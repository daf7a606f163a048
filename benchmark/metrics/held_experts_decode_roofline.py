"""The held experts' product in decode at this configuration's shapes (16
held experts of 6144 x 2048): per traced chunk the larger of reading each
hit expert's weights once and of multiplying the routed pairs
(``experts_hit`` and ``expert_pairs`` of the ``serve.decode`` spans), over
the traced time under ``moe/experts``: what ``moe_experts_roofline`` is
for ``ling3_flash_vl``, whose reader wants that model's widths and
counters."""
from benchmark import costs_gqa_moe, gqa_trace

UNIT, LAYER, MOVES = "%", "kernels", "serve_tokens_per_s"


def read(run):
    return gqa_trace.floor_share(
        run, lambda c, d, peaks: costs_gqa_moe.held_experts_floor_s(
            c["expert_pairs"], c["experts_hit"], d, peaks),
        ("moe", "experts"))
