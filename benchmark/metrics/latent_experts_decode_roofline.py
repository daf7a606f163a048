"""The held experts' product in decode at this configuration's shapes (128
held experts of 1,024 x 2,688 and back, not gated, on the latent): per
traced chunk the larger of reading each hit expert's weights once and of
multiplying the routed pairs (``experts_hit`` and ``expert_pairs`` of the
``serve.decode`` spans), over the traced time under ``moe/experts``: what
``moe_experts_roofline`` and ``held_experts_decode_roofline`` are for the
two other pattern models, whose readers want those models' widths."""
from benchmark import costs_ssm_moe, ssm_trace

UNIT, LAYER, MOVES = "%", "kernels", "serve_tokens_per_s"


def read(run):
    return ssm_trace.floor_share(
        run, lambda c, d, peaks: costs_ssm_moe.latent_experts_floor_s(
            c["expert_pairs"], c["experts_hit"], d, peaks),
        ("moe", "experts"))
