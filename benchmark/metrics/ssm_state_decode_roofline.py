"""The state-space state's update in decode: every active row's float32
state (128 heads x 64 x 128, 4.19 MB a block) read and written once in each
of the five Mamba-2 blocks (``state_rows`` of the ``serve.decode`` spans),
over the traced time under ``mamba2/state``."""
from benchmark import costs_ssm_moe, ssm_trace

UNIT, LAYER, MOVES = "%", "kernels", "serve_tokens_per_s"


def read(run):
    return ssm_trace.floor_share(
        run, lambda c, d, peaks: costs_ssm_moe.ssm_state_floor_s(
            c["state_rows"], d, peaks), ("mamba2", "state"))
