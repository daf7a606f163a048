"""Operations and bytes a kernel needs, from shapes alone.  These are the
benchmark's own counts (not XLA's cost model, which overcounts operand
re-reads): the numerator of every ``*_roofline_pct`` and ``*_mfu_pct``."""

from __future__ import annotations

from typing import Iterable, List, Sequence

BF16 = 2  # bytes


def conv_pass_costs(layer: dict, batch: int, itemsize: int = BF16) -> dict:
    """FLOPs and bytes of ONE pass (forward, input gradient or weight
    gradient: the three move the same tensors and multiply the same
    volume) of one convolution or linear layer described by
    ``cin, cout, kh, kw, hin, win, hout, wout`` (a linear layer is a 1x1
    convolution over a 1x1 image)."""
    macs = (batch * layer["cout"] * layer["hout"] * layer["wout"]
            * layer["cin"] * layer["kh"] * layer["kw"])
    nbytes = itemsize * (
        batch * layer["cin"] * layer["hin"] * layer["win"]
        + layer["cout"] * layer["cin"] * layer["kh"] * layer["kw"]
        + batch * layer["cout"] * layer["hout"] * layer["wout"])
    return {"flops": 2 * macs, "bytes": nbytes}


def train_passes(layers: Sequence[dict]) -> List[int]:
    """Passes the backward needs per layer: forward, weight gradient and
    input gradient, except that the first layer's input gradient (the
    gradient of the images) is not required."""
    return [2 if i == 0 else 3 for i, _ in enumerate(layers)]


def train_flops_per_sample(layers: Sequence[dict]) -> float:
    """Forward+backward FLOPs of the MXU layers per sample.  Recomputed
    work does not count; pooling, LRN and elementwise ops are left out
    (they are under 1% of the total and run on the VPU)."""
    return float(sum(n * conv_pass_costs(l, 1)["flops"]
                     for n, l in zip(train_passes(layers), layers)))


def train_mxu_floor_s(layers: Sequence[dict], batch: int, peaks: dict
                      ) -> float:
    """Least time one training step's MXU passes could take on one chip:
    per pass the larger of FLOPs over peak and bytes over bandwidth."""
    from benchmark.peaks import roofline_floor_s
    total = 0.0
    for n, l in zip(train_passes(layers), layers):
        c = conv_pass_costs(l, batch)
        total += n * roofline_floor_s(c["flops"], c["bytes"], peaks)[0]
    return total


def paged_attention_costs(contexts: Iterable[int], queries: int, width: int,
                          page_size: int, itemsize: int = BF16) -> dict:
    """One paged-attention call (one layer) over rows whose caches hold
    ``contexts[b]`` tokens BEFORE the call, each row with ``queries`` new
    tokens (1 for a decode step, the bucket's real length for a prefill).
    ``width`` = heads x head size.

    Bytes: the K and V pages a row actually has to read (whole pages up
    to its last valid position) plus q in and o out.  FLOPs: 4 x visible
    keys x width per query row (QK^T and PV), causal inside the call."""
    flops = 0
    nbytes = 0
    for ctx in contexts:
        last = ctx + queries                     # valid keys after the call
        pages = -(-last // page_size)
        nbytes += 2 * pages * page_size * width * itemsize   # K and V
        nbytes += 2 * queries * width * itemsize             # q, o
        # query i (0-based) sees ctx + i + 1 keys
        visible = queries * ctx + queries * (queries + 1) // 2
        flops += 4 * visible * width
    return {"flops": float(flops), "bytes": float(nbytes)}
