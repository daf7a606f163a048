"""Forward+backward FLOPs per sample from the layer shapes, times the
traced run's samples per second, over chips x the bf16 peak."""
from benchmark import costs

UNIT, LAYER, MOVES = "%", "model", "train_samples_per_s"


def read(run):
    t = run.train
    if not t or not t["samples_per_s"]:
        return None
    flops = costs.train_flops_per_sample(t["layers"])
    return 100.0 * flops * t["samples_per_s"] \
        / (t["chips"] * run.peaks["bf16_flops"])
