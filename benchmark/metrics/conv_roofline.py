"""Shape-based floor of the step's MXU passes (per pass the larger of
FLOPs over the bf16 peak and bytes over the HBM bandwidth) over their
traced time."""
from benchmark import costs

UNIT, LAYER, MOVES = "%", "kernels", "train_samples_per_s"
GROUP = "MXU convolutions and matmuls"


def read(run):
    if run.trace is None or not run.train:
        return None
    steps = len(run.trace.runs(run.cell.config["programs"]["step"]))
    traced = run.trace.group_seconds().get(GROUP, 0.0)
    if not steps or not traced:
        return None
    per_chip = run.train["batch"] // run.train["chips"]
    floor = costs.train_mxu_floor_s(run.train["layers"], per_chip, run.peaks)
    return 100.0 * floor * steps / traced
