"""A training cell: the configuration's model through the real trainer
(``LocalOptimizer`` or ``DistriOptimizer``) until a deadline trigger."""

from __future__ import annotations

import importlib
import math
import os
import time

from bigdl_tpu.optim import Trigger

from benchmark import cells, harness, stats, trace_capture
from benchmark.trace_reduce import Reduced

COMPILE_STEPS = 1          # the first step compiles
SLICE_AFTER = 10           # traced run: steps into the window
SLICE_STEPS = 8


class WindowTrigger(Trigger):
    """The trainers call ``end_when(state)`` once an iteration, after the
    loop's own ``float(loss)`` sync: every call marks a finished step.
    The trigger stamps each call, opens the window after the compile step
    and ``discard`` more, and ends the run at the deadline."""

    def __init__(self, seconds: float, discard: int, slice_=None):
        self.seconds, self.discard, self.slice = seconds, discard, slice_
        self.stamps = []
        self.start = None
        self.deadline = None

    def __call__(self, state) -> bool:
        now = time.monotonic()
        self.stamps.append(now)
        into = len(self.stamps) - 1 - COMPILE_STEPS - self.discard
        if into == 0:                    # steps finished: compile + discard
            self.start, self.deadline = now, now + self.seconds
        if self.slice is not None:
            if into == SLICE_AFTER:
                self.slice.start()
            elif into == SLICE_AFTER + SLICE_STEPS:
                self.slice.stop()
        return self.deadline is not None and now >= self.deadline


def forward_check(run, model, params, state, reference, cfg, seed):
    """The deterministic forward (dropout off) of 8 seeded images through
    ``model.apply`` in the configuration's precision against the plain
    reference: logits and NLL loss."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.core.precision import mixed_forward

    rs = np.random.default_rng([int(seed), 11])
    shape = tuple(cfg["input_shape"])
    x = jnp.asarray(rs.random((8,) + shape, dtype=np.float32))
    y = jnp.asarray(rs.integers(1, cfg["classes"] + 1, 8).astype(np.float32))
    if cfg["trainer"]["mixed_precision"]:
        apply = jax.jit(lambda p, s, im: mixed_forward(
            model, p, s, im, training=False)[0])
    else:
        apply = jax.jit(lambda p, s, im: model.apply(
            p, s, im, training=False)[0])
    got = np.asarray(apply(params, state, x), np.float32)
    want = np.asarray(jax.jit(reference.forward)(params, x), np.float32)
    scale = float(np.abs(want - want.mean(axis=-1, keepdims=True)).max())
    err = float(np.abs(got - want).max()) / (scale + 1e-30)
    nll = [float(reference.nll_loss(jnp.asarray(a), y)) for a in (got, want)]
    tol = cfg["tolerance"]
    ok = (np.isfinite(got).all() and err <= tol["logits_rel"]
          and abs(nll[0] - nll[1]) <= tol["nll_abs"])
    run.compared.update(logits_rel=[err, tol["logits_rel"]],
                        nll_abs=[abs(nll[0] - nll[1]), tol["nll_abs"]])
    harness.say(f"reference check: 8 images, max|logp - ref| / spread "
                     f"of ref = {err:.3e} (tolerance {tol['logits_rel']}), "
                     f"NLL {nll[0]:.5f} against {nll[1]:.5f} (tolerance "
                     f"{tol['nll_abs']}): {'ok' if ok else 'FAIL'}")
    return bool(ok)


def run(run) -> None:
    import jax
    import numpy as np

    from bigdl_tpu.dataset.dataset import DataSet
    from bigdl_tpu.dataset.transformer import MiniBatch
    from bigdl_tpu.observability.summary import TrainSummary

    cfg, tr = run.cell.config, run.cell.traffic
    chips = run.cell.chips
    batch = int(tr["per_chip_batch"]) * chips
    reference = importlib.import_module(cfg["reference"])

    model = cells.resolve(cfg["model"]["factory"])(
        *cfg["model"].get("args", []), **cfg["model"].get("kwargs", {}))
    # weights on the device in ONE jitted call from the seed
    params, state = jax.jit(model.init)(harness.seed_key(run.seed))
    model.params, model.state = params, state
    watched = [np.asarray(l) for l in
               jax.tree_util.tree_leaves(params)[:2]]

    correct = forward_check(run, model, params, state, reference, cfg,
                            run.seed)
    shape = tuple(cfg["input_shape"])
    layers = reference.mxu_layers(params, shape)

    rs = np.random.default_rng([int(run.seed), 7])
    ring = [MiniBatch(rs.random((batch,) + shape, dtype=np.float32),
                      rs.integers(1, cfg["classes"] + 1, batch)
                      .astype(np.float32))
            for _ in range(int(tr["ring"]))]

    ledger_dir = harness.start_ledger(run)
    slice_ = trace_capture.Slice(os.path.join(run.out_dir, "profile")) \
        if run.trace_on else None
    trigger = WindowTrigger(run.seconds, int(tr.get("discard_steps", 5)),
                            slice_)
    t = cfg["trainer"]
    if tr["optimizer"] == "distri":
        from bigdl_tpu.engine import Engine
        from bigdl_tpu.optim import DistriOptimizer as Optimizer
        Engine.init()
    else:
        from bigdl_tpu.optim import LocalOptimizer as Optimizer
    summary = TrainSummary("", "benchmark", tensorboard=False)
    opt = (Optimizer(model, cells.resolve(t["criterion"])(),
                     DataSet.array(ring), trigger)
           .set_optim_method(cells.resolve(t["optim_method"])(
               **t.get("optim_kwargs", {})))
           .set_mixed_precision(bool(t["mixed_precision"]))
           .set_train_summary(summary))
    try:
        opt.optimize()
    finally:
        if slice_ is not None:
            slice_.stop()
    harness.stop_ledger(run, ledger_dir)

    rate, steps = stats.whole_step_rate(trigger.stamps, trigger.start,
                                        trigger.deadline, batch)
    losses = [v for _, v, _ in summary.read_scalar("Loss")]
    total = opt.state["neval"]
    skipped = int(opt.state.get("skippedSteps", 0))
    moved = max(float(np.abs(np.asarray(a) - b).max()) for a, b in
                zip(jax.tree_util.tree_leaves(model.params)[:2], watched))
    finite = len(losses) == total and all(math.isfinite(v) for v in losses)
    harness.say(f"train: {total} steps ({steps} whole steps in the "
                     f"window), global batch {batch}, first loss "
                     f"{losses[0]:.4f} last {losses[-1]:.4f}, skipped "
                     f"{skipped}, every loss finite: {finite}, "
                     f"max|dw| {moved:.3e}")
    run.window = (trigger.start, trigger.deadline)
    run.setup_s = trigger.start - run.t0
    run.attempted, run.failed = steps, skipped
    run.correct = correct and finite and skipped == 0 and moved > 0
    run.compared.update(steps_skipped=[skipped, 0],
                        losses_not_finite=[int(not finite), 0],
                        weights_unmoved=[int(not moved > 0), 0])
    run.e2e["train_samples_per_s"] = rate
    run.train = {"samples_per_s": rate, "batch": batch, "chips": chips,
                 "steps": steps, "slice_steps": SLICE_STEPS,
                 "layers": layers}
    if slice_ is not None:
        events = slice_.events()
        if events is not None:
            run.trace = Reduced(events)
