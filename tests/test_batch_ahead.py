"""The trainers keep one batch ahead of the device (``optim/batch_ahead.py``):
batch N+1 is fetched and put between step N's dispatch and the next sync;
and they read each step's loss one step late (``optim/local_optimizer.py``
``_run_step``): step N+1 is enqueued before the host waits on step N.

What must NOT change is pinned here for all three loops (``LocalOptimizer``,
``DistriOptimizer`` flat and spec): the sequence of batches the step program
sees (across shuffled rollovers, after a mid-epoch resume), the weights and
the per-step losses that sequence gives, the step count the end trigger
asks for, the steps a snapshot holds, the skipped-step ledger, and when an
error surfaces.
"""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
from bigdl_tpu.dataset import DataSet, MiniBatch
from bigdl_tpu.dataset.transformer import Transformer
from bigdl_tpu.engine import Engine
from bigdl_tpu.observability import ledger as run_ledger
from bigdl_tpu.observability.report import load_ledger
from bigdl_tpu.optim import DistriOptimizer, LocalOptimizer, SGD, Trigger
from bigdl_tpu.optim.local_optimizer import SKIPPED_STEPS, STEPS_AHEAD
from bigdl_tpu.parallel import mesh as mesh_mod
from bigdl_tpu.resilience.fault_injector import FaultInjector
from tests.checkers import record_steps

KINDS = ["local", "distri_flat", "distri_spec"]
BATCHES, ROWS = 4, 8            # an epoch is four steps


def _model():
    m = nn.Sequential()
    m.add(nn.Linear(4, 8)).add(nn.Tanh())
    m.add(nn.Linear(8, 2)).add(nn.LogSoftMax())
    m.build(jax.random.PRNGKey(3))
    return m


def _batches():
    """Distinct batches; batch k is known by its first element, k + 1."""
    rs = np.random.RandomState(0)
    out = []
    for k in range(BATCHES):
        x = rs.rand(ROWS, 4).astype(np.float32)
        x[0, 0] = k + 1
        out.append(MiniBatch(x, (np.arange(ROWS) % 2 + 1)
                             .astype(np.float32)))
    return out


def _mesh(kind):
    shape = "4,1,1" if kind == "distri_flat" else "2,1,2"
    return mesh_mod.build_mesh(shape, devices=jax.devices()[:4])


def _trainer(kind, model, dataset, steps):
    args = (model, nn.ClassNLLCriterion(), dataset,
            Trigger.max_iteration(steps))
    opt = LocalOptimizer(*args) if kind == "local" else \
        DistriOptimizer(*args, mesh=_mesh(kind))
    return opt.set_optim_method(SGD(learning_rate=0.1))


def _marks(seen):
    """The recorded steps' batches by their mark: ``(stepno, k)``."""
    return [(n, int(np.asarray(data)[0, 0]) - 1) for n, data in seen]


def _dataset_order(steps, seed=1):
    """The order ``DataSet.array(batches, seed=1)`` hands its batches out
    in, from its own permutation stream: the identity in epoch 1, one more
    in-place shuffle of the same permutation before every later epoch."""
    rng, perm, out = np.random.RandomState(seed), np.arange(BATCHES), []
    while len(out) < steps:
        if out:
            rng.shuffle(perm)
        out += [int(i) for i in perm]
    return out[:steps]


def _by_hand(kind, order, nan_at=()):
    """The plain jitted step of ``kind`` driven over ``order`` with no
    trainer loop around it, its batch poisoned to NaN at the steps
    ``nan_at``; returns the final weights' leaves and every step's loss."""
    model, batches = _model(), _batches()
    opt = _trainer(kind, model, None, 0)
    crit, optim, cfg = opt.criterion, opt.optim_method, opt.config
    to_device = jnp.asarray
    if kind == "local":
        step = opt._build_step()
        state = [model.params, optim.init_state(model.params)]
        final = lambda: state[0]
    else:
        sharding = mesh_mod.batch_sharding(opt.mesh)
        to_device = lambda a: jax.device_put(a, sharding)
        if kind == "distri_flat":
            from bigdl_tpu.parallel.allreduce import make_distri_train_step
            step, layout, init = make_distri_train_step(
                model, crit, optim, opt.mesh, cfg, compress=opt.compress)
            final = lambda: layout.unflatten(
                np.asarray(state[0]).reshape(-1))
        else:
            from bigdl_tpu.parallel.specs import make_spec_train_step
            step, init, _ = make_spec_train_step(
                model, crit, optim, opt.mesh, cfg)
            final = lambda: state[0]
        state = list(init(model.params))
    clr = jnp.asarray(opt._current_clr(), jnp.float32)
    rng, model_state, losses = opt._rng, model.state, []
    for stepno, k in enumerate(order):
        rng, sub = jax.random.split(rng)
        data = to_device(batches[k].data)
        if stepno in nan_at:
            data = jnp.full_like(data, jnp.nan)
        *state, model_state, loss = step(
            *state, model_state, data, to_device(batches[k].labels), sub,
            jnp.asarray(stepno, jnp.int32), clr)
        losses.append(float(loss))
    return _leaves(final()), losses


def _leaves(params):
    return [np.asarray(l) for l in jax.tree_util.tree_leaves(params)]


@pytest.fixture(autouse=True)
def _fresh_engine():
    yield
    Engine.reset()


@pytest.mark.parametrize("kind", KINDS)
def test_batches_and_weights_are_those_of_the_plain_step(kind):
    """Three epochs of four steps with shuffling, then once more resumed
    from the state of a run stopped in the middle of epoch 2: the step
    sees the dataset's own order, batch for batch, and ends on the weights
    the plain step gives over that order, bit for bit."""
    steps, stop = 3 * BATCHES, BATCHES + 2
    order = _dataset_order(steps)
    assert sorted(order[BATCHES:2 * BATCHES]) == list(range(BATCHES))
    assert order[:BATCHES] != order[BATCHES:2 * BATCHES]    # it shuffles

    model = _model()
    opt = _trainer(kind, model, DataSet.array(_batches()), steps)
    seen = record_steps(opt)
    opt.optimize()
    assert _marks(seen) == list(enumerate(order))
    want, _ = _by_hand(kind, order)
    for got, ref in zip(_leaves(model.params), want):
        np.testing.assert_array_equal(got, ref)

    # a run stopped two steps into epoch 2, and its state carried to a
    # new trainer over a NEW dataset (a fresh shuffle stream to replay)
    model = _model()
    first = _trainer(kind, model, DataSet.array(_batches()), stop)
    first.optimize()
    assert (first.state["epoch"], first.state["recordsProcessedThisEpoch"]
            ) == (2, 2 * ROWS)
    resumed = _trainer(kind, model, DataSet.array(_batches()), steps)
    resumed.set_state(first.state)
    resumed._rng = first._rng
    seen = record_steps(resumed)
    resumed.optimize()
    assert _marks(seen) == list(enumerate(order))[stop:]
    for got, ref in zip(_leaves(model.params), want):
        np.testing.assert_array_equal(got, ref)


# -- errors of the input: held until the loop asks for the batch ----------------

class _Fails(Transformer):
    """Raises when the ``at``-th batch (from 1, over all epochs) is
    asked for."""

    def __init__(self, at):
        self.at, self.asked = at, 0

    def apply(self, prev):
        for x in prev:
            self.asked += 1
            if self.asked == self.at:
                raise OSError("decode failed")
            yield x


def _checkpointed(model, dataset, steps, path):
    opt = _trainer("local", model, dataset, steps)
    return opt.set_checkpoint(path, Trigger.several_iteration(1),
                              auto_resume=True)


@pytest.mark.parametrize("fault", ["iterator", "put"])
def test_an_input_error_surfaces_after_the_running_step_is_recorded(
        tmp_path, fault):
    """Batch 4 cannot be fetched (or put).  It is fetched under step 3
    (``neval`` 2 -> 3): that step's record and checkpoint are written, THEN
    the error surfaces; a relaunch with ``auto_resume`` continues with
    batch 4 and ends on the uninterrupted run's weights."""
    path, steps = str(tmp_path / "snap"), 2 * BATCHES
    order = _dataset_order(steps)
    run_ledger.set_run_dir(str(tmp_path / "ledger"))
    try:
        ds = DataSet.array(_batches())
        opt = _checkpointed(_model(), ds >> _Fails(4) if fault == "iterator"
                            else ds, steps, path)
        if fault == "put":
            put, puts = opt._put_batch, []

            def failing(array):             # data and labels: 2 a batch
                puts.append(1)
                if len(puts) == 2 * 3 + 1:
                    raise OSError("transfer failed")
                return put(array)
            opt._put_batch = failing
        seen = record_steps(opt)
        with pytest.raises(OSError, match="failed"):
            opt.optimize()
        run_ledger.flush()
    finally:
        run_ledger.set_run_dir(None)
    assert opt.state["neval"] == 3
    assert _marks(seen) == list(enumerate(order))[:3]
    records, _ = load_ledger(str(tmp_path / "ledger"))
    assert [r["step"] for r in records if r.get("type") == "step"] == \
        [0, 1, 2]
    assert {"model.3", "state.3"} <= set(os.listdir(path))

    model = _model()
    relaunched = _checkpointed(model, DataSet.array(_batches()), steps, path)
    seen = record_steps(relaunched)
    relaunched.optimize()
    assert _marks(seen) == list(enumerate(order))[3:]
    for got, ref in zip(_leaves(model.params), _by_hand("local", order)[0]):
        np.testing.assert_array_equal(got, ref)


class _Slow(Transformer):
    def __init__(self, at, seconds):
        self.at, self.seconds = at, seconds

    def apply(self, prev):
        for i, x in enumerate(prev):
            if i == self.at:
                time.sleep(self.seconds)
            yield x


def test_a_slow_fetch_is_not_billed_to_the_steps_watchdog():
    """The fetch of batch N+1 runs under step N's span; a decode slower
    than ``step_timeout`` is not a hung step."""
    opt = _trainer("local", _model(),
                   DataSet.array(_batches()) >> _Slow(2, 2.5), BATCHES)
    opt.set_step_timeout(2.0)
    t0 = time.monotonic()
    opt.optimize()
    assert opt.state["neval"] == BATCHES and time.monotonic() - t0 > 2.5


# -- the loss read one step late --------------------------------------------------

class _Summary:
    """A ``TrainSummary`` stand-in: ``(step, loss)`` of every step as it is
    recorded; raises while recording step ``fail_at``."""

    def __init__(self, fail_at=None):
        self.losses, self.fail_at = [], fail_at

    def trigger_for(self, tag):
        return None

    def add_scalar(self, tag, value, step):
        if tag == "Loss":
            self.losses.append((step, value))
            if step == self.fail_at:
                raise RuntimeError(f"summary failed at step {step}")


def _ledgered(tmp_path, opt, summary=None):
    """``opt.optimize()`` with the run ledger on (and ``summary``); returns
    the exception it raised, or None, and the ledger's records."""
    opt.set_train_summary(summary or _Summary())
    run_ledger.set_run_dir(str(tmp_path / "ledger"))
    raised = None
    try:
        opt.optimize()
    except Exception as e:
        raised = e
    finally:
        run_ledger.flush()
        run_ledger.set_run_dir(None)
    records, bad = load_ledger(str(tmp_path / "ledger"))
    assert bad == 0
    return raised, records


def _syncs(records):
    """``(step, ahead)`` of every ``train.sync`` span, in time order."""
    spans = sorted((r for r in records if r.get("type") == "span"
                    and r["name"] == "train.sync"), key=lambda r: r["mono"])
    return [(r["attrs"]["step"], r["attrs"]["ahead"]) for r in spans]


def _step_records(records):
    return [r["step"] for r in records if r.get("type") == "step"]


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("end", ["iterations_1", "iterations_9",
                                 "epochs_2"])
def test_the_lagged_loop_records_the_plain_steps_losses_in_order(
        tmp_path, kind, end):
    """The end trigger counts the steps dispatched, so ``max_iteration(K)``
    runs exactly K steps and ``max_epoch(E)`` exactly E epochs; every
    step's loss is recorded once, in order, equal to the plain step's bit
    for bit, and so are the final weights.  Every step but the first is
    dispatched while the one before it runs, and only the last sync is a
    drain."""
    what, n = end.split("_")
    steps = int(n) * (BATCHES if what == "epochs" else 1)
    trigger = Trigger.max_epoch(int(n)) if what == "epochs" else \
        Trigger.max_iteration(int(n))
    model, summary = _model(), _Summary()
    opt = _trainer(kind, model, DataSet.array(_batches()), steps)
    opt.end_when = trigger
    seen = record_steps(opt)
    raised, records = _ledgered(tmp_path, opt, summary)
    assert raised is None
    order = _dataset_order(steps)
    want, losses = _by_hand(kind, order)
    assert opt.state["neval"] == steps
    assert _marks(seen) == list(enumerate(order))
    assert summary.losses == list(enumerate(losses))
    # each record reads the counters as they stood after its own step,
    # though the loop has counted the next one: the epoch before rollover
    assert [(r["step"], r["epoch"]) for r in records
            if r.get("type") == "step"] == \
        [(n, n // BATCHES + 1) for n in range(steps)]
    for got, ref in zip(_leaves(model.params), want):
        np.testing.assert_array_equal(got, ref)
    assert _syncs(records) == [(n, n < steps - 1) for n in range(steps)]
    ahead = opt.metrics.get(STEPS_AHEAD) if steps > 1 else 0
    assert ahead == steps - 1


@pytest.mark.parametrize("kind", KINDS)
def test_a_snapshot_holds_the_weights_of_exactly_its_steps(tmp_path, kind):
    """A snapshot every four steps of a six-step run: the trigger is asked
    before step 4 is dispatched, step 3 is drained, and the snapshot
    labelled 4 resumes to the plain step's weights after four steps."""
    steps, at = 6, 4
    path = str(tmp_path / "snap")
    opt = _trainer(kind, _model(), DataSet.array(_batches()), steps)
    if kind == "local":
        opt.set_checkpoint(path, Trigger.several_iteration(at))
    else:
        opt.set_sharded_checkpoint(path, Trigger.several_iteration(at))
    raised, records = _ledgered(tmp_path, opt)
    assert raised is None
    assert _syncs(records) == [(0, True), (1, True), (2, True), (3, False),
                               (4, True), (5, False)]
    assert opt.metrics.get(STEPS_AHEAD) == steps - 2
    if kind == "local":
        assert {f for f in os.listdir(path) if "." in f} == \
            {f"model.{at}", f"state.{at}"}

    model = _model()
    resumed = _trainer(kind, model, DataSet.array(_batches()), at)
    resumed.resume_from(path)
    resumed.optimize()                     # nothing left to run
    assert resumed.state["neval"] == at
    want, _ = _by_hand(kind, _dataset_order(at))
    for got, ref in zip(_leaves(model.params), want):
        np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("kind", KINDS)
def test_a_poisoned_step_is_counted_once(tmp_path, kind):
    """``grad.nan`` at step 2: the guard keeps the weights, the step is
    counted as one skipped step when its loss is read (one step late), its
    record is marked skipped, and the run ends on the plain step's
    weights over the same poisoned batch."""
    steps, bad = 5, 2
    model, summary = _model(), _Summary()
    opt = _trainer(kind, model, DataSet.array(_batches()), steps)
    FaultInjector.install(FaultInjector().add("grad.nan", step=bad))
    try:
        raised, records = _ledgered(tmp_path, opt, summary)
    finally:
        FaultInjector.clear()
    assert raised is None
    want, losses = _by_hand(kind, _dataset_order(steps), nan_at=(bad,))
    assert np.isnan(losses[bad])
    assert opt.state["skippedSteps"] == 1
    assert opt.metrics.get(SKIPPED_STEPS) == 1
    assert summary.losses == [(n, l) for n, l in enumerate(losses)
                              if n != bad]
    assert [(r["step"], r["skipped"]) for r in records
            if r.get("type") == "step"] == \
        [(n, n == bad) for n in range(steps)]
    assert [r["step"] for r in records if r.get("kind") == "step.skipped"] \
        == [bad]
    for got, ref in zip(_leaves(model.params), want):
        np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("kind", ["distri_flat", "distri_spec"])
def test_the_drop_budget_counts_the_steps_up_to_the_poisoned_one(tmp_path,
                                                                 kind):
    """Every step from 2 on poisoned, at most half the steps may be
    skipped: step 4 is the third skipped of five and breaks the budget.
    Its loss is read while step 5 runs, so the budget counts the steps up
    to the one read (not the ones dispatched); step 5 is then drained and
    recorded before the error leaves the loop."""
    opt = DistriOptimizer(_model(), nn.ClassNLLCriterion(),
                          DataSet.array(_batches()), Trigger.max_iteration(8),
                          mesh=_mesh(kind), max_drop_percentage=0.5)
    opt.set_optim_method(SGD(learning_rate=0.1))
    inj = FaultInjector()
    for s in range(2, 8):
        inj.add("grad.nan", step=s)
    FaultInjector.install(inj)
    try:
        raised, records = _ledgered(tmp_path, opt)
    finally:
        FaultInjector.clear()
    assert isinstance(raised, RuntimeError)
    assert "3/5 steps skipped" in str(raised)
    assert opt.state["neval"] == 6
    assert [r["step"] for r in records if r.get("kind") == "step.skipped"] \
        == [2, 3, 4, 5]
    assert _syncs(records)[-1] == (5, False)


@pytest.mark.parametrize("kind", KINDS)
def test_an_error_in_a_record_drains_the_step_in_flight(tmp_path, kind):
    """Recording step 1 fails while step 2 is in flight: step 2 is drained
    and recorded before the error leaves the loop, ``neval`` counts it and
    ``model.params`` stays readable (the local loop's names the newest
    step's result)."""
    model, summary = _model(), _Summary(fail_at=1)
    opt = _trainer(kind, model, DataSet.array(_batches()), 2 * BATCHES)
    raised, records = _ledgered(tmp_path, opt, summary)
    assert isinstance(raised, RuntimeError) and "step 1" in str(raised)
    assert opt.state["neval"] == 3
    assert [n for n, _ in summary.losses] == [0, 1, 2]
    assert _step_records(records) == [0, 1, 2]
    assert _syncs(records) == [(0, True), (1, True), (2, False)]
    leaves = _leaves(model.params)
    if kind == "local":
        for got, ref in zip(leaves, _by_hand(kind, _dataset_order(3))[0]):
            np.testing.assert_array_equal(got, ref)
