"""The trainers keep one batch ahead of the device (``optim/batch_ahead.py``):
batch N+1 is fetched and put between step N's dispatch and its sync.

What must NOT change is pinned here for all three loops (``LocalOptimizer``,
``DistriOptimizer`` flat and spec): the sequence of batches the step program
sees (across shuffled rollovers, after a mid-epoch resume), the weights that
sequence gives, and when an error of the input surfaces.
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
from bigdl_tpu.parallel import mesh as mesh_mod
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


def _by_hand(kind, order):
    """The plain jitted step of ``kind`` driven over ``order`` with no
    trainer loop around it; returns the final weights' leaves."""
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
    rng, model_state = opt._rng, model.state
    for stepno, k in enumerate(order):
        rng, sub = jax.random.split(rng)
        *state, model_state, _ = step(
            *state, model_state, to_device(batches[k].data),
            to_device(batches[k].labels), sub,
            jnp.asarray(stepno, jnp.int32), clr)
    return _leaves(final())


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
    want = _by_hand(kind, order)
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
    for got, ref in zip(_leaves(model.params), _by_hand("local", order)):
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
