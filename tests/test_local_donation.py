"""``LocalOptimizer`` donates its training state to the jitted step where
the state lives on an accelerator (``optim/local_optimizer.py``
``state_donation``), so the call allocates no new state buffers a step.

On the CPU it does not donate (donated buffers + the persistent
compilation cache corrupt the CPU heap), so the donating path is driven
here in a CHILD process with the cache off and the policy forced: a
fault there cannot take down an xdist worker.  What must hold with the
state donated: the same losses and weights to the bit as the undonated
run, the caller's trees untouched, a File snapshot that resumes to the
same weights, the non-finite guard keeping the weights, and a
``model.params`` that stays readable when the loop leaves by an
exception after a dispatch.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
from bigdl_tpu.dataset import DataSet, MiniBatch
from bigdl_tpu.optim import LocalOptimizer, SGD, Trigger
from bigdl_tpu.optim.local_optimizer import state_donation

STEPS, BATCHES, BS = 4, 3, 8      # four steps cross an epoch's end
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- (a) the policy --------------------------------------------------------------

@pytest.mark.parametrize("platforms, donates", [
    ({"cpu"}, False),
    ({"tpu"}, True),
    ({"gpu"}, True),
    ({"cpu", "tpu"}, True),       # a mixed set is not CPU-only
])
def test_policy_donates_off_cpu_only(platforms, donates):
    assert state_donation(platforms) is donates


def test_the_cpu_step_does_not_donate():
    m = nn.Sequential().add(nn.Linear(4, 2)).add(nn.LogSoftMax())
    m.build(jax.random.PRNGKey(0))
    opt = LocalOptimizer(m, nn.ClassNLLCriterion(), None)
    step = opt._build_step()
    assert step.donates_state is False
    assert opt._donates_state() is False
    # nothing consumed: the arguments outlive the call
    params = m.params
    opt_state = opt.optim_method.init_state(params)
    step(params, opt_state, m.state, jnp.ones((2, 4)), jnp.ones((2,)),
         jax.random.PRNGKey(1), jnp.asarray(0, jnp.int32),
         jnp.asarray(0.1, jnp.float32))
    assert not any(l.is_deleted() for l in jax.tree_util.tree_leaves(params))


# -- (b) the donating path, in a child process -----------------------------------

def _lenet():
    from bigdl_tpu.models.lenet import LeNet5
    return LeNet5(10).build(seed=1)


def _batches():
    rs = np.random.RandomState(0)
    return [MiniBatch(rs.rand(BS, 784).astype(np.float32),
                      (np.arange(BS) % 10 + 1).astype(np.float32))
            for _ in range(BATCHES)]


class _Losses:
    """A ``TrainSummary`` stand-in that records the loss of every step and
    raises at step ``fail_at`` (after that step's dispatch)."""

    def __init__(self, fail_at=None):
        self.losses, self.fail_at = [], fail_at

    def trigger_for(self, tag):
        return None

    def add_scalar(self, tag, value, step):
        if tag == "Loss":
            self.losses.append(float(value))
        if step == self.fail_at:
            raise RuntimeError(f"summary failed at step {step}")


def _train(model, steps, summary=None, checkpoint=None, resume=None):
    opt = LocalOptimizer(model, nn.ClassNLLCriterion(),
                         DataSet.array(_batches(), seed=2),
                         Trigger.max_iteration(steps))
    opt.set_optim_method(SGD(learning_rate=0.05, momentum=0.9))
    opt.set_seed(7)
    opt.set_train_summary(summary or _Losses())
    if checkpoint is not None:
        path, at = checkpoint
        opt.set_checkpoint(path, lambda state: state["neval"] == at)
    if resume is not None:
        opt.resume_from(resume)
    opt.optimize()
    return opt


def _leaves(tree):
    # copies: a zero-copy view of a CPU buffer is an external reference,
    # and the CPU client then quietly copies instead of donating
    return [np.array(l) for l in jax.tree_util.tree_leaves(tree)]


def _same(a, b):
    return len(a) == len(b) and all(
        x.dtype == y.dtype and np.array_equal(x, y) for x, y in zip(a, b))


def _child_main(tmp):
    """Run in a fresh process (see ``donated`` below): every fact the
    tests assert, as one JSON line on stdout."""
    import bigdl_tpu.optim.local_optimizer as lo
    from bigdl_tpu.observability import ledger as run_ledger
    from bigdl_tpu.observability.report import load_ledger
    from bigdl_tpu.resilience.fault_injector import FaultInjector

    facts = {}
    # the reference: the same run, undonated (the CPU's own policy)
    ref_model, ref = _lenet(), _Losses()
    _train(ref_model, STEPS, ref)
    ref_w = _leaves(ref_model.params)

    lo.state_donation = lambda platforms: True      # force it on the CPU
    probe = LocalOptimizer(_lenet(), nn.ClassNLLCriterion(), None)
    step = probe._build_step()
    facts["step_donates"] = step.donates_state
    args = jax.device_put((probe.model.params,
                           probe.optim_method.init_state(probe.model.params),
                           probe.model.state), may_alias=False)
    step(*args, jnp.ones((BS, 784)), jnp.ones((BS,)), jax.random.PRNGKey(0),
         jnp.asarray(0, jnp.int32), jnp.asarray(0.1, jnp.float32))
    facts["inputs_consumed"] = all(
        l.is_deleted() for l in jax.tree_util.tree_leaves(args[:2]))

    # the donated run: trajectory, weights, the caller's tree, the trace
    run_dir = os.path.join(tmp, "ledger")
    run_ledger.set_run_dir(run_dir)
    model = _lenet()
    before = model.params
    init_w = _leaves(before)
    got = _Losses()
    _train(model, STEPS, got)
    run_ledger.flush()
    run_ledger.set_run_dir(None)
    records, _ = load_ledger(run_dir)
    facts["run_start_donates"] = [r.get("donates_state") for r in records
                                  if r.get("type") == "run.start"]
    facts["dispatch_donates"] = [
        r["attrs"].get("donates_state") for r in records
        if r.get("type") == "span" and r.get("name") == "train.dispatch"]
    facts["losses_equal"] = got.losses == ref.losses and \
        len(got.losses) == STEPS
    facts["weights_equal"] = _same(_leaves(model.params), ref_w)
    facts["caller_not_deleted"] = not any(
        l.is_deleted() for l in jax.tree_util.tree_leaves(before))
    facts["caller_unchanged"] = facts["caller_not_deleted"] and \
        _same(_leaves(before), init_w)

    # a File snapshot written mid-run resumes to the same final weights
    snap = os.path.join(tmp, "snap")
    _train(_lenet(), STEPS, checkpoint=(snap, 2))
    resumed = _lenet()
    _train(resumed, STEPS, resume=snap)
    facts["resume_equal"] = _same(_leaves(resumed.params), ref_w)

    # the guard: a poisoned last step is skipped, the weights kept
    three = _lenet()
    _train(three, STEPS - 1)
    FaultInjector.install(FaultInjector().add("grad.nan", step=STEPS - 1))
    try:
        poisoned = _lenet()
        opt = _train(poisoned, STEPS)
    finally:
        FaultInjector.clear()
    facts["nan_skipped"] = int(opt.state.get("skippedSteps", 0))
    facts["nan_weights_kept"] = _same(_leaves(poisoned.params),
                                      _leaves(three.params))

    # an exception after a dispatch: model.params is the step's result
    failing = _lenet()
    try:
        _train(failing, STEPS, _Losses(fail_at=1))
        facts["raised"] = False
    except RuntimeError:
        facts["raised"] = True
    leaves = jax.tree_util.tree_leaves(failing.params)
    facts["after_raise_readable"] = not any(l.is_deleted() for l in leaves)
    two = _lenet()
    _train(two, 2)
    facts["after_raise_is_newest"] = facts["after_raise_readable"] and \
        _same(_leaves(failing.params), _leaves(two.params))
    print(json.dumps(facts))


@pytest.fixture(scope="module")
def donated(tmp_path_factory):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    tmp = str(tmp_path_factory.mktemp("donation"))
    code = ("import jax; "
            "jax.config.update('jax_enable_compilation_cache', False); "
            "from tests.test_local_donation import _child_main; "
            f"_child_main({tmp!r})")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_forced_step_donates_and_consumes_its_state(donated):
    assert donated["step_donates"] is True
    assert donated["inputs_consumed"]


def test_donated_run_is_bit_equal_to_the_undonated(donated):
    assert donated["losses_equal"]
    assert donated["weights_equal"]


def test_the_callers_tree_survives(donated):
    assert donated["caller_not_deleted"]
    assert donated["caller_unchanged"]


def test_run_start_and_dispatch_carry_donates_state(donated):
    assert donated["run_start_donates"] == [True]
    assert donated["dispatch_donates"] == [True] * STEPS


def test_a_mid_run_snapshot_resumes_to_the_same_weights(donated):
    assert donated["resume_equal"]


def test_a_poisoned_step_is_skipped_with_the_weights_kept(donated):
    assert donated["nan_skipped"] == 1
    assert donated["nan_weights_kept"]


def test_an_exception_after_a_dispatch_leaves_params_readable(donated):
    assert donated["raised"]
    assert donated["after_raise_readable"]
    assert donated["after_raise_is_newest"]
