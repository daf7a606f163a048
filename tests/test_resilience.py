"""Fault-tolerance tests (``bigdl_tpu/resilience``): every recovery path
is *proven* by injecting the fault it recovers from.

The reference inherited these behaviors from Spark (task retry, lineage
recovery, straggler dropping — ``DistriOptimizer.scala:244-272``); here
each one is rebuilt natively and exercised on the 8-device CPU mesh:

* kill-and-resume: a run killed by an injected preemption at step N and
  relaunched with auto-resume lands on the SAME weights as an
  uninterrupted run;
* non-finite guard: an injected NaN gradient is skipped with weights
  kept and the drop ledgered in Metrics;
* torn checkpoints: a partial snapshot dir is never the resume source;
* prefetch/reader faults: background-thread errors propagate (never
  hang), transient I/O errors are retried away.
"""

import time

import pytest

import jax
import jax.numpy as jnp
import numpy as np

import bigdl_tpu.nn as nn
from bigdl_tpu.dataset import DataSet, MiniBatch
from bigdl_tpu.dataset.prefetch import MTTransformer, PrefetchToDevice
from bigdl_tpu.dataset.transformer import Transformer
from bigdl_tpu.engine import Engine
from bigdl_tpu.optim import DistriOptimizer, LocalOptimizer, SGD, Trigger
from bigdl_tpu.optim.local_optimizer import SKIPPED_STEPS
from bigdl_tpu.resilience import (Fault, FaultInjector, InjectedFault,
                                  Watchdog, WatchdogTimeout, retry)
from bigdl_tpu.utils import checkpoint as ckpt


@pytest.fixture(autouse=True)
def _clean_injector():
    FaultInjector.clear()
    yield
    FaultInjector.clear()


def _model():
    m = nn.Sequential()
    m.add(nn.Linear(4, 8))
    m.add(nn.Tanh())
    m.add(nn.Linear(8, 2))
    m.add(nn.LogSoftMax())
    m.build(jax.random.PRNGKey(3))
    return m


def _batches(n=8):
    # identical batches isolate state-restore checks from data order
    rng = np.random.RandomState(0)
    x = rng.rand(8, 4).astype(np.float32)
    y = (np.arange(8) % 2 + 1).astype(np.float32)
    return [MiniBatch(x, y) for _ in range(n)]


def _leaves(params):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(params)]


# -- retry --------------------------------------------------------------------

def test_retry_recovers_transient_and_propagates_hard():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return "ok"

    assert retry(flaky, backoff=0.001, jitter=0.0) == "ok"
    assert calls["n"] == 3

    def hard():
        calls["n"] += 1
        raise ValueError("programming error")

    calls["n"] = 0
    with pytest.raises(ValueError):
        retry(hard, backoff=0.001)
    assert calls["n"] == 1          # non-retryable: no second attempt

    def always():
        raise OSError("down")

    with pytest.raises(OSError):
        retry(always, retries=2, backoff=0.001, jitter=0.0)


# -- fault injector -----------------------------------------------------------

def test_fault_spec_parsing():
    f = Fault.parse("train.step@5")
    assert (f.site, f.step, f.count, f.exc) == \
        ("train.step", 5, 1, InjectedFault)
    f = Fault.parse("io.read*2=OSError")
    assert (f.site, f.step, f.count, f.exc) == ("io.read", None, 2, OSError)
    with pytest.raises(ValueError):
        Fault.parse("x=NoSuchError")
    inj = FaultInjector.from_env("a@1;b*3")
    assert len(inj.faults) == 2


def test_fire_and_should_respect_step_and_count():
    FaultInjector.install(FaultInjector().add("s", step=2).add("q", count=2))
    FaultInjector.fire("s", step=1)                  # no match
    with pytest.raises(InjectedFault):
        FaultInjector.fire("s", step=2)
    FaultInjector.fire("s", step=2)                  # count exhausted
    assert FaultInjector.should("q") and FaultInjector.should("q")
    assert not FaultInjector.should("q")


# -- watchdog -----------------------------------------------------------------

def test_watchdog_fires_on_hung_step():
    with pytest.raises(WatchdogTimeout, match="watchdog"):
        with Watchdog(0.2, label="hung step"):
            # short sleeps: the watchdog's interrupt lands between
            # bytecodes, and one long C-level sleep would sit it out
            for _ in range(1000):
                time.sleep(0.01)


def test_watchdog_disarmed_and_fast_path():
    with Watchdog(None):
        pass
    with Watchdog(30.0, label="quick"):
        x = 1 + 1
    assert x == 2


def test_watchdog_on_timeout_callback():
    fired = []
    with Watchdog(0.05, on_timeout=lambda: fired.append(1)):
        time.sleep(0.3)
    assert fired == [1]


# -- non-finite step guard ----------------------------------------------------

def test_nan_guard_local_skips_and_counts():
    m = _model()
    before = _leaves(m.params)
    opt = LocalOptimizer(m, nn.ClassNLLCriterion(),
                         DataSet.array(_batches()),
                         end_when=Trigger.max_iteration(3))
    opt.set_optim_method(SGD(learning_rate=0.1))
    # step 0 poisoned: its update must be a no-op, steps 1-2 train on
    FaultInjector.install(FaultInjector().add("grad.nan", step=0))
    opt.optimize()
    assert opt.state["skippedSteps"] == 1
    assert opt.metrics.get(SKIPPED_STEPS) == 1
    assert opt.state["neval"] == 3
    after = _leaves(m.params)
    assert any(not np.allclose(a, b) for a, b in zip(before, after)), \
        "healthy steps must still have trained"

    # a run that is ONLY the poisoned step: weights must be untouched
    FaultInjector.install(FaultInjector().add("grad.nan", step=0))
    m2 = _model()
    before2 = _leaves(m2.params)
    opt2 = LocalOptimizer(m2, nn.ClassNLLCriterion(),
                          DataSet.array(_batches()),
                          end_when=Trigger.max_iteration(1))
    opt2.set_optim_method(SGD(learning_rate=0.1))
    opt2.optimize()
    for a, b in zip(before2, _leaves(m2.params)):
        np.testing.assert_array_equal(a, b)


def test_nan_guard_distri_skips_weights_unchanged():
    Engine.reset()
    m = _model()
    before = _leaves(m.params)
    opt = DistriOptimizer(m, nn.ClassNLLCriterion(),
                          DataSet.array(_batches()),
                          end_when=Trigger.max_iteration(1))
    opt.set_optim_method(SGD(learning_rate=0.1, momentum=0.9,
                             dampening=0.0))
    FaultInjector.install(FaultInjector().add("grad.nan", step=0))
    opt.optimize()
    assert opt.state["skippedSteps"] == 1
    assert opt.metrics.get(SKIPPED_STEPS) == 1
    for a, b in zip(before, _leaves(m.params)):
        np.testing.assert_array_equal(a, b)
    Engine.reset()


def test_distri_resumed_run_matches_despite_nan_step():
    """A poisoned step must also not desync a later healthy run: train 3
    steps where step 1 is skipped, against 2 healthy steps from the same
    init consuming the same healthy batches — equal weights."""
    Engine.reset()
    m = _model()
    opt = DistriOptimizer(m, nn.ClassNLLCriterion(),
                          DataSet.array(_batches()),
                          end_when=Trigger.max_iteration(3))
    opt.set_optim_method(SGD(learning_rate=0.1))
    FaultInjector.install(FaultInjector().add("grad.nan", step=1))
    opt.optimize()
    FaultInjector.clear()

    Engine.reset()
    m2 = _model()
    opt2 = DistriOptimizer(m2, nn.ClassNLLCriterion(),
                           DataSet.array(_batches()),
                           end_when=Trigger.max_iteration(2))
    opt2.set_optim_method(SGD(learning_rate=0.1))
    opt2.optimize()
    # identical batches: 2 healthy updates in both runs -> same weights
    for a, b in zip(_leaves(m.params), _leaves(m2.params)):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-6)
    Engine.reset()


def test_max_drop_percentage_aborts_diverged_run():
    Engine.reset()
    m = _model()
    opt = DistriOptimizer(m, nn.ClassNLLCriterion(),
                          DataSet.array(_batches()),
                          end_when=Trigger.max_iteration(20),
                          max_drop_percentage=0.1)
    opt.set_optim_method(SGD(learning_rate=0.1))
    # every step NaN: the budget must cut the run short, loudly
    FaultInjector.install(FaultInjector().add("grad.nan", count=10 ** 6))
    with pytest.raises(RuntimeError, match="max_drop_percentage"):
        opt.optimize()
    Engine.reset()


# -- kill-and-resume (the acceptance path) ------------------------------------

def test_kill_and_resume_matches_uninterrupted(tmp_path):
    """Preemption drill: snapshot every step, injected crash at step 2,
    relaunch the identical script with auto-resume — final weights and
    loss equal the uninterrupted run's."""
    path = str(tmp_path / "sharded")

    def launch(iters, m, snapshot):
        Engine.reset()
        opt = DistriOptimizer(m, nn.ClassNLLCriterion(),
                              DataSet.array(_batches()),
                              end_when=Trigger.max_iteration(iters))
        opt.set_optim_method(SGD(learning_rate=0.1, momentum=0.9,
                                 dampening=0.0))
        if snapshot:
            opt.set_sharded_checkpoint(path, Trigger.several_iteration(1))
        opt.optimize()
        return opt

    # run 1: killed by an injected preemption after step 2's snapshot
    FaultInjector.install(FaultInjector().add("train.step", step=2))
    m1 = _model()
    with pytest.raises(InjectedFault):
        launch(4, m1, snapshot=True)
    FaultInjector.clear()
    assert ckpt.latest_step(path) == 2

    # run 2: the SAME launch command — auto-resume continues to 4
    m2 = _model()
    opt2 = launch(4, m2, snapshot=True)
    assert opt2.state["neval"] == 4

    # reference: uninterrupted 4 steps from the same deterministic init
    m3 = _model()
    opt3 = launch(4, m3, snapshot=False)

    for a, b in zip(_leaves(m2.params), _leaves(m3.params)):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)
    assert opt2.metrics.get("loss") == pytest.approx(
        opt3.metrics.get("loss"), abs=1e-6)
    Engine.reset()


def test_local_auto_resume_matches_uninterrupted(tmp_path):
    path = str(tmp_path / "files")

    def launch(iters, m):
        opt = LocalOptimizer(m, nn.ClassNLLCriterion(),
                             DataSet.array(_batches()),
                             end_when=Trigger.max_iteration(iters))
        opt.set_optim_method(SGD(learning_rate=0.1, momentum=0.9,
                                 dampening=0.0))
        opt.set_checkpoint(path, Trigger.several_iteration(1),
                           auto_resume=True)
        opt.optimize()
        return opt

    FaultInjector.install(FaultInjector().add("train.step", step=2))
    m1 = _model()
    with pytest.raises(InjectedFault):
        launch(4, m1)
    FaultInjector.clear()

    m2 = _model()
    opt2 = launch(4, m2)
    assert opt2.state["neval"] == 4

    m3 = _model()
    opt3 = LocalOptimizer(m3, nn.ClassNLLCriterion(),
                          DataSet.array(_batches()),
                          end_when=Trigger.max_iteration(4))
    opt3.set_optim_method(SGD(learning_rate=0.1, momentum=0.9,
                              dampening=0.0))
    opt3.optimize()
    for a, b in zip(_leaves(m2.params), _leaves(m3.params)):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-6)


def test_resume_from_missing_snapshot_raises(tmp_path):
    m = _model()
    opt = LocalOptimizer(m, nn.ClassNLLCriterion(),
                         DataSet.array(_batches()),
                         end_when=Trigger.max_iteration(1))
    opt.resume_from(str(tmp_path / "nowhere"))
    with pytest.raises(FileNotFoundError):
        opt.optimize()

    Engine.reset()
    m2 = _model()
    opt2 = DistriOptimizer(m2, nn.ClassNLLCriterion(),
                           DataSet.array(_batches()),
                           end_when=Trigger.max_iteration(1))
    opt2.resume_from(str(tmp_path / "nowhere2"))
    with pytest.raises(FileNotFoundError):
        opt2.optimize()
    Engine.reset()


# -- torn checkpoints ---------------------------------------------------------

def test_latest_step_skips_torn_snapshot(tmp_path):
    from jax.sharding import NamedSharding, PartitionSpec as P
    Engine.reset()
    mesh = Engine.init()
    x = jax.device_put(jnp.arange(16, dtype=jnp.float32).reshape(8, 2),
                       NamedSharding(mesh, P("data")))
    path = str(tmp_path / "snaps")
    ckpt.save_sharded(path, {"w": x}, step=1)
    ckpt.wait()
    # a crash mid-save: numeric dir exists, no commit markers
    torn = tmp_path / "snaps" / "2"
    torn.mkdir()
    (torn / "d").write_bytes(b"\0partial")
    assert ckpt.verify_sharded(path, 1)
    assert not ckpt.verify_sharded(path, 2)
    assert ckpt.latest_step(path) == 1
    Engine.reset()


def test_injected_torn_write_is_not_resumed(tmp_path):
    """checkpoint.save fault: the write at step 2 dies mid-flight leaving
    a torn dir; discovery must fall back to the committed step 1."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    Engine.reset()
    mesh = Engine.init()
    x = jax.device_put(jnp.arange(16, dtype=jnp.float32).reshape(8, 2),
                       NamedSharding(mesh, P("data")))
    path = str(tmp_path / "snaps")
    ckpt.save_sharded(path, {"w": x}, step=1)
    ckpt.wait()
    FaultInjector.install(FaultInjector().add("checkpoint.save", step=2))
    with pytest.raises(InjectedFault):
        ckpt.save_sharded(path, {"w": x}, step=2)
    FaultInjector.clear()
    assert ckpt.latest_step(path) == 1
    restored = ckpt.restore_sharded(path, {"w": x}, step=1)
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(x))
    Engine.reset()


def test_latest_file_snapshot_requires_complete_pair(tmp_path):
    opt = LocalOptimizer(_model(), nn.ClassNLLCriterion(),
                         DataSet.array(_batches()))
    d = tmp_path / "files"
    d.mkdir()
    (d / "model.1").write_bytes(b"x")
    (d / "state.1").write_bytes(b"x")
    (d / "state.3").write_bytes(b"x")       # torn: no model.3
    assert opt._latest_file_snapshot(str(d)) == ".1"
    # overwrite_checkpoint_ mode: unsuffixed pair is discoverable too
    d2 = tmp_path / "ow"
    d2.mkdir()
    (d2 / "model").write_bytes(b"x")
    (d2 / "state").write_bytes(b"x")
    assert opt._latest_file_snapshot(str(d2)) == ""
    (d2 / "state").unlink()                 # torn overwrite pair
    assert opt._latest_file_snapshot(str(d2)) is None


def test_set_checkpoint_does_not_disable_sharded_auto_resume(tmp_path):
    Engine.reset()
    opt = DistriOptimizer(_model(), nn.ClassNLLCriterion(),
                          DataSet.array(_batches()))
    opt.set_sharded_checkpoint(str(tmp_path / "s"),
                               Trigger.several_iteration(1))
    opt.set_checkpoint(str(tmp_path / "f"), Trigger.every_epoch())
    assert opt._sharded_auto_resume
    Engine.reset()


# -- data pipeline ------------------------------------------------------------

def test_prefetch_producer_error_propagates():
    def stream():
        yield MiniBatch(np.zeros((2, 3), np.float32), np.zeros((2,)))
        raise ValueError("decoder blew up")

    it = PrefetchToDevice(depth=2).apply(stream())
    next(it)
    with pytest.raises(ValueError, match="decoder blew up"):
        next(it)


def test_prefetch_injected_producer_fault_propagates():
    FaultInjector.install(FaultInjector().add("prefetch.producer"))
    batches = [MiniBatch(np.zeros((2, 3), np.float32), np.zeros((2,)))] * 3
    it = PrefetchToDevice(depth=2).apply(iter(batches))
    with pytest.raises(InjectedFault):
        list(it)


def test_prefetch_transient_put_retried_away():
    FaultInjector.install(
        FaultInjector().add("prefetch.put", count=2, exc=OSError))
    batches = [MiniBatch(np.full((2, 3), i, np.float32),
                         np.zeros((2,))) for i in range(4)]
    out = list(PrefetchToDevice(depth=2).apply(iter(batches)))
    assert len(out) == 4                     # nothing lost, nothing raised
    assert float(np.asarray(out[3].data)[0, 0]) == 3.0


def test_mt_transformer_worker_error_propagates():
    class Identity(Transformer):
        def apply(self, prev):
            return prev

    FaultInjector.install(FaultInjector().add("mt.worker"))
    with pytest.raises(InjectedFault):
        list(MTTransformer(Identity(), workers=2, chunk=2).apply(
            iter(range(10))))


def test_seqfile_open_retries_transient(tmp_path):
    from bigdl_tpu.dataset.seqfile import SeqFileWriter, read_seq_file
    p = str(tmp_path / "f.btsf")
    with SeqFileWriter(p) as w:
        w.append("k1", b"v1")
        w.append("k2", b"v2")
    FaultInjector.install(
        FaultInjector().add("io.read", count=2, exc=OSError))
    assert list(read_seq_file(p)) == [("k1", b"v1"), ("k2", b"v2")]


# -- factory knobs ------------------------------------------------------------

def test_optimizer_factory_forwards_resilience_knobs():
    from bigdl_tpu.optim import Optimizer
    opt = Optimizer(_model(), DataSet.array(_batches()),
                    nn.ClassNLLCriterion(),
                    skip_nonfinite=False, step_timeout=12.5)
    assert isinstance(opt, LocalOptimizer)
    assert opt.skip_nonfinite is False
    assert opt.step_timeout == 12.5
