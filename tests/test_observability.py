"""Observability tests: run ledger, spans, exporters, run-report, and the
PR-2 satellite fixes (profiler/log/metrics).

The tier-1 contract tests live here too: a LeNet smoke run must produce
a parseable ledger (every line strict JSON, monotonic step ids, required
keys) from which ``run-report`` reconstructs the per-phase breakdown
(>=90% of wall), step percentiles, throughput, and a resilience census
matching ``Metrics`` — for BOTH trainers.
"""

import glob
import json
import logging
import math
import os
import struct
import time

import jax
import numpy as np
import pytest

import bigdl_tpu.nn as nn
from bigdl_tpu.dataset.dataset import DataSet
from bigdl_tpu.dataset.transformer import MiniBatch
from bigdl_tpu.engine import Engine
from bigdl_tpu.observability import ledger as run_ledger
from bigdl_tpu.observability import (TrainSummary, ValidationSummary,
                                     metrics_to_prometheus, set_run_dir,
                                     span, tracer)
from bigdl_tpu.observability.report import (build_report, load_ledger,
                                            main as report_main,
                                            render_report)
from bigdl_tpu.optim import (DistriOptimizer, LocalOptimizer, Metrics, SGD,
                             Top1Accuracy, Trigger)
from bigdl_tpu.optim.local_optimizer import SKIPPED_STEPS
from bigdl_tpu.resilience.fault_injector import FaultInjector


@pytest.fixture(autouse=True)
def _clean_ledger():
    """Every test starts and ends with the ledger disabled and the fault
    injector disarmed."""
    set_run_dir(None)
    yield
    set_run_dir(None)
    FaultInjector.clear()


def _read_lines(run_dir):
    """Every ledger line, parsed STRICTLY (parse_constant rejects the
    NaN/Infinity spellings Python's json would otherwise accept)."""
    recs = []
    for name in sorted(os.listdir(run_dir)):
        if not name.endswith(".jsonl"):
            continue
        with open(os.path.join(run_dir, name)) as f:
            for line in f:
                recs.append(json.loads(
                    line, parse_constant=lambda c: pytest.fail(
                        f"non-strict JSON constant {c!r} in ledger")))
    return recs


# -- ledger core --------------------------------------------------------------

def test_ledger_disabled_is_noop(tmp_path):
    assert run_ledger.get_ledger() is None
    with span("anything", k=1) as sid:
        assert sid is None          # zero-bookkeeping fast path
    run_ledger.emit("event", kind="dropped.on.floor")
    assert not list(tmp_path.iterdir())


def test_ledger_env_activation(tmp_path, monkeypatch):
    run_dir = str(tmp_path / "run")
    monkeypatch.setenv("BIGDL_TPU_RUN_DIR", run_dir)
    # force the lazy env check to re-run (set_run_dir(None) latched it)
    monkeypatch.setattr(run_ledger, "_env_checked", False)
    monkeypatch.setattr(run_ledger, "_active", None)
    led = run_ledger.get_ledger()
    assert led is not None and led.dir == run_dir
    run_ledger.emit("event", kind="env.works")
    led.flush()
    assert any(r.get("kind") == "env.works" for r in _read_lines(run_dir))


def test_ledger_lines_are_strict_json_even_for_nan(tmp_path):
    led = set_run_dir(str(tmp_path))
    run_ledger.emit("step", loss=float("nan"))     # unserializable strict
    run_ledger.emit("event", kind="fine", obj=object())  # default=str
    led.flush()
    recs = _read_lines(str(tmp_path))
    types = [r["type"] for r in recs]
    assert "ledger.unserializable" in types    # replaced, not dropped
    assert any(r.get("kind") == "fine" for r in recs)


def test_ledger_overflow_drops_oldest_and_counts(tmp_path):
    led = run_ledger.RunLedger(str(tmp_path), capacity=4)
    # stall the writer by flooding faster than the batch: emit without
    # letting the drain run (no sleep needed — capacity is tiny)
    for i in range(100):
        led.emit({"type": "event", "kind": "flood", "i": i})
    led.close()
    recs = _read_lines(str(tmp_path))
    survived = [r for r in recs if r["type"] != "ledger.dropped"]
    dropped = [r for r in recs if r["type"] == "ledger.dropped"]
    # bounded: never blocks, and whatever was dropped is accounted for
    # (100 flood records + the trace.bind stamp = 101 emitted)
    assert len(survived) + (dropped[0]["count"] if dropped else 0) == 101


# -- spans --------------------------------------------------------------------

def test_span_nesting_parent_links_and_error(tmp_path):
    led = set_run_dir(str(tmp_path))
    with span("outer") as outer_id:
        with span("inner", step=3) as inner_id:
            pass
    with pytest.raises(RuntimeError):
        with span("exploding"):
            raise RuntimeError("boom")
    led.flush()
    by_name = {r["name"]: r for r in _read_lines(str(tmp_path))
               if r["type"] == "span"}
    assert by_name["inner"]["parent"] == outer_id
    assert by_name["inner"]["span"] == inner_id
    assert by_name["inner"]["attrs"] == {"step": 3}
    assert "parent" not in by_name["outer"]
    assert by_name["exploding"]["error"] == "RuntimeError"
    assert by_name["exploding"]["dur_s"] >= 0    # timed despite the raise


def test_begin_span_handle_nests_children(tmp_path):
    led = set_run_dir(str(tmp_path))
    h = tracer.begin_span("setup")
    with span("child"):
        pass
    h.end()
    led.flush()
    by_name = {r["name"]: r for r in _read_lines(str(tmp_path))
               if r["type"] == "span"}
    assert by_name["child"]["parent"] == by_name["setup"]["span"]
    assert by_name["setup"]["dur_s"] >= by_name["child"]["dur_s"]


def test_compile_hook_records_recompiles(tmp_path):
    import jax.numpy as jnp
    led = set_run_dir(str(tmp_path))
    tracer.install_compile_hook()
    # a fresh shape forces a genuine XLA compile
    shape = (3, int(np.random.randint(50, 10_000)))
    jax.jit(lambda x: x * 2 + 1)(jnp.ones(shape)).block_until_ready()
    led.flush()
    compiles = [r for r in _read_lines(str(tmp_path))
                if r["type"] == "compile"]
    assert any(r["event"] == "backend_compile_duration" for r in compiles)


# -- trainer smoke runs (the tier-1 acceptance contract) ----------------------

def _check_smoke_ledger(run_dir, metrics, n_steps, expect_skipped):
    recs = _read_lines(run_dir)                 # every line strict JSON
    steps = [r for r in recs if r["type"] == "step"]
    assert len(steps) == n_steps
    ids = [r["step"] for r in steps]
    assert ids == sorted(ids) and len(set(ids)) == len(ids), \
        f"step ids not monotonic: {ids}"
    for r in steps:                             # required keys
        for key in ("step", "epoch", "records", "dur_s", "records_per_s",
                    "skipped", "ts", "mono"):
            assert key in r, f"step record missing {key}: {r}"
    assert any(r["type"] == "run.start" for r in recs)
    assert any(r["type"] == "run.end" for r in recs)

    rep = build_report(load_ledger(run_dir, strict=True)[0])
    # per-phase breakdown explains >=90% of the wall time
    assert rep["coverage"] is not None and rep["coverage"] >= 0.90, rep
    assert rep["steps"]["count"] == n_steps
    assert rep["steps"]["p50_s"] <= rep["steps"]["p95_s"] \
        <= rep["steps"]["p99_s"]
    assert rep["steps"]["records_per_s"] > 0
    assert "train.step" in rep["phases"]
    # resilience census matches Metrics exactly
    skipped_metric = int(metrics.get(SKIPPED_STEPS)) \
        if expect_skipped else 0
    assert rep["events"].get("step.skipped", 0) == skipped_metric \
        == expect_skipped
    assert rep["events"].get("fault.injected", 0) == expect_skipped
    assert rep["steps"]["skipped"] == expect_skipped
    # run-report CLI contract: exits 0 and renders
    assert report_main([run_dir, "--strict"]) == 0
    # prometheus dump landed next to the ledger
    proms = [n for n in os.listdir(run_dir) if n.endswith(".prom")]
    assert proms, "metrics-*.prom not written"
    text = open(os.path.join(run_dir, proms[0])).read()
    assert "bigdl_tpu_computing_time_average_seconds" in text


def _lenet_batches(n_batches=6, bs=8, seed=0):
    rng = np.random.RandomState(seed)
    return [MiniBatch(rng.rand(bs, 784).astype(np.float32),
                      (np.arange(bs) % 10 + 1).astype(np.float32))
            for _ in range(n_batches)]


def test_lenet_local_smoke_produces_parseable_ledger(tmp_path):
    from bigdl_tpu.models.lenet import LeNet5
    run_dir = str(tmp_path / "run")
    set_run_dir(run_dir)
    # one injected NaN step: the resilience census must line up with
    # Metrics afterwards
    FaultInjector.install(FaultInjector().add("grad.nan", step=2))
    model = LeNet5(10).build(seed=1)
    batches = _lenet_batches()
    opt = LocalOptimizer(model, nn.ClassNLLCriterion(),
                         DataSet.array(batches),
                         Trigger.max_iteration(6))
    opt.set_optim_method(SGD(learning_rate=0.01))
    opt.set_validation(Trigger.every_epoch(), DataSet.array(batches),
                       [Top1Accuracy()])
    ts = TrainSummary(str(tmp_path / "tb"), "lenet")
    vs = ValidationSummary(str(tmp_path / "tb"), "lenet")
    opt.set_train_summary(ts).set_val_summary(vs)
    opt.optimize()
    run_ledger.flush()

    _check_smoke_ledger(run_dir, opt.metrics, n_steps=6, expect_skipped=1)
    # summaries teed: in memory AND in the ledger
    assert len(ts.read_scalar("Throughput")) == 6
    assert len(ts.read_scalar("Loss")) == 5      # NaN loss not teed
    assert len(vs.read_scalar("Top1Accuracy")) == 1
    scalar_tags = {r["tag"] for r in _read_lines(run_dir)
                   if r["type"] == "scalar"}
    assert {"Loss", "Throughput", "LearningRate",
            "Top1Accuracy"} <= scalar_tags


def test_distri_smoke_produces_parseable_ledger(tmp_path):
    Engine.reset()
    run_dir = str(tmp_path / "run")
    set_run_dir(run_dir)
    model = nn.Sequential()
    model.add(nn.Linear(4, 2))
    model.add(nn.LogSoftMax())
    model.build(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    batches = [MiniBatch(rng.rand(8, 4).astype(np.float32),
                         (np.arange(8) % 2 + 1).astype(np.float32))
               for _ in range(4)]
    opt = DistriOptimizer(model, nn.ClassNLLCriterion(),
                          DataSet.array(batches),
                          end_when=Trigger.max_iteration(4))
    opt.optimize()
    run_ledger.flush()
    _check_smoke_ledger(run_dir, opt.metrics, n_steps=4, expect_skipped=0)
    rep = build_report(load_ledger(run_dir)[0])
    # the distri-only seams made it into the breakdown
    for phase in ("h2d", "init", "allreduce.init_shards"):
        assert phase in rep["phases"], sorted(rep["phases"])
    # one put in the open (step 0), one under each step (the last feeds
    # no step): three of the four steps found their input on the device
    h2d = rep["phases"]["h2d"]
    assert (h2d["count"], h2d["ahead"], h2d["steps_ahead"]) == (5, 4, 3)
    assert "input ahead of the device in 3/4 steps (75.0%)" in \
        render_report(rep)
    Engine.reset()


def test_run_report_cli_errors(tmp_path):
    assert report_main([str(tmp_path)]) == 2     # no ledger files
    p = tmp_path / "events-1.jsonl"
    p.write_text('{"type":"event","kind":"ok","ts":1.0,"mono":1.0}\n'
                 'NOT JSON\n')
    assert report_main([str(tmp_path)]) == 0     # tolerant by default
    with pytest.raises(ValueError):
        load_ledger(str(tmp_path), strict=True)


def test_cli_main_dispatch(tmp_path):
    from bigdl_tpu import cli
    (tmp_path / "events-1.jsonl").write_text(
        '{"type":"step","step":0,"dur_s":0.1,"records":8,'
        '"ts":1.0,"mono":1.0}\n')
    assert cli.main(["run-report", str(tmp_path)]) == 0
    assert cli.main(["no-such-command"]) == 2


def test_summary_trigger_aligns_with_checkpoint_triggers(tmp_path):
    """``several_iteration(2)`` on a summary tag must fire on the same
    steps it would fire a checkpoint: after completed steps 2, 4, 6 —
    i.e. the scalars for executed step indices 1, 3, 5."""
    set_run_dir(str(tmp_path / "run"))
    model = nn.Sequential()
    model.add(nn.Linear(4, 2))
    model.add(nn.LogSoftMax())
    model.build(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    batches = [MiniBatch(rng.rand(4, 4).astype(np.float32),
                         (np.arange(4) % 2 + 1).astype(np.float32))
               for _ in range(6)]
    opt = LocalOptimizer(model, nn.ClassNLLCriterion(),
                         DataSet.array(batches),
                         Trigger.max_iteration(6))
    ts = TrainSummary(str(tmp_path / "tb"), "t")
    ts.set_summary_trigger("Loss", Trigger.several_iteration(2))
    # epoch triggers must work too: the trigger reads the REAL state
    # (epoch, isLastBatchOfEpoch), not a neval-only copy
    ts.set_summary_trigger("LearningRate", Trigger.every_epoch())
    opt.set_train_summary(ts)
    opt.optimize()
    assert [s for s, _, _ in ts.read_scalar("Loss")] == [1, 3, 5]
    assert len(ts.read_scalar("Throughput")) == 6   # untriggered: every
    # 6 batches of 4 over 24 records = 1 epoch -> fires once, at its end
    assert [s for s, _, _ in ts.read_scalar("LearningRate")] == [5]


def test_step_record_inf_loss_is_strict_json(tmp_path):
    led = set_run_dir(str(tmp_path))
    opt = LocalOptimizer(object(), object(), object())
    opt._emit_step_record(0, float("inf"), 8, 0.1, clr=0.05)
    opt._emit_step_record(1, float("nan"), 8, 0.1, clr=0.05)
    led.flush()
    recs = _read_lines(str(tmp_path))
    steps = [r for r in recs if r["type"] == "step"]
    # non-finite losses become null, never an unserializable replacement
    assert [r["loss"] for r in steps] == [None, None]
    assert not any(r["type"] == "ledger.unserializable" for r in recs)


def test_seqfile_read_emits_io_records_not_spans(tmp_path):
    from bigdl_tpu.dataset.image import LabeledImage
    from bigdl_tpu.dataset.seqfile import (BGRImgToLocalSeqFile,
                                           LocalSeqFileToBytes)
    rs = np.random.RandomState(0)
    imgs = [LabeledImage(rs.randint(0, 256, (4, 4, 3)).astype(np.float32),
                         1.0) for _ in range(8)]
    files = list(BGRImgToLocalSeqFile(8, str(tmp_path / "part"))
                 .apply(iter(imgs)))
    led = set_run_dir(str(tmp_path / "run"))
    assert len(list(LocalSeqFileToBytes().apply(iter(files)))) == 8
    led.flush()
    recs = _read_lines(str(tmp_path / "run"))
    ios = [r for r in recs if r["type"] == "io"]
    assert len(ios) == 1 and ios[0]["records"] == 8
    # the read overlaps whatever span pulls the pipeline — it must stay
    # OUT of the span/phase accounting
    rep = build_report(load_ledger(str(tmp_path / "run"))[0])
    assert "seqfile.read" in rep["io"]
    assert "seqfile.read" not in rep["phases"]
    assert "seqfile.read" in render_report(rep)


def test_report_coverage_ignores_crashed_runs(tmp_path):
    """A killed run (run.start, no run.end) must not poison the coverage
    figure of the relaunch that shares the run directory."""
    crashed = [
        {"type": "run.start", "thread": 1, "ts": 1.0, "mono": 0.0},
        {"type": "span", "name": "train.step", "span": 1, "thread": 1,
         "ts": 1.0, "mono": 0.1, "dur_s": 50.0},
    ]
    completed = [
        {"type": "run.start", "thread": 2, "ts": 9.0, "mono": 100.0},
        {"type": "span", "name": "train.step", "span": 1, "thread": 2,
         "ts": 9.1, "mono": 100.1, "dur_s": 9.5},
        {"type": "run.end", "thread": 2, "ts": 19.0, "mono": 110.0},
    ]
    (tmp_path / "events-1.jsonl").write_text(
        "".join(json.dumps(r) + "\n" for r in crashed))
    (tmp_path / "events-2.jsonl").write_text(
        "".join(json.dumps(r) + "\n" for r in completed))
    rep = build_report(load_ledger(str(tmp_path))[0])
    assert rep["runs"] == 2 and rep["completed_runs"] == 1
    assert rep["wall_s"] == pytest.approx(10.0)
    # 9.5s of spans inside the 10s completed window; the crashed run's
    # 50s span is excluded (it would have read as 500% coverage)
    assert rep["coverage"] == pytest.approx(0.95)
    assert "1 did not complete" in render_report(rep)


def test_report_crashed_run_same_pid_does_not_steal_next_end(tmp_path):
    """In-process relaunch (fault caught, fresh optimizer in the SAME
    pid): the crashed run.start must not pair with the relaunch's
    run.end and report a wall spanning both runs."""
    recs = [
        {"type": "run.start", "thread": 1, "ts": 1.0, "mono": 0.0},
        {"type": "span", "name": "train.step", "span": 1, "thread": 1,
         "ts": 1.0, "mono": 0.1, "dur_s": 2.0},
        # crash here (no run.end); relaunch in the same process:
        {"type": "run.start", "thread": 1, "ts": 9.0, "mono": 100.0},
        {"type": "span", "name": "train.step", "span": 2, "thread": 1,
         "ts": 9.1, "mono": 100.1, "dur_s": 9.5},
        {"type": "run.end", "thread": 1, "ts": 19.0, "mono": 110.0},
    ]
    (tmp_path / "events-7.jsonl").write_text(
        "".join(json.dumps(r) + "\n" for r in recs))
    rep = build_report(load_ledger(str(tmp_path))[0])
    assert rep["runs"] == 2 and rep["completed_runs"] == 1
    assert rep["wall_s"] == pytest.approx(10.0)      # NOT 110
    assert rep["coverage"] == pytest.approx(0.95)


def test_emit_critical_survives_closed_ledger(tmp_path):
    led = set_run_dir(str(tmp_path))
    run_ledger.emit_critical("event", kind="watchdog.timeout", label="x")
    led.close()
    run_ledger.emit_critical("event", kind="after.close")  # must not raise
    assert any(r.get("kind") == "watchdog.timeout"
               for r in _read_lines(str(tmp_path)))


# -- exporters ----------------------------------------------------------------

def test_train_summary_triggers_and_tfevents(tmp_path):
    from bigdl_tpu.observability.summary import _masked_crc
    s = TrainSummary(str(tmp_path), "app")
    s.set_summary_trigger("Loss", Trigger.several_iteration(2))
    for i in range(4):
        s.add_scalar("Loss", float(i), i)
    assert [v for _, v, _ in s.read_scalar("Loss")] == [0.0, 1.0, 2.0, 3.0]
    assert s.trigger_for("Loss") is not None
    s.close()
    # the event file is framed exactly as TensorBoard expects
    files = os.listdir(os.path.join(str(tmp_path), "app", "train"))
    assert len(files) == 1 and files[0].startswith("events.out.tfevents.")
    data = open(os.path.join(str(tmp_path), "app", "train",
                             files[0]), "rb").read()
    off, n = 0, 0
    while off < len(data):
        (ln,) = struct.unpack("<Q", data[off:off + 8])
        assert struct.unpack("<I", data[off + 8:off + 12])[0] == \
            _masked_crc(data[off:off + 8])
        payload = data[off + 12:off + 12 + ln]
        assert struct.unpack(
            "<I", data[off + 12 + ln:off + 16 + ln])[0] == \
            _masked_crc(payload)
        off += 16 + ln
        n += 1
    assert n == 5          # file_version + 4 scalars


def test_prometheus_rendering_units():
    m = Metrics()
    m.set("computing time average", 2e9)            # ns -> seconds gauge
    m.incr("skipped steps (non-finite)", 3)         # count -> _total
    m.set("get weights wire traffic per node", 1.5, unit="MB/iteration")
    m.set("computing time for each node", [1e9, 2e9])
    text = metrics_to_prometheus(m)
    assert "bigdl_tpu_computing_time_average_seconds 2.0" in text
    assert "bigdl_tpu_skipped_steps_non_finite_total 3.0" in text
    assert "mb_iteration 1.5" in text
    assert 'bigdl_tpu_computing_time_for_each_node_seconds{node="0"} 1.0' \
        in text
    for line in text.splitlines():
        assert line.startswith(("#", "bigdl_tpu_"))


# -- satellite fixes ----------------------------------------------------------

def test_steptimer_phase_attributes_failed_steps():
    from bigdl_tpu.utils.profiler import StepTimer
    m = Metrics()
    t = StepTimer(m)
    with pytest.raises(RuntimeError):
        with t.phase("computing time for each node"):
            raise RuntimeError("step died")
    # the failed step still got its time attributed (try/finally fix)
    assert m.get("computing time for each node") >= 0


def test_init_logging_no_duplicate_lines_and_level_update(capsys):
    from bigdl_tpu.utils.log import init_logging
    logger = logging.getLogger("bigdl_tpu")
    old = (list(logger.handlers), logger.level, logger.propagate)
    root_handler = logging.StreamHandler()
    logging.getLogger().addHandler(root_handler)
    try:
        logger.handlers = []
        init_logging(logging.INFO)
        assert logger.propagate is False     # no double print via root
        logger.info("hello-once")
        assert capsys.readouterr().out.count("hello-once") == 1
        init_logging(logging.DEBUG)          # repeat call retunes level
        assert logger.level == logging.DEBUG
        assert len(logger.handlers) == 1     # no handler stacking
    finally:
        logging.getLogger().removeHandler(root_handler)
        logger.handlers, logger.level, logger.propagate = \
            old[0], old[1], old[2]


def test_metrics_add_distributed_is_elementwise():
    m = Metrics()
    m.set("per node", [1.0, 2.0], unit="count")
    m.add("per node", [10.0, 20.0])
    assert m.get("per node") == [11.0, 22.0]    # NOT length 4
    with pytest.raises(ValueError):
        m.add("per node", [1.0, 2.0, 3.0])      # length mismatch
    with pytest.raises(TypeError):
        m.add("per node", 5.0)                  # scalar onto array
    m.set("scalar", 1.0)
    with pytest.raises(TypeError):
        m.add("scalar", [1.0, 2.0])             # array onto scalar
    m.add("fresh dist", [1.0, 2.0])             # list registers dist
    assert m.get("fresh dist") == [1.0, 2.0]


def test_metrics_gathered_single_process():
    m = Metrics()
    m.set("a", 10.0, parallel=2)
    m.set("b", [1.0, 2.0, 3.0])
    scalars, arrays = m.gathered()
    assert scalars["a"] == (5.0, [5.0])
    assert arrays["b"] == [1.0, 2.0, 3.0]
    assert "per node" in m.summary(across_processes=True)


def test_metrics_snapshot_is_a_copy():
    m = Metrics()
    m.set("x", 1.0)
    local, dist, units = m.snapshot()
    local["x"][0] = 999.0
    assert m.get("x") == 1.0

# -- r10 flight recorder: ledger edge paths -----------------------------------

def test_emit_critical_flushes_under_concurrent_writers(tmp_path):
    """The crash contract under contention: N threads hammering emit()
    while another thread emit_critical()s — every critical record is on
    disk the moment its emit_critical returns, whatever the writer
    thread is doing."""
    import threading
    set_run_dir(str(tmp_path))
    start = threading.Barrier(5)

    def flood(tid):
        start.wait()
        for i in range(2000):
            run_ledger.emit("event", kind="noise", t=tid, i=i)

    writers = [threading.Thread(target=flood, args=(t,))
               for t in range(4)]
    for t in writers:
        t.start()
    start.wait()
    for k in range(8):
        run_ledger.emit_critical("event", kind="critical", k=k)
        on_disk = [r for r in _read_lines(str(tmp_path))
                   if r.get("kind") == "critical"]
        # flush-before-crash: THIS critical record is durable now
        assert any(r["k"] == k for r in on_disk), k
    for t in writers:
        t.join()
    set_run_dir(None)
    recs = _read_lines(str(tmp_path))     # still strict JSON throughout
    assert sum(1 for r in recs if r.get("kind") == "critical") == 8


def test_relaunched_pid_file_collision_appends_history(tmp_path):
    """A relaunched process that lands on the SAME pid (container
    restarts pin pids) must extend the old events file, not truncate
    the crashed run's history."""
    led1 = run_ledger.RunLedger(str(tmp_path))
    led1.emit({"type": "event", "kind": "first.life"})
    led1.close()
    led2 = run_ledger.RunLedger(str(tmp_path))     # same pid, same file
    led2.emit({"type": "event", "kind": "second.life"})
    led2.close()
    from bigdl_tpu.observability.report import ledger_files
    assert len(ledger_files(str(tmp_path))) == 1   # one file, two lives
    recs = _read_lines(str(tmp_path))
    kinds = [r.get("kind") for r in recs]
    assert "first.life" in kinds and "second.life" in kinds
    assert kinds.index("first.life") < kinds.index("second.life")
    # both lives carry a trace.bind, so the reader can tell them apart
    assert sum(1 for r in recs if r["type"] == "trace.bind") == 2


def test_ledger_overflow_accounting_with_final_flood(tmp_path):
    """Drop-oldest accounting survives a flood that ends mid-drain: the
    ledger.dropped record equals exactly the records missing."""
    led = run_ledger.RunLedger(str(tmp_path), capacity=8)
    for i in range(500):
        led.emit({"type": "event", "kind": "f2", "i": i})
    led.close()
    recs = _read_lines(str(tmp_path))
    got = sorted(r["i"] for r in recs if r.get("kind") == "f2")
    binds = sum(1 for r in recs if r["type"] == "trace.bind")
    dropped = sum(r["count"] for r in recs
                  if r["type"] == "ledger.dropped")
    # 500 flood records + the trace.bind stamp, each either on disk or
    # counted in ledger.dropped
    assert len(got) + binds + dropped == 501
    # drop-OLDEST: whatever survives is a suffix-heavy set — the last
    # record emitted is never the one sacrificed
    assert got[-1] == 499


# -- r10 flight recorder: trace context + export ------------------------------

def test_span_link_fields_via_attach(tmp_path):
    import threading
    from bigdl_tpu.observability import trace as run_trace
    set_run_dir(str(tmp_path))
    with span("submitter") as sid:
        wire = run_trace.current_wire()
    assert wire is not None and wire[1] == os.getpid() and wire[2] == sid

    def worker():
        with run_trace.attach(wire):
            with span("work.outer"):
                with span("work.inner"):
                    pass

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    run_ledger.flush()
    recs = _read_lines(str(tmp_path))
    outer = next(r for r in recs if r.get("name") == "work.outer")
    inner = next(r for r in recs if r.get("name") == "work.inner")
    # only the TOP-LEVEL span links; the child keeps a containment parent
    assert outer["link"] == sid and outer["link_pid"] == os.getpid()
    assert "link" not in inner and inner["parent"] == outer["span"]


def test_attach_none_is_noop_and_free():
    from bigdl_tpu.observability import trace as run_trace
    assert run_trace.current_wire() is None      # ledger off -> None
    with run_trace.attach(None):
        with span("x") as sid:
            assert sid is None


def test_trace_export_cli_on_synthetic_ledger(tmp_path, capsys):
    from bigdl_tpu.cli import trace_export
    set_run_dir(str(tmp_path))
    with span("parent"):
        run_ledger.emit("event", kind="mark")
    run_ledger.flush()
    set_run_dir(None)
    out = tmp_path / "t.json"
    assert trace_export([str(tmp_path), "--out", str(out)]) == 0
    payload = json.loads(out.read_text())
    names = {e["name"] for e in payload["traceEvents"]}
    assert "parent" in names and "mark" in names
    assert payload["otherData"]["trace_id"]
    # no ledger files -> exit 2
    assert trace_export([str(tmp_path / "void")]) == 2


class _ObsAugment:
    """Module-level (spawn-picklable) pass-through augment chain: its
    only job is making the ingest workers emit ingest.augment spans.
    Each sample costs 20 ms, which paces the run (a batch of 8 is
    0.16 s of one worker) while the stitch test waits for the second
    worker to come up and serve a chunk."""

    def __call__(self, it):
        for s in it:
            time.sleep(0.02)
            yield s

    def clone_transformer(self):
        return self

    def reseed(self, seed):
        pass


def _workers_with_spans(run_dir) -> int:
    """Ingest-worker ledger files that already hold an augment span (the
    workers' writer threads drain every 0.25 s, so a span reaches the
    file while the run goes on)."""
    n = 0
    for path in glob.glob(os.path.join(run_dir, "events-*.jsonl")):
        if path.endswith(f"events-{os.getpid()}.jsonl"):
            continue
        with open(path, encoding="utf-8") as f:
            n += '"ingest.augment"' in f.read()
    return n


def test_trace_export_stitches_two_worker_training_run(tmp_path):
    """The r10 acceptance path: a 2-ingest-worker training run's per-pid
    ledgers export as ONE valid Chrome trace whose events span >= 3
    distinct pids (trainer + 2 spawn workers) with the cross-process
    links intact (every link edge resolves to a present span, and the
    export carries matching flow-arrow pairs)."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.cli import trace_export
    from bigdl_tpu.dataset.sharded import ShardedDataSet
    from bigdl_tpu.dataset.transformer import Sample, SampleToBatch
    from bigdl_tpu.models.lenet import LeNet5
    from bigdl_tpu.observability import trace as run_trace
    from bigdl_tpu.optim import LocalOptimizer, SGD, Trigger

    rng = np.random.RandomState(0)
    samples = [Sample(rng.rand(784).astype(np.float32),
                      np.float32(i % 10 + 1)) for i in range(48)]
    run_dir = str(tmp_path / "run")
    set_run_dir(run_dir)
    try:
        ds = ShardedDataSet(samples, augment=_ObsAugment(),
                            batcher=SampleToBatch(8), workers=2, chunk=6)
        model = LeNet5(10).build(seed=1)
        # ten iterations, and on until BOTH workers have served a chunk:
        # the executor spawns them one by one and a spawn takes seconds
        # on a loaded machine (six test workers share this one's cores),
        # so the first one up can serve any fixed number of iterations
        # alone and the second then binds its ledger without ever
        # writing a span.  Bounded: with one worker the augment's 20 ms
        # a sample make 300 iterations about a minute.
        class BothWorkersServed(Trigger):
            def __call__(self, state):
                return state["neval"] >= 300 or (
                    state["neval"] >= 10
                    and _workers_with_spans(run_dir) >= 2)

        opt = LocalOptimizer(model, nn.ClassNLLCriterion(), ds,
                             BothWorkersServed())
        opt.set_optim_method(SGD(learning_rate=0.01))
        opt.optimize()
        run_ledger.flush()
    finally:
        set_run_dir(None)

    records, bad = load_ledger(run_dir)
    assert bad == 0
    st = run_trace.stitch_stats(records)
    assert st["pids"] >= 3, st                  # trainer + 2 workers
    assert st["link_edges"] >= 1
    assert st["cross_pid_edges"] >= 1           # worker -> driver links
    assert st["resolved_edges"] == st["link_edges"]   # intact

    out = tmp_path / "trace.json"
    assert trace_export([run_dir, "--out", str(out)]) == 0
    payload = json.loads(out.read_text())
    events = payload["traceEvents"]
    span_pids = {e["pid"] for e in events if e.get("ph") == "X"}
    assert len(span_pids) >= 3
    # flow arrows: every start has its finish, ids pair up, and at
    # least one arrow crosses a process boundary
    starts = {e["id"]: e for e in events if e.get("ph") == "s"}
    finishes = {e["id"]: e for e in events if e.get("ph") == "f"}
    assert starts and set(starts) == set(finishes)
    assert any(starts[i]["pid"] != finishes[i]["pid"] for i in starts)
    # the worker pids' span rows really are the ingest stages
    worker_names = {e["name"] for e in events if e.get("ph") == "X"
                    and e["pid"] != os.getpid()}
    assert "ingest.augment" in worker_names
    # one trace id binds every file
    assert len(payload["otherData"]["trace_ids"]) == 1
    # process metadata rows name the roles
    roles = {e["pid"]: e["args"]["name"] for e in events
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert any("ingest-worker" in v for v in roles.values())
    assert any("LocalOptimizer" in v for v in roles.values())


# -- r10 flight recorder: cost & memory attribution ---------------------------

def test_emit_cost_records_and_dedupes(tmp_path):
    import jax.numpy as jnp
    from bigdl_tpu.observability import costs
    set_run_dir(str(tmp_path))
    x = jnp.ones((16, 16))

    @jax.jit
    def f(a):
        return (a @ a.T).sum()

    r1 = costs.emit_cost("unit.exe", f, x)
    assert r1 is not None and r1["flops"] > 0 and r1["bytes_accessed"] > 0
    assert costs.emit_cost("unit.exe", f, x) is None     # deduped
    # a NEW shape re-prices (the signature is part of the key)
    assert costs.emit_cost("unit.exe", f, jnp.ones((8, 8))) is not None
    run_ledger.flush()
    recs = [r for r in _read_lines(str(tmp_path))
            if r["type"] == "cost.analysis"]
    assert len(recs) == 2
    # a non-jitted callable degrades to None, no record
    assert costs.emit_cost("not.jitted", lambda a: a, x) is None


def test_costs_disabled_paths(tmp_path, monkeypatch):
    from bigdl_tpu.observability import costs
    assert not costs.costs_enabled()             # ledger off
    set_run_dir(str(tmp_path))
    monkeypatch.setenv("BIGDL_TPU_COSTS", "0")
    assert not costs.costs_enabled()             # kill switch
    monkeypatch.delenv("BIGDL_TPU_COSTS")
    assert costs.costs_enabled()


def test_hbm_sampling_noop_on_cpu_and_report_section(tmp_path):
    from bigdl_tpu.observability import costs
    set_run_dir(str(tmp_path))
    costs.sample_hbm(step=0, force=True)     # CPU: memory_stats is None
    run_ledger.flush()
    assert not any(r["type"] == "mem.hbm"
                   for r in _read_lines(str(tmp_path)))
    # synthetic mem.hbm records (what a TPU/GPU backend emits) render
    run_ledger.emit("mem.hbm", step=16, peak_bytes=3 * 10**9,
                    bytes_in_use=2 * 10**9, devices=[])
    run_ledger.emit("mem.hbm", step=32, peak_bytes=4 * 10**9,
                    bytes_in_use=2 * 10**9, devices=[])
    run_ledger.flush()
    records, _ = load_ledger(str(tmp_path))
    rep = build_report(records)
    assert rep["hbm"]["samples"] == 2
    assert rep["hbm"]["peak_bytes"] == 4 * 10**9
    assert "hbm high watermark" in render_report(rep)


def test_run_report_json_carries_all_sections(tmp_path, capsys):
    """run-report --json: machine-readable output with the same
    sections the text renderer draws from — CI trends per-phase times
    without screen-scraping."""
    from bigdl_tpu.observability.report import main as report_main
    set_run_dir(str(tmp_path))
    with span("phase.a"):
        pass
    run_ledger.emit("step", step=0, loss=1.0, records=8, dur_s=0.01)
    run_ledger.emit("cost.analysis", label="x", flops=10.0,
                    bytes_accessed=5.0, output_bytes=1.0,
                    intensity_flops_per_byte=2.0)
    run_ledger.flush()
    set_run_dir(None)
    assert report_main([str(tmp_path), "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    for key in ("phases", "steps", "events", "compile", "io", "scalars",
                "serving", "fleet", "fleet_hosts", "rollout",
                "fleet_trace",
                "fleet_telemetry", "param_bytes",
                "ingest", "lint", "mesh",
                "elastic", "tuning", "costs", "hbm", "slo", "trace_ids",
                "link_edges", "coverage", "wall_s", "record_count",
                "malformed_lines"):
        assert key in rep, key
    assert rep["costs"]["x"]["flops"] == 10.0
    assert rep["phases"]["phase.a"]["count"] == 1


# -- r10 flight recorder: Prometheus histograms -------------------------------

def test_metrics_histogram_prometheus_exposition():
    from bigdl_tpu.optim.metrics import LATENCY_BUCKETS_S
    m = Metrics()
    for v in (0.0005, 0.004, 0.004, 0.3, 99.0):
        m.observe("serve.latency", v, LATENCY_BUCKETS_S)
    text = metrics_to_prometheus(m)
    assert "# TYPE bigdl_tpu_serve_latency_seconds histogram" in text
    # cumulative le buckets on the FIXED ladder
    assert 'bigdl_tpu_serve_latency_seconds_bucket{le="0.001"} 1' in text
    assert 'bigdl_tpu_serve_latency_seconds_bucket{le="0.005"} 3' in text
    assert 'bigdl_tpu_serve_latency_seconds_bucket{le="0.5"} 4' in text
    assert 'bigdl_tpu_serve_latency_seconds_bucket{le="+Inf"} 5' in text
    assert "bigdl_tpu_serve_latency_seconds_count 5" in text
    assert f"bigdl_tpu_serve_latency_seconds_sum" in text


def test_metrics_histogram_fixed_ladder_contract():
    m = Metrics()
    m.observe("lat", 0.1, buckets=(0.1, 1.0))
    with pytest.raises(ValueError):
        m.observe("lat", 0.1, buckets=(0.2, 1.0))    # ladder drifted
    with pytest.raises(ValueError):
        m.observe("other", 0.1, buckets=(1.0, 0.1))  # not ascending
    # aggregation across workers: same ladder, counts add
    w1, w2 = Metrics(), Metrics()
    for v in (0.05, 0.2):
        w1.observe("lat", v, buckets=(0.1, 1.0))
    for v in (0.07, 5.0):
        w2.observe("lat", v, buckets=(0.1, 1.0))
    h1 = w1.hist_snapshot()["lat"]
    h2 = w2.hist_snapshot()["lat"]
    assert h1["buckets"] == h2["buckets"]
    merged = [a + b for a, b in zip(h1["counts"], h2["counts"])]
    assert merged == [2, 1, 1]       # le=0.1: 2, le=1.0: 1, +Inf: 1
    assert h1["count"] + h2["count"] == 4
