"""Single-host trainer.

Parity: ``optim/LocalOptimizer.scala:40-244``.  The reference clones one
model replica per core sharing a weight storage and sums gradients
chunk-parallel; on TPU the whole iteration — forward, backward, gradient
reduction, optimizer update — is ONE jitted XLA program over the full batch
(the batch dimension is the replica dimension; XLA owns the parallelism the
``Engine.default`` thread pool provided).

Host Python keeps only what the reference's driver loop kept: the data
iterator, epoch/iteration counters, triggers, validation, checkpointing,
throughput logging.
"""

from __future__ import annotations

import logging
import math
import os
import re
import time
import threading
from functools import partial
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.core.precision import training_loss
from bigdl_tpu.observability import costs
from bigdl_tpu.observability import ledger as run_ledger
from bigdl_tpu.observability import tracer
from bigdl_tpu.optim.batch_ahead import BatchAhead, _sync_shuffles
from bigdl_tpu.optim.metrics import Metrics
from bigdl_tpu.optim.optim_method import SGD, Default, OptimMethod
from bigdl_tpu.optim.trigger import Trigger
from bigdl_tpu.optim.validation import ValidationMethod
from bigdl_tpu.resilience.fault_injector import FaultInjector
from bigdl_tpu.resilience.watchdog import Watchdog
from bigdl_tpu.utils.file import File
from bigdl_tpu.utils.table import T, Table

logger = logging.getLogger("bigdl_tpu.optim")

# metric/ledger name for non-finite skipped steps (the reference's
# dropped-gradient accounting, DistriOptimizer.scala:244-272)
SKIPPED_STEPS = "skipped steps (non-finite)"


def _default_step_timeout() -> Optional[float]:
    """Watchdog timeout from ``BIGDL_TPU_STEP_TIMEOUT`` (seconds; unset/0
    disarms).  Per-optimizer override via ``set_step_timeout``."""
    raw = os.environ.get("BIGDL_TPU_STEP_TIMEOUT", "")
    try:
        t = float(raw) if raw else 0.0
    except ValueError:
        raise ValueError(
            f"BIGDL_TPU_STEP_TIMEOUT={raw!r} is not a number of seconds")
    return t if t > 0 else None


def state_donation(platforms) -> bool:
    """Whether the trainer's step donates its state on devices of these
    ``platforms``: the rule of the SPMD steps (``parallel/allreduce.py``,
    ``parallel/specs.py``).  Donated, XLA writes the new state into the
    old state's buffers and the call allocates none; on a CPU-only set
    it does not donate (donated buffers + the persistent compilation
    cache corrupt the CPU heap, and memory is not the constraint
    there)."""
    return not set(platforms) <= {"cpu"}


class LocalOptimizer:

    def __init__(self, model, criterion, dataset,
                 end_when: Optional[Trigger] = None):
        self.model = model
        self.criterion = criterion
        self.dataset = dataset
        self.end_when = end_when or Trigger.max_epoch(1)
        self.optim_method: OptimMethod = SGD()
        self.config = T()
        self.state = T(epoch=1, neval=0)
        self.validation_trigger: Optional[Trigger] = None
        self.validation_dataset = None
        self.validation_methods: List[ValidationMethod] = []
        self.checkpoint_trigger: Optional[Trigger] = None
        self.checkpoint_path: Optional[str] = None
        # Reference default (``optim/Optimizer.scala``): keep one
        # ``model.<neval>`` snapshot per trigger; ``overWriteCheckpoint()``
        # opts in to overwriting.
        self.overwrite_checkpoint = False
        self.metrics = Metrics()
        # -- observability (bigdl_tpu.observability) --
        self.train_summary = None        # TrainSummary facade (optional)
        self.val_summary = None          # ValidationSummary facade
        self.mixed_precision = False
        self._rng = jax.random.PRNGKey(0)
        self._resume_opt_state = None
        # -- mesh sharding (parallel/mesh.py + specs.py) --
        self._mesh = None                # set_mesh: GSPMD spec sharding
        self._partition_rules = None
        self._data_sharding = None
        # -- resilience (bigdl_tpu.resilience) --
        self.skip_nonfinite = True       # in-step non-finite guard
        self.step_timeout = _default_step_timeout()
        self.auto_resume = False         # discover latest snapshot at start
        self._resume_path: Optional[str] = None   # explicit resume_from

    # -- builder API (Optimizer.scala parity) -------------------------------

    def set_optim_method(self, method: OptimMethod):
        self.optim_method = method
        return self

    def set_config(self, config: Table):
        self.config.update_(config)
        return self

    def set_state(self, state: Table):
        """Restore optimizer progress.  Accepts either a bare state Table
        or a ``state.<neval>`` snapshot written by ``_maybe_checkpoint``
        (``{"state": ..., "opt_state": ...}``) — the snapshot form also
        restores the optim-method state (momentum buffers etc.) at the
        next ``optimize()``."""
        if isinstance(state, dict) and "state" in state \
                and "opt_state" in state:
            self._resume_opt_state = state["opt_state"]
            state = state["state"]
        self.state.update_(state)
        return self

    def set_end_when(self, trigger: Trigger):
        self.end_when = trigger
        return self

    def set_validation(self, trigger: Trigger, dataset,
                       methods: Sequence[ValidationMethod]):
        self.validation_trigger = trigger
        self.validation_dataset = dataset
        self.validation_methods = list(methods)
        return self

    def set_checkpoint(self, path: str, trigger: Trigger,
                       auto_resume: bool = False):
        """File-format snapshots under ``path`` on ``trigger``.  With
        ``auto_resume=True`` a relaunched run first restores the latest
        snapshot found there (preemption-safe: launch the identical
        script, it continues where the killed run left off)."""
        self.checkpoint_path = path
        self.checkpoint_trigger = trigger
        self.auto_resume = auto_resume
        return self

    def resume_from(self, path: str):
        """Explicitly resume from the latest committed snapshot under
        ``path`` (regardless of where new checkpoints go).  The restore
        happens at ``optimize()``; missing/empty ``path`` raises — an
        explicit resume silently starting from scratch would train a
        fresh model while the operator believes it continued."""
        self._resume_path = path
        return self

    def set_step_timeout(self, seconds: Optional[float]):
        """Arm the step watchdog: a step (compute + collectives + host
        sync) exceeding ``seconds`` fails fast with a stack-dump
        diagnostic (``resilience.Watchdog``) instead of hanging the
        run.  ``None``/0 disarms.  Default from
        ``BIGDL_TPU_STEP_TIMEOUT``."""
        self.step_timeout = seconds
        return self

    def set_skip_nonfinite(self, enabled: bool = True):
        """Toggle the in-step non-finite guard (on by default): a step
        with NaN/inf loss or gradients keeps the previous weights and
        optimizer state and is counted under ``skipped steps
        (non-finite)`` in ``Metrics``."""
        self.skip_nonfinite = enabled
        return self

    def set_train_summary(self, summary):
        """Tee per-step scalars (``Loss``, ``Throughput``,
        ``LearningRate``) into a ``TrainSummary`` (reference
        ``Optimizer.setTrainSummary``): TensorBoard event files + the run
        ledger.  Per-tag cadence via ``summary.set_summary_trigger``."""
        self.train_summary = summary
        return self

    def set_val_summary(self, summary):
        """Tee validation results into a ``ValidationSummary`` (reference
        ``Optimizer.setValidationSummary``), one tag per method."""
        self.val_summary = summary
        return self

    def overwrite_checkpoint_(self):
        self.overwrite_checkpoint = True
        return self

    def set_mixed_precision(self, enabled: bool = True):
        """bf16 compute / f32 master weights (``core/precision.py``) — the
        TPU analogue of the reference's fp16 codec, applied to compute."""
        self.mixed_precision = enabled
        return self

    def set_seed(self, seed: int):
        self._rng = jax.random.PRNGKey(seed)
        return self

    def set_mesh(self, mesh, partition_rules=None):
        """Shard this trainer's state over ``mesh`` per the PartitionSpec
        registry (``parallel/specs.py``): params and optimizer state are
        placed fsdp/tp-sharded, batches land batch-sharded over the dp
        axes, and the ordinary jitted step is left to GSPMD — the
        single-host trainer becomes the mesh trainer without a second
        step implementation.  ``partition_rules`` default to the
        registry's canonical zoo rules."""
        self._mesh = mesh
        self._partition_rules = partition_rules
        from bigdl_tpu.parallel.mesh import batch_sharding
        self._data_sharding = batch_sharding(mesh)
        return self

    def _place_state(self, params, opt_state):
        """Adopt the mesh (no-op without ``set_mesh``): committed
        NamedSharding placement per the registry.  Optimizer-state
        entries whose tree STRUCTURE mirrors the params (momentum /
        Adam moment trees) take the matching param leaf's sharding —
        same-shape params can carry different specs (wq vs wo), so
        shape matching would commit some moments to transposed layouts
        and buy a reshard every step; anything else (step counters) is
        replicated."""
        if self._mesh is None:
            return params, opt_state
        from bigdl_tpu.parallel.specs import SpecRegistry
        registry = SpecRegistry(self._partition_rules)
        placed = registry.place(params, self._mesh)
        if opt_state is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            shardings = registry.shardings(params, self._mesh)
            p_def = jax.tree_util.tree_structure(params)
            repl = NamedSharding(self._mesh, PartitionSpec())

            def put_entry(entry):
                if jax.tree_util.tree_structure(entry) == p_def:
                    return jax.tree_util.tree_map(jax.device_put,
                                                  entry, shardings)
                return jax.tree_util.tree_map(
                    lambda t: jax.device_put(jnp.asarray(t), repl),
                    entry)

            if isinstance(opt_state, dict):
                opt_state = {k: put_entry(v)
                             for k, v in opt_state.items()}
            else:
                opt_state = put_entry(opt_state)
        return placed, opt_state

    def _put_batch(self, array):
        """Host batch -> device: batch-sharded over the mesh's dp axes
        when ``set_mesh`` is active, plain transfer otherwise (only
        enqueued: the step that reads it waits for it).  A batch the
        ingest pipeline already staged on the device passes through."""
        if self._data_sharding is None or isinstance(array, jax.Array):
            return jnp.asarray(array)
        return jax.device_put(np.asarray(array), self._data_sharding)

    # -- the jitted step -----------------------------------------------------

    def _donates_state(self) -> bool:
        """``state_donation`` for the devices the state lives on: the
        mesh's with ``set_mesh``, the default backend's otherwise."""
        devices = self._mesh.devices.flat if self._mesh is not None \
            else jax.devices()
        return state_donation({d.platform for d in devices})

    def _build_step(self):
        """The jitted step ``(params, opt_state, model_state, data, labels,
        rng, stepno, clr) -> (params, opt_state, model_state, loss)``.
        Where ``_donates_state`` says so it donates its first three
        arguments, which are dead after the call (``step.donates_state``
        records the decision)."""
        model, criterion, optim = self.model, self.criterion, self.optim_method
        config = self.config

        mixed = self.mixed_precision
        guard = self.skip_nonfinite

        def step(params, opt_state, model_state, data, labels, rng,
                 stepno, clr):
            def loss_fn(p):
                return training_loss(
                    model, criterion, p, model_state, data, labels, rng,
                    compute_dtype=jnp.bfloat16 if mixed else None)
            (loss, new_ms), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            cfg = config.clone()
            cfg["clr"] = clr
            with jax.named_scope("update"):
                new_params, new_opt = optim.update(grads, params,
                                                   opt_state, cfg, stepno)
            if guard:
                # skip-and-keep-weights: a non-finite loss/gradient step
                # must not poison the parameters OR the optimizer state
                # (a single NaN in a momentum buffer corrupts every later
                # step).  NaN loss is the driver's skip signal.
                with jax.named_scope("guard"):
                    ok = jnp.isfinite(loss)
                    for g in jax.tree_util.tree_leaves(grads):
                        ok &= jnp.all(jnp.isfinite(g))
                    sel = lambda new, old: jax.tree_util.tree_map(
                        lambda a, b: jnp.where(ok, a, b), new, old)
                    new_params = sel(new_params, params)
                    new_opt = sel(new_opt, opt_state)
                    new_ms = sel(new_ms, model_state)
                    loss = jnp.where(ok, loss, jnp.nan)
            return new_params, new_opt, new_ms, loss

        # the guard's where(ok, new, old) reads the donated inputs inside
        # the program, which donation allows
        donate = (0, 1, 2) if self._donates_state() else ()
        step = jax.jit(step, donate_argnums=donate)
        step.donates_state = bool(donate)
        return step

    def _current_clr(self) -> float:
        """Host-side schedule evaluation, passed into the jitted step as a
        traced scalar so LR changes never retrace."""
        sched = getattr(self.optim_method, "schedule", None) or Default()
        cfg = getattr(self.optim_method, "defaults", T()).clone()
        cfg.update_(self.config)
        st = T(evalCounter=self.state.get("neval", 0),
               epoch=self.state.get("epoch", 1))
        return float(sched.current_rate(cfg, st))

    # -- resume (File snapshots) ---------------------------------------------

    def _latest_file_snapshot(self, path: str) -> Optional[str]:
        """Suffix of the newest complete snapshot pair under ``path`` —
        ``".<n>"`` for the largest numbered pair, ``""`` for the
        overwrite-mode ``model``/``state`` pair, None when neither
        exists.  Both files must be present: a crash between the two
        writes leaves a torn pair that must not be resumed."""
        if not os.path.isdir(path):
            return None
        names = set(os.listdir(path))
        steps = [int(m.group(1)) for m in
                 (re.fullmatch(r"state\.(\d+)", f) for f in names) if m]
        good = [s for s in sorted(steps, reverse=True)
                if f"model.{s}" in names]
        if good:
            return f".{good[0]}"
        if "state" in names and "model" in names:   # overwrite_checkpoint_
            return ""
        return None

    def _maybe_resume(self):
        """Restore the latest committed File snapshot when requested via
        ``resume_from`` (mandatory — missing snapshot raises) or
        ``auto_resume`` (best-effort — fresh start when none exists)."""
        path = self._resume_path or \
            (self.checkpoint_path if self.auto_resume else None)
        if not path:
            return
        suffix = self._latest_file_snapshot(path)
        if suffix is None:
            if self._resume_path is not None:
                raise FileNotFoundError(
                    f"resume_from({path!r}): no complete model/state "
                    "snapshot pair found")
            logger.info("auto_resume: no snapshot under %s — fresh start",
                        path)
            return
        model_snap = File.load(f"{path}/model{suffix}")
        snap = File.load(f"{path}/state{suffix}")
        self.model.params = model_snap["params"]
        self.model.state = model_snap["model_state"]
        if "rng" in snap:
            self._rng = jnp.asarray(snap["rng"])
        self.set_state(snap)
        logger.info("resumed File snapshot %s/{model,state}%s "
                    "(epoch %d, neval %d)", path, suffix or " (overwrite)",
                    self.state["epoch"], self.state["neval"])

    def _record_skipped_step(self) -> int:
        """Ledger a non-finite skipped step; returns the running count."""
        skipped = self.state.get("skippedSteps", 0) + 1
        self.state["skippedSteps"] = skipped
        self.metrics.incr(SKIPPED_STEPS)
        run_ledger.emit("event", kind="step.skipped",
                        step=self.state["neval"], total=skipped)
        logger.warning(
            "step %d: non-finite loss/gradient — update skipped, weights "
            "kept (%d skipped so far)", self.state["neval"], skipped)
        return skipped

    # -- observability (run ledger + summaries) ------------------------------

    def _run_start(self, **attrs) -> None:
        """Open the run in the ledger (and arm the XLA compile hook) when
        observability is enabled; free otherwise.  ``attrs`` ride on the
        ``run.start`` record."""
        if not run_ledger.enabled():
            return
        tracer.install_compile_hook()
        tracer.reset_stack()     # a prior failed run must not parent us
        run_ledger.emit_clock()
        run_ledger.emit(
            "run.start", kind=type(self).__name__, pid=os.getpid(),
            thread=threading.get_ident(),
            trace=run_ledger.trace_id(),
            process_index=jax.process_index(),
            process_count=jax.process_count(),
            device_count=jax.device_count(),
            platform=jax.default_backend(),
            start_step=self.state.get("neval", 0),
            start_epoch=self.state.get("epoch", 1), **attrs)

    def _close_ingest(self) -> None:
        """Shut down a sharded ingest pipeline's worker pool when the
        run completes (``ShardedDataSet`` keeps its process pool alive
        across epochs on purpose — per-epoch respawn would bill
        interpreter startup to every epoch's first batches).  Datasets
        without a ``close()`` are untouched.  On the failure path
        (e.g. ``IngestWorkerDied``) the pool has already torn itself
        down, and idle workers never block interpreter exit."""
        for ds in (self.dataset, self.validation_dataset):
            close = getattr(ds, "close", None)
            if callable(close):
                close()

    def _run_end(self, wall_s: float) -> None:
        """Close the run record, dump the Metrics counters as Prometheus
        text next to the ledger, and force a flush so the files are
        complete the moment ``optimize()`` returns."""
        led = run_ledger.get_ledger()
        if led is None:
            return
        run_ledger.emit("run.end", kind=type(self).__name__,
                        pid=os.getpid(), wall_s=wall_s,
                        steps=self.state["neval"],
                        epoch=self.state["epoch"],
                        skipped=self.state.get("skippedSteps", 0))
        from bigdl_tpu.observability.prometheus import write_prometheus
        write_prometheus(self.metrics,
                         os.path.join(led.dir,
                                      f"metrics-{os.getpid()}.prom"))
        led.flush()

    def _emit_step_record(self, stepno: int, loss: float, records: int,
                          dur_s: float, clr: float) -> None:
        # isfinite, not isnan: an INF loss (diverging, or guard off)
        # must also become null — a bare inf would make the strict-JSON
        # writer replace the whole step record
        finite = math.isfinite(loss)
        run_ledger.emit("step", step=stepno, epoch=self.state["epoch"],
                        loss=loss if finite else None, records=records,
                        dur_s=dur_s,
                        records_per_s=records / max(dur_s, 1e-9),
                        skipped=math.isnan(loss) and self.skip_nonfinite)
        ts = self.train_summary
        if ts is not None:
            # called AFTER the loop updates neval/isLastBatchOfEpoch, so
            # the triggers read the same post-step state the checkpoint/
            # validation triggers do — one Trigger spec fires summaries
            # and snapshots at the same steps.  ``clr`` is the rate the
            # step ACTUALLY ran with (re-evaluating the schedule here,
            # post-increment, would log the next step's rate).
            for tag, val in (("Loss", loss),
                             ("Throughput", records / max(dur_s, 1e-9)),
                             ("LearningRate", clr)):
                trig = ts.trigger_for(tag)
                if (trig is None or trig(self.state)) and \
                        math.isfinite(val):
                    ts.add_scalar(tag, val, stepno)

    def _tee_val_scalars(self, results) -> None:
        vs = self.val_summary
        if vs is None or not results:
            return
        for m, r in zip(self.validation_methods, results):
            vs.add_scalar(str(m), float(r.result()[0]),
                          self.state["neval"])

    # -- main loop -----------------------------------------------------------

    def _run_step(self, feed: BatchAhead, stepno: int, label: str, data,
                  dispatch, donates_state: Optional[bool] = None, **attrs):
        """One step of any of the trainer loops, the next batch started
        under it: ``dispatch(data)`` enqueues the step program and
        returns ``(*new_state, loss)``; ``feed.start()`` then fetches and
        puts batch N+1 while the device runs step N; the sync on the
        loss comes last.  Returns ``(new_state, float(loss))``.

        The order matters: a put BEFORE the dispatch would delay the
        step by the time the host spends in it (the SPMD loops block on
        the copy); after the sync nothing overlaps it.  The watchdog
        guards the dispatch and the sync, each with the whole timeout,
        and not the fetch between them: a slow decode is not a hung
        step.  ``donates_state``, where the loop knows it, rides on the
        ``train.dispatch`` span."""
        watchdog = partial(Watchdog, self.step_timeout, label=label)
        with tracer.span("train.step", step=stepno, **attrs):
            with watchdog():
                if FaultInjector.should("grad.nan", stepno):
                    # inside the span: the poison (first use compiles
                    # full_like) is step work, not an inter-span hole in
                    # the coverage accounting
                    data = jnp.full_like(data, jnp.nan)  # NaN fwd -> grads
                # the call returns once the program is enqueued; the
                # wait for it is the sync's
                with tracer.span("train.dispatch", **(
                        {} if donates_state is None
                        else {"donates_state": donates_state})):
                    *new_state, loss = dispatch(data)
            feed.start()
            # blocks: the whole fused step (compute + collectives) — the
            # hang point the watchdog guards (a wedged host stalls every
            # other host's collective exactly here)
            with watchdog(), tracer.span("train.sync"):
                return new_state, float(loss)

    def optimize(self):
        self._run_start(donates_state=self._donates_state())
        with tracer.span("init", optimizer=type(self).__name__):
            self._maybe_resume()
            if self.model.params is None:
                self.model.build()
            params, model_state = self.model.params, self.model.state
            if self._resume_opt_state is not None:
                opt_state = self._resume_opt_state
            else:
                opt_state = self.optim_method.init_state(params)
            # mesh mode (set_mesh): state adopts the registry shardings
            # and the SAME jitted step below becomes the GSPMD trainer
            params, opt_state = self._place_state(params, opt_state)
            step = self._build_step()
            if step.donates_state:
                # the step consumes the state it is given: hand it a copy
                # of its own, so the trees the caller holds (model.params,
                # model.state, a resumed opt_state; the placement above
                # may return them as they are) stay readable
                params, opt_state, model_state = jax.device_put(
                    (params, opt_state, model_state), may_alias=False)

            count_this_epoch = self.state.get("recordsProcessedThisEpoch",
                                              0)
            # resume: replay the shuffles of completed epochs so the fresh
            # dataset's permutation stream matches the interrupted run's
            _sync_shuffles(self.dataset, self.state.get("epoch", 1) - 1)
            ds_size = self.dataset.size()
            feed = BatchAhead(
                self.dataset, partial(self.dataset.data, train=True),
                records_of=lambda data: data.shape[0],
                put=lambda data, labels: (self._put_batch(data),
                                          self._put_batch(labels)),
                metrics=self.metrics, epoch=self.state.get("epoch", 1),
                records_done=count_this_epoch, epoch_records=ds_size)
        wall_start = time.time()

        cost_done = False          # one cost.analysis per optimize()
        while not self.end_when(self.state):
            data, labels, bs = feed.take()
            self._rng, sub = jax.random.split(self._rng)

            stepno = self.state["neval"]
            t0 = time.time()
            clr_val = self._current_clr()
            clr = jnp.asarray(clr_val, jnp.float32)
            if not cost_done:
                cost_done = True
                if costs.costs_enabled():
                    # price the train-step executable once (FLOPs/bytes
                    # via XLA's cost model).  One extra AOT compile,
                    # under its own top-level span so the report's
                    # coverage figure stays honest about the time.
                    with tracer.span("cost.analysis"):
                        costs.emit_cost(
                            "train.step", step, params, opt_state,
                            model_state, data, labels, sub,
                            jnp.asarray(stepno, jnp.int32), clr,
                            kind=type(self).__name__)

            def dispatch(data):
                nonlocal params, opt_state, model_state
                params, opt_state, model_state, loss = step(
                    params, opt_state, model_state, data, labels, sub,
                    jnp.asarray(stepno, jnp.int32), clr)
                # the call may have consumed the state it was given: from
                # here on the facade names its result, also when the loop
                # leaves by an exception before the bookkeeping
                self.model.params, self.model.state = params, model_state
                return params, opt_state, model_state, loss

            _, loss = self._run_step(
                feed, stepno, f"train step {stepno}", data, dispatch,
                donates_state=step.donates_state)
            dt = time.time() - t0
            # everything after the step itself — metrics/ledger/summary
            # bookkeeping, logging, the epoch's counters, validation and
            # checkpoint triggers — is span-attributed too, so the
            # run-report breakdown accounts for the loop's host-side
            # time, not just its device time
            with tracer.span("loop.bookkeeping"):
                self.metrics.add("computing time average", dt * 1e9)
                # HBM high-watermark sample (mem.hbm; no-op on backends
                # without memory_stats — one memoized check)
                costs.sample_hbm(step=stepno)
                if self.skip_nonfinite and math.isnan(loss):
                    self._record_skipped_step()

                count_this_epoch += bs
                self.state["neval"] += 1
                # persisted so a mid-epoch state snapshot resumes the
                # epoch where it left off instead of replaying it from
                # zero
                self.state["recordsProcessedThisEpoch"] = count_this_epoch
                self.state["isLastBatchOfEpoch"] = \
                    count_this_epoch >= ds_size
                # post-update, pre-rollover: summary triggers see the
                # completed-step counters (incl. isLastBatchOfEpoch)
                self._emit_step_record(stepno, loss, bs, dt, clr_val)
                logger.info(
                    "Epoch %d %d/%d loss %.6f throughput %.1f "
                    "records/second", self.state["epoch"],
                    count_this_epoch, ds_size, loss, bs / max(dt, 1e-9))

                if count_this_epoch >= ds_size:
                    self.state["epoch"] += 1
                    count_this_epoch = 0
                    self.state["recordsProcessedThisEpoch"] = 0

                self._maybe_validate()
                self._maybe_checkpoint(opt_state)
                self.state["isLastBatchOfEpoch"] = False
                # injected preemption AFTER the snapshot logic: the
                # crash a relaunch with auto_resume must recover from
                FaultInjector.fire("train.step", step=self.state["neval"])

        wall = time.time() - wall_start
        logger.info("Training finished in %.1fs (%d iterations)",
                    wall, self.state["neval"])
        self._close_ingest()
        self._run_end(wall)
        return self.model

    # -- validation / checkpoint ---------------------------------------------

    def _maybe_validate(self):
        if not self.validation_trigger or \
                not self.validation_trigger(self.state):
            return None
        return self.validate()

    def validate(self):
        with tracer.span("validate", step=self.state.get("neval", 0)):
            results = _evaluate(self.model, self.validation_dataset,
                                self.validation_methods)
        if not results:
            logger.warning(
                "validation dataset produced no batches (too few records "
                "for the batch size with drop_last?) — skipping")
            return None
        for m, r in zip(self.validation_methods, results):
            logger.info("%s is %r", m, r)
        self.state["lastValidation"] = results
        self._tee_val_scalars(results)
        return results

    def _maybe_checkpoint(self, opt_state):
        if not self.checkpoint_trigger or not self.checkpoint_path or \
                not self.checkpoint_trigger(self.state):
            return
        neval = self.state["neval"]
        suffix = "" if self.overwrite_checkpoint else f".{neval}"
        with tracer.span("checkpoint.save", step=neval):
            File.save({"params": self.model.params,
                       "model_state": self.model.state},
                      f"{self.checkpoint_path}/model{suffix}", True)
            # rng rides along so an auto-resumed run continues the
            # dropout-mask stream instead of replaying from
            # PRNGKey(seed); state is written LAST —
            # _latest_file_snapshot treats the state file as the commit
            # marker for the pair
            File.save({"state": dict(self.state), "opt_state": opt_state,
                       "rng": np.asarray(self._rng)},
                      f"{self.checkpoint_path}/state{suffix}", True)


def _evaluate(model, dataset, methods):
    """Shared evaluation loop (``optim/Validator.scala`` role).

    An empty dataset (fewer records than the batch size with drop_last)
    returns [] — callers must not assume one result per method then.
    """
    eval_fn = jax.jit(partial(model.apply, training=False))
    results = None
    for batch in dataset.data(train=False):
        data = jnp.asarray(batch.data)
        labels = batch.labels
        y, _ = eval_fn(model.params, model.state, data)
        rs = [m(y, labels) for m in methods]
        results = rs if results is None else \
            [a + b for a, b in zip(results, rs)]
    return [] if results is None else results


class LocalValidator:
    """Standalone evaluation (``optim/LocalValidator.scala``)."""

    def __init__(self, model, dataset):
        self.model = model
        self.dataset = dataset

    def test(self, methods: Sequence[ValidationMethod]):
        if self.model.params is None:
            self.model.build()
        return _evaluate(self.model, self.dataset, list(methods))


Validator = LocalValidator
