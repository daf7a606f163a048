"""Distributed synchronous-SGD trainer.

Parity: ``optim/DistriOptimizer.scala`` (the centerpiece, SURVEY.md section
3.2).  The reference's per-iteration structure — two Spark jobs (fwd/bwd +
gradient scatter, then sharded update + weight republish) over BlockManager
fetches — collapses into ONE jitted SPMD program built by
``make_distri_train_step``: all-gather weights, local fwd/bwd, psum_scatter
gradients, ZeRO-1 sharded optimizer update.  The driver loop keeps exactly
the responsibilities the reference's driver kept (``DistriOptimizer.scala:
110-327``): iterate data, counters/epochs, hyperparameter schedule, metrics,
validation, checkpoint.

Divergences (documented per SURVEY.md section 7):
  * Straggler dropping (``kthLargest`` timeouts, ``:244-272``): SPMD
    collectives are synchronous by construction, so there is no slow
    *gradient* to drop — but the same accounting now guards against bad
    gradients instead: the in-step non-finite guard skips the update and
    the ``drop_percentage``/``max_drop_percentage`` knobs budget those
    skipped steps (see ``__init__``).  Stragglers in the wall-clock sense
    are covered by the step watchdog (``resilience.Watchdog``).
  * ``finishedModelNum`` division becomes a fixed /N (no drops).

The "node" of the reference maps to a mesh device along the ``data`` axis;
per-node multi-core replicas map to the per-device batch dimension.
"""

from __future__ import annotations

import logging
import math
import os
import time
from contextlib import nullcontext as _nullcontext
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from bigdl_tpu.engine import Engine
from bigdl_tpu.observability import costs
from bigdl_tpu.observability import ledger as run_ledger
from bigdl_tpu.observability import tracer
from bigdl_tpu.optim.batch_ahead import (BatchAhead, _base_dataset,
                                         _sync_shuffles)
from bigdl_tpu.optim.local_optimizer import LocalOptimizer
from bigdl_tpu.parallel import mesh as mesh_mod
from bigdl_tpu.parallel.allreduce import (make_distri_eval_fn,
                                          make_distri_eval_from_shard,
                                          make_distri_train_step)
from bigdl_tpu.resilience.fault_injector import FaultInjector
from bigdl_tpu.resilience.watchdog import Watchdog

logger = logging.getLogger("bigdl_tpu.optim")

_SHARDING_MODES = ("auto", "flat", "spec")


def _host_pair(batch):
    """``(data, labels)`` of a MiniBatch as host arrays.  A batch the
    ingest already put on the device (``ShardedDataSet(staging=True,
    sharding=...)``, ``PrefetchToDevice``) passes as it is: ``np.asarray``
    would force it BACK to the host."""
    if jax.process_count() == 1 and isinstance(batch.data, jax.Array):
        return batch.data, batch.labels
    return np.asarray(batch.data), np.asarray(batch.labels)


def _fetch_global(arr) -> np.ndarray:
    """Host copy of a possibly cross-process sharded array.  Single
    process: plain device_get.  Multi-host: every process all-gathers the
    shards it cannot address (``getModel``'s reassembly, but no single
    host ever owned the blocks)."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        return np.asarray(multihost_utils.process_allgather(arr,
                                                            tiled=True))
    return np.asarray(jax.device_get(arr))


class DistriOptimizer(LocalOptimizer):

    def __init__(self, model, criterion, dataset,
                 end_when=None, mesh=None,
                 compress: Optional[str] = "bf16",
                 drop_percentage: float = 0.0,
                 max_drop_percentage: float = 0.0,
                 partition_rules=None,
                 sharding: str = "auto"):
        """``drop_percentage``/``max_drop_percentage``: the reference's
        straggler knobs (``DistriOptimizer.scala:244-272``), remapped.
        SPMD collectives are synchronous, so there are no slow gradients
        to drop; the knobs instead budget the in-step non-finite guard's
        *skipped* steps (the same "some updates were dropped this epoch"
        accounting, reported in ``Metrics`` under ``skipped steps
        (non-finite)``).  ``max_drop_percentage > 0`` turns the budget
        into a hard cap: training aborts with a diagnostic once more
        than that fraction of steps has been skipped — a model emitting
        NaNs every step should fail loudly, not "train" on frozen
        weights.  ``drop_percentage`` is the expected/tolerated rate:
        crossing it logs a one-time warning (the reference used it to
        derive the per-iteration timeout; there is no timeout to derive
        here).

        ``sharding`` selects the training-state layout over the mesh:

        * ``"flat"`` — the ZeRO-1 flat parameter ring
          (``parallel/allreduce.py``), spanning the mesh's data AND fsdp
          axes: per-device parameter+optimizer bytes shrink by the whole
          ring size, wire economy stays the audited (n-1)/n.  No tensor
          parallelism (a ``tp`` axis > 1 is rejected with a pointer
          here).
        * ``"spec"`` — the PartitionSpec-registry layout
          (``parallel/specs.py``): every parameter keeps its natural
          global shape, sharded per the registry's ``fsdp``/``tp``
          rules, GSPMD inserts the collectives.  Slightly more wire than
          the flat ring, but supports tensor parallelism and — because
          global shapes are mesh-independent — checkpoints that restore
          onto a DIFFERENT mesh shape.
        * ``"auto"`` (default) — ``"spec"`` when the mesh has a tp axis
          > 1 or ``partition_rules`` were given, else ``"flat"``.

        ``partition_rules``: optional rule list for the spec registry
        (default: ``parallel.specs.default_rules()``)."""
        super().__init__(model, criterion, dataset, end_when)
        self.mesh = mesh or Engine.mesh()
        self.compress = compress
        if sharding not in _SHARDING_MODES:
            raise ValueError(
                f"sharding={sharding!r}: choose from {_SHARDING_MODES}")
        self.sharding = sharding
        self.partition_rules = partition_rules
        self.sharded_checkpoint_path: Optional[str] = None
        self.sharded_checkpoint_trigger = None
        self.drop_percentage = drop_percentage
        self.max_drop_percentage = max_drop_percentage
        self._sharded_auto_resume = True
        self._drop_warned = False
        # -- elasticity (resilience/elastic.py) --
        self._elastic = None                  # ElasticCoordinator
        self._elastic_restore_step = None     # generation-pinned restore

    def _check_drop_budget(self, skipped: int) -> None:
        """Enforce the straggler knobs over the skipped-step ledger:
        ``drop_percentage`` is the expected/tolerated rate — crossing it
        warns once; ``max_drop_percentage`` is the hard cap — crossing
        it aborts (the reference aborts the epoch when dropped gradients
        exceed the budget, ``DistriOptimizer.scala:244-272``)."""
        total = max(self.state["neval"] + 1, 1)
        if self.drop_percentage and not self._drop_warned and \
                skipped > total * self.drop_percentage:
            self._drop_warned = True
            logger.warning(
                "%d/%d steps skipped for non-finite loss/gradients — "
                "above the expected drop_percentage=%s; the model may "
                "be starting to diverge", skipped, total,
                self.drop_percentage)
        if not self.max_drop_percentage:
            return
        if skipped > total * self.max_drop_percentage:
            raise RuntimeError(
                f"{skipped}/{total} steps skipped for non-finite "
                f"loss/gradients, exceeding max_drop_percentage="
                f"{self.max_drop_percentage}: the model is diverging "
                "(weights are intact from the last good step — lower "
                "the learning rate or resume from a snapshot)")

    def _validate_from_shard(self, wshard, model_state):
        """Validation consuming the ZeRO-1 weight shard directly — the
        full weights are all_gathered on-device inside the jitted eval,
        never copied to the host (VERDICT r1 weak #7)."""
        if not self.validation_dataset or not self.validation_methods:
            return None
        assert jax.process_count() == 1, \
            "multi-host validation goes through validate() (host-local)"
        with tracer.span("validate", step=self.state.get("neval", 0)):
            if self._shard_eval_fn is None:
                self._shard_eval_fn = make_distri_eval_from_shard(
                    self.model, self._layout, self.mesh)
            results = _sharded_eval_loop(
                self._shard_eval_fn, (wshard, model_state),
                self.validation_dataset, self.validation_methods,
                self.mesh)
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

    def set_sharded_checkpoint(self, path: str, trigger,
                               auto_resume: bool = True):
        """Device-sharded training-state snapshots (orbax;
        ``utils/checkpoint.py``) — each host writes its own shards, no
        driver-side weight reassembly.  With ``auto_resume`` (default on
        — a preempted pod relaunching the same script must continue, not
        restart) ``optimize()`` resumes from the latest *committed* step
        found under ``path``; torn snapshots from an interrupted save are
        screened out by ``checkpoint.verify_sharded``.  Complements the
        File-based ``set_checkpoint`` full snapshots (the reference's
        ``model.<neval>`` format)."""
        self.sharded_checkpoint_path = path
        self.sharded_checkpoint_trigger = trigger
        # own flag — set_checkpoint()'s auto_resume (File format) must
        # not clobber the sharded default
        self._sharded_auto_resume = auto_resume
        return self

    def resume_from(self, path: str):
        """Explicitly resume from the latest committed SHARDED (orbax)
        snapshot under ``path``, independent of where new snapshots go.
        Missing/empty ``path`` raises at ``optimize()`` — an explicit
        resume must never silently train from scratch."""
        self._resume_path = path
        return self

    def set_elastic(self, coordinator):
        """Make this trainer ELASTIC: ``coordinator`` (an
        :class:`~bigdl_tpu.resilience.elastic.ElasticCoordinator`) is
        polled at every step boundary; when the fleet commits a new
        generation (a host's lease lapsed, or a join request was
        admitted), the in-flight epoch aborts at that boundary, the
        ``(data, fsdp, tp)`` mesh is rebuilt at the new world size
        (``data`` resizes first; an unsatisfiable shape raises the typed
        ``ElasticReshapeError``), the optimizer state is resharded from
        the generation's committed checkpoint, the dataset cursor is
        replayed, and training continues.  Requires
        ``set_sharded_checkpoint`` — without committed snapshots there
        is nothing to reshard from.  Works with both ``sharding="spec"``
        (orbax reshards across mesh shapes natively, the PR-7 path) and
        ``sharding="flat"`` (the ring-layout snapshot is re-flattened
        through the host, layout-portable)."""
        self._elastic = coordinator
        return self

    def _comm_metrics(self, layout, n, wshard):
        """Per-iteration communication accounting under the reference's
        metric names (``DistriOptimizer.scala:115-119,148-151``).  The
        fused SPMD step has no separately-timeable phases, so: the byte
        counts come from the layout arithmetic (cross-checked against
        the compiled HLO by ``parallel/comm_audit.py`` /
        ``bench_comm.py``), and the phase TIMES are measured on
        stand-alone probe programs running the identical collectives —
        an unoverlapped upper bound on their in-step cost."""
        from bigdl_tpu.parallel.allreduce import make_phase_probes
        from bigdl_tpu.parallel.comm_audit import expected_step_traffic

        traffic = expected_step_traffic(layout)
        wire_mb = traffic["ring_wire_bytes_per_device_per_phase"] / 1e6
        self.metrics.set("get weights wire traffic per node", wire_mb,
                         unit="MB/iteration")
        self.metrics.set("aggregate gradient wire traffic per node",
                         wire_mb, unit="MB/iteration")
        if n <= 1:
            return                    # 1-device collectives are no-ops
        # the timed probes cost two small compiles + a few collective
        # runs at startup: do them once per optimizer instance, and not
        # at all when opted out
        if getattr(self, "_comm_probed", False) or \
                os.environ.get("BIGDL_TPU_COMM_PROBES", "1") == "0":
            return
        self._comm_probed = True
        with tracer.span("allreduce.comm_probe", n=n):
            gw, rs = make_phase_probes(layout, self.mesh)
            gflat = jnp.zeros((layout.padded,), layout.dtype)
            for fn, arg, name in ((gw, wshard, "get weights average"),
                                  (rs, gflat, "aggregate gradient time")):
                jax.block_until_ready(fn(arg))          # compile + warm
                t0 = time.time()
                out = None
                for _ in range(3):
                    out = fn(arg)
                jax.block_until_ready(out)
                self.metrics.set(name, (time.time() - t0) / 3 * 1e9)

    def _shard_iterators(self):
        """Per-shard iterators when the dataset supports them; None (flat
        iteration) otherwise.  Support is decided by inspecting the base
        of the transformer chain — NOT by catching AttributeError, which
        would also swallow genuine bugs inside a real shard_iterators."""
        base = _base_dataset(self.dataset)   # unwrap TransformedDataSet
        if not hasattr(base, "shard_iterators"):
            return None
        return self.dataset.shard_iterators(train=True)

    @staticmethod
    def _global_batch(batches):
        """Assemble one global batch from one batch of every shard (the
        ZippedPartitionsWithLocalityRDD role: each mesh slot consumes
        its own partition)."""
        if not hasattr(batches[0], "data"):
            raise TypeError(
                "distributed dataset shards must yield MiniBatches — add a "
                "SampleToBatch/GreyImgToBatch transformer to the pipeline")
        data = np.concatenate([b.data for b in batches], axis=0)
        labels = np.concatenate([np.atleast_1d(b.labels) for b in batches],
                                axis=0)
        return data, labels

    def _epoch_stream(self, by_shard: bool):
        """One epoch's stream of ``(data, labels)`` host batches: the
        per-shard iterators zipped into global batches when ``by_shard``
        and the dataset has them, the dataset's own stream otherwise."""
        shard_iters = self._shard_iterators() if by_shard else None
        if shard_iters:
            return map(self._global_batch, zip(*shard_iters))
        return map(_host_pair, self.dataset.data(train=True))

    def _batch_ahead(self, n: int, ds_size: int, by_shard: bool):
        """The SPMD loops' input (``BatchAhead``): batches checked
        against the ring and put batch-sharded over the mesh."""
        nproc = jax.process_count()
        sharding = mesh_mod.batch_sharding(self.mesh)
        local_rows = None

        def records_of(data) -> int:
            nonlocal local_rows
            if nproc > 1:
                # every process must contribute the same number of rows
                # per step or the global shapes diverge and the next
                # collective hangs — fail fast locally instead
                if local_rows is None:
                    local_rows = data.shape[0]
                elif data.shape[0] != local_rows:
                    raise ValueError(
                        f"multihost local batch changed {local_rows} -> "
                        f"{data.shape[0]}; use drop_last batching so "
                        "every process feeds fixed-size batches")
            bs = data.shape[0] * nproc      # global batch
            if bs % n != 0:
                raise ValueError(
                    f"global batch size {bs} must be a multiple of the "
                    f"dp shard count {n} (data x fsdp axes; the reference "
                    f"enforces batch % nodeNumber == 0 the same way)")
            return bs

        def put(data, labels):
            if nproc == 1:
                # a no-op view for a staged batch whose sharding matches
                batch = jax.device_put((data, labels), sharding)
            else:
                # true multi-host: each process contributes ONLY its
                # local rows; the global array is assembled without any
                # host holding (or shipping) the full batch — the
                # per-host ingest locality the reference got from
                # partition-zipped RDDs
                batch = tuple(
                    jax.make_array_from_process_local_data(
                        sharding, a, (a.shape[0] * nproc,) + a.shape[1:])
                    for a in (data, labels))
            # attribute H2D honestly: ahead of the step it is time the
            # host would have spent in the running step's sync
            return jax.block_until_ready(batch)

        return BatchAhead(
            self.dataset, partial(self._epoch_stream, by_shard),
            records_of, put, self.metrics,
            epoch=self.state.get("epoch", 1),
            records_done=self.state.get("recordsProcessedThisEpoch", 0),
            epoch_records=ds_size)

    def _compute_dtype(self):
        """``set_mixed_precision`` reaches the SPMD steps as their
        ``compute_dtype`` (bf16 compute over f32 master shards)."""
        return jnp.bfloat16 if self.mixed_precision else None

    def _sharding_mode(self) -> str:
        if self.sharding != "auto":
            return self.sharding
        return "spec" if (mesh_mod.tp_size(self.mesh) > 1 or
                          self.partition_rules is not None) else "flat"

    def _emit_mesh_event(self, mode: str, collective_bytes: dict) -> None:
        """``mesh.topology`` ledger record: the mesh shape and the
        analytic per-axis collective bytes per device per step —
        run-report renders these as the mesh line."""
        run_ledger.emit("mesh.topology", mode=mode,
                        **mesh_mod.describe(self.mesh),
                        collective_bytes=collective_bytes)

    def optimize(self):
        if self._elastic is not None:
            return self._optimize_elastic()
        if self._sharding_mode() == "spec":
            return self._optimize_spec()
        return self._optimize_flat()

    # -- elasticity (resilience/elastic.py) ----------------------------------

    def _optimize_elastic(self):
        """The elastic outer loop: run the (flat or spec) inner loop
        until it either finishes or a new fleet generation commits; on a
        generation change, reshape and go again.  The reshape itself is
        an in-process relaunch: rebuild the mesh at the new world size,
        then let the inner loop's own resume path reshard the
        generation's committed snapshot onto it (the PR-7 cross-mesh
        restore) and fast-forward the dataset cursor."""
        from bigdl_tpu.resilience.elastic import ElasticWorldChanged
        from bigdl_tpu.utils import checkpoint as ckpt

        coord = self._elastic
        if not (self.sharded_checkpoint_path and
                self.sharded_checkpoint_trigger):
            raise ValueError(
                "elastic training requires set_sharded_checkpoint(...): "
                "a membership change reshards from the last committed "
                "snapshot, so there must be one")
        if not self._sharded_auto_resume:
            raise ValueError(
                "elastic training requires set_sharded_checkpoint("
                "auto_resume=True): with auto_resume off the reshape "
                "path would skip the committed-snapshot restore and the "
                "resized fleet would silently diverge")
        if self._resume_path and \
                self._resume_path != self.sharded_checkpoint_path:
            # the generation pins restore steps discovered in the
            # snapshot dir; honoring a DIFFERENT resume_from source
            # would either ignore it or restore a wrong-directory step —
            # fail loudly instead of warm-starting wrong
            raise ValueError(
                "elastic training resumes from its own sharded snapshot "
                f"directory ({self.sharded_checkpoint_path!r}); "
                f"resume_from({self._resume_path!r}) cannot be honored — "
                "warm-start by copying a committed snapshot into the "
                "snapshot directory instead")
        path = self.sharded_checkpoint_path
        coord.set_restore_step_source(lambda: ckpt.latest_step(path))
        if coord.base_shape is None:
            # seed the coordinator's reshape template from the trainer's
            # own mesh so fsdp/tp survive the first reshape — otherwise
            # an elastic (2,2,2) trainer would silently flatten to pure
            # data parallelism on attempt one
            coord.base_shape = mesh_mod.MeshShape(
                1, mesh_mod.fsdp_size(self.mesh),
                mesh_mod.tp_size(self.mesh))
        gen = coord.start()
        # pristine state for a snapshot-less reshape (deterministic
        # fresh restart): rng AND the initial weights — a validation or
        # File-checkpoint trigger writes trained params back into
        # self.model mid-attempt, which must not leak into a "fresh"
        # generation
        import copy
        rng0 = self._rng
        if self.model.params is None:
            self.model.build()
        params0 = copy.deepcopy(jax.tree_util.tree_map(
            np.asarray, self.model.params))
        state0 = copy.deepcopy(jax.tree_util.tree_map(
            np.asarray, self.model.state))
        clean_exit = False
        try:
            while True:
                # the generation pins the restore step: every member of
                # the new world reshards the SAME committed snapshot, so
                # the fleets' replayed timelines are identical
                self._elastic_restore_step = gen.restore_step
                if gen.restore_step is not None:
                    # committed snapshots exist (and only accumulate):
                    # the pristine fresh-restart copies can never be
                    # needed again — free the host memory they pin
                    params0 = state0 = None
                shape = coord.mesh_shape()
                self.mesh = mesh_mod.build_mesh(shape)
                self._attempt_t0 = time.time()
                try:
                    result = self._optimize_spec() \
                        if self._sharding_mode() == "spec" \
                        else self._optimize_flat()
                    clean_exit = True
                    return result
                except ElasticWorldChanged as e:
                    old_world, old_shape = gen.world, shape
                    gen = e.generation
                    with Watchdog.pause("elastic.reshape"):
                        # commit in-flight async saves BEFORE tearing the
                        # attempt down — a snapshot mid-write must land
                        # whole or not at all
                        ckpt.wait()
                        try:
                            new_shape = coord.mesh_shape()
                        except Exception:
                            self._run_end(time.time() - self._attempt_t0)
                            raise
                        run_ledger.emit(
                            "event", kind="elastic.reshape", gen=gen.gen,
                            old_world=old_world, new_world=gen.world,
                            old_mesh=str(old_shape), new_mesh=str(new_shape),
                            restore_step=gen.restore_step,
                            aborted_step=self.state["neval"])
                        logger.warning(
                            "elastic: generation %d — reshaping %s -> %s "
                            "(world %d -> %d), resharding from committed "
                            "step %s", gen.gen, old_shape, new_shape,
                            old_world, gen.world, gen.restore_step)
                        # close the aborted attempt's run window honestly
                        # (its spans/steps stay in the breakdown)
                        self._run_end(time.time() - self._attempt_t0)
                        # the restore below may land in an EARLIER epoch
                        # than the aborted attempt reached: rewind the
                        # dataset's shuffle stream so _sync_shuffles can
                        # replay it forward to exactly the restored epoch
                        self._rewind_shuffles()
                        if gen.restore_step is None:
                            # no committed snapshot existed at proposal
                            # time: the new world deterministically
                            # restarts from scratch (counters, rng AND
                            # weights — half-reset state would lie
                            # about progress)
                            self.state["neval"] = 0
                            self.state["epoch"] = 1
                            self.state["recordsProcessedThisEpoch"] = 0
                            self._rng = rng0
                            if params0 is not None:
                                self.model.params = copy.deepcopy(params0)
                                self.model.state = copy.deepcopy(state0)
        finally:
            # a crashing host is LOST (its lease must lapse and the
            # fleet must reshape around it); only a completed run is a
            # graceful departure
            coord.stop(leave=clean_exit)

    def _elastic_step_boundary(self):
        """Step-boundary membership poll (no-op without set_elastic):
        ack/commit handling lives in the coordinator; a committed world
        change surfaces here as ElasticWorldChanged, aborting the epoch
        BEFORE the next batch is consumed."""
        if self._elastic is None:
            return
        from bigdl_tpu.resilience.elastic import ElasticWorldChanged
        gen = self._elastic.check(step=self.state["neval"])
        if gen is not None:
            raise ElasticWorldChanged(gen)

    def _elastic_should_write(self) -> bool:
        """Snapshot-writer gate: in an elastic fleet exactly one host
        (the generation's writer) publishes snapshots to the shared
        directory — the single-writer discipline a shared filesystem
        needs; non-elastic runs are unaffected."""
        return self._elastic is None or self._elastic.is_writer()

    def _rewind_shuffles(self) -> None:
        """Reset the dataset's shuffle stream to epoch 0 so a restore
        into an earlier epoch can replay the permutations forward
        (``_sync_shuffles`` only advances).  Datasets expose
        ``reset_shuffle()`` for this (it also zeroes the replay counter
        ``_sync_shuffles`` keys on); without one, a same-or-later-epoch
        restore still works (no rewind needed) and an earlier-epoch
        restore fails loudly in ``_emit_elastic_restore``'s guard."""
        reset = getattr(_base_dataset(self.dataset), "reset_shuffle",
                        None)
        if callable(reset):
            reset()

    def _emit_elastic_restore(self, restored_step: int, prev_neval: int,
                              mode: str) -> None:
        """Guard the shuffle-replay contract, then ledger the
        resharded-restore + resumed-step transition."""
        if self._elastic is None:
            return
        # the restore may land in an EARLIER epoch than the dataset's
        # shuffle stream has reached; _rewind_shuffles could only help
        # if the dataset exposes reset_shuffle() — without it,
        # _sync_shuffles would silently keep the LATER permutation and
        # the fast-forward would skip the wrong records.  Fail loudly
        # instead (runs before _sync_shuffles, which only advances).
        base = _base_dataset(self.dataset)
        done = getattr(base, "_shuffles_done", 0)
        if done > self.state["epoch"] - 1:
            raise RuntimeError(
                f"elastic restore landed in epoch {self.state['epoch']} "
                f"but the dataset's shuffle stream is already "
                f"{done} shuffles ahead and "
                f"{type(base).__name__} has no reset_shuffle() — "
                "implement reset_shuffle() (rewind to the identity "
                "permutation + reseeded RNG) so the cursor replay can "
                "reproduce the interrupted epoch's record order")
        gen = self._elastic.generation()
        run_ledger.emit("event", kind="elastic.restore",
                        step=restored_step, gen=gen.gen, sharding=mode,
                        mesh=str(self._elastic.mesh_shape()))
        run_ledger.emit("event", kind="elastic.resume",
                        step=restored_step, gen=gen.gen,
                        epoch=self.state["epoch"],
                        records_this_epoch=self.state.get(
                            "recordsProcessedThisEpoch", 0),
                        replayed_steps=max(0, prev_neval - restored_step))

    def _restore_flat_portable(self, resume_path: str, step: int,
                               layout, n: int, wshard, opt_shard):
        """Cross-ring-size restore for the FLAT layout: the snapshot's
        ``wshard``/``opt_shard`` were written as ``(n_old,
        shard_size_old)`` rings, which a different world cannot restore
        in place (the LANE-aligned shard sizes change with n).  Re-flatten
        through the host instead: the padded flat vector's first
        ``layout.size`` elements are ring-size-independent, so the old
        ring re-grids onto the new one exactly — momentum buffers
        included, bit-for-bit.  (Spec mode needs none of this: global
        shapes are mesh-independent and orbax reshards natively.)"""
        from bigdl_tpu.utils import checkpoint as ckpt

        snap = ckpt.restore_sharded(resume_path, None, step=step)

        def regrid(tgt, src):
            src = np.asarray(src)
            if src.ndim > 2:
                raise ValueError(
                    f"elastic flat restore: unexpected {src.ndim}-d ring "
                    "leaf — the flat layout holds (n, shard) buffers and "
                    "(n,) broadcast scalars only")
            if src.ndim == 2:
                # an (n_old, shard_size_old) ring leaf: flatten, take
                # the true payload, re-pad and re-grid.  Both bounds
                # checked: a smaller ring cannot hold this model, and a
                # ring larger than this model + its maximum possible
                # LANE padding is a DIFFERENT model whose tail would be
                # silently truncated
                from bigdl_tpu.parallel.allreduce import LANE
                max_pad = src.shape[0] * (LANE + 1)
                if not (layout.size <= src.size
                        < layout.size + max_pad):
                    raise ValueError(
                        f"elastic flat restore: snapshot ring holds "
                        f"{src.size} elements, this model needs "
                        f"{layout.size} (+ at most {max_pad} LANE "
                        "padding) — the snapshot is from a different "
                        "model")
                flat = src.reshape(-1)[:layout.size]
                padded = np.concatenate(
                    [flat, np.zeros((layout.padded - layout.size,),
                                    flat.dtype)])
                out = padded.reshape(n, layout.shard_size)
            elif src.ndim == 1:
                # per-ring-slot scalar state (broadcast counters)
                out = np.broadcast_to(src[:1], (n,)).copy()
            else:
                out = src
            return jax.device_put(jnp.asarray(out, tgt.dtype), tgt.sharding)

        new_w = regrid(wshard, snap["wshard"])
        new_opt = jax.tree_util.tree_map(regrid, opt_shard,
                                         snap["opt_shard"])
        return snap, new_w, new_opt

    # -- the flat (ZeRO-1 ring) trainer --------------------------------------

    def _optimize_flat(self):
        if mesh_mod.tp_size(self.mesh) > 1:
            raise ValueError(
                f"sharding='flat' cannot use the mesh's tp axis "
                f"(size {mesh_mod.tp_size(self.mesh)}): the flat ZeRO-1 "
                "ring replicates work across tp ranks — use "
                "sharding='spec' (the PartitionSpec-registry trainer) "
                "for tensor parallelism")
        self._run_start()
        # with-block (not a begin/end handle): an exception during setup
        # must close the init span too — graftlint: span-unclosed
        with tracer.span("init", optimizer=type(self).__name__):
            if self._resume_path is None and self.sharded_checkpoint_path \
                    is None and self.auto_resume and self.checkpoint_path:
                # no sharded source configured: fall back to the File-format
                # snapshots (restores model params + opt state + counters;
                # the opt state is laid back over the mesh below)
                self._maybe_resume()
            if self.model.params is None:
                self.model.build()
            mesh = self.mesh
            # the flat ring spans data x fsdp: every dp slot owns a weight
            # shard, so fsdp>1 shrinks resident bytes without a layout change
            n = mesh_mod.dp_size(mesh)

            step, layout, init_fn = make_distri_train_step(
                self.model, self.criterion, self.optim_method, mesh,
                self.config, compress=self.compress,
                compute_dtype=self._compute_dtype(),
                guard_nonfinite=self.skip_nonfinite)
            self._layout = layout
            self._shard_eval_fn = None        # built lazily on first trigger
            wshard, opt_shard = init_fn(self.model.params)
            self._comm_metrics(layout, n, wshard)
            from bigdl_tpu.parallel.comm_audit import expected_step_traffic
            ring = layout.axis if isinstance(layout.axis, tuple) \
                else (layout.axis,)
            per_phase = expected_step_traffic(layout)[
                "ring_wire_bytes_per_device_per_phase"]
            # both phases (getWeights AG + aggregateGradient RS) ride the
            # joint data x fsdp ring — attributed to it as one figure
            self._emit_mesh_event("flat", {"+".join(ring): 2 * per_phase})
            if self._resume_opt_state is not None:
                # a state.<neval> snapshot restored via set_state: lay the
                # saved optimizer state back out over the mesh.  Shape-check
                # first: the r5 LANE alignment changed shard sizes, so a
                # pre-r5 snapshot must fail HERE with a layout message, not
                # deep inside the jitted step with a broadcast error.
                def _check(tgt, src):
                    if tuple(np.shape(src)) != tuple(tgt.shape):
                        raise ValueError(
                            f"optimizer-state snapshot shard shape "
                            f"{np.shape(src)} does not match this run's "
                            f"layout {tuple(tgt.shape)} — the snapshot was "
                            "written under a different shard layout (e.g. "
                            "pre-r5 unaligned shards, or a different device "
                            "count); re-snapshot from the full weights "
                            "instead of resuming sharded state")
                    return jax.device_put(jnp.asarray(src), tgt.sharding)
                opt_shard = jax.tree_util.tree_map(
                    _check, opt_shard, self._resume_opt_state)
            model_state = self.model.state

            count_this_epoch = self.state.get("recordsProcessedThisEpoch", 0)

            def _snapshot(wshard, opt_shard, model_state):
                """ONE pytree literal shared by save and restore — adding a
                field in only one place becomes a structure mismatch instead
                of silent state loss."""
                # counters as 0-d int64 ndarrays: orbax's standard handler
                # round-trips ndarrays on every version; bare numpy scalars
                # are rejected by some
                return {"wshard": wshard, "opt_shard": opt_shard,
                        "model_state": model_state,
                        "rng": np.asarray(self._rng),
                        "neval": np.asarray(self.state["neval"], np.int64),
                        "epoch": np.asarray(self.state["epoch"], np.int64),
                        "records_this_epoch": np.asarray(count_this_epoch,
                                                         np.int64)}

            # resume source: explicit resume_from wins; else the snapshot dir
            # itself when auto_resume (preemption-safe relaunch: the SAME
            # script continues where the killed run left off)
            resume_path = self._resume_path or \
                (self.sharded_checkpoint_path if self._sharded_auto_resume
                 else None)
            if resume_path:
                from bigdl_tpu.utils import checkpoint as ckpt
                if self._elastic is not None:
                    # the generation pins the restore step so every
                    # member reshards the SAME committed snapshot; None
                    # means the leader saw no committed snapshot —
                    # deterministic fresh start, NOT a per-host
                    # latest_step race
                    last = self._elastic_restore_step
                else:
                    last = ckpt.latest_step(resume_path)   # committed only
                if last is None and self._resume_path is not None \
                        and self._elastic is None:
                    raise FileNotFoundError(
                        f"resume_from({resume_path!r}): no committed sharded "
                        "snapshot found (torn/uncommitted directories are "
                        "not resumable)")
                if last is not None:
                    prev_neval = self.state["neval"]
                    if self._elastic is not None:
                        # ring-size-portable restore (the world may have
                        # changed); watchdogs pause across it — resharding
                        # is a legitimate stall, not a hung step
                        with Watchdog.pause("elastic.restore"):
                            snap, wshard, opt_shard = \
                                self._restore_flat_portable(
                                    resume_path, last, layout, n,
                                    wshard, opt_shard)
                    else:
                        try:
                            snap = ckpt.restore_sharded(
                                resume_path,
                                _snapshot(wshard, opt_shard, model_state),
                                step=last)
                        except Exception as e:
                            raise ValueError(
                                f"sharded checkpoint at "
                                f"{resume_path} step {last} "
                                "does not match this run's shard layout "
                                f"(shard_size={layout.shard_size}, "
                                f"n={n}): it was likely written under a "
                                "different layout (pre-r5 unaligned shards "
                                "or a different device count). Restore the "
                                "full weights via File snapshots instead."
                            ) from e
                        wshard = snap["wshard"]
                        opt_shard = snap["opt_shard"]
                    model_state = snap["model_state"]
                    self._rng = jnp.asarray(np.asarray(snap["rng"]))
                    self.state["neval"] = int(snap["neval"])
                    self.state["epoch"] = int(snap["epoch"])
                    count_this_epoch = int(snap["records_this_epoch"])
                    self.state["recordsProcessedThisEpoch"] = \
                        count_this_epoch
                    logger.info("resumed sharded checkpoint step %d "
                                "(epoch %d, %d records into it)", last,
                                self.state["epoch"], count_this_epoch)
                    self._emit_elastic_restore(last, prev_neval, "flat")

            # resume: replay completed epochs' shuffles so the fresh dataset's
            # permutation stream matches the interrupted run's
            _sync_shuffles(self.dataset, self.state.get("epoch", 1) - 1)
            # per-process datasets hold this host's records only; epoch
            # accounting runs on global counts
            ds_size = self.dataset.size() * jax.process_count()
            feed = self._batch_ahead(n, ds_size, by_shard=True)
        wall_start = time.time()

        cost_done = False          # one cost.analysis per optimize()
        while not self.end_when(self.state):
            # elastic membership poll BEFORE the batch is taken: a
            # committed generation change aborts exactly at a step
            # boundary (no step in a stale world; the batch in flight
            # from the old stream is dropped with this loop's feed)
            self._elastic_step_boundary()
            data, labels, bs = feed.take()
            t0 = time.time()
            self._rng, sub = jax.random.split(self._rng)
            clr_val = self._current_clr()
            clr = jnp.asarray(clr_val, jnp.float32)

            stepno = self.state["neval"]
            if not cost_done:
                cost_done = True
                if costs.costs_enabled():
                    # price the flat-ring step executable once (FLOPs/
                    # bytes via XLA's cost model; one extra AOT compile,
                    # span-attributed so coverage stays honest)
                    with tracer.span("cost.analysis"):
                        costs.emit_cost(
                            "train.step", step, wshard, opt_shard,
                            model_state, data, labels, sub,
                            jnp.asarray(stepno, jnp.int32), clr,
                            kind=type(self).__name__, sharding="flat")
            (wshard, opt_shard, model_state), loss = self._run_step(
                feed, stepno, f"train step {stepno} (SPMD, n={n})", data,
                lambda data: step(
                    wshard, opt_shard, model_state, data, labels, sub,
                    jnp.asarray(stepno, jnp.int32), clr),
                n=n)
            # the whole iteration but the first put, for throughput
            dt = time.time() - t0

            # Reference metric names (DistriOptimizer.scala:115-119,
            # 148-151, 180-182, 214).  The fused XLA step has no separate
            # get-weights / aggregate phases to time from the host — the
            # collectives overlap with compute inside one program — so the
            # whole step lands under "computing time"; use
            # utils.profiler.trace for the intra-step breakdown.
            # host-side loop tail span-attributed too (see the
            # LocalOptimizer loop): counters, logging, snapshot and
            # validation triggers
            with tracer.span("loop.bookkeeping"):
                costs.sample_hbm(step=stepno)
                if self.skip_nonfinite and math.isnan(loss):
                    self._check_drop_budget(self._record_skipped_step())
                self.metrics.add("computing time average", dt * 1e9)
                self.metrics.add("computing time for each node", dt * 1e9)
                self.metrics.set("loss", loss, unit="scalar")
                count_this_epoch += bs
                self.state["neval"] += 1
                self.state["recordsProcessedThisEpoch"] = count_this_epoch
                self.state["isLastBatchOfEpoch"] = count_this_epoch >= ds_size
                # post-update, pre-rollover: summary triggers see the
                # completed-step counters (incl. isLastBatchOfEpoch)
                self._emit_step_record(stepno, loss, bs, dt, clr_val)
                logger.info(
                    "Epoch %d %d/%d loss %.6f throughput %.1f records/second",
                    self.state["epoch"], count_this_epoch, ds_size, loss,
                    bs / max(dt, 1e-9))

                if count_this_epoch >= ds_size:
                    self.state["epoch"] += 1
                    count_this_epoch = 0
                    self.state["recordsProcessedThisEpoch"] = 0

                if self.sharded_checkpoint_trigger and \
                        self.sharded_checkpoint_path and \
                        self._elastic_should_write() and \
                        self.sharded_checkpoint_trigger(self.state):
                    from bigdl_tpu.utils import checkpoint as ckpt
                    # async: returns after the device->host snapshot; the
                    # write overlaps the next training steps
                    with tracer.span("checkpoint.sharded.save",
                                     step=self.state["neval"]):
                        ckpt.save_sharded(self.sharded_checkpoint_path,
                                          _snapshot(wshard, opt_shard,
                                                    model_state),
                                          step=self.state["neval"],
                                          detach=layout.donates_state)

                do_val = bool(self.validation_trigger and
                              self.validation_trigger(self.state))
                do_ckpt = bool(self.checkpoint_trigger and self.checkpoint_path
                               and self.checkpoint_trigger(self.state))
                multi = jax.process_count() > 1
                if do_ckpt or (do_val and multi):
                    # getModel parity (DistriOptimizer.scala:475-502): File
                    # snapshots genuinely need host bytes, and multi-host
                    # validation stays host-local (per-host data shards can't
                    # be device_put against one global sharding) — ONE
                    # reassembly serves both triggers
                    with tracer.span("get_model"):
                        self.model.params = layout.unflatten(
                            _fetch_global(wshard).reshape(-1))
                        self.model.state = model_state
                if do_val:
                    if multi:
                        self.validate()
                    else:
                        # weights stay in HBM: the sharded evaluator
                        # all_gathers the owned slices on-device (no getModel
                        # host trip)
                        self._validate_from_shard(wshard, model_state)
                if do_ckpt:
                    fetched = jax.tree_util.tree_map(_fetch_global, opt_shard)
                    if jax.process_index() == 0:
                        self._maybe_checkpoint(fetched)
                self.state["isLastBatchOfEpoch"] = False
                # injected preemption AFTER the snapshot logic: the crash a
                # relaunch with auto_resume must recover from
                FaultInjector.fire("train.step", step=self.state["neval"])

        with tracer.span("get_model"):
            self.model.params = layout.unflatten(
                _fetch_global(wshard).reshape(-1))
            self.model.state = model_state
        if self.sharded_checkpoint_path:
            from bigdl_tpu.utils import checkpoint as ckpt
            ckpt.wait()   # commit in-flight async snapshots
        wall = time.time() - wall_start
        logger.info("Training finished in %.1fs (%d iterations)",
                    wall, self.state["neval"])
        self._close_ingest()
        self._run_end(wall)
        return self.model

    # -- the spec-sharded (PartitionSpec-registry) trainer -------------------

    def _optimize_spec(self):
        """The registry-sharded SPMD loop (``sharding="spec"``).

        The training state is the params/opt-state pytree itself, placed
        per the spec registry — fsdp/tp sharded, GSPMD collectives —
        instead of the flat ZeRO-1 ring.  Every leaf keeps its
        mesh-independent GLOBAL shape, which is what makes the sharded
        orbax snapshots portable across mesh shapes: restoring against a
        fresh placement on a different ``(data, fsdp, tp)`` reshards in
        orbax, no host round-trip.  Driver responsibilities (counters,
        schedule, triggers, drop budget, ledger) mirror the flat loop.
        """
        from bigdl_tpu.parallel.specs import SpecRegistry, \
            make_spec_train_step

        if jax.process_count() > 1:
            raise ValueError(
                "sharding='spec' is single-controller for now — "
                "multi-host runs use the flat ring (sharding='flat')")
        self._run_start()
        with tracer.span("init", optimizer=type(self).__name__,
                         sharding="spec"):
            if self.model.params is None:
                self.model.build()
            mesh = self.mesh
            registry = SpecRegistry(self.partition_rules)
            step, init_fn, _ = make_spec_train_step(
                self.model, self.criterion, self.optim_method, mesh,
                self.config, registry=registry,
                compute_dtype=self._compute_dtype(),
                guard_nonfinite=self.skip_nonfinite)
            params, opt_state = init_fn(self.model.params)
            model_state = self.model.state
            self._emit_mesh_event(
                "spec", registry.traffic(self.model.params, mesh))
            n = mesh_mod.dp_size(mesh)

            count_this_epoch = self.state.get("recordsProcessedThisEpoch", 0)

            def _snapshot(params, opt_state, model_state):
                # counters as 0-d int64 ndarrays (orbax round-trip contract,
                # same as the flat loop's snapshot)
                return {"params": params, "opt_state": opt_state,
                        "model_state": model_state,
                        "rng": np.asarray(self._rng),
                        "neval": np.asarray(self.state["neval"], np.int64),
                        "epoch": np.asarray(self.state["epoch"], np.int64),
                        "records_this_epoch": np.asarray(count_this_epoch,
                                                         np.int64)}

            resume_path = self._resume_path or \
                (self.sharded_checkpoint_path if self._sharded_auto_resume
                 else None)
            if resume_path:
                from bigdl_tpu.utils import checkpoint as ckpt
                if self._elastic is not None:
                    # generation-pinned restore (see the flat loop)
                    last = self._elastic_restore_step
                else:
                    last = ckpt.latest_step(resume_path)
                if last is None and self._resume_path is not None \
                        and self._elastic is None:
                    raise FileNotFoundError(
                        f"resume_from({resume_path!r}): no committed sharded "
                        "snapshot found (torn/uncommitted directories are "
                        "not resumable)")
                if last is not None:
                    prev_neval = self.state["neval"]
                    # the target pytree carries THIS mesh's shardings: a
                    # snapshot written on a different mesh shape reshards on
                    # restore (global shapes are mesh-independent here) —
                    # which is exactly how an elastic generation change
                    # reshards onto the resized world
                    with Watchdog.pause("elastic.restore") \
                            if self._elastic is not None else _nullcontext():
                        snap = ckpt.restore_sharded(
                            resume_path,
                            _snapshot(params, opt_state, model_state),
                            step=last)
                    params = snap["params"]
                    opt_state = snap["opt_state"]
                    model_state = snap["model_state"]
                    self._rng = jnp.asarray(snap["rng"])
                    self.state["neval"] = int(snap["neval"])
                    self.state["epoch"] = int(snap["epoch"])
                    count_this_epoch = int(snap["records_this_epoch"])
                    self.state["recordsProcessedThisEpoch"] = \
                        count_this_epoch
                    logger.info("resumed spec-sharded checkpoint step %d "
                                "(epoch %d, %d records into it)", last,
                                self.state["epoch"], count_this_epoch)
                    self._emit_elastic_restore(last, prev_neval, "spec")

            _sync_shuffles(self.dataset, self.state.get("epoch", 1) - 1)
            ds_size = self.dataset.size()
            feed = self._batch_ahead(n, ds_size, by_shard=False)
        wall_start = time.time()

        cost_done = False          # one cost.analysis per optimize()
        while not self.end_when(self.state):
            self._elastic_step_boundary()
            data, labels, bs = feed.take()
            t0 = time.time()
            self._rng, sub = jax.random.split(self._rng)
            clr_val = self._current_clr()
            clr = jnp.asarray(clr_val, jnp.float32)

            stepno = self.state["neval"]
            if not cost_done:
                cost_done = True
                if costs.costs_enabled():
                    with tracer.span("cost.analysis"):
                        costs.emit_cost(
                            "train.step", step, params, opt_state,
                            model_state, data, labels, sub,
                            jnp.asarray(stepno, jnp.int32), clr,
                            kind=type(self).__name__, sharding="spec")
            (params, opt_state, model_state), loss = self._run_step(
                feed, stepno, f"train step {stepno} (spec, n={n})", data,
                lambda data: step(
                    params, opt_state, model_state, data, labels, sub,
                    jnp.asarray(stepno, jnp.int32), clr),
                n=n, sharding="spec")
            dt = time.time() - t0

            with tracer.span("loop.bookkeeping"):
                costs.sample_hbm(step=stepno)
                if self.skip_nonfinite and math.isnan(loss):
                    self._check_drop_budget(self._record_skipped_step())
                self.metrics.add("computing time average", dt * 1e9)
                self.metrics.set("loss", loss, unit="scalar")
                count_this_epoch += bs
                self.state["neval"] += 1
                self.state["recordsProcessedThisEpoch"] = count_this_epoch
                self.state["isLastBatchOfEpoch"] = \
                    count_this_epoch >= ds_size
                self._emit_step_record(stepno, loss, bs, dt, clr_val)
                logger.info(
                    "Epoch %d %d/%d loss %.6f throughput %.1f "
                    "records/second", self.state["epoch"],
                    count_this_epoch, ds_size, loss, bs / max(dt, 1e-9))

                if count_this_epoch >= ds_size:
                    self.state["epoch"] += 1
                    count_this_epoch = 0
                    self.state["recordsProcessedThisEpoch"] = 0

                if self.sharded_checkpoint_trigger and \
                        self.sharded_checkpoint_path and \
                        self._elastic_should_write() and \
                        self.sharded_checkpoint_trigger(self.state):
                    from bigdl_tpu.utils import checkpoint as ckpt
                    with tracer.span("checkpoint.sharded.save",
                                     step=self.state["neval"]):
                        ckpt.save_sharded(self.sharded_checkpoint_path,
                                          _snapshot(params, opt_state,
                                                    model_state),
                                          step=self.state["neval"],
                                          detach=step.donates_state)

                if self.validation_trigger and \
                        self.validation_trigger(self.state):
                    # sharded params apply directly under jit — GSPMD
                    # gathers on use, no host reassembly
                    self.model.params = params
                    self.model.state = model_state
                    self.validate()
                if self.checkpoint_trigger and self.checkpoint_path and \
                        self.checkpoint_trigger(self.state):
                    with tracer.span("get_model"):
                        self.model.params = jax.tree_util.tree_map(
                            _fetch_global, params)
                        self.model.state = model_state
                    self._maybe_checkpoint(jax.tree_util.tree_map(
                        _fetch_global, opt_state))
                self.state["isLastBatchOfEpoch"] = False
                FaultInjector.fire("train.step", step=self.state["neval"])

        with tracer.span("get_model"):
            self.model.params = jax.tree_util.tree_map(_fetch_global,
                                                       params)
            self.model.state = model_state
        if self.sharded_checkpoint_path:
            from bigdl_tpu.utils import checkpoint as ckpt
            ckpt.wait()
        wall = time.time() - wall_start
        logger.info("Training finished in %.1fs (%d iterations)",
                    wall, self.state["neval"])
        self._close_ingest()
        self._run_end(wall)
        return self.model


def _sharded_eval_loop(eval_fn, fixed_args, dataset, methods, mesh):
    """Shared batch loop for mesh-sharded evaluation: pad ragged final
    batches to the data-axis size, shard onto the mesh, reduce the
    ValidationResults by their monoid ``+``."""
    n = mesh_mod.dp_size(mesh)
    sharding = mesh_mod.batch_sharding(mesh)
    results = None
    for batch in dataset.data(train=False):
        data = np.asarray(batch.data)
        labels = np.asarray(batch.labels)
        pad = (-len(data)) % n
        if pad:  # pad ragged final batch (repeat row 0), mask out below
            filler = np.repeat(data[:1], pad, axis=0)
            data = np.concatenate([data, filler], axis=0)
        y = eval_fn(*fixed_args, jax.device_put(data, sharding))
        y = np.asarray(jax.device_get(y))
        if pad:
            y = y[:len(y) - pad]
        rs = [m(y, labels) for m in methods]
        results = rs if results is None else \
            [a + b for a, b in zip(results, rs)]
    return [] if results is None else results


class DistriValidator:
    """Mesh-sharded standalone evaluation (``optim/DistriValidator.scala``).
    Falls back to replicating the last ragged batch."""

    def __init__(self, model, dataset, mesh=None):
        self.model = model
        self.dataset = dataset
        self.mesh = mesh or Engine.mesh()

    def test(self, methods):
        if self.model.params is None:
            self.model.build()
        eval_fn = make_distri_eval_fn(self.model, self.mesh)
        # empty dataset -> [] (same contract as local _evaluate)
        return _sharded_eval_loop(
            eval_fn, (self.model.params, self.model.state),
            self.dataset, methods, self.mesh)
