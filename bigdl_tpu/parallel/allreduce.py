"""Partitioned parameter all-reduce — the communication backend.

Parity: ``parameters/AllReduceParameter.scala:55-238`` + the FP16 wire codec
(``parameters/FP16CompressedTensor.scala``).  The reference implements a
range-partitioned synchronous all-reduce as Spark BlockManager fetches:
per iteration (a) all-gather fp16 weight slices, (b) scatter fp16 gradient
slices, (c) each node sums its owned slice, (d) sharded optimizer update,
(e) republish the owned weight slice.

TPU-native design (SURVEY.md section 2.6 "TPU-native equivalent"): the same
partitioned algorithm expressed as XLA collectives over the mesh's ICI —
structurally 1:1:

  putGradients + aggregrateGradientPartition  ->  lax.psum_scatter
  optimMethod.optimize on the owned slice     ->  sharded update on the
                                                  flat shard (ZeRO-1)
  sendWeightPartition + getWeights            ->  lax.all_gather

Weights live as ONE flat padded fp32 vector logically range-partitioned
across the mesh's batch axes — ``data`` alone, or the joint
``data x fsdp`` ring of the trainer mesh (``parallel/mesh.py``), so an
fsdp axis shrinks per-device resident parameter+optimizer bytes by its
size with no layout change — exactly the reference's
``taskSize``/``extraSize`` partitioning
(``AllReduceParameter.scala:69-71``) — and the optimizer state
(momentum etc.) exists only for the local shard on each device.  FP16 wire
compression maps to bf16 gradient collectives (``compress="bf16"``), bf16
having the same 1-sign/8-exp layout the reference's truncation codec
preserves (it keeps the top 16 bits of the IEEE754 float — i.e. bf16).

Everything here is shard_map-traced: one fused XLA program per step, with
the collectives riding ICI (or faked on the CPU test mesh).
"""

from __future__ import annotations

from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax
from bigdl_tpu.compat import shard_map
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# an axis argument: one mesh axis name, or a tuple of them — the ring
# then spans their product (how the flat ZeRO-1 partition generalises to
# the (data, fsdp) mesh: every dp x fsdp slot owns one weight shard, so
# per-device resident parameter+optimizer bytes shrink by the whole ring
# size).  None = resolve the mesh's batch axes (parallel.mesh.dp_axes).
AxisSpec = Union[str, Tuple[str, ...], None]


def resolve_ring_axis(mesh: Mesh, axis: AxisSpec):
    """Normalise ``axis``: None -> the mesh's dp axes; a 1-tuple -> its
    bare name (identical collectives, simpler HLO metadata)."""
    if axis is None:
        from bigdl_tpu.parallel.mesh import dp_axes
        axis = dp_axes(mesh)
    if isinstance(axis, (tuple, list)):
        axis = tuple(axis)
        return axis[0] if len(axis) == 1 else axis
    return axis


def ring_size(mesh: Mesh, axis) -> int:
    """Number of ring participants: the product over the named axes."""
    names = axis if isinstance(axis, tuple) else (axis,)
    n = 1
    for a in names:
        n *= mesh.shape[a]
    return n


# TPU minor-dim lane tile.  Shard sizes are aligned to this because the
# XLA:TPU backend keeps a LANE-aligned 1-D ``all-gather`` native but
# rewrites an unaligned one into dynamic-update-slice + full-buffer
# all-reduce — 2x the ring wire bytes (r5 measured on AOT v5e:2x4
# executables: shard 2785 decomposes, every multiple of 128 tried from
# 128 to 2944 survives).  A few hundred padding floats buy half the
# getWeights traffic.
LANE = 128


class AllReduceParameter:
    """Flat-partitioned parameter/optimizer-state layout over a mesh axis.

    ``taskSize = size / partitionNum`` with padding instead of the
    reference's ``extraSize`` remainder handling (padding keeps every shard
    identical, which XLA strongly prefers over ragged shards; shards are
    additionally LANE-aligned — see ``LANE``).

    ``rs_mode`` selects the aggregate-gradient collective:

    * ``"a2a"`` (default): ``lax.all_to_all`` of per-destination chunks +
      local f32 sum.  XLA:TPU's ``reduce-scatter-decomposer`` pass
      unconditionally rewrites the ``reduce-scatter`` HLO into a
      full-buffer all-reduce + slice (r5: verified on every size/dtype/
      alignment probed, and none of the exposed ``xla_tpu_*reduce_scatter*``
      flags disable it) — 2x the authored ring wire.  all-to-all is kept
      native by the backend and moves exactly the authored (n-1)/n of the
      buffer; summing the n received chunks locally in f32 also matches
      the reference's codec numerics (slices cross the wire compressed
      ONCE, accumulation happens uncompressed —
      ``parameters/FP16CompressedTensor.scala`` + ``AllReduceParameter
      .scala:202-216``), strictly better than the bf16-accumulating
      all-reduce the decomposed form runs.
    * ``"psum_scatter"``: the r1-r4 form, kept for A/B measurement of the
      decomposed program.
    """

    def __init__(self, params_template, mesh: Mesh, axis: AxisSpec = None,
                 compress: Optional[str] = "bf16", rs_mode: str = "a2a"):
        self.mesh = mesh
        # the partition ring may span multiple mesh axes (data x fsdp on
        # the trainer mesh) — collectives take the tuple directly
        self.axis = resolve_ring_axis(mesh, axis)
        self.compress = compress
        if rs_mode not in ("a2a", "psum_scatter"):
            raise ValueError(
                f"rs_mode must be 'a2a' or 'psum_scatter', got {rs_mode!r}"
                " (a silent fallthrough here would ship the 2x-wire"
                " decomposed program)")
        self.rs_mode = rs_mode
        self.n = ring_size(mesh, self.axis)
        flat, self.unravel = ravel_pytree(params_template)
        self.dtype = flat.dtype          # f32 normally; f64 under jax x64
        self.size = flat.shape[0]
        per = -(-self.size // self.n)                   # ceil per-shard
        self.shard_size = -(-per // LANE) * LANE        # LANE-align
        self.padded = self.shard_size * self.n

    def pad_flat(self, flat: jnp.ndarray) -> jnp.ndarray:
        return jnp.concatenate(
            [flat, jnp.zeros((self.padded - self.size,), flat.dtype)])

    def flatten(self, params) -> jnp.ndarray:
        return self.pad_flat(ravel_pytree(params)[0])

    def unflatten(self, flat_padded: jnp.ndarray):
        return self.unravel(flat_padded[:self.size])

    # -- the collective sequence (runs inside shard_map) --------------------

    def reduce_scatter_flat(self, gflat: jnp.ndarray) -> jnp.ndarray:
        """The aggregate-gradient collective on a full padded flat vector
        -> this node's summed shard, in the master dtype (no count
        division — callers own that)."""
        with jax.named_scope("aggregate_gradient"):
            if self.rs_mode == "a2a":
                x = gflat.reshape(self.n, self.shard_size)
                if self.compress == "bf16":
                    x = x.astype(jnp.bfloat16)
                # row j -> device j; received row r = device r's chunk
                # for THIS device; f32 sum of the n rows = the owned
                # summed slice (same ownership as psum_scatter tiled)
                y = lax.all_to_all(x, self.axis, split_axis=0,
                                   concat_axis=0)
                return jnp.sum(y.astype(self.dtype), axis=0)
            if self.compress == "bf16":
                gflat = gflat.astype(jnp.bfloat16)
            gshard = lax.psum_scatter(gflat, self.axis,
                                      scatter_dimension=0, tiled=True)
            return gshard.astype(self.dtype)

    def reduce_scatter_gradients(self, grads_pytree, count) -> jnp.ndarray:
        """putGradients + aggregrateGradientPartition: local full gradient
        -> owned flat shard summed across nodes, divided by ``count``
        (the reference divides by finishedModelNum,
        ``DistriOptimizer.scala:230``)."""
        return self.reduce_scatter_flat(self.flatten(grads_pytree)) / count

    def all_gather_weights(self, wshard: jnp.ndarray):
        """sendWeightPartition + getWeights: owned weight shard -> full
        params pytree on every node."""
        with jax.named_scope("get_weights"):
            if self.compress == "bf16":
                # wire-compress parity: weights cross the interconnect
                # in bf16
                flat = lax.all_gather(wshard.astype(jnp.bfloat16),
                                      self.axis,
                                      tiled=True).astype(self.dtype)
            else:
                flat = lax.all_gather(wshard, self.axis, tiled=True)
        return self.unflatten(flat)

    def local_shard(self, flat_padded: jnp.ndarray) -> jnp.ndarray:
        """Extract this node's owned range (inside shard_map)."""
        idx = lax.axis_index(self.axis)
        return lax.dynamic_slice_in_dim(flat_padded, idx * self.shard_size,
                                        self.shard_size)


# the flag set validated by BENCH_comm_r5.json's :async rows — the
# single source of truth; bench_comm.py's experiment builds on it
ASYNC_COLLECTIVE_FLAGS = {
    "xla_tpu_enable_async_all_to_all": "true",
    "xla_tpu_enable_latency_hiding_scheduler": "true",
}


def async_collective_options(mesh: Mesh):
    """Compiler options for the distributed step, gated by
    ``BIGDL_TPU_ASYNC_COLLECTIVES`` (default off → ``None``) and by the
    mesh actually being TPU (the CPU compiler REJECTS tpu-prefixed
    options rather than ignoring them).

    When enabled, the aggregate-gradient all-to-all compiles to a real
    ``-start``/``-done`` pair with compute scheduled inside the window
    (r5 measured: 3-5 compute ops between start and done on the
    LeNet/Inception v5e programs; ``BENCH_comm_r5.json`` ``:async``
    rows).  Off by default because the win is unvalidated on real
    multi-chip hardware from this one-chip environment — flip it on a
    pod and compare step time.  The all-gather stays synchronous either
    way (measured negative; flags listed in the artifact's
    ``async_negative_flags``)."""
    import os

    raw = os.environ.get("BIGDL_TPU_ASYNC_COLLECTIVES", "0").lower()
    if raw in ("0", "", "false", "no", "off"):
        return None
    if raw not in ("1", "true", "yes", "on"):
        # an unrecognized spelling silently measuring baseline-vs-
        # baseline would produce a false "no win on real hardware"
        raise ValueError(
            f"BIGDL_TPU_ASYNC_COLLECTIVES={raw!r}: use 1/true/yes/on "
            "or 0/false/no/off")
    platforms = {d.platform for d in mesh.devices.flat}
    if "tpu" not in platforms:
        return None
    return dict(ASYNC_COLLECTIVE_FLAGS)


def make_distri_train_step(model, criterion, optim, mesh: Mesh,
                           config, axis: AxisSpec = None,
                           compress: Optional[str] = "bf16",
                           params_template=None,
                           compute_dtype=None, rs_mode: str = "a2a",
                           guard_nonfinite: bool = True):
    """Build the jitted SPMD training step — the body of
    ``DistriOptimizer``'s per-iteration Spark jobs collapsed into one XLA
    program (SURVEY.md section 3.2 call stack).

    Layout contract:
      * ``wshard``     : (n, shard_size) sharded P(axis)   — owned weights
      * ``opt_shard``  : pytree of (n, shard_size) P(axis) — optimizer state
      * ``model_state``: replicated (BN running stats are psum-averaged)
      * ``data/labels``: batch-sharded P(axis) on dim 0

    ``guard_nonfinite``: skip-and-keep-weights semantics for a step whose
    loss or aggregated gradients are non-finite — the update, optimizer
    state and model state all keep their previous values, and the
    returned loss is NaN (the driver's skip signal).  Consensus across
    shards costs NO extra collective: each node that sees a bad local
    loss/owned-gradient-slice poisons its loss to NaN *before* the loss
    ``pmean``, so the existing reduction broadcasts the verdict — every
    node computes the identical ``ok`` and the weight shards cannot
    diverge.  This is the TPU-native analogue of the reference dropping
    a diverged sub-gradient and continuing (``DistriOptimizer.scala:
    244-272`` dropped-gradient accounting); the driver counts the skips
    in ``Metrics`` under the ``drop_percentage`` knobs.

    Returns (step_fn, param_layout, init_fn) where init_fn(params) builds
    (wshard, opt_shard) with correct shardings from a replicated pytree.
    """
    layout = AllReduceParameter(
        params_template if params_template is not None
        else model.params, mesh, axis, compress, rs_mode=rs_mode)
    axis = layout.axis          # resolved: one name or the dp-axes tuple
    n = layout.n

    def _local_step(wshard, opt_shard, model_state, data, labels, rng,
                    stepno, clr):
        # per-node RNG stream (Dropout masks must differ across replicas,
        # like the reference's per-thread Mersenne-Twister instances)
        rng = jax.random.fold_in(rng, lax.axis_index(axis))
        # (1) getWeights: assemble full weights from the partition ring
        params = layout.all_gather_weights(wshard[0])
        # (2) local forward/backward on this node's batch shard
        def loss_fn(p):
            from bigdl_tpu.core.precision import training_loss
            return training_loss(model, criterion, p, model_state, data,
                                 labels, rng, compute_dtype=compute_dtype)
        (loss, new_ms), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        # (3) reduce-scatter: own the summed gradient slice (mean over nodes)
        gshard = layout.reduce_scatter_gradients(grads, count=n)
        if guard_nonfinite:
            # poison-before-pmean: NaN propagates through the mean, so
            # the existing loss reduction doubles as the cross-shard
            # skip consensus (see make_distri_train_step docstring)
            with jax.named_scope("guard"):
                bad = ~(jnp.isfinite(loss)
                        & jnp.all(jnp.isfinite(gshard)))
                loss = jnp.where(bad, jnp.nan, loss)
        # (4) sharded optimizer update on the owned slice (ZeRO-1)
        cfg = config.clone()
        cfg["clr"] = clr
        opt_in = jax.tree_util.tree_map(lambda t: t[0], opt_shard)
        with jax.named_scope("update"):
            new_wshard, new_opt = optim.update(gshard, wshard[0], opt_in,
                                               cfg, stepno)
        # (5) losses/state reductions for the driver
        loss = lax.pmean(loss, axis)
        new_ms = jax.tree_util.tree_map(
            lambda t: lax.pmean(t, axis), new_ms)
        if guard_nonfinite:
            with jax.named_scope("guard"):
                ok = jnp.isfinite(loss)       # identical on every node
                new_wshard = jnp.where(ok, new_wshard, wshard[0])
                new_opt = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(ok, new, old[0]),
                    new_opt, opt_shard)
                new_ms = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(ok, new, old),
                    new_ms, model_state)
        return (new_wshard[None], jax.tree_util.tree_map(
            lambda t: t[None], new_opt), new_ms, loss)

    smapped = shard_map(
        _local_step, mesh=mesh,
        in_specs=(P(axis), P(axis), P(), P(axis), P(axis), P(), P(), P()),
        out_specs=(P(axis), P(axis), P(), P()),
        check_vma=False)
    # wshard/opt_shard donation halves the training state's HBM residency
    # on TPU, but on the CPU backend donated buffers + cached executables
    # corrupt the heap (use-after-free observed with the persistent
    # compilation cache on jaxlib 0.4.x) — and CPU meshes are the test
    # topology, where memory is not the constraint; donate only where it
    # pays and is safe
    platforms = {d.platform for d in mesh.devices.flat}
    donate = () if platforms <= {"cpu"} else (0, 1)
    # recorded so the checkpoint path knows whether the training state's
    # buffers can be reused out from under an async save
    layout.donates_state = bool(donate)
    step = jax.jit(smapped, donate_argnums=donate,
                   compiler_options=async_collective_options(mesh))

    def init_fn(params):
        """Replicated pytree -> sharded (wshard, opt_shard) device arrays
        (parameters.init parity, ``AllReduceParameter.scala:102-118``)."""
        from bigdl_tpu.observability import tracer
        with tracer.span("allreduce.init_shards", n=n,
                         shard_size=layout.shard_size):
            flat = layout.pad_flat(ravel_pytree(params)[0])
            wshard = flat.reshape(n, layout.shard_size)
            opt_state = optim.init_state(jnp.zeros((layout.shard_size,)))
            opt_shard = jax.tree_util.tree_map(
                lambda t: jnp.broadcast_to(t, (n,) + t.shape), opt_state)
            sharding = NamedSharding(mesh, P(axis))
            wshard = jax.device_put(wshard, sharding)
            opt_shard = jax.tree_util.tree_map(
                lambda t: jax.device_put(t, NamedSharding(
                    mesh, P(*((axis,) + (None,) * (t.ndim - 1))))),
                opt_shard)
            return wshard, opt_shard

    return step, layout, init_fn


def make_phase_probes(layout: AllReduceParameter, mesh: Mesh):
    """Isolated getWeights / aggregateGradient collectives, jitted alone.

    The reference times these phases per iteration ("get weights
    average" / "aggregate gradient time", ``DistriOptimizer.scala:
    115-119,148-151``).  In the fused SPMD step they are inseparable
    from compute (that's the point — the scheduler may interleave
    them), so the driver measures these stand-alone probes instead: the
    same collective, same payload, same mesh — an unoverlapped
    upper bound on the in-step cost.  Byte-level accounting comes from
    ``parallel/comm_audit.py``.

    Returns ``(get_weights_fn(wshard), aggregate_gradient_fn(gflat))``:
    the first consumes the (n, shard_size) ZeRO-1 weight layout, the
    second a replicated full padded flat gradient.
    """
    axis = layout.axis

    def _gw(wshard):
        return layout.all_gather_weights(wshard[0])

    def _rs(gflat):
        return layout.reduce_scatter_flat(gflat)

    gw = jax.jit(shard_map(_gw, mesh=mesh, in_specs=(P(axis),),
                           out_specs=P(), check_vma=False))
    rs = jax.jit(shard_map(_rs, mesh=mesh, in_specs=(P(),),
                           out_specs=P(axis), check_vma=False))
    return gw, rs


def make_distri_eval_fn(model, mesh: Mesh, axis: AxisSpec = None):
    """Sharded inference step (DistriValidator role,
    ``optim/DistriValidator.scala``)."""
    axis = resolve_ring_axis(mesh, axis)

    def _eval(params, model_state, data):
        y, _ = model.apply(params, model_state, data, training=False)
        return y

    smapped = shard_map(_eval, mesh=mesh,
                        in_specs=(P(), P(), P(axis)),
                        out_specs=P(axis), check_vma=False)
    return jax.jit(smapped)


def make_distri_eval_from_shard(model, layout: "AllReduceParameter",
                                mesh: Mesh, axis: AxisSpec = None):
    """Sharded inference consuming the ZeRO-1 weight shard DIRECTLY: the
    full weights are assembled by an on-device all_gather inside the
    program (the same collective the train step's getWeights phase runs)
    — validation never round-trips the parameters through the host
    (VERDICT r1 weak #7; the reference paid the host trip via getModel,
    ``DistriOptimizer.scala:475-502``).

    The gather runs UNCOMPRESSED regardless of the training step's wire
    codec: validation metrics must reflect the exact master weights (the
    ones getModel/checkpoints expose), not bf16-rounded copies."""
    import copy

    axis = resolve_ring_axis(mesh, axis if axis is not None
                             else layout.axis)
    exact = copy.copy(layout)
    exact.compress = None

    def _eval(wshard, model_state, data):
        params = exact.all_gather_weights(wshard[0])
        y, _ = model.apply(params, model_state, data, training=False)
        return y

    smapped = shard_map(_eval, mesh=mesh,
                        in_specs=(P(axis), P(), P(axis)),
                        out_specs=P(axis), check_vma=False)
    return jax.jit(smapped)
