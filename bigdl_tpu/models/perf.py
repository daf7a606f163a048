"""Synthetic-data training throughput harnesses.

Parity: ``models/utils/LocalOptimizerPerf.scala`` (single-chip) and
``models/utils/DistriOptimizerPerf.scala`` (multi-chip): push
constant/random ImageNet-shaped batches through the full train step for a
fixed iteration count and log per-iteration throughput.

The reference's ``coreNumber``/``nodeNumber x corePerNode`` topology flags
map to the TPU mesh: the local harness runs the jitted step on one chip;
the distributed harness builds an ``n_devices`` data-parallel mesh (the
driver-style ZeRO-1 sharded step from ``parallel.allreduce``) — on a CPU
host set ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` like the
tests do.
"""

from __future__ import annotations

import argparse
import logging
import time

logger = logging.getLogger("bigdl_tpu.models.perf")

_INPUT_SIZES = {
    "alexnet": (3, 227, 227),
    "alexnetowt": (3, 224, 224),
    "inception_v1": (3, 224, 224),
    "inception_v2": (3, 224, 224),
    "vgg16": (3, 224, 224),
    "vgg19": (3, 224, 224),
}


def _build(name: str, class_num: int = 1000):
    from bigdl_tpu.models.alexnet import AlexNet, AlexNet_OWT
    from bigdl_tpu.models.inception import Inception_v1, Inception_v2
    from bigdl_tpu.models.vgg import Vgg_16, Vgg_19
    factory = {"alexnet": AlexNet, "alexnetowt": AlexNet_OWT,
               "inception_v1": Inception_v1, "inception_v2": Inception_v2,
               "vgg16": Vgg_16, "vgg19": Vgg_19}
    if name not in factory:
        raise SystemExit(
            f"model can only be {' | '.join(sorted(factory))}, got {name}")
    return factory[name](class_num)


def _parser(name: str) -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(name)
    p.add_argument("-b", "--batchSize", type=int, default=128)
    p.add_argument("-i", "--iteration", type=int, default=50)
    p.add_argument("-m", "--model", default="inception_v1",
                   help="alexnet | alexnetowt | inception_v1 | inception_v2"
                        " | vgg16 | vgg19")
    p.add_argument("-d", "--inputdata", default="random",
                   choices=["constant", "random"])
    p.add_argument("--dataType", default="float",
                   choices=["float", "double"],
                   help="float = f32 (bf16 on MXU); double enables jax "
                        "x64 (reference DistriOptimizerPerf flag parity; "
                        "f64 is VPU-only on TPU — expect a large slowdown)")
    p.add_argument("-c", "--corePerNode", type=int, default=None,
                   help="accepted for reference flag parity; XLA owns "
                        "intra-device parallelism, so this is ignored")
    return p


def _apply_data_type(args) -> type:
    import numpy as np
    if args.corePerNode is not None:
        logger.info("corePerNode=%d accepted for flag parity and ignored "
                    "(XLA owns intra-device parallelism)", args.corePerNode)
    if args.dataType == "double":
        import jax
        jax.config.update("jax_enable_x64", True)
        return np.float64
    return np.float32


def _cast_floats(tree, np_dtype):
    """Cast every floating leaf of a pytree (params/state) to np_dtype —
    the double path needs f64 parameters, not just f64 inputs."""
    import numpy as np
    if np_dtype is np.float32:
        return tree
    import jax
    import jax.numpy as jnp

    def cast(l):
        if hasattr(l, "dtype") and jnp.issubdtype(l.dtype, jnp.floating):
            return jnp.asarray(l, np_dtype)
        return l
    return jax.tree_util.tree_map(cast, tree)


def _synthetic_batch(model_name: str, batch: int, kind: str,
                     dtype=None):
    import numpy as np
    dtype = dtype or np.float32
    c, h, w = _INPUT_SIZES[model_name]
    if kind == "constant":
        data = np.full((batch, c, h, w), 0.01, dtype)
    else:
        data = np.random.RandomState(0).rand(batch, c, h, w).astype(dtype)
    labels = (np.arange(batch) % 1000 + 1).astype(dtype)
    return data, labels


def local_perf_main(argv=None):
    """``LocalOptimizerPerf`` — one chip, jitted train step."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.nn import ClassNLLCriterion
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.utils.log import init_logging
    from bigdl_tpu.utils.table import T

    args = _parser("local-optimizer-perf").parse_args(argv)
    init_logging()
    np_dtype = _apply_data_type(args)
    model = _build(args.model)
    params, state = model.init(jax.random.PRNGKey(0))
    params = _cast_floats(params, np_dtype)
    state = _cast_floats(state, np_dtype)
    criterion = ClassNLLCriterion()
    optim = SGD(learning_rate=0.01)
    opt_state = optim.init_state(params)
    cfg = T()

    @jax.jit
    def train_step(p, o, s, x, y, rng, stepno):
        def loss_fn(pp):
            out, new_s = model.apply(pp, s, x, training=True, rng=rng)
            return criterion.apply(out, y), new_s
        (loss, new_s), grads = jax.value_and_grad(loss_fn, has_aux=True)(p)
        c = cfg.clone()
        c["clr"] = jnp.asarray(-0.01, jnp.float32)
        new_p, new_o = optim.update(grads, p, o, c, stepno)
        return new_p, new_o, new_s, loss

    data, labels = _synthetic_batch(args.model, args.batchSize,
                                    args.inputdata, np_dtype)
    rng = jax.random.PRNGKey(1)
    params, opt_state, state, loss = train_step(
        params, opt_state, state, data, labels, rng,
        jnp.asarray(0, jnp.int32))
    jax.block_until_ready(loss)    # compile outside the timed loop

    total0 = time.time()
    for i in range(1, args.iteration + 1):
        t0 = time.time()
        params, opt_state, state, loss = train_step(
            params, opt_state, state, data, labels, rng,
            jnp.asarray(i, jnp.int32))
        jax.block_until_ready(loss)
        dt = time.time() - t0
        logger.info(
            "Iteration %d, Loss %.4f, Throughput %.1f records/second",
            i, float(loss), args.batchSize / dt)
    total = time.time() - total0
    ips = args.batchSize * args.iteration / total
    logger.info("Average throughput %.1f records/second", ips)
    return ips


def infer_perf_main(argv=None):
    """Inference throughput — the jitted fixed-shape eval forward
    ``api.DLClassifier`` compiles (bf16 by default; ``--dataType
    double`` for the f64 path), batch images/sec on one chip.  The
    root-level ``bench_infer.py`` is the artifact-writing superset;
    this subcommand makes the measurement available from the installed
    CLI (``bigdl-tpu-perf infer``)."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.utils.log import init_logging

    p = _parser("infer-perf")
    p.add_argument("--fp32", action="store_true",
                   help="keep f32 activations (default casts to bf16, "
                        "the throughput policy)")
    args = p.parse_args(argv)
    init_logging()
    np_dtype = _apply_data_type(args)
    model = _build(args.model)
    params, state = model.init(jax.random.PRNGKey(0))
    params = _cast_floats(params, np_dtype)
    state = _cast_floats(state, np_dtype)
    if not args.fp32 and args.dataType == "float":
        from bigdl_tpu.core.precision import cast_tree
        params = cast_tree(params, jnp.bfloat16)

    @jax.jit
    def fwd(p, s, x):
        y, _ = model.apply(p, s, x, training=False)
        return jnp.argmax(y, axis=-1)        # tiny fetch (api.py policy)

    data, _ = _synthetic_batch(args.model, args.batchSize,
                               args.inputdata, np_dtype)
    if not args.fp32 and args.dataType == "float":
        data = data.astype(jnp.bfloat16)
    preds = fwd(params, state, data)
    jax.block_until_ready(preds)             # compile outside timing
    import numpy as _np

    total0 = time.time()
    for i in range(1, args.iteration + 1):
        t0 = time.time()
        preds = fwd(params, state, data)
        _np.asarray(preds)
        logger.info("Iteration %d, Throughput %.1f records/second",
                    i, args.batchSize / (time.time() - t0))
    ips = args.batchSize * args.iteration / (time.time() - total0)
    logger.info("Average inference throughput %.1f records/second", ips)
    return ips


def distri_perf_main(argv=None):
    """``DistriOptimizerPerf`` — data-parallel mesh over all devices."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from bigdl_tpu.nn import ClassNLLCriterion
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.parallel.allreduce import make_distri_train_step
    from bigdl_tpu.utils.log import init_logging
    from bigdl_tpu.utils.table import T

    p = _parser("distri-optimizer-perf")
    p.add_argument("-n", "--nodeNumber", type=int, default=0,
                   help="devices to use (0 = all visible)")
    args = p.parse_args(argv)
    init_logging()
    np_dtype = _apply_data_type(args)

    devices = jax.devices()
    n = args.nodeNumber or len(devices)
    mesh = Mesh(np.asarray(devices[:n]).reshape(n, 1), ("data", "model"))
    logger.info("mesh: %d-way data parallel over %s", n, devices[0].platform)

    model = _build(args.model)
    params, state = model.init(jax.random.PRNGKey(0))
    params = _cast_floats(params, np_dtype)
    state = _cast_floats(state, np_dtype)
    model.params, model.state = params, state
    criterion = ClassNLLCriterion()
    optim = SGD(learning_rate=0.01)

    # bf16 wire compression would silently truncate the f64 path the
    # --dataType flag promises, so it is float-only
    compress = "bf16" if args.dataType == "float" else None
    step, layout, init_fn = make_distri_train_step(
        model, criterion, optim, mesh, T(), compress=compress)
    wshard, opt_shard = init_fn(params)

    data, labels = _synthetic_batch(args.model, args.batchSize,
                                    args.inputdata, np_dtype)
    data = jax.device_put(data, NamedSharding(mesh, P("data")))
    labels = jax.device_put(labels, NamedSharding(mesh, P("data")))
    rng = jax.random.PRNGKey(1)

    wshard, opt_shard, state, loss = step(
        wshard, opt_shard, state, data, labels, rng,
        jnp.asarray(0, jnp.int32), jnp.asarray(-0.01, jnp.float32))
    jax.block_until_ready(loss)

    total0 = time.time()
    for i in range(1, args.iteration + 1):
        t0 = time.time()
        wshard, opt_shard, state, loss = step(
            wshard, opt_shard, state, data, labels, rng,
            jnp.asarray(i, jnp.int32), jnp.asarray(-0.01, jnp.float32))
        jax.block_until_ready(loss)
        dt = time.time() - t0
        logger.info(
            "Iteration %d, Loss %.4f, Throughput %.1f records/second",
            i, float(loss), args.batchSize / dt)
    total = time.time() - total0
    ips = args.batchSize * args.iteration / total
    logger.info("Average throughput %.1f records/second", ips)
    return ips



def ingest_perf_main(argv=None):
    """ImageNet ingest-pipeline throughput: record files -> decode ->
    crop/flip -> MT batch pack, measured in images/sec on the host.

    The reference has no standalone ingest benchmark (Spark hid the
    pipeline inside executors); on TPU the host pipeline must outrun the
    chip (SURVEY.md §7 hard part 3), so this harness exists to check it.
    Generates synthetic record files once under --workDir, then streams
    them through the real training pipeline.
    """
    import json
    import os

    import numpy as np

    from bigdl_tpu.dataset.image import LabeledImage
    from bigdl_tpu.dataset.seqfile import (BGRImgToLocalSeqFile,
                                           seq_file_paths)
    from bigdl_tpu.utils.log import init_logging

    p = argparse.ArgumentParser("ingest-perf")
    p.add_argument("-b", "--batchSize", type=int, default=256)
    p.add_argument("-n", "--images", type=int, default=4096,
                   help="synthetic images to generate")
    p.add_argument("--size", type=int, default=256,
                   help="stored image edge (shorter-side-256 convention)")
    p.add_argument("--crop", type=int, default=224)
    p.add_argument("-w", "--workers", type=int, default=1,
                   help="ingest worker PROCESSES; scale to the host's "
                        "core count (one pipeline per core, the "
                        "reference-executor model). >1 on a 1-core host "
                        "only adds scheduling overhead")
    p.add_argument("--workDir", default="/tmp/bigdl_tpu_ingest")
    p.add_argument("-e", "--epochs", type=int, default=2,
                   help="passes over the data (first warms the page cache)")
    args = p.parse_args(argv)
    init_logging()

    os.makedirs(args.workDir, exist_ok=True)
    # regenerate when the workload parameters change — stale files would
    # silently benchmark the wrong workload
    params = {"images": args.images, "size": args.size,
              "workers": args.workers}
    marker = os.path.join(args.workDir, "params.json")
    try:
        with open(marker) as f:
            stale = json.load(f) != params
    except (OSError, ValueError):   # missing / truncated marker -> stale
        stale = True
    if stale or not seq_file_paths(args.workDir):
        for f in seq_file_paths(args.workDir):
            os.remove(f)
        rng = np.random.RandomState(0)

        def imgs():
            for i in range(args.images):
                yield LabeledImage(
                    rng.randint(0, 256, (args.size, args.size, 3))
                    .astype(np.float32), float(i % 1000 + 1))

        # at least one file per worker, else -w cannot scale
        block = max(1, args.images // max(args.workers, 4))
        files = list(BGRImgToLocalSeqFile(
            block, os.path.join(args.workDir, "part")).apply(imgs()))
        with open(marker, "w") as f:
            json.dump(params, f)
        logger.info("generated %d record files (%d images)",
                    len(files), args.images)

    shards = seq_file_paths(args.workDir)
    pool = None
    n_pool = 1
    if args.workers > 1:
        if args.workers > (os.cpu_count() or 1):
            logger.warning(
                "%d workers on a %d-core host — expect overhead, "
                "not speedup", args.workers, os.cpu_count() or 1)
        if args.workers > len(shards):
            logger.warning("only %d file shards for %d workers — "
                           "parallelism capped", len(shards), args.workers)
        # multi-PROCESS over file shards: the per-image python chain is
        # GIL-bound (threads plateau ~800 img/s/core), so scale the way
        # the reference scaled — one full pipeline per worker process per
        # file shard (its executors).  Pool is created and warmed OUTSIDE
        # the timed region: spawn startup (interpreter + imports) is a
        # one-time cost, not ingest throughput.
        from concurrent.futures import ProcessPoolExecutor
        import multiprocessing
        ctx = multiprocessing.get_context("spawn")
        n_pool = min(args.workers, len(shards))
        pool = ProcessPoolExecutor(n_pool, mp_context=ctx)

    ips = 0.0
    try:
        if pool is not None:
            # warm EVERY worker before timing: a barrier keyed to the
            # pool size stops one fast-spawning worker from draining all
            # the warm tasks while its peers are still importing.  A
            # Manager barrier proxy is used because raw mp sync
            # primitives cannot be pickled into pool tasks.  Inside the
            # try so a failed warm-up still tears the pool down.
            mgr = ctx.Manager()
            try:
                barrier = mgr.Barrier(n_pool)
                list(pool.map(_ingest_warm, [barrier] * n_pool))
            finally:
                mgr.shutdown()
        for epoch in range(args.epochs):
            t0 = time.time()
            count = 0
            if pool is not None:
                for c in pool.map(
                        _ingest_shard_count,
                        [(s, args.crop, args.batchSize) for s in shards]):
                    count += c
            else:
                pipeline = _ingest_pipeline(args.crop, args.batchSize)
                for batch in pipeline(iter(shards)):
                    count += batch.data.shape[0]
            dt = time.time() - t0
            ips = count / dt
            logger.info("epoch %d: %d images in %.2fs -> %.1f images/sec "
                        "(%d workers)", epoch, count, dt, ips, n_pool)
    finally:
        if pool is not None:
            pool.shutdown()
    return ips


def _ingest_warm(barrier):
    """Force worker-process imports before the timed region; the barrier
    makes every pool process participate."""
    _ingest_pipeline(224, 256)
    barrier.wait(timeout=300)
    return 0


def _ingest_pipeline(crop, batch_size):
    from bigdl_tpu.dataset.image import BGRImgCropper, HFlip
    from bigdl_tpu.dataset.prefetch import MTLabeledBGRImgToBatch
    from bigdl_tpu.dataset.seqfile import (LocalSeqFileToBytes,
                                           SeqBytesToBGRImg)
    return (LocalSeqFileToBytes() >> SeqBytesToBGRImg() >>
            BGRImgCropper(crop, crop) >> HFlip(0.5) >>
            MTLabeledBGRImgToBatch(crop, crop, batch_size, workers=2))


def _ingest_shard_count(job):
    """One worker process: run the full pipeline over one record file."""
    path, crop, batch_size = job
    n = 0
    for batch in _ingest_pipeline(crop, batch_size)(iter([path])):
        n += batch.data.shape[0]
    return n


def longcontext_perf_main(argv=None):
    """Long-context training throughput: one TransformerLM train step
    (remat + the fused attention kernel; the streaming variant engages
    once K/V exceed the VMEM budget — T=16384 at the default head dim)
    at a given sequence length.  No reference analogue (SURVEY.md §5.7:
    the reference has no attention); this is the TPU-native long-context
    flagship benchmark.

    Measured on one v5e chip (bf16 mixed precision, L=8 E=512):
    T=8192 ~47k tok/s, T=16384 ~20k tok/s, loss decreasing.
    """
    import argparse

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.core.precision import mixed_forward
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.nn import ClassNLLCriterion, TimeDistributedCriterion
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.utils.log import init_logging
    from bigdl_tpu.utils.table import T

    p = argparse.ArgumentParser("longcontext-perf")
    p.add_argument("-t", "--seqLen", type=int, default=8192)
    p.add_argument("-b", "--batchSize", type=int, default=1)
    p.add_argument("-l", "--layers", type=int, default=8)
    p.add_argument("-e", "--embed", type=int, default=512)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--vocab", type=int, default=8192)
    p.add_argument("-i", "--iteration", type=int, default=5)
    p.add_argument("--no-remat", dest="remat", action="store_false")
    args = p.parse_args(argv)
    init_logging()

    model = TransformerLM(args.vocab, max_len=args.seqLen,
                          embed_dim=args.embed, num_heads=args.heads,
                          num_layers=args.layers, remat=args.remat)
    params, state = model.init(jax.random.PRNGKey(0))
    crit = TimeDistributedCriterion(ClassNLLCriterion(), size_average=True)
    optim = SGD(learning_rate=0.1)
    opt_state = optim.init_state(params)
    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(1, args.vocab + 1,
                                 (args.batchSize, args.seqLen)))
    tgt = jnp.asarray(np.roll(np.asarray(ids), -1, axis=1)
                      .astype(np.float32))

    @jax.jit
    def step(p_, o_, i):
        def loss_fn(pp):
            out, _ = mixed_forward(model, pp, state, ids, training=True,
                                   rng=jax.random.PRNGKey(1))
            return crit.apply(out, tgt)
        loss, g = jax.value_and_grad(loss_fn)(p_)
        # no clr override: SGD derives it from learning_rate, so tuning
        # the constructor actually takes effect
        p2, o2 = optim.update(g, p_, o_, T(), i)
        return p2, o2, loss

    params, opt_state, loss = step(params, opt_state,
                                   jnp.asarray(0, jnp.int32))
    first = float(loss)             # device sync (see bench.py note)
    t0 = time.time()
    for i in range(1, args.iteration + 1):
        params, opt_state, loss = step(params, opt_state,
                                       jnp.asarray(i, jnp.int32))
    last = float(loss)
    dt = (time.time() - t0) / args.iteration
    toks = args.batchSize * args.seqLen / dt
    logger.info("T=%d L=%d E=%d remat=%s: %.1f ms/step, %.0f tokens/sec, "
                "loss %.3f -> %.3f", args.seqLen, args.layers, args.embed,
                args.remat, dt * 1e3, toks, first, last)
    return toks


def main(argv=None):
    """Subcommand dispatcher (also the ``bigdl-tpu-perf`` console entry
    point): ``local`` (default) / ``distri`` / ``infer`` / ``ingest`` /
    ``longcontext``."""
    import sys
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == "distri":
        return distri_perf_main(argv[1:])
    if argv and argv[0] == "infer":
        return infer_perf_main(argv[1:])
    if argv and argv[0] == "ingest":
        return ingest_perf_main(argv[1:])
    if argv and argv[0] == "longcontext":
        return longcontext_perf_main(argv[1:])
    if argv and argv[0] == "local":
        return local_perf_main(argv[1:])
    return local_perf_main(argv)


if __name__ == "__main__":
    main()
