"""Sharded multi-process ingest tests (PR 6).

The contracts under test, in the order the ISSUE states them:

* **shard partition exactness** — `partition_range`/`worker_shard` tile
  the record set exactly once across hosts x workers, uneven splits
  included;
* **seeded-augmentation reproducibility** — the sample stream is a
  function of (seed, epoch, position) only: changing the worker count
  (0, 1, 2, 3...) never changes a single record;
* **ring backpressure** — a slow consumer bounds the upstream pull
  (pre-allocated slots ARE the buffer; nothing queues unboundedly);
* **bf16-cast parity** — the staging ring's host-side cast produces
  exactly the values the f32 path casts to on device;
* **worker-death propagation** — a killed decode process surfaces a
  typed `IngestWorkerDied` at the trainer's `next()`, never a hang;
* **stage attribution** — `run-report` over a training run names the
  bound ingest stage from per-stage spans;
* **config knobs** — `BIGDL_TPU_INGEST_*` env defaults with API-arg
  precedence and strict parsing.
"""

import json
import os

import numpy as np
import pytest

from bigdl_tpu.dataset import ingest_config
from bigdl_tpu.dataset.image import (BGRImgCropper, BGRImgToBatch, HFlip,
                                     LabeledImage)
from bigdl_tpu.dataset.ingest_pool import (IngestPool, IngestWorkerDied,
                                           fold_seed)
from bigdl_tpu.dataset.prefetch import MTTransformer
from bigdl_tpu.dataset.sharded import (ShardedDataSet, partition_range,
                                       worker_shard)
from bigdl_tpu.dataset.staging import StagingRing
from bigdl_tpu.dataset.transformer import (Lambda, MiniBatch, Sample,
                                           SampleToBatch, Transformer)
from bigdl_tpu.resilience.fault_injector import FaultInjector

pytestmark = pytest.mark.ingest


@pytest.fixture(autouse=True)
def _disarm():
    FaultInjector.clear()
    yield
    FaultInjector.clear()


def _images(n, h=8, w=8, seed=0):
    rng = np.random.RandomState(seed)
    return [LabeledImage(rng.rand(h, w, 3).astype(np.float32),
                         float(i % 10) + 1) for i in range(n)]


def _samples(n, dim=784, seed=0):
    rng = np.random.RandomState(seed)
    return [Sample(rng.rand(dim).astype(np.float32),
                   np.float32(i % 10 + 1)) for i in range(n)]


# -- shard partition exactness ------------------------------------------------

def test_partition_range_tiles_exactly():
    for n in (0, 1, 2, 5, 7, 24, 97, 100):
        for count in (1, 2, 3, 5, 8, 13):
            parts = [partition_range(n, i, count) for i in range(count)]
            assert [x for r in parts for x in r] == list(range(n)), \
                (n, count)
            # balanced to within one item
            sizes = [len(r) for r in parts]
            assert max(sizes) - min(sizes) <= 1


def test_partition_range_rejects_bad_index():
    with pytest.raises(ValueError):
        partition_range(10, 3, 3)
    with pytest.raises(ValueError):
        partition_range(10, -1, 3)


def test_worker_shard_every_record_once_across_hosts_and_workers():
    # uneven on purpose: 101 records over 3 hosts x 4 workers
    items = list(range(101))
    seen = []
    for h in range(3):
        for w in range(4):
            seen += worker_shard(items, h, 3, w, 4)
    assert sorted(seen) == items
    assert len(seen) == len(items)          # no duplicates either


def test_sharded_dataset_hosts_partition_records():
    items = _images(11)
    streams = []
    for h in range(3):
        ds = ShardedDataSet(items, workers=0, chunk=4, host_index=h,
                            host_count=3)
        streams.append([r.label for r in ds.data(train=False)])
        assert ds.size() == len(streams[-1])
    flat = [l for s in streams for l in s]
    assert sorted(flat) == sorted(r.label for r in items)


# -- seeded reproducibility / order preservation ------------------------------

def _stream(items, workers, seed=7, chunk=5, epochs=1):
    """Full decoded/augmented stream at a given worker count; the
    augment chain is stochastic (crop + flip), which is exactly what
    must NOT vary with the worker count."""
    aug = BGRImgCropper(4, 4, seed=seed) >> HFlip(seed=seed + 1)
    ds = ShardedDataSet(items, augment=aug, workers=workers, chunk=chunk,
                        seed=seed)
    out = []
    try:
        for _ in range(epochs):
            out.append([(r.label, np.asarray(r.data).copy())
                        for r in ds.data(train=True)])
            ds.shuffle()
    finally:
        ds.close()
    return out


def test_worker_count_never_changes_the_sample_stream():
    items = _images(37)
    base = _stream(items, workers=0, epochs=2)
    for workers in (1, 3):
        got = _stream(items, workers=workers, epochs=2)
        for e, (eb, eg) in enumerate(zip(base, got)):
            assert [l for l, _ in eb] == [l for l, _ in eg], \
                f"order diverged at epoch {e} with {workers} workers"
            for (_, xb), (_, xg) in zip(eb, eg):
                assert np.array_equal(xb, xg), \
                    f"augmentation diverged at epoch {e} " \
                    f"with {workers} workers"


def test_epochs_and_seeds_do_change_augmentation():
    items = _images(16)
    (e0, e1) = _stream(items, workers=0, epochs=2)
    # shuffle() permutes order AND reseeds augmentation per chunk
    assert [l for l, _ in e0] != [l for l, _ in e1]
    other = _stream(items, workers=0, seed=99)[0]
    same = _stream(items, workers=0)[0]
    assert any(not np.array_equal(x, y)
               for (_, x), (_, y) in zip(same, other))


def test_fold_seed_distinct_across_epoch_and_chunk():
    seen = {fold_seed(1, e, c) for e in range(32) for c in range(32)}
    assert len(seen) == 32 * 32


def test_reseed_gives_each_chain_leaf_a_distinct_stream():
    a, b = BGRImgCropper(4, 4), BGRImgCropper(4, 4)
    chain = a >> b
    chain.reseed(123)
    assert a._rng.randint(1 << 30) != b._rng.randint(1 << 30)
    # deterministic: same seed, same draws
    chain.reseed(123)
    first = (a._rng.randint(1 << 30), b._rng.randint(1 << 30))
    chain.reseed(123)
    assert first == (a._rng.randint(1 << 30), b._rng.randint(1 << 30))


def test_pack_in_workers_identical_batches_to_driver_pack():
    items = _images(43, h=10, w=10)
    aug = BGRImgCropper(6, 6, seed=3)

    def batches(pack_in_workers, workers):
        ds = ShardedDataSet(items, augment=aug.clone_transformer(),
                            batcher=BGRImgToBatch(8),
                            pack_in_workers=pack_in_workers,
                            workers=workers, chunk=5, seed=3)
        try:
            return [(np.asarray(b.data).copy(),
                     np.asarray(b.labels).copy())
                    for b in ds.data(train=False)]
        finally:
            ds.close()

    ref = batches(False, 0)
    assert [d.shape[0] for d, _ in ref] == [8, 8, 8, 8, 8, 3]
    for pw, w in ((True, 0), (True, 2)):
        got = batches(pw, w)
        assert len(got) == len(ref)
        for (dr, lr), (dg, lg) in zip(ref, got):
            assert np.array_equal(dr, dg) and np.array_equal(lr, lg)


def test_from_seq_folder_counts_records_and_streams_images(tmp_path):
    from bigdl_tpu.dataset.seqfile import BGRImgToLocalSeqFile
    rng = np.random.RandomState(2)
    imgs = [LabeledImage(
        rng.randint(0, 256, (6, 5, 3)).astype(np.float32),
        float(i % 4 + 1)) for i in range(10)]
    d = tmp_path / "seq"
    d.mkdir()
    files = list(BGRImgToLocalSeqFile(4, str(d / "part")).apply(
        iter(imgs)))
    assert len(files) == 3                 # 4 + 4 + 2

    ds = ShardedDataSet.from_seq_folder(str(d), workers=0)
    try:
        assert ds.size() == 10             # records, not files
        out = list(ds.data(train=False))
        assert len(out) == 10
        # files are the shard/chunk unit; records come back in order
        assert [r.label for r in out] == [i.label for i in imgs]
        # decode really ran: shapes survive the byte round-trip
        assert out[0].data.shape == (6, 5, 3)
    finally:
        ds.close()


def test_pack_in_workers_needs_sized_batcher():
    with pytest.raises(ValueError, match="batch_size"):
        ShardedDataSet(_images(4), batcher=Lambda(lambda x: x),
                       pack_in_workers=True, workers=0)


def test_pack_in_workers_drop_last_drops_once_not_per_chunk():
    # drop_last must act on the STREAM tail (driver), never on each
    # worker chunk's tail — per-chunk dropping would lose 3 records of
    # every 5-record chunk here
    items = _images(43, h=10, w=10)

    def batches(pack_in_workers, workers):
        ds = ShardedDataSet(items,
                            batcher=BGRImgToBatch(8, drop_last=True),
                            pack_in_workers=pack_in_workers,
                            workers=workers, chunk=5)
        try:
            return [(np.asarray(b.data).copy(),
                     np.asarray(b.labels).copy())
                    for b in ds.data(train=False)]
        finally:
            ds.close()

    ref = batches(False, 0)
    assert [d.shape[0] for d, _ in ref] == [8] * 5    # 43 -> 5x8, 3 dropped
    for pw, w in ((True, 0), (True, 2)):
        got = batches(pw, w)
        assert [d.shape[0] for d, _ in got] == [8] * 5
        for (dr, lr), (dg, lg) in zip(ref, got):
            assert np.array_equal(dr, dg) and np.array_equal(lr, lg)


def test_pack_in_workers_rejects_dynamic_padding_batcher():
    # per-chunk max padding would hand the driver ragged blocks
    with pytest.raises(ValueError, match="fixed_length"):
        ShardedDataSet(_samples(8),
                       batcher=SampleToBatch(4, feature_padding=0.0),
                       pack_in_workers=True, workers=0)
    # fixed_length makes every block the same width: allowed
    ds = ShardedDataSet(
        [Sample(np.arange(n % 5 + 3, dtype=np.float32),
                np.float32(n % 3 + 1)) for n in range(12)],
        batcher=SampleToBatch(4, feature_padding=0.0, fixed_length=8),
        pack_in_workers=True, workers=0, chunk=5)
    try:
        out = list(ds.data(train=False))
    finally:
        ds.close()
    assert [b.size() for b in out] == [4, 4, 4]
    assert all(np.asarray(b.data).shape[1] == 8 for b in out)


# -- ingest_config knobs ------------------------------------------------------

def test_ingest_env_defaults_and_arg_precedence(monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_INGEST_DEPTH", "5")
    monkeypatch.setenv("BIGDL_TPU_INGEST_WORKERS", "7")
    monkeypatch.setenv("BIGDL_TPU_INGEST_CHUNK", "11")
    assert ingest_config.depth() == 5
    assert ingest_config.workers() == 7
    assert ingest_config.chunk() == 11
    # the API argument wins over the env
    assert ingest_config.depth(3) == 3
    assert ingest_config.workers(0) == 0
    assert ingest_config.chunk(2) == 2


def test_ingest_env_strict_parsing(monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_INGEST_DEPTH", "two")
    with pytest.raises(ValueError):
        ingest_config.depth()
    monkeypatch.setenv("BIGDL_TPU_INGEST_DEPTH", "1")
    with pytest.raises(ValueError):                 # can't double-buffer
        ingest_config.depth()
    monkeypatch.setenv("BIGDL_TPU_INGEST_DTYPE", "f64")
    with pytest.raises(ValueError):
        ingest_config.pack_dtype()
    with pytest.raises(ValueError):
        ingest_config.depth(1)
    with pytest.raises(ValueError):
        ingest_config.start_method("thread")


def test_ingest_dtype_spellings(monkeypatch):
    import ml_dtypes
    monkeypatch.setenv("BIGDL_TPU_INGEST_DTYPE", "bf16")
    assert ingest_config.pack_dtype() == np.dtype(ml_dtypes.bfloat16)
    monkeypatch.setenv("BIGDL_TPU_INGEST_DTYPE", "f32")
    assert ingest_config.pack_dtype() == np.dtype(np.float32)
    monkeypatch.delenv("BIGDL_TPU_INGEST_DTYPE")
    assert ingest_config.pack_dtype() is None


def test_prefetch_and_mt_read_the_env(monkeypatch):
    from bigdl_tpu.dataset.prefetch import PrefetchToDevice
    monkeypatch.setenv("BIGDL_TPU_INGEST_DEPTH", "4")
    monkeypatch.setenv("BIGDL_TPU_INGEST_WORKERS", "3")
    monkeypatch.setenv("BIGDL_TPU_INGEST_CHUNK", "9")
    pf = PrefetchToDevice()
    assert pf.depth == 4
    mt = MTTransformer(Lambda(lambda x: x))
    assert mt.workers == 3 and mt.chunk == 9


def test_mt_transformer_workers_zero_runs_in_process():
    mt = MTTransformer(Lambda(lambda x: x * 2), workers=0)
    assert list(mt(iter(range(10)))) == [x * 2 for x in range(10)]


# -- staging ring -------------------------------------------------------------

def _batches(n, bs=4, shape=(3, 6, 6), seed=0):
    rng = np.random.RandomState(seed)
    return [MiniBatch(rng.rand(bs, *shape).astype(np.float32),
                      (np.arange(bs) % 3 + 1).astype(np.float32))
            for _ in range(n)]


def test_staging_ring_roundtrip_and_device_residency():
    import jax
    src = _batches(5)
    out = list(StagingRing(depth=2).apply(iter(src)))
    assert len(out) == 5
    for s, o in zip(src, out):
        assert isinstance(o.data, jax.Array)
        np.testing.assert_array_equal(np.asarray(o.data), s.data)
        np.testing.assert_array_equal(np.asarray(o.labels), s.labels)


def test_staging_ring_bf16_cast_parity_with_f32_path():
    import jax.numpy as jnp
    src = _batches(3, seed=3)
    staged = list(StagingRing(depth=2, dtype="bf16").apply(
        iter(MiniBatch(b.data.copy(), b.labels.copy()) for b in src)))
    for s, o in zip(src, staged):
        assert o.data.dtype == jnp.bfloat16
        # parity: host-side cast == device-side cast of the f32 batch
        np.testing.assert_array_equal(
            np.asarray(o.data, np.float32),
            np.asarray(jnp.asarray(s.data).astype(jnp.bfloat16),
                       np.float32))
        # labels keep their dtype
        assert np.asarray(o.labels).dtype == np.float32


def test_staging_ring_short_trailing_batch_ok():
    src = _batches(3) + [MiniBatch(
        np.ones((2, 3, 6, 6), np.float32), np.ones(2, np.float32))]
    out = list(StagingRing(depth=2).apply(iter(src)))
    assert [b.size() for b in out] == [4, 4, 4, 2]


def test_staging_ring_oversize_batch_raises():
    src = [MiniBatch(np.ones((2, 3, 4, 4), np.float32),
                     np.ones(2, np.float32)),
           MiniBatch(np.ones((5, 3, 4, 4), np.float32),
                     np.ones(5, np.float32))]
    with pytest.raises(ValueError, match="slot capacity"):
        list(StagingRing(depth=2).apply(iter(src)))


def test_staging_ring_backpressure_bounds_upstream():
    import time
    pulled = [0]

    def src():
        for b in _batches(64):
            pulled[0] += 1
            yield b

    it = StagingRing(depth=2).apply(src())
    next(it)                      # consumer takes ONE batch, then stalls
    time.sleep(0.5)
    # bounded in flight: depth slots + depth ready + the two pipeline
    # threads' in-hand batches — nothing close to the 64 available
    assert pulled[0] <= 2 * 2 + 3, \
        f"slow consumer but upstream pulled {pulled[0]} batches"
    it.close()                    # abandon: threads must release


def test_staging_ring_upstream_error_propagates_typed():
    class Boom(RuntimeError):
        pass

    def src():
        yield _batches(1)[0]
        raise Boom("decode failed")

    it = StagingRing(depth=2).apply(src())
    with pytest.raises(Boom):
        list(it)


def test_staging_ring_stage_fault_site():
    FaultInjector.install(FaultInjector().add("ingest.stage"))
    with pytest.raises(RuntimeError, match="injected fault"):
        list(StagingRing(depth=2).apply(iter(_batches(3))))


# -- process pool: death + error propagation ----------------------------------

class _BadDecode(Transformer):
    """Top-level so spawn can pickle it into the worker process."""

    def apply(self, prev):
        for r in prev:
            raise KeyError("bad record")
        return iter(())


def test_pool_worker_exception_propagates_as_itself():
    ds = ShardedDataSet(_samples(8), decode=_BadDecode(), workers=1,
                        chunk=4)
    try:
        with pytest.raises(KeyError):
            list(ds.data(train=False))
    finally:
        ds.close()


def test_pool_worker_kill_raises_typed_ingest_worker_died(monkeypatch):
    # env-armed so the SPAWNED workers inherit and re-arm themselves
    monkeypatch.setenv("BIGDL_TPU_FAULTS", "ingest.worker.kill@2")
    FaultInjector.clear()               # parent re-arms lazily from env
    ds = ShardedDataSet(_samples(40), workers=2, chunk=5)
    try:
        with pytest.raises(IngestWorkerDied):
            list(ds.data(train=False))
    finally:
        ds.close()
        monkeypatch.delenv("BIGDL_TPU_FAULTS")
        FaultInjector.clear()


def test_pool_worker_raise_fault_site(monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_FAULTS", "ingest.worker@1")
    FaultInjector.clear()
    ds = ShardedDataSet(_samples(20), workers=1, chunk=5)
    try:
        with pytest.raises(RuntimeError, match="injected fault"):
            list(ds.data(train=False))
    finally:
        ds.close()
        monkeypatch.delenv("BIGDL_TPU_FAULTS")
        FaultInjector.clear()


def test_worker_death_never_hangs_interpreter_exit(tmp_path):
    # regression: with enough pickled chunks in flight to fill the call
    # queue's pipe, a killed worker left the executor's feeder thread
    # blocked writing to nobody, and the atexit join of the manager
    # thread hung interpreter EXIT after the typed IngestWorkerDied had
    # already surfaced.  The whole failure contract is "typed error,
    # then your process is yours again" — drill it end-to-end in a real
    # interpreter.
    import subprocess
    import sys
    import textwrap

    import bigdl_tpu

    script = tmp_path / "drill.py"
    script.write_text(textwrap.dedent("""
        import numpy as np
        from bigdl_tpu.dataset.sharded import ShardedDataSet
        from bigdl_tpu.dataset.transformer import Sample

        def main():
            rng = np.random.RandomState(0)
            samples = [Sample(rng.rand(784).astype(np.float32),
                              np.float32(1)) for _ in range(512)]
            ds = ShardedDataSet(samples, workers=2, chunk=16)
            list(ds.data(train=False))

        if __name__ == "__main__":
            main()
    """))
    env = dict(os.environ,
               BIGDL_TPU_FAULTS="ingest.worker.kill@2",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=str(
                   __import__("pathlib").Path(
                       bigdl_tpu.__file__).parents[1]))
    # a hang fails the test via TimeoutExpired instead of wedging CI
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "IngestWorkerDied" in proc.stderr


def test_pool_survives_close_and_reuse():
    pool = IngestPool(None, None, workers=1)
    jobs = [(i, fold_seed(1, 0, i), [i]) for i in range(4)]
    assert list(pool.run(iter(jobs))) == [0, 1, 2, 3]
    pool.close()
    assert list(pool.run(iter(jobs))) == [0, 1, 2, 3]   # rebuilt
    pool.close()


# -- trainer integration ------------------------------------------------------

def _lenet_opt(ds, iters=8):
    import bigdl_tpu.nn as nn
    from bigdl_tpu.models.lenet import LeNet5
    from bigdl_tpu.optim import LocalOptimizer, SGD, Trigger
    model = LeNet5(10).build(seed=1)
    opt = LocalOptimizer(model, nn.ClassNLLCriterion(), ds,
                         Trigger.max_iteration(iters))
    opt.set_optim_method(SGD(learning_rate=0.01))
    return opt


def test_trainer_over_staged_sharded_dataset_and_report_names_bound_stage(
        tmp_path):
    from bigdl_tpu.observability import ledger as run_ledger
    from bigdl_tpu.observability import set_run_dir
    from bigdl_tpu.observability.report import (build_report, load_ledger,
                                                render_report)
    run_dir = str(tmp_path / "run")
    set_run_dir(run_dir)
    try:
        ds = ShardedDataSet(_samples(48), batcher=SampleToBatch(8),
                            staging=True, workers=2, chunk=6)
        opt = _lenet_opt(ds, iters=10)
        opt.optimize()
        run_ledger.flush()
    finally:
        set_run_dir(None)
    records, bad = load_ledger(run_dir)
    assert bad == 0
    rep = build_report(records)
    ingest = rep["ingest"]
    assert ingest is not None
    # driver-side pack + ring stage/h2d always span; bound is one of them
    assert {"ingest.pack", "ingest.stage",
            "ingest.h2d"} <= set(ingest["stages"])
    assert ingest["bound_stage"] in ingest["stages"]
    for st in ingest["stages"].values():
        assert st["records"] > 0 and st["capacity_records_per_s"] > 0
    txt = render_report(rep)
    assert "ingest pipeline" in txt and ingest["bound_stage"] in txt


def test_trainer_kill_one_ingest_worker_ends_typed_not_hung(monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_FAULTS", "ingest.worker.kill@3")
    FaultInjector.clear()
    ds = ShardedDataSet(_samples(48), batcher=SampleToBatch(8),
                        workers=2, chunk=6)
    opt = _lenet_opt(ds, iters=12)
    try:
        with pytest.raises(IngestWorkerDied):
            opt.optimize()
    finally:
        ds.close()
        monkeypatch.delenv("BIGDL_TPU_FAULTS")
        FaultInjector.clear()


def test_trainer_epoch_rollover_reshuffles_sharded_stream():
    # 2 epochs through the trainer: the ShardedDataSet's finite epoch
    # stream must roll over exactly at ds.size() records
    ds = ShardedDataSet(_samples(32), batcher=SampleToBatch(8),
                        workers=0, chunk=8)
    opt = _lenet_opt(ds, iters=8)         # 4 batches/epoch -> 2 epochs
    opt.optimize()
    assert opt.state["epoch"] == 3        # 2 completed rollovers


# -- bench smoke --------------------------------------------------------------

def test_bench_ingest_single_process_smoke(tmp_path, capsys):
    from bigdl_tpu.cli import main as cli_main
    out_path = str(tmp_path / "bench.json")
    rc = cli_main(["bench-ingest", "--smoke", "--workers-list", "0",
                   "--records", "24", "--batch-size", "8", "--chunk", "6",
                   "--out", out_path,
                   "--run-dir", str(tmp_path / "ledger")])
    assert rc == 0
    with open(out_path) as f:
        art = json.load(f)
    assert art["metric"] == "ingest_images_per_sec"
    assert art["worker_scaling_imgs_per_sec"]["0"] > 0
    stages = art["stage_attribution"]
    assert {"ingest.decode", "ingest.augment", "ingest.pack"} <= \
        set(stages)
    assert art["bound_stage"] in stages
