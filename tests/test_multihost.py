"""True multi-process distributed training (the analogue of a multi-host
TPU pod, which the single-process 8-device conftest mesh cannot cover):
two OS processes, each with 2 virtual CPU devices and its own half of
the data, train through DistriOptimizer over one global mesh with gloo
collectives.  All workers must converge to IDENTICAL weights — any
break in the cross-process batch assembly
(``make_array_from_process_local_data``) or the collective layout shows
up as a checksum mismatch or a hang (timeout).
"""

import os
import pytest
import socket
import subprocess
import sys

pytestmark = pytest.mark.slow


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _run_workers(extra_args):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    worker = os.path.join(repo, "tests", "multihost_worker.py")
    port = _free_port()
    env = dict(os.environ,
               PYTHONPATH=repo + os.pathsep + os.environ.get("PYTHONPATH",
                                                             ""))
    # the worker forces the cpu platform itself (config.update); scrub
    # env that could steer backend selection before that runs
    env.pop("XLA_FLAGS", None)

    procs = [subprocess.Popen(
        [sys.executable, worker, "--proc", str(i), "--nproc", "2",
         "--port", str(port)] + extra_args,
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True) for i in range(2)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=400)
            outs.append(out)
    finally:
        for p in procs:       # a gloo hang must not orphan workers
            if p.poll() is None:
                p.kill()
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"worker failed:\n{out[-3000:]}"

    sums = {}
    for out in outs:
        for line in out.splitlines():
            if line.startswith("WORKER"):
                parts = line.split()
                wid, checksum = parts[1], parts[3]
                sums[wid] = checksum          # hex: exact comparison
                sums[wid + "_epoch"] = parts[4].split("=")[1]
    assert {"0", "1"} <= set(sums), f"missing worker output: {outs}"
    # all-gathered weights must be bitwise-identical across processes
    assert sums["0"] == sums["1"]
    sums["_outs"] = outs
    return sums


def test_two_process_distri_training_agrees(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    sums = _run_workers(["--ckpt", ckpt])

    # cross-process Metrics (optim/Metrics.scala parity): both processes
    # saw a 2-node breakdown and agree on the aggregated mean
    metrics = {}
    for out in sums["_outs"]:
        for line in out.splitlines():
            if line.startswith("METRICS"):
                parts = line.split()
                metrics[parts[1]] = (parts[2], parts[3])
    assert metrics["0"][0] == "nodes=2", metrics
    assert metrics["0"] == metrics["1"], metrics

    # exactly one process wrote the shared File-format snapshot, and it
    # reassembles the full (all-gathered) weights
    snaps = sorted(os.listdir(ckpt))
    assert any(n.startswith("model.") for n in snaps), snaps
    from bigdl_tpu.utils.file import File
    snap = File.load(os.path.join(ckpt, next(
        n for n in snaps if n.startswith("model."))))
    assert "params" in snap and "model_state" in snap


def test_two_process_metrics_gathered_and_mismatch():
    """Metrics.gathered()/summary(across_processes=True) over a REAL
    2-process mesh (optim/Metrics.scala three-scope parity), plus the
    mismatched-name-set failure mode: a per-process-unique metric name
    must raise a ValueError on every process — the digest pre-check in
    ``gathered()`` — rather than hanging the pod inside a diverged
    variable-shape allgather."""
    sums = _run_workers(["--metrics-selftest"])
    selftests = [line for out in sums["_outs"]
                 for line in out.splitlines()
                 if line.startswith("SELFTEST")]
    assert sorted(s.split()[1] for s in selftests) == ["0", "1"], selftests
    assert all("nodes=2" in s for s in selftests), selftests


def test_two_process_sharded_checkpoint_resume(tmp_path):
    """Kill-and-resume across processes: run 6 iterations with per-step
    orbax snapshots, then start FRESH processes that auto-resume and
    finish to 12.  The resumed fleet must land on exactly the weights an
    uninterrupted 12-iteration fleet produces."""
    sharded = str(tmp_path / "sharded")
    # 10 of 8-iters/epoch = interrupted 2 steps INTO EPOCH 2, past a
    # shuffle boundary: resume must replay epoch 1's shuffle too
    _run_workers(["--iters", "10", "--sharded", sharded])
    resumed = _run_workers(["--iters", "20", "--sharded", sharded])
    uninterrupted = _run_workers(["--iters", "20"])
    assert resumed["0"] == uninterrupted["0"]


def test_two_process_seqfile_ingest_training(tmp_path):
    """The documented pod ingest recipe end to end: record files on a
    shared filesystem, each process reading only its host_shard_paths
    slice, decode + batch + train over the global mesh."""
    import numpy as np

    from bigdl_tpu.dataset.image import LabeledImage
    from bigdl_tpu.dataset.seqfile import BGRImgToLocalSeqFile

    rs = np.random.RandomState(0)
    d = tmp_path / "records"
    d.mkdir()
    imgs = [LabeledImage(rs.randint(0, 256, (8, 8, 3)).astype(np.float32),
                         float(i % 2 + 1)) for i in range(64)]
    files = list(BGRImgToLocalSeqFile(16, str(d / "part")).apply(iter(imgs)))
    assert len(files) == 4          # 2 files per host after round-robin

    sums = _run_workers(["--iters", "6", "--seqdir", str(d)])
    # 64 global records / 16 per step = 4 steps/epoch: 6 iters must end
    # in epoch 2 — file-counting size() regressions roll epochs every
    # step and show up here as a large epoch number
    assert sums["0_epoch"] == "2", sums
