"""Deterministic elastic-training chaos drill — ``python -m
bigdl_tpu.cli train-drill``.

The serving runtime proves its failure isolation with ``serve-drill``;
this is the *training* analogue, and the headline proof of the elastic
membership layer (``resilience/elastic.py``): a fleet of N **real OS
processes** on one box — each a simulated TPU host owning
``--devices-per-host`` virtual CPU devices and running the full
``DistriOptimizer`` loop — coordinates through the file-backed
:class:`ElasticCoordinator`, and the drill:

1. **bootstraps** the fleet: N hosts heartbeat, the leader commits
   generation 1, everyone trains;
2. **kills one host mid-epoch** (SIGKILL — no goodbye): the survivors
   detect the lapsed lease, two-phase-commit generation 2, rebuild the
   ``(data, fsdp, tp)`` mesh at the smaller world, reshard the
   generation's pinned committed checkpoint onto it, replay the dataset
   cursor and continue;
3. **re-admits the host**: a fresh process with the same id requests a
   join, generation 3 grows the mesh back, every member (survivors
   included) reshards the same committed snapshot and the grown fleet
   finishes the run.

Simulated collectives: each host computes the full global step
deterministically over the global batch (the union of all members' row
shards), which is numerically *identical* to what real cross-host
collectives produce — every host ends each step with the same weights,
so membership, generation and reshape machinery are exercised for real
while the drill stays runnable with no gloo/ICI transport at all.  This
is also what revives the multihost slow tier on CPU-only containers
(``tests/test_elastic.py``).

Asserted (exit 0 iff all hold):

* every surviving/rejoined host process exits 0;
* all hosts' final weights agree (same committed restore step + same
  replayed steps ⇒ identical trajectories);
* the final evaluation loss matches an uninterrupted same-seed,
  fixed-fleet run within the declared ``--loss-tol``;
* generations committed ≥ 3 (bootstrap, shrink, grow) and the rejoined
  host is a member of the final one;
* the ledger carries the full transition trail (``elastic.lease_lost``,
  ``elastic.join``, ``elastic.generation``, ``elastic.reshape``,
  ``elastic.restore``, ``elastic.resume``, ``watchdog.paused``);
* zero lost or double-counted training records: every surviving host's
  step records cover step 0..N-1 exactly, each consuming exactly the
  global batch — each record trained exactly once per epoch in the
  surviving timeline, across both transitions.

``--smoke`` is the fast CI preset (2 hosts, 1 device each), wired into
``make-dist.sh`` beside the lint gate.  The per-step throttle
(``--step-delay-ms``) exists only to give wall-clock room for lease
expiry and process spawn between membership events; it never touches
the numerics.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time
from typing import Dict, List, Optional

FEATURES = 4
CLASSES = 2
DATA_SEED = 0
MODEL_SEED = 7
OPT_SEED = 3


def _expect(cond: bool, what: str, failures: List[str]) -> None:
    tag = "ok" if cond else "FAIL"
    print(f"  [{tag}] {what}")
    if not cond:
        failures.append(what)


def _host_name(i: int) -> str:
    return f"h{i}"


def _corpus(records: int):
    import numpy as np

    from bigdl_tpu.dataset.transformer import Sample
    rs = np.random.RandomState(DATA_SEED)
    x = rs.randn(records, FEATURES).astype(np.float32)
    y = (((x[:, 0] * x[:, 1]) > 0).astype(np.float32)) + 1.0
    return [Sample(x[i], y[i]) for i in range(records)]


def _model():
    import bigdl_tpu.nn as nn
    m = nn.Sequential()
    m.add(nn.Linear(FEATURES, 16))
    m.add(nn.Tanh())
    m.add(nn.Linear(16, CLASSES))
    m.add(nn.LogSoftMax())
    m.build(seed=MODEL_SEED)
    return m


def _dataset(args, throttle_s: float):
    """The drill corpus through :class:`ShardedDataSet` (workers=0 =
    in-process): the deterministic (seed, shuffle-count) permutation and
    ``reset_shuffle`` rewind are exactly what the elastic cursor replay
    leans on.  The throttle sleeps per record on the augment seam —
    timing only, identical records."""
    from bigdl_tpu.dataset.sharded import ShardedDataSet
    from bigdl_tpu.dataset.transformer import SampleToBatch
    augment = _Throttle(throttle_s / max(args.batch, 1)) \
        if throttle_s > 0 else None
    return ShardedDataSet(_corpus(args.records),
                          augment=augment,
                          batcher=SampleToBatch(args.batch),
                          workers=0, seed=11)


class _Throttle:
    """Per-record sleep transformer (timing lever, numerics-neutral).
    Duck-typed against the Transformer seam so this module's top level
    stays jax-free for ``--help``."""

    def __init__(self, delay_s: float):
        self.delay_s = delay_s

    def apply(self, prev):
        for rec in prev:
            time.sleep(self.delay_s)
            yield rec

    def __call__(self, prev):
        return self.apply(iter(prev))

    def clone_transformer(self):
        return _Throttle(self.delay_s)

    def reseed(self, seed: int) -> None:
        pass                       # stateless: nothing to reseed

    def and_then(self, other):
        from bigdl_tpu.dataset.transformer import ChainedTransformer
        return ChainedTransformer(self, other)


def _eval_loss(model, records) -> float:
    """Deterministic full-corpus NLL of the final weights — the drill's
    loss-curve-continuity figure (a pure function of the weights, so it
    compares across differently-interrupted runs)."""
    import numpy as np

    import bigdl_tpu.nn as nn
    x = np.stack([np.asarray(s.feature) for s in records])
    y = np.asarray([float(s.label) for s in records])
    out = model.forward(x)
    return float(nn.ClassNLLCriterion().apply(out, y))


def _build_optimizer(args, model, ds, mesh):
    import bigdl_tpu.nn as nn
    from bigdl_tpu.optim import DistriOptimizer, SGD, Trigger
    opt = DistriOptimizer(model, nn.ClassNLLCriterion(), ds,
                          end_when=Trigger.max_iteration(args.iters),
                          mesh=mesh, compress=None,
                          sharding=args.sharding)
    opt.set_optim_method(SGD(learning_rate=0.3, momentum=0.9,
                             dampening=0.0))
    opt.set_seed(OPT_SEED)
    return opt


# -- the simulated-host process (spawned by the driver) -----------------------

def _host_main(args) -> int:
    import jax
    jax.config.update("jax_platforms", "cpu")
    from bigdl_tpu.compat import force_cpu_devices
    force_cpu_devices(args.hosts * args.devices_per_host)

    from bigdl_tpu.observability import ledger as run_ledger
    from bigdl_tpu.optim import Trigger
    from bigdl_tpu.parallel import mesh as mesh_mod
    from bigdl_tpu.resilience.elastic import ElasticCoordinator
    from bigdl_tpu.utils.file import File

    coord = ElasticCoordinator(
        os.path.join(args.dir, "coord"), args.host_id,
        lease_s=args.lease_ms / 1e3, poll_s=0.02,
        devices_per_host=args.devices_per_host,
        bootstrap_world=args.hosts)
    ds = _dataset(args, args.step_delay_ms / 1e3)
    model = _model()
    opt = _build_optimizer(
        args, model, ds,
        mesh_mod.build_mesh((args.devices_per_host, 1, 1)))
    opt.set_sharded_checkpoint(os.path.join(args.dir, "ckpt"),
                               Trigger.several_iteration(args.ckpt_every))
    opt.set_elastic(coord)

    if args.standby_gen:
        # warm standby (the re-admission half of the drill): imports and
        # construction happened ABOVE, but the join request waits until
        # the fleet has committed generation --standby-gen — so the
        # heavy process spawn never races the shrink protocol
        gen_path = os.path.join(args.dir, "coord", "generation.json")
        while True:
            try:
                with open(gen_path) as f:
                    if int(json.load(f).get("gen", 0)) >= args.standby_gen:
                        break
            except (OSError, json.JSONDecodeError, ValueError):
                pass
            time.sleep(0.05)

    opt.optimize()

    loss = _eval_loss(model, _corpus(args.records))
    File.save({"params": model.params},
              os.path.join(args.dir, f"final-{args.host_id}.bin"), True)
    run_ledger.flush()
    print(f"DRILLHOST {args.host_id} OK pid={os.getpid()} "
          f"loss={loss:.6f} neval={opt.state['neval']} "
          f"epoch={opt.state['epoch']} gen={coord.generation().gen}",
          flush=True)
    return 0


# -- the driver ---------------------------------------------------------------

def _spawn_host(args, host_id: str, run_dir: str, standby_gen: int = 0):
    cmd = [sys.executable, "-m", "bigdl_tpu.cli", "train-drill",
           "--host-id", host_id, "--dir", args.dir,
           "--hosts", str(args.hosts),
           "--devices-per-host", str(args.devices_per_host),
           "--batch", str(args.batch), "--records", str(args.records),
           "--iters", str(args.iters),
           "--step-delay-ms", str(args.step_delay_ms),
           "--lease-ms", str(args.lease_ms),
           "--ckpt-every", str(args.ckpt_every),
           "--sharding", args.sharding]
    if standby_gen:
        cmd += ["--standby-gen", str(standby_gen)]
    env = dict(os.environ, BIGDL_TPU_RUN_DIR=run_dir,
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   p for p in [os.getcwd()] + sys.path if p))
    env.pop("XLA_FLAGS", None)
    env.pop("BIGDL_TPU_FAULTS", None)
    return subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)


def _lease_step(coord_dir: str, host: str) -> int:
    try:
        with open(os.path.join(coord_dir, "hosts", f"{host}.json")) as f:
            return int(json.load(f).get("step", 0))
    except (OSError, json.JSONDecodeError, ValueError):
        return 0


def _committed_gen(coord_dir: str) -> int:
    try:
        with open(os.path.join(coord_dir, "generation.json")) as f:
            return int(json.load(f).get("gen", 0))
    except (OSError, json.JSONDecodeError, ValueError):
        return 0


def _wait_for(pred, what: str, timeout_s: float) -> bool:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.05)
    print(f"  timeout waiting for: {what}")
    return False


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        "train-drill",
        description="Deterministic elastic-training chaos drill "
                    "(docs/distributed.md#elasticity)")
    p.add_argument("--hosts", type=int, default=3)
    p.add_argument("--devices-per-host", type=int, default=2)
    p.add_argument("--batch", type=int, default=24,
                   help="GLOBAL batch — fixed across membership changes "
                        "(must divide by every world's dp size)")
    p.add_argument("--records", type=int, default=96)
    p.add_argument("--iters", type=int, default=40)
    p.add_argument("--kill-at", type=int, default=6,
                   help="SIGKILL the victim once it has trained this "
                        "many steps (mid-epoch by construction)")
    p.add_argument("--step-delay-ms", type=float, default=150.0,
                   help="per-step throttle: wall-clock room for lease "
                        "expiry + respawn between membership events "
                        "(numerics-neutral)")
    p.add_argument("--lease-ms", type=float, default=800.0)
    p.add_argument("--ckpt-every", type=int, default=2,
                   help="snapshot cadence in steps: >1 makes the shrink "
                        "genuinely roll back and REPLAY steps from the "
                        "committed snapshot")
    p.add_argument("--sharding", choices=("flat", "spec"), default="spec")
    p.add_argument("--loss-tol", type=float, default=0.05,
                   help="declared tolerance on |elastic - uninterrupted| "
                        "final evaluation loss")
    p.add_argument("--dir", default=None,
                   help="drill working directory (default: a temp dir, "
                        "removed on success)")
    p.add_argument("--run-dir", default=None,
                   help="run-ledger directory (default: <dir>/ledger)")
    p.add_argument("--smoke", action="store_true",
                   help="fast CI preset: 2 hosts x 1 device, fewer steps")
    p.add_argument("--host-id", default=None, help=argparse.SUPPRESS)
    p.add_argument("--standby-gen", type=int, default=0,
                   help=argparse.SUPPRESS)
    args = p.parse_args(argv)

    if args.smoke:
        args.hosts, args.devices_per_host = 2, 1
        args.batch, args.records, args.iters = 8, 32, 30
        args.kill_at = 4
        args.step_delay_ms = 120.0
        args.lease_ms = 600.0

    if args.host_id:
        return _host_main(args)

    own_dir = args.dir is None
    if own_dir:
        args.dir = tempfile.mkdtemp(prefix="bigdl-train-drill-")
    os.makedirs(args.dir, exist_ok=True)
    run_dir = args.run_dir or os.path.join(args.dir, "ledger")
    coord_dir = os.path.join(args.dir, "coord")
    # the driver's own in-process reference run stays OUT of the census
    from bigdl_tpu.observability import ledger as run_ledger
    run_ledger.set_run_dir(None)
    os.environ.pop("BIGDL_TPU_RUN_DIR", None)

    failures: List[str] = []
    n_dev = args.hosts * args.devices_per_host
    victim = _host_name(args.hosts - 1)
    print(f"train-drill: {args.hosts} hosts x {args.devices_per_host} "
          f"device(s), sharding={args.sharding}, {args.iters} steps, "
          f"batch {args.batch} over {args.records} records")
    print(f"  dir: {args.dir}")

    # -- phase 0: the uninterrupted same-seed reference run (in-process)
    print("phase 0: uninterrupted reference run")
    import jax
    jax.config.update("jax_platforms", "cpu")
    from bigdl_tpu.compat import force_cpu_devices
    force_cpu_devices(n_dev)
    from bigdl_tpu.parallel import mesh as mesh_mod
    ref_model = _model()
    ref_args = argparse.Namespace(**vars(args))
    ref_args.step_delay_ms = 0.0
    ref_opt = _build_optimizer(ref_args, ref_model,
                               _dataset(ref_args, 0.0),
                               mesh_mod.build_mesh((n_dev, 1, 1)))
    ref_opt.optimize()
    ref_loss = _eval_loss(ref_model, _corpus(args.records))
    print(f"  reference final eval loss: {ref_loss:.6f}")

    # -- phase 1: bootstrap the fleet
    print(f"phase 1: bootstrap {args.hosts} simulated host processes")
    procs: Dict[str, subprocess.Popen] = {}
    outs: Dict[str, str] = {}
    rejoin: Optional[subprocess.Popen] = None
    try:
        for i in range(args.hosts):
            procs[_host_name(i)] = _spawn_host(args, _host_name(i),
                                               run_dir)
        # warm standby for the re-admission (imports now, joins later)
        rejoin = _spawn_host(args, victim, run_dir, standby_gen=2)
        _expect(_wait_for(lambda: _committed_gen(coord_dir) >= 1,
                          "generation 1 (bootstrap)", 120),
                "fleet bootstrapped: generation 1 committed", failures)

        # -- phase 2: SIGKILL the victim mid-epoch
        print(f"phase 2: kill {victim} mid-epoch (step >= {args.kill_at})")
        ok = _wait_for(
            lambda: _lease_step(coord_dir, victim) >= args.kill_at,
            f"{victim} reaching step {args.kill_at}", 120)
        _expect(ok, f"victim reached step {args.kill_at}", failures)
        procs[victim].send_signal(signal.SIGKILL)
        procs[victim].wait(timeout=30)
        _expect(_wait_for(lambda: _committed_gen(coord_dir) >= 2,
                          "generation 2 (shrink)", 120),
                "survivors committed generation 2 after the lease "
                "lapsed", failures)

        # -- phase 3: the standby host joins; fleet grows back
        print(f"phase 3: re-admit {victim} (standby joins at gen 2)")
        _expect(_wait_for(lambda: _committed_gen(coord_dir) >= 3,
                          "generation 3 (grow)", 120),
                "grown fleet committed generation 3", failures)

        # -- phase 4: everyone runs to completion
        print("phase 4: fleet completes the run")
        finals = {h: procs[h] for h in procs if h != victim}
        finals[victim] = rejoin
        for h, proc in finals.items():
            try:
                outs[h], _ = proc.communicate(timeout=300)
            except subprocess.TimeoutExpired:
                proc.kill()
                outs[h], _ = proc.communicate()
                _expect(False, f"host {h} finished in time", failures)
        for h, proc in finals.items():
            _expect(proc.returncode == 0,
                    f"host {h} exited 0",
                    failures)
            if proc.returncode != 0:
                print(f"---- {h} output tail ----\n{outs[h][-2500:]}")
    finally:
        for proc in list(procs.values()) + ([rejoin] if rejoin else []):
            if proc.poll() is None:
                proc.kill()

    hosts_line: Dict[str, dict] = {}
    for h, out in outs.items():
        for line in out.splitlines():
            if line.startswith(f"DRILLHOST {h} OK"):
                kv = dict(tok.split("=", 1) for tok in line.split()[3:])
                hosts_line[h] = kv

    # -- phase 5: convergence + loss continuity
    print("phase 5: convergence checks")
    import numpy as np
    from bigdl_tpu.utils.file import File

    def flat_params(host):
        snap = File.load(os.path.join(args.dir, f"final-{host}.bin"))
        return np.concatenate(
            [np.ravel(np.asarray(l))
             for l in jax.tree_util.tree_leaves(snap["params"])])

    all_done = sorted(hosts_line)
    _expect(len(all_done) == args.hosts,
            f"all {args.hosts} hosts reported a final state", failures)
    if len(all_done) >= 2:
        base = flat_params(all_done[0])
        agree = all(np.allclose(flat_params(h), base, atol=1e-6)
                    for h in all_done[1:])
        _expect(agree, "every host's final weights agree (survivors AND "
                "the rejoined host)", failures)
    if hosts_line:
        loss = float(hosts_line[sorted(hosts_line)[0]]["loss"])
        _expect(abs(loss - ref_loss) <= args.loss_tol,
                f"final eval loss {loss:.6f} within {args.loss_tol} of "
                f"the uninterrupted run's {ref_loss:.6f}", failures)

    # -- phase 6: the ledger trail + record accounting
    print("phase 6: ledger trail + record accounting")
    from bigdl_tpu.observability.report import build_report, load_ledger
    records, _bad = load_ledger(run_dir)
    events = [r for r in records if r.get("type") == "event"]
    kinds: Dict[str, int] = {}
    for e in events:
        k = str(e.get("kind", ""))
        kinds[k] = kinds.get(k, 0) + 1
    _expect(kinds.get("elastic.lease_lost", 0) >= 1,
            "elastic.lease_lost on the ledger", failures)
    _expect(kinds.get("elastic.join", 0) >= 1,
            "elastic.join on the ledger", failures)
    _expect(kinds.get("elastic.generation", 0) >= 3,
            "three elastic.generation commits (bootstrap, shrink, grow)",
            failures)
    _expect(kinds.get("elastic.reshape", 0) >= 2,
            "elastic.reshape for shrink AND grow", failures)
    _expect(kinds.get("elastic.restore", 0) >= 2,
            "elastic.restore resharded-restore events", failures)
    _expect(kinds.get("watchdog.paused", 0) >= 1,
            "watchdog paused across the reshape windows", failures)

    pid_of = {h: int(kv["pid"]) for h, kv in hosts_line.items()}
    # the LEADER's timeline is the canonical one: it writes the
    # snapshots, so its restore step never jumps it forward — its step
    # records must tile 0..N-1 exactly.  (A non-leader lagging a step
    # behind a commit legitimately fast-forwards; its correctness is the
    # weight-equality check above.)
    leader = _host_name(0)
    steps_ok = leader in pid_of
    if steps_ok:
        recs = [r for r in records if r.get("type") == "step"
                and r["_pid"] == pid_of[leader]]
        covered = {int(r["step"]) for r in recs}
        steps_ok = covered == set(range(args.iters)) and \
            all(int(r.get("records", 0)) == args.batch for r in recs)
    _expect(steps_ok,
            f"zero lost/double-counted records: the leader's timeline "
            f"covers steps 0..{args.iters - 1} exactly, {args.batch} "
            "records each (every record exactly once per epoch, across "
            "both transitions)", failures)
    # replay accounting: every resume's replayed_steps must equal the
    # rollback its own reshape declared (aborted step - restored step)
    replay_ok = True
    reshapes = {}
    for e in events:
        if e.get("kind") == "elastic.reshape":
            reshapes[(e["_pid"], int(e.get("gen", -1)))] = e
    for e in events:
        if e.get("kind") != "elastic.resume":
            continue
        rs = reshapes.get((e["_pid"], int(e.get("gen", -1))))
        if rs is not None:
            want = max(0, int(rs.get("aborted_step", 0)) -
                       int(e.get("step", 0)))
            if int(e.get("replayed_steps", -1)) != want:
                replay_ok = False
    replayed = sum(int(e.get("replayed_steps", 0)) for e in events
                   if e.get("kind") == "elastic.resume")
    _expect(replay_ok,
            f"rollback replay accounting consistent ({replayed} step(s) "
            "replayed from committed snapshots)", failures)
    joiner_steps = [r for r in records if r.get("type") == "step"
                    and r["_pid"] == pid_of.get(victim, -1)]
    _expect(len(joiner_steps) >= 1,
            f"the rejoined {victim} trained in the grown fleet "
            f"({len(joiner_steps)} steps)", failures)

    rep = build_report(records)
    el = rep.get("elastic") or {}
    _expect(el.get("generations", 0) >= 3 and
            el.get("hosts_lost", 0) >= 1 and
            el.get("hosts_joined", 0) >= 1,
            "run-report elasticity census agrees (generations="
            f"{el.get('generations')}, lost={el.get('hosts_lost')}, "
            f"joined={el.get('hosts_joined')}, reshapes="
            f"{el.get('reshapes')}, steps_replayed="
            f"{el.get('steps_replayed')})", failures)

    print("\n-- drill summary --")
    for k in sorted(k for k in kinds if k.startswith("elastic.")
                    or k == "watchdog.paused"):
        print(f"  {k:<24} {kinds[k]}")
    print(f"  ledger: {run_dir} — render with "
          f"`python -m bigdl_tpu.cli run-report {run_dir}`")
    if failures:
        print(f"\ntrain-drill: {len(failures)} check(s) FAILED "
              f"(artifacts kept under {args.dir})")
        return 1
    print("\ntrain-drill: all checks passed")
    if own_dir:
        shutil.rmtree(args.dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
