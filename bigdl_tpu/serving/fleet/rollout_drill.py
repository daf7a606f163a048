"""Live rollout chaos drill — ``python -m bigdl_tpu.cli rollout-drill``.

The r18 headline proof, in two phases (exit 0 iff BOTH hold):

**Phase A — SIGKILL mid-shift.**  A two-host fleet (h0 leader + warm
standby h1) serves tenant ``m`` at v1 under continuous client traffic
via the file bus.  The driver publishes v2 — bit-identical weights, a
"refresh" rollout, so every output is bit-equal to the single-server
reference REGARDLESS of which version answered and the convergence
claim is assertable through the kill.  h0's
:class:`~.rollout.RolloutController` discovers it, shadows + canaries
(bit gate) + starts the stride-weight traffic shift; the instant the
``shift`` transition is durable the driver SIGKILLs h0 — controller
and serving host die together, mid-shift, inboxes non-empty.  h1's
lease watch commits generation 2, salvages and re-drives h0's
unresponded requests, resolves tenant ``m``'s spec through
:func:`~.rollout.resolve_recovery` (pre-promote → the incumbent v1
wins) and — as the new leader — runs controller recovery, writing the
durable rollback.  Asserted: zero lost requests, every response ok and
bit-equal to the winner's single-``FleetServer`` reference, exactly
one committed version in the resolved state AND in generation 2's
``versions`` payload, no sampled instant with no serving version, and
the full ``rollout.*`` ledger trail across both hosts' run dirs
(run-report's ``rollout`` census agrees).

**Phase B — divergent canary auto-rollback** (in-process).  A
deliberately-divergent v2 is published; the canary gate (declared
``RUNG_BUDGETS`` rung) must fail it and the controller must roll back
with the incumbent untouched — shadow deregistered, route cleared,
state at v1 — and the incumbent's SLO hit rate no worse than a
no-rollout baseline run of the same traffic.

Results (plus the zero-downtime gate) land in
``BENCH_rollout_r18.json``.  ``--smoke`` is the fast CI preset wired
into ``make-dist.sh``.
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
import threading
import time
from typing import Dict, List, Optional

from bigdl_tpu.serving.fleet.fleet_drill import _expect, _wait_for

FEATURES = 6
CLASSES = 3
TENANT = "m"


def _row(seq: int) -> List[float]:
    return [((seq * 7 + j * 3) % 11) / 11.0 for j in range(FEATURES)]


def _build_model(seed: int):
    import jax

    import bigdl_tpu.nn as nn
    m = nn.Sequential()
    m.add(nn.Linear(FEATURES, CLASSES))
    m.add(nn.LogSoftMax())
    m.build(jax.random.PRNGKey(seed))
    return m


def _build_spec(pub_dir: str, version: int, name: str,
                forward_delay_s: float = 0.0):
    """The drill's TenantSpec for ``version``: weights RESTORED from
    the publication dir (the real checkpoint path, not a seed replay).
    ``spec.version`` is stamped so the committed placement payload
    carries cross-host version agreement."""
    from bigdl_tpu.api import DLClassifier
    from bigdl_tpu.serving.fleet import TenantSpec
    from bigdl_tpu.utils.checkpoint import restore_sharded

    class _SlowClassifier(DLClassifier):
        def _run(self, feats):
            if forward_delay_s > 0:
                time.sleep(forward_delay_s)
            return super()._run(feats)

    m = _build_model(0)
    m.params = restore_sharded(pub_dir, None, step=int(version))
    clf = _SlowClassifier(m, batch_shape=(4, FEATURES))
    spec = TenantSpec(name, classifier=clf, weight=2, min_workers=1,
                      queue_capacity=512, max_delay_s=0.002)
    spec.version = int(version)
    return spec


def _rollout_dirs(root: str):
    return os.path.join(root, "pub"), os.path.join(root, "rollout")


# -- the simulated-host process (spawned by the driver) -----------------------

def _host_main(args) -> int:
    import jax
    jax.config.update("jax_platforms", "cpu")

    from bigdl_tpu.observability import ledger as run_ledger
    from bigdl_tpu.serving.fleet.cluster import HostAgent
    from bigdl_tpu.serving.fleet.rollout import (RolloutConfig,
                                                 RolloutController,
                                                 read_state,
                                                 resolve_recovery)
    from bigdl_tpu.utils.checkpoint import discover_versions

    pub_dir, state_dir = _rollout_dirs(args.dir)
    delay = args.forward_delay_ms / 1e3

    def make_spec(version, name):
        return _build_spec(pub_dir, version, name, delay)

    def catalog():
        # which version must ``m`` serve RIGHT NOW?  Exactly what the
        # last durable rollout transition resolves to — a host that
        # (re)registers the tenant after the controller died converges
        # on the same winner recovery converges on, never split weights
        res = resolve_recovery(read_state(state_dir, TENANT))
        v = res["version"]
        if v is None:
            vs = discover_versions(pub_dir)
            v = vs[-1] if vs else 1
        return make_spec(int(v), TENANT)

    agent = HostAgent(args.dir, args.host_id, {TENANT: catalog},
                      lease_s=args.lease_ms / 1e3,
                      bootstrap_world=args.hosts, max_workers=2)
    gen = agent.start()
    print(f"DRILLHOST {args.host_id} UP pid={os.getpid()} gen={gen.gen} "
          f"tenants={','.join(sorted(agent.local_tenants())) or '-'}",
          flush=True)
    cfg = RolloutConfig(gate="bit", canary_requests=args.canary,
                        canary_timeout_s=60.0,
                        shift_steps=(0.25, 0.5, 0.75, 1.0),
                        hold_s=args.hold_ms / 1e3, timeout_s=180.0,
                        drain_timeout_s=15.0)
    ctl: Optional[RolloutController] = None
    stop_file = os.path.join(args.dir, "stop")
    while not os.path.exists(stop_file) and not agent.fenced:
        if ctl is None and agent.fleet is not None \
                and agent.coord.is_writer():
            # the LEADER runs the controller; a successor's first act
            # (inside run()) is recover() — complete or roll back
            ctl = RolloutController(agent.fleet, TENANT, pub_dir,
                                    state_dir, make_spec,
                                    config=cfg).start(poll_s=0.1)
            print(f"DRILLHOST {args.host_id} CONTROLLER", flush=True)
        time.sleep(0.05)
    if ctl is not None:
        ctl.stop(timeout=60.0)
    agent.stop(leave=True)
    run_ledger.flush()
    print(f"DRILLHOST {args.host_id} OK pid={os.getpid()} "
          f"gen={agent.coord.generation().gen} fenced={agent.fenced}",
          flush=True)
    return 0


def _spawn_host(args, host_id: str, run_dir: str) -> subprocess.Popen:
    cmd = [sys.executable, "-m", "bigdl_tpu.cli", "rollout-drill",
           "--host-id", host_id, "--dir", args.dir,
           "--hosts", str(args.hosts),
           "--canary", str(args.canary),
           "--hold-ms", str(args.hold_ms),
           "--forward-delay-ms", str(args.forward_delay_ms),
           "--lease-ms", str(args.lease_ms)]
    env = dict(os.environ,
               BIGDL_TPU_RUN_DIR=os.path.join(run_dir, host_id),
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   p for p in [os.getcwd()] + sys.path if p))
    env.pop("XLA_FLAGS", None)
    env.pop("BIGDL_TPU_FAULTS", None)
    env.pop("BIGDL_TPU_TRACE_ID", None)
    return subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)


def _committed(coord: str) -> dict:
    try:
        with open(os.path.join(coord, "generation.json")) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return {}


def _committed_gen(coord: str) -> int:
    try:
        return int(_committed(coord).get("gen", 0))
    except (TypeError, ValueError):
        return 0


# -- phase A: SIGKILL mid-shift ----------------------------------------------

def _phase_a(args, failures: List[str]) -> dict:
    from bigdl_tpu.observability import ledger as run_ledger
    from bigdl_tpu.serving.fleet.cluster import ClusterClient
    from bigdl_tpu.serving.fleet.rollout import (RolloutController,
                                                 read_state,
                                                 resolve_recovery)
    from bigdl_tpu.utils.checkpoint import publish_version

    run_dir = args.run_dir or os.path.join(args.dir, "ledger")
    coord_dir = os.path.join(args.dir, "coord")
    pub_dir, state_dir = _rollout_dirs(args.dir)

    print("phase A: publish v1, bootstrap the fleet")
    params = _build_model(7).params
    publish_version(pub_dir, params, 1)
    RolloutController.bootstrap_state(state_dir, TENANT, 1)

    procs: Dict[str, subprocess.Popen] = {}
    outs: Dict[str, str] = {}
    stop_traffic = threading.Event()
    stop_sampler = threading.Event()
    rids: List[str] = []
    sampler = {"samples": 0, "empty": 0}
    try:
        for i in range(args.hosts):
            procs[f"h{i}"] = _spawn_host(args, f"h{i}", run_dir)
        _expect(_wait_for(lambda: _committed_gen(coord_dir) >= 1,
                          "generation 1 (bootstrap)", 180),
                "fleet bootstrapped: generation 1 committed", failures)
        placement = (_committed(coord_dir).get("payload") or {}) \
            .get("placement") or {}
        _expect(placement.get(TENANT) == ["h0"],
                f"tenant {TENANT!r} packed on h0: {placement}",
                failures)
        versions1 = (_committed(coord_dir).get("payload") or {}) \
            .get("versions") or {}
        _expect(versions1.get(TENANT) == 1,
                f"generation 1 payload names v1: {versions1}", failures)

        # driver becomes a fleet client with its own ledger subdir
        run_ledger.set_run_dir(os.path.join(run_dir, "client"))
        client = ClusterClient(args.dir, resubmit_s=3.0)

        def traffic():
            seq = 0
            while not stop_traffic.is_set():
                rids.append(client.submit(TENANT, seq, _row(seq)))
                seq += 1
                time.sleep(args.traffic_ms / 1e3)

        def sample_serving():
            # the zero-downtime probe: at every sampled instant the
            # durable rollout state must resolve to SOME serving
            # version — a window with none is a stranded fleet
            while not stop_sampler.is_set():
                res = resolve_recovery(read_state(state_dir, TENANT))
                sampler["samples"] += 1
                if res["version"] is None:
                    sampler["empty"] += 1
                time.sleep(0.025)

        tt = threading.Thread(target=traffic, daemon=True)
        st = threading.Thread(target=sample_serving, daemon=True)
        tt.start()
        st.start()

        rdir = os.path.join(args.dir, "bus", "responses")
        _expect(_wait_for(lambda: os.path.isdir(rdir)
                          and len(os.listdir(rdir)) >= 3,
                          "pre-rollout responses", 120),
                "v1 serving live traffic before the rollout", failures)

        print("phase A: publish v2 (bit-identical refresh), wait for "
              "the shift, SIGKILL h0")
        publish_version(pub_dir, params, 2)
        in_shift = _wait_for(
            lambda: (read_state(state_dir, TENANT) or {})
            .get("phase") == "shift",
            "durable 'shift' transition", 120)
        _expect(in_shift, "rollout reached the traffic shift "
                "(canary passed)", failures)
        procs["h0"].send_signal(signal.SIGKILL)
        procs["h0"].wait(timeout=30)
        print(f"  killed h0 (pid {procs['h0'].pid}) mid-shift")

        _expect(_wait_for(lambda: _committed_gen(coord_dir) >= 2,
                          "generation 2 (re-place)", 120),
                "survivor committed generation 2 after the lease "
                "lapsed", failures)
        resolved = _wait_for(
            lambda: (read_state(state_dir, TENANT) or {})
            .get("phase") in ("idle", "committed"),
            "rollout state resolved by the successor", 90)
        _expect(resolved, "successor resolved the interrupted rollout",
                failures)
        time.sleep(1.0)            # post-recovery serving window
        stop_traffic.set()
        tt.join(10)

        print(f"phase A: collect every terminal state "
              f"({len(rids)} submitted)")
        results: Dict[str, dict] = {}
        lost: List[str] = []
        deadline = time.monotonic() + args.result_timeout_s
        for rid in rids:
            budget = max(1.0, deadline - time.monotonic())
            try:
                results[rid] = client.result(rid, timeout_s=budget)
            except TimeoutError:
                lost.append(rid)
        stop_sampler.set()
        st.join(5)

        with open(os.path.join(args.dir, "stop"), "w") as f:
            f.write("done")
        for h, proc in procs.items():
            if h == "h0":
                continue
            try:
                outs[h], _ = proc.communicate(timeout=120)
            except subprocess.TimeoutExpired:
                proc.kill()
                outs[h], _ = proc.communicate()
                _expect(False, f"host {h} finished in time", failures)
        for h in sorted(outs):
            _expect(procs[h].returncode == 0, f"host {h} exited 0",
                    failures)
            if procs[h].returncode != 0:
                print(f"---- {h} output tail ----\n{outs[h][-2500:]}")
    finally:
        stop_traffic.set()
        stop_sampler.set()
        for proc in procs.values():
            if proc.poll() is None:
                proc.kill()

    # -- convergence + zero-lost + bit-equality
    final = read_state(state_dir, TENANT) or {}
    final_res = resolve_recovery(final)
    winner = final_res["version"]
    _expect(final.get("phase") in ("idle", "committed")
            and winner == 1,
            f"fleet converged to exactly one committed version "
            f"(phase={final.get('phase')}, version={winner})", failures)
    rb = [h for h in final.get("history", [])
          if h.get("outcome") == "rolled_back"]
    _expect(len(rb) == 1 and rb[0].get("version") == 2
            and rb[0].get("reason") == "recovery",
            f"v2 rolled back by recovery: {final.get('history')}",
            failures)

    _expect(not lost, f"zero lost requests ({len(results)}/{len(rids)} "
            f"terminal{'' if not lost else ' — LOST: ' + str(lost[:5])})",
            failures)
    oks = {rid: r for rid, r in results.items()
           if r.get("status") == "ok"}
    sheds = [r for r in results.values() if r.get("status") == "shed"]
    _expect(len(oks) == len(results),
            f"every request served ok through the kill "
            f"({len(oks)} ok / {len(sheds)} shed)", failures)
    _expect(sampler["empty"] == 0,
            f"no sampled instant with no serving version "
            f"({sampler['samples']} samples)", failures)

    print("phase A: bit-equality against the winner's single-server "
          "reference")
    from bigdl_tpu.observability import ledger as led
    led.set_run_dir(None)
    from bigdl_tpu.serving.fleet import FleetServer
    n = max((int(r["seq"]) for r in results.values()), default=-1) + 1
    ref: Dict[int, int] = {}
    with FleetServer([_build_spec(pub_dir, int(winner or 1), TENANT)],
                     autoscale=False) as single:
        futs = [(seq, single.submit(TENANT, _row(seq)))
                for seq in range(n)]
        for seq, fut in futs:
            ref[seq] = int(fut.result(timeout=60))
    mismatches = [rid for rid, r in oks.items()
                  if ref.get(int(r["seq"])) != int(r["prediction"])]
    _expect(not mismatches,
            f"outputs bit-equal to v{winner}'s reference "
            f"({len(oks)} compared"
            f"{'' if not mismatches else ' — MISMATCH: ' + str(mismatches[:5])})",
            failures)

    versions2 = (_committed(coord_dir).get("payload") or {}) \
        .get("versions") or {}
    _expect(versions2.get(TENANT) == winner,
            f"generation 2 payload agrees on the winner: {versions2}",
            failures)

    # -- the durable rollout trail, merged across both hosts' ledgers
    print("phase A: ledger trail + run-report rollout census")
    from bigdl_tpu.observability.fleet import load_fleet
    from bigdl_tpu.observability.report import build_report
    records, _bad, _dirs = load_fleet(run_dir)
    kinds: Dict[str, int] = {}
    for r in records:
        if r.get("type") == "event":
            k = str(r.get("kind", ""))
            kinds[k] = kinds.get(k, 0) + 1
    for k in ("rollout.discovered", "rollout.shadow", "rollout.canary",
              "rollout.verdict", "rollout.shift", "rollout.resume",
              "rollout.rollback", "rollout.rolled_back"):
        _expect(kinds.get(k, 0) >= 1, f"durable {k} on the merged "
                f"ledger", failures)
    rep = build_report(records)
    census = rep.get("rollout") or {}
    _expect(census.get("rollbacks", 0) >= 1
            and census.get("shift_steps", 0) >= 1
            and (census.get("canary_verdicts") or {}).get("pass", 0) >= 1
            and 2 in (census.get("versions_seen") or []),
            f"run-report rollout census agrees: {census}", failures)

    return {"submitted": len(rids), "ok": len(oks),
            "shed": len(sheds), "lost": len(lost),
            "bit_mismatches": len(mismatches),
            "final_version": winner,
            "final_phase": final.get("phase"),
            "downtime_samples": sampler["samples"],
            "downtime_empty_windows": sampler["empty"],
            "rollout_events": {k: v for k, v in sorted(kinds.items())
                               if k.startswith("rollout.")}}


# -- phase B: divergent canary auto-rollback ---------------------------------

def _phase_b(args, failures: List[str]) -> dict:
    from bigdl_tpu.observability import ledger as run_ledger
    from bigdl_tpu.serving.fleet import FleetServer
    from bigdl_tpu.serving.fleet.rollout import (RolloutConfig,
                                                 RolloutController)
    from bigdl_tpu.utils.checkpoint import publish_version

    run_ledger.set_run_dir(None)
    root = os.path.join(args.dir, "phaseb")
    pub_dir, state_dir = _rollout_dirs(root)
    print("phase B: divergent v2 must auto-roll-back at the canary "
          "gate")
    publish_version(pub_dir, _build_model(7).params, 1)
    publish_version(pub_dir, _build_model(99).params, 2)  # divergent

    def drive(fleet, seconds: float):
        futs = []
        seq = 0
        end = time.monotonic() + seconds
        while time.monotonic() < end:
            futs.append(fleet.submit(TENANT, _row(seq)))
            seq += 1
            time.sleep(args.traffic_ms / 1e3)
        return [int(f.result(timeout=30)) for f in futs]

    # no-rollout baseline: same traffic, same spec, nothing shifting
    with FleetServer([_build_spec(pub_dir, 1, TENANT)], max_workers=2,
                     autoscale=False) as base:
        n_base = len(drive(base, args.phase_b_s))
        hit_base = base.registry.get(TENANT).slo.snapshot()["hit_rate"]

    fleet = FleetServer([_build_spec(pub_dir, 1, TENANT)],
                        max_workers=2, autoscale=False)
    RolloutController.bootstrap_state(state_dir, TENANT, 1)
    ctl = RolloutController(
        fleet, TENANT, pub_dir, state_dir,
        lambda v, name: _build_spec(pub_dir, v, name),
        config=RolloutConfig(gate="w8", canary_requests=args.canary,
                             canary_timeout_s=60.0,
                             shift_steps=(0.5, 1.0),
                             hold_s=args.hold_ms / 1e3,
                             timeout_s=120.0))
    stop = threading.Event()
    served: List[int] = []

    def traffic():
        seq = 0
        while not stop.is_set():
            try:
                served.append(fleet.submit(TENANT, _row(seq)))
            except Exception:
                pass
            seq += 1
            time.sleep(args.traffic_ms / 1e3)

    tt = threading.Thread(target=traffic, daemon=True)
    tt.start()
    t0 = time.monotonic()
    out = ctl.run_once()
    rollback_s = time.monotonic() - t0
    stop.set()
    tt.join(10)
    settled = [int(f.result(timeout=30)) for f in served]
    hit_roll = fleet.registry.get(TENANT).slo.snapshot()["hit_rate"]
    st = ctl.state() or {}

    _expect(out is not None and out.get("outcome") == "rolled_back"
            and out.get("reason") == "canary_gate",
            f"divergent canary auto-rolled-back: {out}", failures)
    verdict = (out or {}).get("verdict") or {}
    _expect(verdict.get("passed") is False
            and verdict.get("agreement", 1.0) < 1.0
            - verdict.get("allowed_drop", 0.0),
            f"the verdict measured real divergence: {verdict}",
            failures)
    _expect(sorted(x.name for x in fleet.registry.tenants())
            == [TENANT] and fleet.get_route(TENANT) is None,
            "incumbent untouched: shadow deregistered, route cleared",
            failures)
    _expect(st.get("phase") == "idle" and st.get("version") == 1,
            f"durable state back at v1: phase={st.get('phase')}, "
            f"version={st.get('version')}", failures)
    _expect(ctl.discover() is None,
            "the rolled-back version is never retried", failures)
    _expect(len(settled) == len(served) and len(settled) > 0,
            f"every request during the aborted rollout served "
            f"({len(settled)})", failures)
    _expect(hit_roll >= hit_base - 1e-9,
            f"incumbent SLO hit rate unharmed "
            f"({hit_roll:.4f} with rollout vs {hit_base:.4f} baseline)",
            failures)
    fleet.drain()
    return {"baseline_requests": n_base,
            "rollout_requests": len(settled),
            "baseline_hit_rate": hit_base,
            "rollout_hit_rate": hit_roll,
            "canary_verdict": verdict,
            "rolled_back": (out or {}).get("outcome") == "rolled_back",
            "rollback_reason": (out or {}).get("reason"),
            "time_to_rollback_s": rollback_s}


# -- the driver ---------------------------------------------------------------

def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        "rollout-drill",
        description="Live train→deploy rollout chaos drill "
                    "(docs/serving.md#live-rollout-r18)")
    p.add_argument("--hosts", type=int, default=2)
    p.add_argument("--canary", type=int, default=12,
                   help="mirrored pairs the canary gate needs")
    p.add_argument("--hold-ms", type=float, default=1000.0,
                   help="observation window per shift step (also the "
                        "kill window)")
    p.add_argument("--traffic-ms", type=float, default=8.0,
                   help="client inter-request gap")
    p.add_argument("--forward-delay-ms", type=float, default=5.0,
                   help="per-forward throttle: keeps inboxes non-empty "
                        "at the kill (numerics-neutral)")
    p.add_argument("--lease-ms", type=float, default=800.0)
    p.add_argument("--phase-b-s", type=float, default=2.0,
                   help="phase B baseline traffic duration")
    p.add_argument("--result-timeout-s", type=float, default=120.0)
    p.add_argument("--dir", default=None,
                   help="drill working directory (default: a temp dir, "
                        "removed on success)")
    p.add_argument("--run-dir", default=None,
                   help="run-ledger directory (default: <dir>/ledger)")
    p.add_argument("--out", default="BENCH_rollout_r18.json")
    p.add_argument("--smoke", action="store_true",
                   help="fast CI preset: fewer canary pairs, shorter "
                        "holds")
    p.add_argument("--host-id", default=None, help=argparse.SUPPRESS)
    args = p.parse_args(argv)

    if args.smoke:
        args.canary = 8
        args.hold_ms = 700.0
        args.traffic_ms = 6.0
        args.lease_ms = 600.0
        args.phase_b_s = 1.2

    if args.hosts < 2:
        print("rollout-drill: --hosts must be >= 2 (the mid-shift kill "
              "needs a warm standby to converge the fleet)")
        return 2
    if args.host_id:
        return _host_main(args)

    own_dir = args.dir is None
    if own_dir:
        args.dir = tempfile.mkdtemp(prefix="bigdl-rollout-drill-")
    os.makedirs(args.dir, exist_ok=True)

    import jax
    jax.config.update("jax_platforms", "cpu")
    from bigdl_tpu.observability import ledger as run_ledger
    run_ledger.set_run_dir(None)
    os.environ.pop("BIGDL_TPU_RUN_DIR", None)
    os.environ.pop("BIGDL_TPU_TRACE_ID", None)

    failures: List[str] = []
    print(f"rollout-drill: {args.hosts} host processes, canary="
          f"{args.canary}, hold={args.hold_ms:.0f}ms")
    print(f"  dir: {args.dir}")
    a = _phase_a(args, failures)
    b = _phase_b(args, failures)

    gates = {
        "zero_lost": a.get("lost") == 0,
        "all_ok": a.get("ok") == a.get("submitted"),
        "bit_equal": a.get("bit_mismatches") == 0,
        "one_committed_version": a.get("final_phase")
        in ("idle", "committed") and a.get("final_version") == 1,
        "zero_downtime": a.get("downtime_empty_windows") == 0
        and a.get("ok") == a.get("submitted"),
        "canary_rollback": bool(b.get("rolled_back"))
        and b.get("rollback_reason") == "canary_gate",
        "incumbent_slo_unharmed": b.get("rollout_hit_rate", 0.0)
        >= b.get("baseline_hit_rate", 1.0) - 1e-9,
    }
    bench = {"bench": "rollout_r18", "smoke": bool(args.smoke),
             "phase_a": a, "phase_b": b, "gates": gates,
             "pass": all(gates.values()) and not failures}
    with open(args.out, "w") as f:
        json.dump(bench, f, indent=2, default=str)
    print(f"\n-- gates ({args.out}) --")
    for k, v in gates.items():
        print(f"  [{'ok' if v else 'FAIL'}] {k}")
        if not v and f"gate {k}" not in failures:
            failures.append(f"gate {k}")

    if failures:
        print(f"\nrollout-drill: {len(failures)} check(s) FAILED "
              f"(artifacts kept under {args.dir})")
        return 1
    print("\nrollout-drill: all checks passed")
    if own_dir:
        shutil.rmtree(args.dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
