"""Cross-host serving chaos drill — ``python -m bigdl_tpu.cli
fleet-drill``.

``serve-drill`` proves one process survives its own workers dying;
``train-drill`` proves the training fleet survives host loss.  This is
the serving fleet's host-loss proof, and the headline for r16's
sharded control plane (``serving/fleet/cluster.py``): N **real OS
processes** on one box, each a :class:`HostAgent` — a local
``FleetServer`` wrapped in file-backed fleet membership — and the
drill:

1. **bootstraps** the fleet: N hosts heartbeat, the leader commits
   generation 1 with the tenant placement map stamped in its payload
   (hot tenants replicated, cold tenants packed);
2. **drives traffic** through the committed placement via
   :class:`ClusterClient` (requests are atomically-renamed files in
   per-host inboxes — accepted means on disk, terminal means a
   response file exists);
3. **SIGKILLs one host mid-traffic** (no goodbye, inbox non-empty by
   construction): survivors detect the lapsed lease, two-phase-commit
   generation 2 whose payload re-places the dead host's tenants onto
   surviving capacity, and each re-placed tenant's new primary
   salvages the dead host's unresponded requests and re-drives them in
   sequence order;
4. **collects every terminal state** and shuts the fleet down
   gracefully.

Asserted (exit 0 iff all hold):

* every surviving host process exits 0;
* **zero lost requests**: every accepted request reaches a terminal
  response — ``ok`` or a shed with a typed, attributed reason;
* per-tenant ``ok`` outputs are **bit-equal** to an undisturbed
  single-host (one ``FleetServer``) run of the same rows — batching,
  placement, spill and salvage may move work, never change it;
* survivors committed generation 2 and re-placed the victim's tenants
  (``fleet.host.place`` register events at gen 2);
* the ledger carries the full trail (``fleet.host.join`` for every
  host, ``elastic.lease_lost`` + ``fleet.host.lost`` for the victim,
  ``elastic.generation`` x2) and ``run-report``'s ``fleet_hosts``
  census agrees;
* **the flight recorder stitches (r17)**: every host writes its own
  ledger subdirectory (one run dir per host — the on-disk shape a real
  multi-machine fleet produces), the driver's submit spans land in a
  ``client`` subdirectory, and the merged fleet trace
  (``observability.fleet.load_fleet`` over the whole tree) resolves
  EVERY cross-host link edge — including requests spilled between
  survivors and requests salvaged off the SIGKILLed host and re-driven
  — with the victim's pre-kill dispatches present in the timeline
  (real spans where its drain got them to disk, synthesized from its
  durable ``bus.claim`` anchors where it did not), and ``fleet-report``
  census figures (per-tenant cross-host SLO, terminal counts) agree
  with the per-host ledgers.

The drill SIGKILLs the victim only after it has written at least one
response: a victim that dies before serving anything leaves no
pre-kill trail to assert on (and, worse, makes the join/bind records
racy).  The kill still lands mid-traffic — two thirds of the plan is
submitted after it.

``--smoke`` is the fast CI preset (3 hosts — host loss needs at least
that — fewer requests), wired into ``make-dist.sh`` beside the
lint/train-drill/serve-drill gates.  The per-forward throttle
(``--forward-delay-ms``) exists to keep inboxes non-empty at the kill
(so salvage is exercised for real); it never touches the numerics.
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
from typing import Dict, List, Optional, Tuple

FEATURES = 6

# (name, seed, classes, weight): "hot" replicates by weight, the others
# pack — the placement shapes the drill's blast radius
TENANTS = (("hot", 11, 3, 4), ("warm", 22, 4, 2), ("cold", 33, 2, 1))


def _expect(cond: bool, what: str, failures: List[str]) -> None:
    tag = "ok" if cond else "FAIL"
    print(f"  [{tag}] {what}")
    if not cond:
        failures.append(what)


def _wait_for(pred, what: str, timeout_s: float) -> bool:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.05)
    print(f"  timeout waiting for: {what}")
    return False


def _host_name(i: int) -> str:
    return f"h{i}"


def _row(tenant_idx: int, seq: int) -> List[float]:
    return [((seq * 7 + j * 3 + tenant_idx * 5) % 11) / 11.0
            for j in range(FEATURES)]


def _plan(per_tenant: int) -> List[Tuple[str, int, List[float]]]:
    """The request plan, interleaved round-robin across tenants so the
    kill lands mid-stream for everyone.  Pure function of its argument
    — the cluster run and the single-host reference replay the SAME
    plan."""
    out = []
    for seq in range(per_tenant):
        for idx, (name, _seed, _classes, _w) in enumerate(TENANTS):
            out.append((name, seq, _row(idx, seq)))
    return out


def drill_specs(forward_delay_s: float = 0.0):
    """The drill's tenant catalog — identical in every host process and
    in the driver's reference run (same seeds, same weights, so
    placement AND outputs are reproducible).  ``forward_delay_s``
    throttles each forward (timing room for the kill window;
    numerics-neutral)."""
    import jax

    import bigdl_tpu.nn as nn
    from bigdl_tpu.api import DLClassifier
    from bigdl_tpu.serving.fleet import TenantSpec

    class _SlowClassifier(DLClassifier):
        def _run(self, feats):
            if forward_delay_s > 0:
                time.sleep(forward_delay_s)
            return super()._run(feats)

    specs = []
    for name, seed, classes, weight in TENANTS:
        m = nn.Sequential()
        m.add(nn.Linear(FEATURES, classes))
        m.add(nn.LogSoftMax())
        m.build(jax.random.PRNGKey(seed))
        clf = _SlowClassifier(m, batch_shape=(4, FEATURES))
        specs.append(TenantSpec(name, classifier=clf, weight=weight,
                                min_workers=1, queue_capacity=512,
                                max_delay_s=0.002))
    return specs


def _committed(coord: str) -> dict:
    try:
        with open(os.path.join(coord, "generation.json")) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return {}


def _responded_by(root: str, host: str) -> bool:
    """True once any terminal response attributed to ``host`` is on the
    bus — the driver gates the SIGKILL on this so the victim's durable
    pre-kill trail (bus.claim anchors, at least one respond) exists."""
    rdir = os.path.join(root, "bus", "responses")
    try:
        names = os.listdir(rdir)
    except OSError:
        return False
    for name in names:
        try:
            with open(os.path.join(rdir, name)) as f:
                if json.load(f).get("host") == host:
                    return True
        except (OSError, json.JSONDecodeError, AttributeError):
            continue
    return False


def _committed_gen(coord: str) -> int:
    try:
        return int(_committed(coord).get("gen", 0))
    except (TypeError, ValueError):
        return 0


# -- the simulated-host process (spawned by the driver) -----------------------

def _host_main(args) -> int:
    import jax
    jax.config.update("jax_platforms", "cpu")

    from bigdl_tpu.observability import ledger as run_ledger
    from bigdl_tpu.serving.fleet.cluster import HostAgent

    agent = HostAgent(args.dir, args.host_id,
                      drill_specs(args.forward_delay_ms / 1e3),
                      lease_s=args.lease_ms / 1e3,
                      bootstrap_world=args.hosts,
                      max_workers=args.workers_per_host)
    gen = agent.start()
    print(f"DRILLHOST {args.host_id} UP pid={os.getpid()} gen={gen.gen} "
          f"tenants={','.join(sorted(agent.local_tenants())) or '-'}",
          flush=True)
    stop_file = os.path.join(args.dir, "stop")
    while not os.path.exists(stop_file) and not agent.fenced:
        time.sleep(0.05)
    agent.stop(leave=True)
    run_ledger.flush()
    final_gen = agent.coord.generation().gen
    print(f"DRILLHOST {args.host_id} OK pid={os.getpid()} "
          f"gen={final_gen} fenced={agent.fenced}", flush=True)
    return 0


# -- the driver ---------------------------------------------------------------

def _spawn_host(args, host_id: str, run_dir: str) -> subprocess.Popen:
    cmd = [sys.executable, "-m", "bigdl_tpu.cli", "fleet-drill",
           "--host-id", host_id, "--dir", args.dir,
           "--hosts", str(args.hosts),
           "--workers-per-host", str(args.workers_per_host),
           "--forward-delay-ms", str(args.forward_delay_ms),
           "--lease-ms", str(args.lease_ms)]
    # one run dir PER HOST — the on-disk shape a real multi-machine
    # fleet produces (each machine writes locally; fleet-report merges
    # the collected tree).  The trace env is scrubbed on purpose: peer
    # hosts must converge on the fleet trace id by ADOPTING it from the
    # committed generation payload, not by environment inheritance
    # (which no real cross-machine fleet has).
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


def _pick_victim(coord_dir: str, leader: str) -> str:
    """The most interesting host to kill: a non-leader that is PRIMARY
    for at least one tenant (its death forces re-placement + salvage,
    not just a replica shrink).  Deterministic given the committed
    placement."""
    placement = (_committed(coord_dir).get("payload") or {}) \
        .get("placement") or {}
    primaries: Dict[str, int] = {}
    for hosts in placement.values():
        if hosts:
            primaries[hosts[0]] = primaries.get(hosts[0], 0) + 1
    candidates = sorted(h for h in primaries if h != leader)
    if candidates:
        return max(candidates, key=lambda h: (primaries[h], h))
    return sorted(set(_committed(coord_dir).get("hosts", []))
                  - {leader})[-1]


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        "fleet-drill",
        description="Cross-host serving fleet chaos drill "
                    "(docs/serving.md#cross-host-fleet-r16)")
    p.add_argument("--hosts", type=int, default=3)
    p.add_argument("--per-tenant", type=int, default=40,
                   help="requests per tenant (the plan interleaves "
                        "tenants round-robin)")
    p.add_argument("--kill-after", type=int, default=None,
                   help="SIGKILL the victim after this many requests "
                        "were submitted (default: a third of the plan)")
    p.add_argument("--workers-per-host", type=int, default=3)
    p.add_argument("--forward-delay-ms", type=float, default=20.0,
                   help="per-forward throttle: keeps inboxes non-empty "
                        "at the kill so salvage is exercised for real "
                        "(numerics-neutral)")
    p.add_argument("--lease-ms", type=float, default=800.0)
    p.add_argument("--result-timeout-s", type=float, default=120.0)
    p.add_argument("--dir", default=None,
                   help="drill working directory (default: a temp dir, "
                        "removed on success)")
    p.add_argument("--run-dir", default=None,
                   help="run-ledger directory (default: <dir>/ledger)")
    p.add_argument("--smoke", action="store_true",
                   help="fast CI preset: 3 hosts (host loss needs at "
                        "least that), fewer requests")
    p.add_argument("--host-id", default=None, help=argparse.SUPPRESS)
    args = p.parse_args(argv)

    if args.smoke:
        args.hosts = 3
        args.per_tenant = 12
        args.forward_delay_ms = 15.0
        args.lease_ms = 600.0

    if args.hosts < 3:
        print("fleet-drill: --hosts must be >= 3 (killing one of two "
              "leaves no fleet to re-place onto)")
        return 2
    if args.host_id:
        return _host_main(args)

    own_dir = args.dir is None
    if own_dir:
        args.dir = tempfile.mkdtemp(prefix="bigdl-fleet-drill-")
    os.makedirs(args.dir, exist_ok=True)
    run_dir = args.run_dir or os.path.join(args.dir, "ledger")
    coord_dir = os.path.join(args.dir, "coord")
    # the driver's in-process reference run stays OUT of the census;
    # its trace env is scrubbed so the fleet id provably arrives by
    # adoption from the committed payload, not by inheritance
    from bigdl_tpu.observability import ledger as run_ledger
    run_ledger.set_run_dir(None)
    os.environ.pop("BIGDL_TPU_RUN_DIR", None)
    os.environ.pop("BIGDL_TPU_TRACE_ID", None)

    failures: List[str] = []
    plan = _plan(args.per_tenant)
    kill_after = args.kill_after if args.kill_after is not None \
        else len(plan) // 3
    print(f"fleet-drill: {args.hosts} host processes, "
          f"{len(TENANTS)} tenants x {args.per_tenant} requests, "
          f"kill after {kill_after} submissions")
    print(f"  dir: {args.dir}")

    # -- phase 0: the undisturbed single-host reference run (in-process)
    print("phase 0: single-host reference run")
    import jax
    jax.config.update("jax_platforms", "cpu")
    from bigdl_tpu.serving.fleet import FleetServer
    ref: Dict[Tuple[str, int], int] = {}
    with FleetServer(drill_specs(0.0), autoscale=False) as single:
        futs = [(name, seq, single.submit(name, row))
                for name, seq, row in plan]
        for name, seq, fut in futs:
            ref[(name, seq)] = int(fut.result(timeout=60))
    print(f"  reference predictions: {len(ref)}")

    # -- phase 1: bootstrap the fleet.  From here the driver is a fleet
    # CLIENT and records its own ledger (submit spans) in a per-role
    # subdirectory beside the hosts' — the merged timeline needs the
    # originating end of every cross-host edge.
    print(f"phase 1: bootstrap {args.hosts} host processes")
    run_ledger.set_run_dir(os.path.join(run_dir, "client"))
    from bigdl_tpu.serving.fleet.cluster import ClusterClient
    procs: Dict[str, subprocess.Popen] = {}
    outs: Dict[str, str] = {}
    victim = None
    try:
        for i in range(args.hosts):
            procs[_host_name(i)] = _spawn_host(args, _host_name(i),
                                               run_dir)
        _expect(_wait_for(lambda: _committed_gen(coord_dir) >= 1,
                          "generation 1 (bootstrap)", 180),
                "fleet bootstrapped: generation 1 committed with a "
                "placement payload", failures)
        placement = (_committed(coord_dir).get("payload") or {}) \
            .get("placement") or {}
        _expect(set(placement) == {n for n, *_ in TENANTS},
                f"every tenant placed: {placement}", failures)
        hot_replicas = len(placement.get("hot", []))
        _expect(hot_replicas >= 2,
                f"hot tenant replicated across {hot_replicas} hosts",
                failures)

        # -- phase 2: traffic, with a SIGKILL mid-stream
        victim = _pick_victim(coord_dir, _host_name(0))
        print(f"phase 2: drive {len(plan)} requests, SIGKILL {victim} "
              f"after {kill_after}")
        client = ClusterClient(args.dir, resubmit_s=5.0)
        submitted: List[str] = []
        for n, (name, seq, row) in enumerate(plan):
            submitted.append(client.submit(name, seq, row))
            if n + 1 == kill_after:
                # gate the kill on the victim having SERVED something:
                # its durable pre-kill trail (bus.claim anchors, one
                # respond) is what phase 7 stitches the salvage chain to
                _wait_for(lambda: _responded_by(args.dir, victim),
                          f"a pre-kill response from {victim}", 90)
                procs[victim].send_signal(signal.SIGKILL)
                procs[victim].wait(timeout=30)
                print(f"  killed {victim} (pid "
                      f"{procs[victim].pid})")
        _expect(_wait_for(lambda: _committed_gen(coord_dir) >= 2,
                          "generation 2 (re-place)", 120),
                "survivors committed generation 2 after the lease "
                "lapsed", failures)
        placement2 = (_committed(coord_dir).get("payload") or {}) \
            .get("placement") or {}
        _expect(all(victim not in hosts
                    for hosts in placement2.values()),
                f"victim {victim} re-placed out of every tenant: "
                f"{placement2}", failures)

        # -- phase 3: every accepted request reaches a terminal state
        print("phase 3: collect every terminal state (zero lost)")
        results: Dict[str, dict] = {}
        lost: List[str] = []
        deadline = time.monotonic() + args.result_timeout_s
        for rid in submitted:
            budget = max(1.0, deadline - time.monotonic())
            try:
                results[rid] = client.result(rid, timeout_s=budget)
            except TimeoutError:
                lost.append(rid)
        _expect(not lost,
                f"zero lost requests ({len(results)}/{len(submitted)} "
                f"terminal{'' if not lost else ' — LOST: ' + str(lost[:5])})",
                failures)

        # -- phase 4: graceful shutdown
        print("phase 4: graceful fleet shutdown")
        with open(os.path.join(args.dir, "stop"), "w") as f:
            f.write("done")
        for h, proc in procs.items():
            if h == victim:
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
        for proc in procs.values():
            if proc.poll() is None:
                proc.kill()

    # -- phase 5: typed sheds + bit-equal outputs
    print("phase 5: typed sheds + bit-equality against single-host")
    oks = {rid: r for rid, r in results.items()
           if r.get("status") == "ok"}
    sheds = {rid: r for rid, r in results.items()
             if r.get("status") == "shed"}
    _expect(len(oks) + len(sheds) == len(results),
            f"every terminal state is ok or shed "
            f"({len(oks)} ok / {len(sheds)} shed)", failures)
    _expect(all(r.get("reason") and r.get("host")
                for r in sheds.values()),
            "every shed carries a typed reason and an attributed host",
            failures)
    _expect(len(oks) >= 0.8 * len(submitted),
            f"the fleet actually served through the kill "
            f"({len(oks)}/{len(submitted)} ok)", failures)
    mismatches = [rid for rid, r in oks.items()
                  if ref[(r["tenant"], int(r["seq"]))]
                  != int(r["prediction"])]
    _expect(not mismatches,
            "per-tenant outputs bit-equal to the single-host run "
            f"({len(oks)} compared"
            f"{'' if not mismatches else ' — MISMATCH: ' + str(mismatches[:5])})",
            failures)

    # -- phase 6: the ledger trail + fleet_hosts census (merged across
    # every host's run dir by the fleet loader)
    print("phase 6: ledger trail + run-report census")
    run_ledger.set_run_dir(None)       # flush + close the client ledger
    from bigdl_tpu.observability.fleet import fleet_census, load_fleet
    from bigdl_tpu.observability.report import build_report
    records, _bad, host_dirs = load_fleet(run_dir)
    _expect(set(host_dirs) >= set(procs) | {"client"},
            f"fleet loader discovered every host's run dir "
            f"({sorted(host_dirs)})", failures)
    events = [r for r in records if r.get("type") == "event"]
    kinds: Dict[str, int] = {}
    for e in events:
        k = str(e.get("kind", ""))
        kinds[k] = kinds.get(k, 0) + 1
    joined = {e.get("host") for e in events
              if e.get("kind") == "fleet.host.join"}
    _expect(len(joined) == args.hosts,
            f"fleet.host.join for every host ({sorted(joined)})",
            failures)
    _expect(kinds.get("elastic.lease_lost", 0) >= 1,
            "elastic.lease_lost for the victim", failures)
    _expect(kinds.get("fleet.host.lost", 0) >= 1,
            "fleet.host.lost on the ledger", failures)
    salvaged = sum(int(e.get("salvaged", 0)) for e in events
                   if e.get("kind") == "fleet.host.lost")
    print(f"  salvaged request files: {salvaged}; spills: "
          f"{kinds.get('fleet.host.spill', 0)}")
    replaced = [e for e in events
                if e.get("kind") == "fleet.host.place"
                and e.get("action") == "register"
                and int(e.get("gen", 0)) >= 2]
    _expect(len(replaced) >= 1,
            f"the victim's tenants were re-placed onto survivors "
            f"({len(replaced)} gen>=2 register events)", failures)
    _expect(kinds.get("elastic.generation", 0) >= 2,
            "two elastic.generation commits (bootstrap, re-place)",
            failures)
    rep = build_report(records)
    fh = rep.get("fleet_hosts") or {}
    _expect(fh.get("hosts_joined", 0) == args.hosts and
            fh.get("hosts_lost", 0) >= 1 and
            fh.get("generations", 0) >= 2 and
            fh.get("placements", 0) >= 1,
            "run-report fleet_hosts census agrees (joined="
            f"{fh.get('hosts_joined')}, lost={fh.get('hosts_lost')}, "
            f"generations={fh.get('generations')}, placements="
            f"{fh.get('placements')}, spills={fh.get('spills')}, "
            f"salvaged={fh.get('salvaged')})", failures)

    # -- phase 7: the merged flight recorder (r17) — ONE stitched
    # timeline out of N per-host ledgers, every cross-host edge resolved
    print("phase 7: merged fleet trace + telemetry plane")
    from bigdl_tpu.observability import trace as run_trace
    census = fleet_census(records)
    stitch = census.get("trace") or {}
    _expect(stitch.get("link_edges", 0) > 0 and
            stitch.get("resolved_edges") == stitch.get("link_edges"),
            "merged trace resolves every cross-host link edge "
            f"({stitch.get('resolved_edges')}/{stitch.get('link_edges')} "
            f"resolved, {stitch.get('cross_pid_edges')} cross-pid)",
            failures)
    fleet_tid = (_committed(coord_dir).get("payload") or {}).get("trace")
    _expect(bool(fleet_tid)
            and fleet_tid in (stitch.get("trace_ids") or []),
            f"committed fleet trace id adopted across the ledgers "
            f"({fleet_tid})", failures)
    victim_pid = procs[victim].pid if victim in procs else None
    built = run_trace.build_trace(records)
    victim_spans = [e for e in built.get("traceEvents", [])
                    if e.get("ph") == "X" and e.get("pid") == victim_pid]
    _expect(len(victim_spans) >= 1,
            f"killed host's pre-kill spans appear in the merged "
            f"timeline ({len(victim_spans)} on pid {victim_pid})",
            failures)
    victim_claims = [r for r in records
                     if r.get("kind") == "bus.claim"
                     and r.get("host") == victim]
    _expect(len(victim_claims) >= 1,
            f"durable bus.claim anchors survived the victim's SIGKILL "
            f"({len(victim_claims)})", failures)
    redrives = [r for r in records
                if r.get("kind") == "bus.claim"
                and r.get("salvaged_from")]
    _expect(len(redrives) >= 1,
            f"salvaged requests re-driven with links to the dead "
            f"host's accepts ({len(redrives)})", failures)
    terminal = sum(int(t.get("requests", 0))
                   for t in census.get("tenants", {}).values())
    _expect(terminal == len(results),
            f"fleet census terminal count agrees with the client "
            f"({terminal}/{len(results)})", failures)
    # per-tenant cross-host SLO: the census figures must be exactly the
    # sums of the per-host run.end snapshots (independently recomputed)
    slo_agrees = True
    for tenant in sorted(census.get("tenants", {})):
        ssum = msum = 0
        for r in records:
            if (r.get("type") == "run.end"
                    and r.get("kind") == "FleetServer"):
                snap = ((r.get("tenants") or {}).get(tenant)
                        or {}).get("slo") or {}
                ssum += int(snap.get("samples", 0) or 0)
                msum += int(snap.get("misses", 0) or 0)
        cslo = census["tenants"][tenant].get("slo") or {}
        if ssum and (cslo.get("samples") != ssum
                     or cslo.get("misses") != msum):
            slo_agrees = False
            print(f"  census/ledger SLO mismatch for {tenant}: "
                  f"census={cslo} vs samples={ssum} misses={msum}")
    _expect(slo_agrees, "per-tenant cross-host SLO figures agree with "
            "the per-host ledgers", failures)
    ft = rep.get("fleet_trace") or {}
    _expect(ft.get("submits") == len(plan),
            f"one client submit span per planned request "
            f"({ft.get('submits')}/{len(plan)})", failures)
    tel = census.get("telemetry") or {}
    survivors = sorted(h for h in procs if h != victim)
    _expect(all(h in tel for h in survivors),
            f"telemetry heartbeat blocks from every survivor "
            f"(have {sorted(tel)})", failures)

    print("\n-- drill summary --")
    for k in sorted(k for k in kinds
                    if k.startswith(("fleet.host.", "elastic."))):
        print(f"  {k:<24} {kinds[k]}")
    print(f"  ledger: {run_dir} — render with "
          f"`python -m bigdl_tpu.cli fleet-report {run_dir}`")
    if failures:
        print(f"\nfleet-drill: {len(failures)} check(s) FAILED "
              f"(artifacts kept under {args.dir})")
        return 1
    print("\nfleet-drill: all checks passed")
    if own_dir:
        shutil.rmtree(args.dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
