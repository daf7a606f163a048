"""Elastic multihost training (``resilience/elastic.py``) — membership
coordinator protocol, mesh reshape math, watchdog pause/rearm, the
spec-sharded torn-writer screen, the in-process world-change
integration, and the ``train-drill`` chaos drill.

The drill tests double as the REVIVED multihost tier: they exercise
true multi-process fleets (membership, generation commits, resharding
restores, cursor replay) with *simulated collectives* — every host
computes the full global step deterministically, which is numerically
identical to real cross-host collectives — so they run on CPU-only
containers where the gloo-backed ``test_multihost.py`` tier cannot.
"""

import json
import os
import shutil
import subprocess
import sys
import threading
import time

import pytest

import jax
import jax.numpy as jnp
import numpy as np

import bigdl_tpu.nn as nn
from bigdl_tpu.dataset.dataset import DataSet
from bigdl_tpu.dataset.sharded import ShardedDataSet
from bigdl_tpu.dataset.transformer import (Sample, SampleToBatch,
                                           Transformer)
from bigdl_tpu.observability import ledger as run_ledger
from bigdl_tpu.observability.report import build_report, load_ledger
from bigdl_tpu.optim import DistriOptimizer, SGD, Trigger
from bigdl_tpu.parallel import mesh as mesh_mod
from bigdl_tpu.parallel.mesh import MeshShape
from bigdl_tpu.resilience.elastic import (ElasticCoordinator,
                                          ElasticReshapeError,
                                          StaleGenerationError,
                                          reshape_for_world)
from bigdl_tpu.resilience.watchdog import Watchdog
from bigdl_tpu.utils import checkpoint as ckpt
from tests.checkers import record_steps

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- reshape math -------------------------------------------------------------

def test_reshape_for_world_data_absorbs_fsdp_tp_preserved():
    assert reshape_for_world("1x2x2", 8) == MeshShape(2, 2, 2)
    assert reshape_for_world((1, 1, 1), 3) == MeshShape(3, 1, 1)
    # shrink: data takes the hit, fsdp/tp intact
    assert reshape_for_world("4x2x1", 4) == MeshShape(2, 2, 1)
    assert reshape_for_world(MeshShape(2, 2, 2), 16) == MeshShape(4, 2, 2)


def test_reshape_for_world_unsatisfiable_is_typed():
    with pytest.raises(ElasticReshapeError):
        reshape_for_world("1x2x2", 6)        # 6 % 4 != 0
    with pytest.raises(ElasticReshapeError):
        reshape_for_world("1x2x2", 2)        # fewer devices than fsdp*tp
    # the typed error is a RuntimeError (catchable at the trainer seam)
    assert issubclass(ElasticReshapeError, RuntimeError)


# -- the membership coordinator (no training, threads as hosts) ---------------

def _coord(root, hid, **kw):
    kw.setdefault("lease_s", 0.5)
    kw.setdefault("poll_s", 0.01)
    return ElasticCoordinator(str(root), hid, **kw)


def _start_bg(coord, out):
    t = threading.Thread(target=lambda: out.update(gen=coord.start()),
                         daemon=True)
    t.start()
    return t


def _check_until_change(coord, timeout_s=10.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        gen = coord.check()
        if gen is not None:
            return gen
        time.sleep(0.01)
    raise AssertionError("no generation change within the deadline")


def test_coordinator_bootstrap_two_phase_commit(tmp_path):
    a = _coord(tmp_path, "a", bootstrap_world=2)
    b = _coord(tmp_path, "b", bootstrap_world=2)
    got = {}
    t = _start_bg(b, got)
    ga = a.start()
    t.join(timeout=10)
    assert ga.gen == 1 and ga.hosts == ("a", "b")
    assert ga.restore_step is None
    assert got["gen"] == ga
    # single-writer discipline: the lowest member id owns snapshots
    assert a.is_writer() and not b.is_writer()
    assert a.world_size() == 2
    assert a.mesh_shape() == MeshShape(2, 1, 1)
    # steady state: no proposal pending -> check returns None
    assert a.check(step=0) is None and b.check(step=0) is None
    a.stop()
    b.stop()


def test_coordinator_lease_loss_bumps_generation(tmp_path):
    a = _coord(tmp_path, "a", bootstrap_world=2)
    b = _coord(tmp_path, "b", bootstrap_world=2)
    got = {}
    t = _start_bg(b, got)
    a.start()
    t.join(timeout=10)
    a.set_restore_step_source(lambda: 7)
    b.stop(leave=False)              # silent death: the lease just lapses
    gen = _check_until_change(a)
    assert gen.gen == 2 and gen.hosts == ("a",)
    # the generation pins the committed restore step for every member
    assert gen.restore_step == 7
    a.stop()


def test_coordinator_join_request_admitted(tmp_path):
    a = _coord(tmp_path, "a", bootstrap_world=1)
    ga = a.start()
    assert ga.hosts == ("a",)
    b = _coord(tmp_path, "b", bootstrap_world=1)
    got = {}
    t = _start_bg(b, got)             # existing fleet -> join request
    gen = _check_until_change(a)
    t.join(timeout=10)
    assert gen.gen == 2 and gen.hosts == ("a", "b")
    assert got["gen"] == gen
    a.stop()
    b.stop()


def test_coordinator_fenced_host_raises(tmp_path):
    """A host whose lease lapsed while it was paused (GC, swap) must NOT
    keep training a stale world: once a generation without it commits,
    its next step-boundary check raises instead of returning."""
    a = _coord(tmp_path, "a", bootstrap_world=2, lease_s=0.3)
    b = _coord(tmp_path, "b", bootstrap_world=2, lease_s=0.3)
    got = {}
    t = _start_bg(b, got)
    a.start()
    t.join(timeout=10)
    # b's heartbeat dies but b itself does not know
    b._stop.set()
    b._hb.join(timeout=2)
    gen = _check_until_change(a)
    assert gen.hosts == ("a",)
    with pytest.raises(RuntimeError, match="fenced"):
        b.check(step=5)
    a.stop()


def test_coordinator_graceful_leave_is_not_a_lost_lease(tmp_path):
    run_ledger.set_run_dir(str(tmp_path / "ledger"))
    try:
        a = _coord(tmp_path / "c", "a", bootstrap_world=2)
        b = _coord(tmp_path / "c", "b", bootstrap_world=2)
        got = {}
        t = _start_bg(b, got)
        a.start()
        t.join(timeout=10)
        b.stop(leave=True)            # clean departure
        gen = _check_until_change(a)
        assert gen.hosts == ("a",)
        run_ledger.flush()
    finally:
        run_ledger.set_run_dir(None)
    records, _ = load_ledger(str(tmp_path / "ledger"))
    kinds = [r.get("kind") for r in records if r.get("type") == "event"]
    assert "elastic.left" in kinds
    assert "elastic.lease_lost" not in kinds
    a.stop()


# -- serving-side reuse edges (r16): the fleet control plane drives the
# -- same coordinator, so the edges the dispatch path newly exercises get
# -- coordinator-level coverage here, next to the trainer-side protocol


def _placement_payload(gen, hosts, leases):
    """A deterministic stand-in for the fleet's placement source: every
    member can compute it, so whoever leads stamps the same map."""
    return {"placement": {"tenant-a": sorted(hosts)[:1]}, "gen": gen,
            "world": len(hosts)}


def test_coordinator_lease_expiry_during_inflight_placement_commit(tmp_path):
    """A proposed member's lease lapses while a placement-carrying
    proposal is in flight: the leader must supersede with a higher
    generation, and the payload committed is the one recomputed for the
    FINAL member set — never the map proposed for the world that died
    mid-commit."""
    a = _coord(tmp_path, "a", bootstrap_world=3, lease_s=0.4)
    b = _coord(tmp_path, "b", bootstrap_world=3, lease_s=0.4)
    c = _coord(tmp_path, "c", bootstrap_world=3, lease_s=0.4)
    for h in (a, b, c):
        h.set_payload_source(_placement_payload)
    got_b, got_c = {}, {}
    tb, tc = _start_bg(b, got_b), _start_bg(c, got_c)
    ga = a.start()
    tb.join(timeout=10)
    tc.join(timeout=10)
    assert ga.hosts == ("a", "b", "c")
    assert ga.payload == _placement_payload(1, ["a", "b", "c"], {})

    b.stop(leave=False)               # silent death -> gen 2 proposal
    got = {}
    t = threading.Thread(
        target=lambda: got.update(gen=_check_until_change(a)), daemon=True)
    t.start()
    # wait for the in-flight proposal (gen 2 = {a, c}; c never acks
    # because we never run its check loop) ...
    deadline = time.monotonic() + 10.0
    while not os.path.exists(str(tmp_path / "proposal.json")):
        assert time.monotonic() < deadline, "no proposal appeared"
        time.sleep(0.01)
    # ... then let c's lease lapse MID-COMMIT (heartbeat dies silently)
    c._stop.set()
    c._hb.join(timeout=2)
    t.join(timeout=20)
    gen = got["gen"]
    assert gen.gen >= 3 and gen.hosts == ("a",)
    # the committed payload is for the surviving world, not the dead one
    assert gen.payload == _placement_payload(gen.gen, ["a"], {})
    a.stop()
    with pytest.raises(StaleGenerationError):
        c.check()


def test_coordinator_leader_failover_mid_proposal_serving_members(tmp_path):
    """The LEADER dies with its proposal still pending, in a serving
    (non-trainer) member set: the next-lowest surviving host must adopt
    leadership, supersede the orphaned proposal with a higher
    generation, and commit without either dead host."""
    a = _coord(tmp_path, "a", bootstrap_world=3, lease_s=0.4,
               role="serving host")
    b = _coord(tmp_path, "b", bootstrap_world=3, lease_s=0.4,
               role="serving host")
    c = _coord(tmp_path, "c", bootstrap_world=3, lease_s=0.4,
               role="serving host")
    b.set_payload_source(_placement_payload)
    got_b, got_c = {}, {}
    tb, tc = _start_bg(b, got_b), _start_bg(c, got_c)
    a.start()
    tb.join(timeout=10)
    tc.join(timeout=10)

    c.stop(leave=False)               # c dies silently
    time.sleep(0.6)                   # let c's lease lapse
    a._leader_duties()                # leader proposes gen 2 = {a, b} ...
    prop = json.load(open(tmp_path / "proposal.json"))
    assert prop["gen"] == 2 and prop["leader"] == "a"
    a._stop.set()                     # ... then dies mid-proposal,
    a._hb.join(timeout=2)             # before anyone acked

    gen = _check_until_change(b, timeout_s=20.0)
    assert gen.hosts == ("b",)
    assert gen.gen > prop["gen"]      # superseded, never committed as-is
    # the new leader stamped a payload for the world it actually leads
    assert gen.payload == _placement_payload(gen.gen, ["b"], {})
    assert b.is_writer()
    b.stop()


def test_coordinator_payload_and_lease_info_roundtrip(tmp_path):
    """The two r16 hooks: per-host info rides the lease (the leader's
    placement input), and the leader-stamped payload rides the
    committed generation (every member's placement output)."""
    a = _coord(tmp_path, "a", bootstrap_world=1)
    a.set_lease_info_source(lambda: {"backlog": {"tenant-a": 3}})
    a.set_payload_source(_placement_payload)
    ga = a.start()
    assert ga.payload == _placement_payload(1, ["a"], {})
    leases = a.read_leases()
    assert leases["a"]["info"] == {"backlog": {"tenant-a": 3}}
    # a joining host sees the SAME committed payload (no payload source
    # of its own needed: the generation record carries it)
    b = _coord(tmp_path, "b", bootstrap_world=1)
    got = {}
    t = _start_bg(b, got)
    gen = _check_until_change(a)
    t.join(timeout=10)
    assert got["gen"] == gen
    assert gen.payload == _placement_payload(gen.gen, ["a", "b"], {})
    # a failing info source degrades to a bare lease, not a dead one
    a.set_lease_info_source(lambda: 1 / 0)
    a._write_lease()
    assert "a" in a._live_hosts(a.read_leases())
    a.stop()
    b.stop()


def test_coordinator_fenced_raises_typed_and_ledgers(tmp_path):
    """The r16 hardening of the fence: a typed ``StaleGenerationError``
    (so the serving dispatch loop can catch it apart from other
    runtime failures) carrying host/gen/role, plus an
    ``elastic.fenced`` ledger event for the census."""
    run_ledger.set_run_dir(str(tmp_path / "ledger"))
    try:
        a = _coord(tmp_path / "c", "a", bootstrap_world=2, lease_s=0.3)
        b = _coord(tmp_path / "c", "b", bootstrap_world=2, lease_s=0.3,
                   role="serving host")
        got = {}
        t = _start_bg(b, got)
        a.start()
        t.join(timeout=10)
        b._stop.set()
        b._hb.join(timeout=2)
        gen = _check_until_change(a)
        assert gen.hosts == ("a",)
        with pytest.raises(StaleGenerationError) as ei:
            b.check()
        err = ei.value
        assert isinstance(err, RuntimeError)       # catchable at old seams
        assert err.host == "b" and err.gen == gen.gen
        assert err.role == "serving host"
        assert "fenced" in str(err)
        run_ledger.flush()
    finally:
        run_ledger.set_run_dir(None)
    records, _ = load_ledger(str(tmp_path / "ledger"))
    fenced = [r for r in records if r.get("kind") == "elastic.fenced"]
    assert len(fenced) == 1
    assert fenced[0]["host"] == "b" and fenced[0]["role"] == "serving host"
    assert fenced[0]["gen"] == gen.gen
    a.stop()


# -- watchdog pause/rearm across reshape windows ------------------------------

def test_watchdog_pause_rearms_and_ledgers(tmp_path):
    run_ledger.set_run_dir(str(tmp_path))
    fired = []
    try:
        with Watchdog(0.15, label="paused-step",
                      on_timeout=lambda: fired.append(1)):
            with Watchdog.pause("elastic.reshape"):
                # well past the timeout: a reshape-window stall must not
                # bill the step's watchdog budget
                time.sleep(0.35)
            # rearmed FRESH on exit; the block finishes inside it
        assert not fired
        # control: the same overrun without a pause does fire
        with Watchdog(0.1, label="hung-step",
                      on_timeout=lambda: fired.append(1)):
            time.sleep(0.3)
        assert fired
        run_ledger.flush()
    finally:
        run_ledger.set_run_dir(None)
    records, _ = load_ledger(str(tmp_path))
    pauses = [r for r in records if r.get("kind") == "watchdog.paused"]
    assert len(pauses) == 1
    assert pauses[0]["label"] == "elastic.reshape"
    assert pauses[0]["dur_s"] >= 0.3


def test_watchdog_armed_during_pause_starts_on_resume():
    fired = []
    with Watchdog.pause("window"):
        with Watchdog(0.2, label="inside",
                      on_timeout=lambda: fired.append(1)):
            time.sleep(0.3)           # paused: no timer running
    assert not fired


# -- dataset repartition + cursor replay --------------------------------------

def test_sharded_dataset_repartitions_exactly_at_any_host_count():
    items = list(range(37))
    for world in (1, 2, 3, 5):
        shards = [ShardedDataSet(items, host_index=h, host_count=world,
                                 workers=0).items for h in range(world)]
        flat = [x for s in shards for x in s]
        assert sorted(flat) == items          # every record exactly once


def test_sharded_dataset_shuffle_rewind_replays_deterministically():
    ds = ShardedDataSet(list(range(24)), workers=0, seed=5)
    ds.shuffle()
    p1 = ds._perm.copy()
    ds.shuffle()
    p2 = ds._perm.copy()
    ds.reset_shuffle()
    np.testing.assert_array_equal(ds._perm, np.arange(24))
    ds.shuffle()
    np.testing.assert_array_equal(ds._perm, p1)   # same (seed, count)
    ds.shuffle()
    np.testing.assert_array_equal(ds._perm, p2)


# -- satellite: spec-sharded torn-writer screen at two mesh shapes ------------

def _mlp():
    m = nn.Sequential()
    m.add(nn.Linear(4, 8))
    m.add(nn.Tanh())
    m.add(nn.Linear(8, 2))
    m.add(nn.LogSoftMax())
    m.build(jax.random.PRNGKey(3))
    return m


def _batches():
    rng = np.random.RandomState(0)
    x = rng.rand(8, 4).astype(np.float32)
    y = (np.arange(8) % 2 + 1).astype(np.float32)
    from bigdl_tpu.dataset import MiniBatch
    return [MiniBatch(x, y) for _ in range(8)]


def _spec_run(mesh_shape, iters, snap_path=None, resume_path=None):
    m = _mlp()
    opt = DistriOptimizer(m, nn.ClassNLLCriterion(),
                          DataSet.array(_batches()),
                          end_when=Trigger.max_iteration(iters),
                          mesh=mesh_mod.build_mesh(mesh_shape),
                          sharding="spec")
    opt.set_optim_method(SGD(learning_rate=0.1, momentum=0.9,
                             dampening=0.0))
    if snap_path:
        opt.set_sharded_checkpoint(snap_path, Trigger.several_iteration(1))
    if resume_path:
        opt.resume_from(resume_path)
    opt.optimize()
    return m, opt


def test_spec_writer_death_leaves_torn_dir_discovery_skips(tmp_path):
    """The PR-1 torn-checkpoint contract on the SPEC-sharded path, at
    two restore mesh shapes: a writer killed mid-save leaves a snapshot
    directory without orbax's commit markers; discovery must skip it
    and the cross-mesh restore must resume the last COMMITTED step."""
    path = str(tmp_path / "snaps")
    _spec_run((2, 2, 2), 3, snap_path=path)
    assert ckpt.latest_step(path) == 3

    # a host killed mid-save: data files landed, finalize never ran —
    # the exact on-disk state minus the commit markers
    shutil.copytree(os.path.join(path, "3"), os.path.join(path, "4"))
    for name in ("_CHECKPOINT_METADATA", "_METADATA",
                 "commit_success.txt"):
        p = os.path.join(path, "4", name)
        if os.path.isdir(p):
            shutil.rmtree(p)
        elif os.path.exists(p):
            os.remove(p)
    assert not ckpt.verify_sharded(path, 4)
    assert ckpt.latest_step(path) == 3        # torn step 4 screened out

    # uninterrupted same-seed reference
    m_ref, _ = _spec_run((2, 2, 2), 5)
    ref = np.concatenate([np.ravel(np.asarray(l)) for l in
                          jax.tree_util.tree_leaves(m_ref.params)])
    for restore_shape in ((2, 2, 2), (4, 2, 1)):
        m, opt = _spec_run(restore_shape, 5, resume_path=path)
        assert opt.state["neval"] == 5        # resumed 3, trained 2 more
        got = np.concatenate([np.ravel(np.asarray(l)) for l in
                              jax.tree_util.tree_leaves(m.params)])
        np.testing.assert_allclose(got, ref, atol=1e-5, rtol=1e-5)


# -- in-process elastic world change (join + loss) ----------------------------

class _Throttle(Transformer):
    """Per-batch sleep: wall-clock room for the membership protocol
    between steps; numerics untouched."""

    def __init__(self, delay_s):
        self.delay_s = delay_s

    def apply(self, prev):
        for x in prev:
            time.sleep(self.delay_s)
            yield x


def _corpus():
    rs = np.random.RandomState(0)
    x = rs.randn(64, 4).astype(np.float32)
    y = (((x[:, 0] * x[:, 1]) > 0).astype(np.float32)) + 1.0
    return [Sample(x[i], y[i]) for i in range(64)]


def _mlp16():
    m = nn.Sequential()
    m.add(nn.Linear(4, 16))
    m.add(nn.Tanh())
    m.add(nn.Linear(16, 2))
    m.add(nn.LogSoftMax())
    m.build(seed=7)
    return m


def _lease_step(root, host):
    try:
        with open(os.path.join(root, "hosts", f"{host}.json")) as f:
            return int(json.load(f).get("step", 0))
    except (OSError, json.JSONDecodeError, ValueError):
        return 0


def _elastic_world_change_run(tmp_path, sharding):
    """Host "a" trains elastically; a peer coordinator thread joins at
    step 3 (world 1 -> 2: mesh 2 devices -> 4) and silently dies at step
    8 (world back to 1).  Returns (model, run_dir, coordinator)."""
    root = str(tmp_path / "coord")
    run_dir = str(tmp_path / "ledger")
    run_ledger.set_run_dir(run_dir)
    try:
        ds = DataSet.array(_corpus()) >> SampleToBatch(8) >> \
            _Throttle(0.12)
        m = _mlp16()
        coord = ElasticCoordinator(root, "a", lease_s=0.5, poll_s=0.02,
                                   devices_per_host=2, bootstrap_world=1)
        opt = DistriOptimizer(m, nn.ClassNLLCriterion(), ds,
                              end_when=Trigger.max_iteration(14),
                              mesh=mesh_mod.build_mesh((2, 1, 1)),
                              compress=None, sharding=sharding)
        opt.set_optim_method(SGD(learning_rate=0.3, momentum=0.9,
                                 dampening=0.0))
        opt.set_seed(3)
        opt.set_sharded_checkpoint(str(tmp_path / "ckpt"),
                                   Trigger.several_iteration(2))
        opt.set_elastic(coord)
        taken = record_steps(opt)

        def peer():
            while _lease_step(root, "a") < 3:
                time.sleep(0.02)
            cb = ElasticCoordinator(root, "b", lease_s=0.5, poll_s=0.02,
                                    devices_per_host=2,
                                    bootstrap_world=1)
            cb.start()
            while _lease_step(root, "a") < 8:
                cb.check()
                time.sleep(0.02)
            cb.stop(leave=False)      # silent death

        t = threading.Thread(target=peer, daemon=True)
        t.start()
        opt.optimize()
        t.join(timeout=30)
        run_ledger.flush()
    finally:
        run_ledger.set_run_dir(None)
    assert opt.state["neval"] == 14
    return m, run_dir, coord, taken


def _uninterrupted_reference(sharding):
    ds = DataSet.array(_corpus()) >> SampleToBatch(8)
    m = _mlp16()
    opt = DistriOptimizer(m, nn.ClassNLLCriterion(), ds,
                          end_when=Trigger.max_iteration(14),
                          mesh=mesh_mod.build_mesh((2, 1, 1)),
                          compress=None, sharding=sharding)
    opt.set_optim_method(SGD(learning_rate=0.3, momentum=0.9,
                             dampening=0.0))
    opt.set_seed(3)
    opt.optimize()
    return m


def _flat_weights(m):
    return np.concatenate([np.ravel(np.asarray(l)) for l in
                           jax.tree_util.tree_leaves(m.params)])


def _assert_world_change_run(tmp_path, sharding):
    m, run_dir, coord, taken = _elastic_world_change_run(tmp_path,
                                                        sharding)
    # every step, the replayed ones too, trained the batch the
    # uninterrupted order gives it: the batch in flight when a world
    # change aborted an attempt was dropped, not trained, and the restore
    # replayed the shuffle stream to the snapshot's epoch
    x = np.stack([s.feature for s in _corpus()])
    rng, perm = np.random.RandomState(1), np.arange(64)
    rows = list(perm.reshape(8, 8).copy())   # epoch 1: as stored
    rng.shuffle(perm)                        # epoch 2: the first shuffle
    rows += list(perm.reshape(8, 8))
    assert {n for n, _ in taken} == set(range(14))
    for stepno, data in taken:
        np.testing.assert_array_equal(data, x[rows[stepno]])
    # the fleet saw: bootstrap (gen 1) -> join (gen 2) -> loss (gen 3)
    final = coord._read_generation()
    assert final.gen >= 3 and final.hosts == ("a",)
    # loss-curve continuity: both transitions resharded from committed
    # snapshots, so the run lands within float-reassociation tolerance
    # of the uninterrupted same-seed run
    ref = _uninterrupted_reference(sharding)
    np.testing.assert_allclose(_flat_weights(m), _flat_weights(ref),
                               atol=5e-2)
    records, _ = load_ledger(run_dir)
    kinds = {}
    for r in records:
        if r.get("type") == "event":
            k = str(r.get("kind", ""))
            kinds[k] = kinds.get(k, 0) + 1
    assert kinds.get("elastic.generation", 0) >= 3
    assert kinds.get("elastic.join", 0) >= 1
    assert kinds.get("elastic.lease_lost", 0) >= 1
    assert kinds.get("elastic.reshape", 0) >= 2
    assert kinds.get("elastic.restore", 0) >= 2
    assert kinds.get("elastic.resume", 0) >= 2
    assert kinds.get("watchdog.paused", 0) >= 2
    # the run-report elasticity census renders the same story
    rep = build_report(records)
    el = rep["elastic"]
    assert el["generations"] >= 3
    assert el["max_generation"] == final.gen
    assert el["hosts_joined"] >= 1 and el["hosts_lost"] >= 1
    assert el["reshapes"] >= 2 and el["restores"] >= 2
    assert el["steps_replayed"] >= 0
    assert el["watchdog_pauses"] >= 2


def test_elastic_world_change_spec(tmp_path):
    """Join + lease-loss against a live spec-sharded trainer, in one
    process: mesh grows 2 -> 4 devices and shrinks back, resharding the
    committed snapshot each time (the PR-7 cross-mesh restore, live)."""
    _assert_world_change_run(tmp_path, "spec")


@pytest.mark.slow
def test_elastic_world_change_flat(tmp_path):
    """Same drill on the flat ZeRO-1 ring: the ring-size-portable
    restore re-grids the (n_old, shard) snapshot onto the new ring."""
    _assert_world_change_run(tmp_path, "flat")


def test_elastic_requires_sharded_checkpoint(tmp_path):
    coord = _coord(tmp_path, "a", bootstrap_world=1)
    opt = DistriOptimizer(_mlp16(), nn.ClassNLLCriterion(),
                          DataSet.array(_corpus()) >> SampleToBatch(8),
                          end_when=Trigger.max_iteration(1),
                          mesh=mesh_mod.build_mesh((2, 1, 1)))
    opt.set_elastic(coord)
    with pytest.raises(ValueError, match="set_sharded_checkpoint"):
        opt.optimize()


def test_elastic_rejects_auto_resume_off(tmp_path):
    """auto_resume=False would make the reshape path skip the
    committed-snapshot restore and silently diverge the resized
    fleet — rejected at optimize()."""
    coord = _coord(tmp_path / "c", "a", bootstrap_world=1)
    opt = DistriOptimizer(_mlp16(), nn.ClassNLLCriterion(),
                          DataSet.array(_corpus()) >> SampleToBatch(8),
                          end_when=Trigger.max_iteration(1),
                          mesh=mesh_mod.build_mesh((2, 1, 1)))
    opt.set_sharded_checkpoint(str(tmp_path / "snaps"),
                               Trigger.several_iteration(1),
                               auto_resume=False)
    opt.set_elastic(coord)
    with pytest.raises(ValueError, match="auto_resume"):
        opt.optimize()


def test_elastic_rejects_foreign_resume_from(tmp_path):
    """The generation pins restore steps discovered in the snapshot
    dir; a resume_from pointing elsewhere would be silently ignored or
    restore a wrong-directory step — it must be rejected loudly."""
    coord = _coord(tmp_path / "c", "a", bootstrap_world=1)
    opt = DistriOptimizer(_mlp16(), nn.ClassNLLCriterion(),
                          DataSet.array(_corpus()) >> SampleToBatch(8),
                          end_when=Trigger.max_iteration(1),
                          mesh=mesh_mod.build_mesh((2, 1, 1)))
    opt.set_sharded_checkpoint(str(tmp_path / "snaps"),
                               Trigger.several_iteration(1))
    opt.resume_from(str(tmp_path / "other-run"))
    opt.set_elastic(coord)
    with pytest.raises(ValueError, match="resume_from"):
        opt.optimize()


# -- run-report elasticity census (synthetic ledger) --------------------------

def test_report_elastic_census_fields(tmp_path):
    recs = [
        {"type": "event", "kind": "elastic.generation", "gen": 1,
         "hosts": ["a", "b", "c"], "world": 3, "mono": 1.0, "ts": 1.0},
        {"type": "event", "kind": "elastic.lease_lost", "host": "c",
         "gen": 2, "mono": 2.0, "ts": 2.0},
        {"type": "event", "kind": "elastic.generation", "gen": 2,
         "hosts": ["a", "b"], "world": 2, "mono": 3.0, "ts": 3.0},
        {"type": "event", "kind": "elastic.reshape", "gen": 2,
         "mono": 4.0, "ts": 4.0},
        {"type": "event", "kind": "elastic.restore", "gen": 2,
         "step": 10, "mono": 5.0, "ts": 5.0},
        {"type": "event", "kind": "elastic.resume", "gen": 2,
         "step": 10, "replayed_steps": 3, "mono": 6.0, "ts": 6.0},
        {"type": "event", "kind": "elastic.join", "host": "c",
         "gen": 3, "mono": 7.0, "ts": 7.0},
        {"type": "event", "kind": "elastic.generation", "gen": 3,
         "hosts": ["a", "b", "c"], "world": 3, "mono": 8.0, "ts": 8.0},
        {"type": "event", "kind": "watchdog.paused",
         "label": "elastic.reshape", "dur_s": 0.5, "mono": 9.0,
         "ts": 9.0},
    ]
    (tmp_path / "events-1.jsonl").write_text(
        "".join(json.dumps(r) + "\n" for r in recs))
    records, _ = load_ledger(str(tmp_path))
    rep = build_report(records)
    el = rep["elastic"]
    assert el == {"generations": 3, "max_generation": 3,
                  "final_world": 3, "hosts_lost": 1, "hosts_joined": 1,
                  "reshapes": 1, "restores": 1, "steps_replayed": 3,
                  "watchdog_pauses": 1, "fenced": 0}
    # a run with no elastic events reports None (section omitted)
    assert build_report([{"type": "step", "step": 0, "_pid": 1}])[
        "elastic"] is None


# -- the chaos drill (the revived multi-process multihost tier) ---------------

def _run_drill(tmp_path, extra):
    env = dict(os.environ,
               PYTHONPATH=REPO + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)
    env.pop("BIGDL_TPU_RUN_DIR", None)
    env.pop("BIGDL_TPU_FAULTS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.cli", "train-drill",
         "--dir", str(tmp_path / "drill")] + extra,
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, timeout=500)
    return proc


def test_train_drill_smoke(tmp_path):
    """The headline acceptance drill in its CI shape: 2 simulated host
    processes, one SIGKILLed mid-epoch and re-admitted; exit 0 means
    every check held (generation commits, resharded restores, weight
    agreement, loss continuity, zero lost/double-counted records)."""
    proc = _run_drill(tmp_path, ["--smoke"])
    assert proc.returncode == 0, proc.stdout[-4000:]
    assert "all checks passed" in proc.stdout
    # the drill's ledger renders an elasticity census through run-report
    records, _ = load_ledger(str(tmp_path / "drill" / "ledger"))
    el = build_report(records)["elastic"]
    assert el["generations"] >= 3
    assert el["hosts_lost"] >= 1 and el["hosts_joined"] >= 1


@pytest.mark.slow
@pytest.mark.parametrize("sharding", ["spec", "flat"])
def test_train_drill_full(tmp_path, sharding):
    """Full 3-host x 2-device drill, both sharding modes — the
    multi-process multihost tier, revived with simulated collectives."""
    proc = _run_drill(tmp_path, ["--sharding", sharding])
    assert proc.returncode == 0, proc.stdout[-4000:]
    assert "all checks passed" in proc.stdout
