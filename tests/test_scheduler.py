"""Scale-out serving tests (ISSUE 8): worker pool, shape buckets,
continuous batching (``bigdl_tpu/serving/scheduler``).

The acceptance criteria, as tests:

* pool: pred parity through ``num_workers > 1`` with a bucket ladder;
  one worker's injected forwards open ITS breaker only while the fleet
  keeps serving; drain reaches a terminal state for every accepted
  request (zero lost);
* buckets: strict ladder validation, nearest-rung pick, per-batch
  ``bucket``/``padding_efficiency`` on the ledger and in the report's
  per-bucket census;
* continuous batching: greedy output BIT-EQUAL to
  ``TransformerLM.generate`` per request across mixed prompt/budget
  traffic with fewer slots than requests (admit + evict really
  interleave); an over-capacity admit sheds typed
  (``SlotCapacityError``) and cannot corrupt a neighbor slot's
  in-flight generation; slot occupancy lands in ``serve.slots``
  records and the report;
* serving x mesh: ``InferenceServer`` over ``DLClassifier(mesh=...)``
  with dp > 1 — pred parity, worker placement recorded in
  ``mesh.topology``;
* ``bench-serve --smoke`` runs on the fast tier and writes a
  well-formed artifact.
"""

import os

import pytest

import jax
import numpy as np

import bigdl_tpu.nn as nn
from bigdl_tpu.api import DLClassifier
from bigdl_tpu.models.transformer import TransformerLM
from bigdl_tpu.parallel.mesh import build_mesh, worker_placement
from bigdl_tpu.resilience import FaultInjector
from bigdl_tpu.serving import (BreakerOpenError, BucketLadder,
                               ContinuousGenerator, ForwardFailedError,
                               InferenceServer, InvalidRequestError,
                               SlotCapacityError, SlotManager,
                               pad_to_bucket)

pytestmark = pytest.mark.serving

FEATURES = 4


@pytest.fixture(autouse=True)
def _clean_injector():
    FaultInjector.clear()
    yield
    FaultInjector.clear()


def _model():
    m = nn.Sequential()
    m.add(nn.Linear(FEATURES, 3))
    m.add(nn.LogSoftMax())
    m.build(jax.random.PRNGKey(0))
    return m


def _rows(n, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.rand(FEATURES).astype(np.float32) for _ in range(n)]


def _settle(server, timeout=5.0):
    """Wait until no worker has a batch in flight (the in-flight count
    decrements AFTER futures resolve, so tests that rely on the
    least-loaded tie-break must wait for it)."""
    import time
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        if all(w["pending"] == 0
               for w in server.stats()["workers"].values()):
            return
        time.sleep(0.001)


def _lm(vocab=64, max_len=64, embed=32, heads=2, layers=2, **kw):
    m = TransformerLM(vocab_size=vocab, max_len=max_len, embed_dim=embed,
                      num_heads=heads, num_layers=layers, **kw)
    params, state = m.init(jax.random.PRNGKey(0))
    return m, params, state


# -- bucket ladder ------------------------------------------------------------

def test_bucket_ladder_pick_and_validation():
    lad = BucketLadder([32, 8, 128])
    assert list(lad) == [8, 32, 128]
    assert lad.pick(1) == 8 and lad.pick(8) == 8
    assert lad.pick(9) == 32 and lad.pick(128) == 128
    with pytest.raises(ValueError, match="exceeds the largest"):
        lad.pick(129)
    with pytest.raises(ValueError, match="empty"):
        BucketLadder([])
    with pytest.raises(ValueError, match="duplicate"):
        BucketLadder([8, 8])
    with pytest.raises(ValueError, match="non-positive"):
        BucketLadder([0, 8])
    x = np.ones((3, FEATURES), np.float32)
    assert pad_to_bucket(x, 8).shape == (8, FEATURES)
    assert np.all(pad_to_bucket(x, 8)[3:] == 0)
    with pytest.raises(ValueError, match="do not fit"):
        pad_to_bucket(x, 2)


# -- worker pool + buckets ----------------------------------------------------

def test_pool_pred_parity_with_buckets_and_ledger(tmp_path):
    """Mixed partial waves through 3 workers and a 3-rung ladder: every
    prediction matches the eager forward, and the ledger's serve.batch
    records carry worker, bucket, and padding efficiency — rendered by
    the report's per-worker / per-bucket censuses."""
    from bigdl_tpu.observability import ledger as run_ledger
    from bigdl_tpu.observability.report import build_report, load_ledger

    run_dir = str(tmp_path / "pool")
    run_ledger.set_run_dir(run_dir)
    try:
        m = _model()
        clf = DLClassifier(m, (8, FEATURES))
        server = InferenceServer(clf, num_workers=3,
                                 batch_buckets=[2, 4, 8],
                                 max_delay_s=0.003)
        rows = _rows(34)                  # 4 full waves + a tail of 2
                                          # (the tail fits rung 2)
        got = server.predict(rows)
        eager = np.argmax(np.asarray(m.forward(np.stack(rows))),
                          axis=1) + 1
        np.testing.assert_array_equal(got, eager)
        st = server.stats()
        assert set(st["workers"]) == {0, 1, 2}
        assert st["buckets"] == [2, 4, 8]
        assert server.drain(timeout=10)
    finally:
        run_ledger.set_run_dir(None)

    records, bad = load_ledger(run_dir, strict=True)
    assert bad == 0
    batches = [r for r in records if r.get("type") == "serve.batch"
               and r.get("status") == "ok"]
    assert batches
    for b in batches:
        assert b["worker"] in (0, 1, 2)
        assert b["bucket"] in (2, 4, 8)
        assert 0.0 < b["padding_efficiency"] <= 1.0
        assert b["size"] <= b["bucket"]
    # at least one partial batch really landed in a smaller rung
    assert any(b["bucket"] < 8 for b in batches)
    rep = build_report(records)["serving"]
    assert set(rep["workers"]) <= {0, 1, 2} and rep["workers"]
    assert rep["buckets"]
    for bk, e in rep["buckets"].items():
        assert 0.0 < e["mean_padding_efficiency"] <= 1.0
    start = next(r for r in records if r.get("type") == "run.start")
    assert start["workers"] == 3 and start["buckets"] == [2, 4, 8]


def test_pool_isolates_one_faulted_worker():
    """The pool acceptance drill, as a unit test: kill worker 0's
    forwards through its per-worker fault site — its breaker opens,
    every other worker keeps serving, drain loses zero requests."""
    m = _model()
    server = InferenceServer(DLClassifier(m, (4, FEATURES)),
                             num_workers=2, max_delay_s=0.05,
                             breaker_threshold=2, breaker_reset_s=60.0)
    accepted = []
    try:
        FaultInjector.install(
            FaultInjector().add("serve.worker0.forward", count=2))
        for _ in range(2):                # sequential: tie-break -> w0
            futs = [server.submit(r) for r in _rows(4)]
            accepted += futs
            for f in futs:
                assert isinstance(f.exception(timeout=10),
                                  ForwardFailedError)
            _settle(server)
        ws = server.stats()["workers"]
        assert ws[0]["breaker"] == "open"
        assert ws[1]["breaker"] == "closed"
        # the fleet keeps serving around the open breaker
        rows = _rows(8, seed=7)
        futs = [server.submit(r) for r in rows]
        accepted += futs
        got = [f.result(timeout=10) for f in futs]
        eager = np.argmax(np.asarray(m.forward(np.stack(rows))),
                          axis=1) + 1
        assert got == [int(v) for v in eager]
        assert server.stats()["workers"][0]["breaker"] == "open"
    finally:
        FaultInjector.clear()
        assert server.drain(timeout=10)
    assert all(f.done() for f in accepted)


def test_fleet_open_sheds_and_recovers():
    """When EVERY worker's breaker is open, submissions shed fast; after
    the cooldown the probe path closes a breaker and traffic recovers —
    the pool generalisation of the single-breaker lifecycle."""
    server = InferenceServer(DLClassifier(_model(), (2, FEATURES)),
                             num_workers=2, max_delay_s=0.02,
                             breaker_threshold=1, breaker_reset_s=0.1)
    try:
        # one armed fault per worker: each wave trips one breaker
        FaultInjector.install(FaultInjector()
                              .add("serve.worker0.forward", count=1)
                              .add("serve.worker1.forward", count=1))
        for _ in range(2):
            futs = [server.submit(r) for r in _rows(2)]
            for f in futs:
                assert isinstance(f.exception(timeout=10),
                                  ForwardFailedError)
            _settle(server)
        assert set(server.pool.breaker_states().values()) == {"open"}
        with pytest.raises(BreakerOpenError, match="every worker"):
            server.submit(_rows(1)[0])
        FaultInjector.clear()
        import time
        time.sleep(0.15)                  # cooldown -> probes admit
        assert server.predict(_rows(2, seed=3)).shape == (2,)
    finally:
        assert server.drain(timeout=10)


def test_worker_placement_over_mesh():
    mesh = build_mesh("2,2,2", devices=jax.devices()[:8])
    place = worker_placement(mesh, 3)
    assert [p["worker"] for p in place] == [0, 1, 2]
    assert [p["dp_group"] for p in place] == [0, 1, 2]   # 4 dp groups
    for p in place:
        assert len(p["devices"]) == 2                    # tp span
    flat = [d for p in worker_placement(mesh, 4) for d in p["devices"]]
    assert sorted(flat) == [int(d.id) for d in mesh.devices.flat]


def test_server_over_meshed_classifier(tmp_path):
    """Serving x mesh: the pool serves a ``DLClassifier(mesh=...)``
    with dp > 1 — pred parity with the un-meshed classifier, and the
    ledger records the serving mesh topology WITH the pool's worker
    placement."""
    from bigdl_tpu.observability import ledger as run_ledger
    from bigdl_tpu.observability.report import load_ledger

    m = _model()
    rows = _rows(12)
    plain = InferenceServer(DLClassifier(m, (4, FEATURES)),
                            max_delay_s=0.003)
    try:
        want = plain.predict(rows)
    finally:
        plain.drain(timeout=10)

    run_dir = str(tmp_path / "mesh")
    run_ledger.set_run_dir(run_dir)
    try:
        m2 = _model()
        mesh = build_mesh("2,2,2", devices=jax.devices()[:8])
        clf = DLClassifier(m2, (4, FEATURES), mesh=mesh)
        server = InferenceServer(clf, num_workers=2, max_delay_s=0.003)
        got = server.predict(rows)
        np.testing.assert_array_equal(got, want)
        assert server.drain(timeout=10)
    finally:
        run_ledger.set_run_dir(None)
    records, _ = load_ledger(run_dir, strict=True)
    topo = next(r for r in records if r.get("type") == "mesh.topology")
    assert topo["mode"] == "serving"
    assert topo["axes"] == {"data": 2, "fsdp": 2, "tp": 2}
    assert [w["worker"] for w in topo["workers"]] == [0, 1]
    # bucket must divide the dp shards; 4 % (2*2) == 0 holds above, and
    # an indivisible ladder is rejected at construction
    with pytest.raises(ValueError, match="dp shards"):
        InferenceServer(DLClassifier(_model(), (4, FEATURES), mesh=mesh),
                        batch_buckets=[2, 4], warmup=False)


# -- continuous batching ------------------------------------------------------

def test_continuous_matches_generate_bit_exact():
    """The correctness core: continuous batching with fewer slots than
    requests (admit/evict really interleave, mixed prompt lengths and
    budgets, two seq rungs) produces BIT-EQUAL greedy output to a
    per-request ``TransformerLM.generate``."""
    m, params, state = _lm()
    rs = np.random.RandomState(1)
    prompts = [rs.randint(1, 65, size=rs.randint(3, 14)).astype(np.int32)
               for _ in range(7)]
    budgets = [int(rs.randint(1, 12)) for _ in range(7)]
    refs = [np.asarray(m.generate(params, state, p[None], max_new=n,
                                  temperature=0.0))[0]
            for p, n in zip(prompts, budgets)]
    with ContinuousGenerator(m, params, state, num_slots=3,
                             seq_buckets=[8, 16], steps_per_sync=3) as g:
        futs = [g.submit(p, n) for p, n in zip(prompts, budgets)]
        outs = [f.result(timeout=60) for f in futs]
    for r, o in zip(refs, outs):
        np.testing.assert_array_equal(r, o)


def test_continuous_rope_model_parity():
    """Slot-addressable decode under per-row RoPE positions (the
    (B, T) apply_rope layout)."""
    m, params, state = _lm(position="rope")
    rs = np.random.RandomState(2)
    prompts = [rs.randint(1, 65, size=rs.randint(3, 9)).astype(np.int32)
               for _ in range(4)]
    refs = [np.asarray(m.generate(params, state, p[None], max_new=5,
                                  temperature=0.0))[0] for p in prompts]
    with ContinuousGenerator(m, params, state, num_slots=2,
                             seq_buckets=[16], steps_per_sync=2) as g:
        outs = [f.result(timeout=60)
                for f in [g.submit(p, 5) for p in prompts]]
    for r, o in zip(refs, outs):
        np.testing.assert_array_equal(r, o)


def test_over_capacity_admit_sheds_typed_not_corrupts():
    """The KV-overrun regression (satellite): an admit whose
    prompt+max_new exceeds the cache capacity raises SlotCapacityError
    at submit — and a neighbor's IN-FLIGHT generation is unaffected
    (the hazard being guarded: an admitted overrun would clamp into the
    last cache slot and corrupt whoever owns it)."""
    m, params, state = _lm(max_len=32)
    rs = np.random.RandomState(3)
    prompts = [rs.randint(1, 65, size=6).astype(np.int32)
               for _ in range(3)]
    refs = [np.asarray(m.generate(params, state, p[None], max_new=20,
                                  temperature=0.0))[0] for p in prompts]
    with ContinuousGenerator(m, params, state, num_slots=3,
                             seq_buckets=[8], steps_per_sync=2) as g:
        futs = [g.submit(p, 20) for p in prompts]   # 6+20 <= 32: fits
        with pytest.raises(SlotCapacityError, match="overrun"):
            g.submit(rs.randint(1, 65, size=8).astype(np.int32), 30)
        with pytest.raises(SlotCapacityError, match="prefill bucket"):
            g.submit(rs.randint(1, 65, size=12).astype(np.int32), 4)
        outs = [f.result(timeout=60) for f in futs]
    for r, o in zip(refs, outs):                     # neighbors intact
        np.testing.assert_array_equal(r, o)
    # the same bound holds eagerly on generate() itself
    with pytest.raises(ValueError, match="exceeds cache length"):
        m.generate(params, state, prompts[0][None], max_new=27)


def test_slot_manager_unit():
    sm = SlotManager(2, max_len=32, max_prompt=16)
    with pytest.raises(SlotCapacityError):
        sm.check(20, 13)
    with pytest.raises(SlotCapacityError):
        sm.check(17, 1)
    sm.check(16, 16)
    a, b = sm.alloc(), sm.alloc()
    assert {a, b} == {0, 1} and sm.alloc() is None
    assert sm.free_count == 0 and sm.active_count == 2
    sm.release(a)
    assert sm.alloc() == a


def test_continuous_occupancy_and_report(tmp_path):
    """Slot lifecycle observability: serve.slots records carry
    occupancy, the report renders the slots census, prefill/decode are
    distinct span phases, and eviction really frees slots mid-run
    (more requests than slots all complete)."""
    from bigdl_tpu.observability import ledger as run_ledger
    from bigdl_tpu.observability.report import build_report, load_ledger

    run_dir = str(tmp_path / "gen")
    run_ledger.set_run_dir(run_dir)
    try:
        m, params, state = _lm()
        rs = np.random.RandomState(4)
        with ContinuousGenerator(m, params, state, num_slots=2,
                                 seq_buckets=[8],
                                 steps_per_sync=2) as g:
            futs = [g.submit(rs.randint(1, 65, size=5).astype(np.int32),
                             int(rs.randint(2, 8))) for _ in range(6)]
            for f in futs:
                assert f.result(timeout=60) is not None
            st = g.stats()
            assert st["completed"] == 6
            assert 0.0 < st["mean_occupancy"] <= 1.0
    finally:
        run_ledger.set_run_dir(None)
    records, bad = load_ledger(run_dir, strict=True)
    assert bad == 0
    slots = [r for r in records if r.get("type") == "serve.slots"]
    assert slots and all(0 <= s["occupancy"] <= 1 for s in slots)
    spans = {r.get("name") for r in records if r.get("type") == "span"}
    assert "serve.prefill" in spans and "serve.decode" in spans
    rep = build_report(records)["serving"]
    assert rep["slots"]["capacity"] == 2
    assert rep["slots"]["tokens"] > 0
    assert 0.0 < rep["slots"]["mean_occupancy"] <= 1.0
    reqs = [r for r in records if r.get("type") == "serve.request"]
    assert sum(1 for r in reqs if r["status"] == "ok") == 6
    end = next(r for r in records if r.get("type") == "run.end")
    assert end["kind"] == "ContinuousGenerator" and end["completed"] == 6


def test_continuous_admission_sheds():
    m, params, state = _lm()
    g = ContinuousGenerator(m, params, state, num_slots=1,
                            seq_buckets=[8], queue_capacity=2)
    try:
        with pytest.raises(InvalidRequestError):
            g.submit(np.zeros(0, np.int32), 4)
        with pytest.raises(InvalidRequestError):
            g.submit(np.ones(4, np.int32), 0)
        with pytest.raises(SlotCapacityError):
            g.submit(np.ones(4, np.int32), 80)
        # every shed reason feeds the census, not just queue ones
        c = g.stats()["counters"]
        assert c["serve.shed.invalid"] == 2
        assert c["serve.shed.over_capacity"] == 1
    finally:
        assert g.drain(timeout=30)
    from bigdl_tpu.serving import DrainingError
    with pytest.raises(DrainingError):
        g.submit(np.ones(4, np.int32), 2)


def test_bucketed_runner_enforces_rungs():
    """The executable cache is a contract, not a convention: an
    off-ladder bucket and a pad/dispatch mismatch both fail loudly
    instead of letting jit mint a surprise steady-state executable
    (the runtime backstop for graftlint's shape-bucket-mismatch)."""
    from bigdl_tpu.serving import BucketedRunner

    runner = BucketedRunner(DLClassifier(_model(), (4, FEATURES)),
                            BucketLadder([2, 4]))
    runner.warmup()
    with pytest.raises(ValueError, match="not a ladder rung"):
        runner.run(np.zeros((3, FEATURES), np.float32), 3)
    with pytest.raises(ValueError, match="shape-bucket mismatch"):
        runner.run(np.zeros((2, FEATURES), np.float32), 4)
    out = runner.run(runner.pack(_rows(3), 4), 4)
    assert np.asarray(out).shape[0] == 4


# -- bench smoke (CI mode) ----------------------------------------------------

def test_bench_serve_smoke(tmp_path):
    from bigdl_tpu.cli import bench_serve
    import json

    out = str(tmp_path / "BENCH_serve_smoke.json")
    assert bench_serve(["--smoke", "--out", out]) == 0
    with open(out) as f:
        rep = json.load(f)
    assert set(rep["modes"]) == {"static", "bucketed", "continuous"}
    assert set(rep["ablations"]) == {"paged_prefix", "paged_prefix_spec"}
    for mode in list(rep["modes"].values()) + \
            list(rep["ablations"].values()):
        assert mode["tokens_per_s"] > 0
        assert mode["latency_p95_s"] > 0
        assert mode["useful_tokens"] == \
            rep["modes"]["static"]["useful_tokens"]
    assert 0 < rep["modes"]["continuous"]["mean_slot_occupancy"] <= 1
    assert 0 < rep["modes"]["static"]["mean_padding_efficiency"] <= 1
    acc = rep["acceptance"]
    assert acc["best_vs_continuous_tokens_per_s"] > 0
    assert set(acc["per_feature_vs_continuous"]) == set(rep["ablations"])
    # the shared-head mix really hit the prefix cache, and the draft
    # really had proposals judged (rates are config-dependent, their
    # PRESENCE and range are the contract)
    assert 0 < acc["prefix_hit_rate"] <= 1
    assert 0 <= acc["draft_accept_rate"] <= 1
    assert rep["ablations"]["paged_prefix_spec"]["draft_accept_rate"] \
        == acc["draft_accept_rate"]
    assert acc["outputs_bit_equal_across_variants"] is True
    # token-level occupancy (the figure row occupancy overstates)
    for mode in [rep["modes"]["continuous"], *rep["ablations"].values()]:
        assert 0 < mode["mean_token_occupancy"] <= 1
