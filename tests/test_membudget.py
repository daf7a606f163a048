"""HBM pressure survival tests (ISSUE 20): device-memory budgeter,
typed byte-starvation sheds, host-RAM KV offload tier
(``bigdl_tpu/serving/scheduler/membudget.py`` + the session machinery
in ``continuous.py``).

The acceptance criteria, as tests:

* budgeter: charge/discharge/transfer accounting is exact and fails
  loudly on below-zero or unknown classes; ``admit`` runs the reclaim
  ladder; ``require_possible`` sheds only the can-never-fit request;
* park/resume: a parked-then-resumed session's outputs are BIT-EQUAL
  to the never-parked reference (learned positions AND rope), with
  prefix-shared pages refcount-pinned on device through the park;
* budget accounting is exact across the whole session lifecycle —
  after close-all, ``kv_pages`` and ``host_offload`` charges are zero;
* the concurrent park-vs-decode race resolves to "park after the turn
  retires, or not at all" — never a corrupted output;
* a request whose bytes can never fit sheds typed
  (``MemoryBudgetError``) at admission while neighbors land intact;
* run-report's ``memory`` census carries the ``mem.budget`` /
  ``mem.offload`` trail with an exact-key ``--json`` shape.
"""

import json

import pytest

import numpy as np

from bigdl_tpu.serving.errors import MemoryBudgetError
from bigdl_tpu.serving.scheduler.membudget import (CHARGE_CLASSES,
                                                   MemoryBudgeter)
from bigdl_tpu.serving.scheduler.paging import HostOffloadTier

pytestmark = pytest.mark.serving


def _lm(**kw):
    import jax

    from bigdl_tpu.models.transformer import TransformerLM
    kw.setdefault("vocab_size", 64)
    kw.setdefault("max_len", 64)
    m = TransformerLM(embed_dim=32, num_heads=2, num_layers=2, **kw)
    params, state = m.init(jax.random.PRNGKey(0))
    return m, params, state


def _gen(m, params, state, **kw):
    from bigdl_tpu.serving.scheduler.continuous import ContinuousGenerator
    kw.setdefault("num_slots", 2)
    kw.setdefault("seq_buckets", [16])
    kw.setdefault("steps_per_sync", 2)
    kw.setdefault("page_size", 4)
    return ContinuousGenerator(m, params, state, **kw)


def _ref(m, params, state, prompt, max_new):
    return np.asarray(m.generate(params, state,
                                 np.asarray(prompt, np.int32)[None],
                                 max_new=max_new, temperature=0.0))[0]


# -- the budgeter alone -------------------------------------------------------

def test_budgeter_accounting_exact():
    b = MemoryBudgeter()
    b.set_budget("a", 1000)
    b.charge("a", "kv_pages", 600)
    b.charge("a", "prefix_pages", 100)
    assert b.charged("a") == 700 and b.charged("a", "kv_pages") == 600
    assert b.headroom("a") == 300
    assert b.occupancy("a") == pytest.approx(0.7)
    # host_offload is NOT device bytes: parking frees headroom
    b.transfer("a", "kv_pages", "host_offload", 400)
    assert b.charged("a", "kv_pages") == 200
    assert b.charged("a", "host_offload") == 400
    assert b.headroom("a") == 700
    b.discharge("a", "prefix_pages", 100)
    with pytest.raises(ValueError, match="below zero"):
        b.discharge("a", "kv_pages", 300)
    with pytest.raises(ValueError, match="unknown charge class"):
        b.charge("a", "rope_tables", 1)
    # unlimited tenant: no budget, no occupancy, admit always passes
    assert b.budget("z") is None and b.headroom("z") is None
    assert b.occupancy("z") == 0.0
    b.admit("z", 10 ** 12, what="huge")
    snap = b.snapshot()
    assert set(snap["tenants"]["a"]["charged"]) == set(CHARGE_CLASSES)
    b.drop_tenant("a")
    assert "a" not in b.snapshot()["tenants"]


def test_budgeter_admit_reclaims_then_sheds_typed():
    b = MemoryBudgeter()
    b.set_budget("a", 100)
    b.charge("a", "rung_executables", 80)
    freed = {"n": 0}

    def reclaimer(tenant, need):
        got = min(need, b.charged("a", "rung_executables"))
        b.discharge("a", "rung_executables", got)
        freed["n"] += got
        return got

    b.register_reclaimer("rungs", reclaimer)
    b.admit("a", 60, what="req")           # reclaims 40, then fits
    assert freed["n"] == 40
    b.charge("a", "kv_pages", 60)
    # can never fit: require_possible sheds even with zero charged
    with pytest.raises(MemoryBudgetError, match="can never fit"):
        b.require_possible("a", 101, what="monster")
    b.require_possible("a", 100, what="barely")    # possible: passes
    with pytest.raises(MemoryBudgetError) as ei:
        b.admit("a", 100, what="req2")     # ladder dry at 60 charged
    assert ei.value.reason == "byte_starved"
    assert b.snapshot()["tenants"]["a"]["sheds"] == 2


def test_host_offload_tier_bookkeeping():
    t = HostOffloadTier()
    t.park("s1", [{"k": np.ones(2)}], 100)
    t.park("s2", [], 0)
    assert len(t) == 2 and "s1" in t
    with pytest.raises(ValueError, match="already parked"):
        t.park("s1", [], 1)
    payload = t.resume("s1")
    assert payload[0]["k"].shape == (2,)
    with pytest.raises(KeyError):
        t.resume("s1")
    assert t.drop("s2") == 0 and t.drop("nope") == 0
    st = t.stats()
    assert st["parks"] == 2 and st["resumes"] == 1
    assert st["parked_bytes"] == 0 and st["peak_parked_bytes"] == 100


# -- park/resume bit-equality -------------------------------------------------

@pytest.mark.parametrize("position", ["learned", "rope"])
def test_park_resume_bit_equal_vs_never_parked(position):
    """An explicitly parked session's next turn (transparent resume)
    is bit-equal to the single-shot reference over the same history —
    for learned positions and rope both.  What is parked is whole pages
    of the token-major pool, ``(pages, page size, width)`` a tensor:
    park and resume index the page axis and nothing else."""
    m, params, state = _lm(position=position)
    t1 = np.arange(1, 9, dtype=np.int32)
    t2 = np.array([11, 12, 13], np.int32)
    with _gen(m, params, state, num_pages=32) as g:
        out1 = g.submit(t1, 5, session="s").result(timeout=60)
        held = g.session_info("s")["private_pages"]
        assert g.park("s").result(timeout=30) is True
        info = g.session_info("s")
        assert info["state"] == "parked" and info["private_pages"] == 0
        assert g.stats()["offload"]["parked_sessions"] == 1
        payload = g._offload._parked["s"][0]
        assert len(payload) == 2 and all(
            t.shape == (held, 4, 128) for layer in payload
            for t in layer.values())
        out2 = g.submit(t2, 5, session="s").result(timeout=60)
        assert g.session_info("s")["state"] == "resident"
    np.testing.assert_array_equal(
        out1, _ref(m, params, state, t1, 5))
    np.testing.assert_array_equal(
        out2, _ref(m, params, state,
                   np.concatenate([t1, out1, t2]), 5))


def test_park_pins_shared_prefix_pages_on_device():
    """Two sessions share a page-aligned prefix; parking one moves
    ONLY its private pages — the shared pages stay on device,
    refcount-pinned, and the other session keeps decoding bit-equal
    against them."""
    m, params, state = _lm()
    shared = np.arange(1, 9, dtype=np.int32)          # 2 full pages
    with _gen(m, params, state, num_pages=32) as g:
        oa = g.submit(shared, 4, session="a").result(timeout=60)
        ob = g.submit(shared, 4, session="b").result(timeout=60)
        np.testing.assert_array_equal(oa, ob)
        ia = g.session_info("a")
        assert ia["shared_pages"] >= 1
        assert g.park("a").result(timeout=30) is True
        # the shared pages did not leave the device with the park:
        # only the private tail bytes are in the host tier
        pb = g.stats()["pages"]["page_bytes"]
        parked = g.stats()["offload"]["parked_bytes"]
        assert parked == ia["private_pages"] * pb
        # the neighbor still decodes THROUGH the pinned shared pages
        ob2 = g.submit(np.array([20, 21], np.int32), 4,
                       session="b").result(timeout=60)
        np.testing.assert_array_equal(
            ob2, _ref(m, params, state,
                      np.concatenate([shared, ob, [20, 21]]), 4))
        # resume the parked one: bit-equal too
        oa2 = g.submit(np.array([20, 21], np.int32), 4,
                       session="a").result(timeout=60)
        np.testing.assert_array_equal(oa2, ob2)


def test_budget_accounting_exact_across_lifecycle():
    """Every page the generator touches is charged and discharged
    exactly: mid-flight the kv/offload charges match the live page
    census, and after close-all both return to zero."""
    m, params, state = _lm()
    bud = MemoryBudgeter()
    with _gen(m, params, state, num_pages=32, budgeter=bud,
              budget_tenant="t") as g:
        pb = g.stats()["pages"]["page_bytes"]
        for i in range(3):
            g.submit(np.arange(1, 9, dtype=np.int32), 4,
                     session=f"s{i}").result(timeout=60)
        assert g.park("s0").result(timeout=30) is True
        snap = bud.snapshot()["tenants"]["t"]["charged"]
        st = g.stats()
        live_priv = sum(
            g.session_info(f"s{i}")["private_pages"] for i in range(3))
        assert snap["kv_pages"] == live_priv * pb
        assert snap["host_offload"] == st["offload"]["parked_bytes"]
        held = (st["prefix"]["inserted_pages"]
                - st["prefix"]["evicted_pages"])
        assert snap["prefix_pages"] == held * pb
        for i in range(3):
            assert g.close_session(f"s{i}").result(timeout=30) is True
        g.drain(timeout=30)
        snap = bud.snapshot()["tenants"]["t"]["charged"]
        assert snap["kv_pages"] == 0 and snap["host_offload"] == 0
    assert bud.snapshot()["device_bytes"] == \
        bud.snapshot()["tenants"]["t"]["charged"]["prefix_pages"]


def test_concurrent_park_vs_decode_race():
    """A park racing a live turn resolves to 'after the turn retires,
    or not at all' — the scheduler thread owns the page table, so the
    command can only observe the session idle or busy, never mid-step.
    Either way the output is bit-equal and the session survives."""
    m, params, state = _lm()
    t1 = np.arange(1, 7, dtype=np.int32)
    with _gen(m, params, state, num_pages=32) as g:
        fut = g.submit(t1, 12, session="s")
        parks = [g.park("s") for _ in range(4)]   # racing commands
        out = fut.result(timeout=60)
        results = [p.result(timeout=30) for p in parks]
        assert all(r in (True, False) for r in results)
        info = g.session_info("s")
        assert info is not None and info["state"] in ("resident",
                                                      "parked")
        # deterministic tail: once the turn retired, a park sticks
        if info["state"] != "parked":
            assert g.park("s").result(timeout=30) is True
        out2 = g.submit(np.array([9], np.int32), 4,
                        session="s").result(timeout=60)
    np.testing.assert_array_equal(out, _ref(m, params, state, t1, 12))
    np.testing.assert_array_equal(
        out2, _ref(m, params, state,
                   np.concatenate([t1, out, [9]]), 4))


def test_byte_starved_shed_typed_neighbors_intact():
    """A request whose worst-case KV bytes exceed the whole tenant
    budget sheds MemoryBudgetError at submit; in-flight neighbors land
    bit-equal and the shed is attributed in the budgeter census."""
    m, params, state = _lm()
    bud = MemoryBudgeter()
    rs = np.random.RandomState(5)
    prompts = [rs.randint(1, 65, size=6).astype(np.int32)
               for _ in range(3)]
    with _gen(m, params, state, num_pages=16, budgeter=bud,
              budget_tenant="t") as g:
        pb = g.stats()["pages"]["page_bytes"]
        bud.set_budget("t", 15 * pb)
        futs = [g.submit(p, 5) for p in prompts]
        flood = rs.randint(1, 65, size=10).astype(np.int32)
        with pytest.raises(MemoryBudgetError,
                           match="can never fit") as ei:
            g.submit(flood, 64 - flood.size)       # 16 pages > budget
        assert ei.value.reason == "byte_starved"
        # the session path sheds through the same guard
        with pytest.raises(MemoryBudgetError, match="can never fit"):
            g.submit(flood, 64 - flood.size, session="big")
        assert g.session_info("big") is None       # no zombie latch
        outs = [f.result(timeout=60) for f in futs]
    for p, o in zip(prompts, outs):
        np.testing.assert_array_equal(o, _ref(m, params, state, p, 5))
    assert bud.snapshot()["tenants"]["t"]["sheds"] == 2


# -- run-report memory census -------------------------------------------------

def test_run_report_memory_census_exact_json(tmp_path):
    from bigdl_tpu.observability import ledger as run_ledger
    from bigdl_tpu.observability.report import (build_report,
                                                load_ledger,
                                                render_report)
    run_ledger.set_run_dir(str(tmp_path))
    try:
        b = MemoryBudgeter()
        b.set_budget("a", 1000)
        b.charge("a", "kv_pages", 600)
        b.transfer("a", "kv_pages", "prefix_pages", 200)
        b.transfer("a", "kv_pages", "host_offload", 300)
        b.discharge("a", "kv_pages", 100)
        with pytest.raises(MemoryBudgetError):
            b.require_possible("a", 2000, what="monster")
        run_ledger.emit("mem.offload", action="park", sid="s0",
                        pages=2, bytes=300, reason="pressure", kv_pos=9)
        run_ledger.emit("mem.offload", action="resume", sid="s0",
                        pages=2, bytes=300, kv_pos=9)
        run_ledger.emit("mem.offload", action="close", sid="s0",
                        kv_pos=9)
        run_ledger.flush()
    finally:
        run_ledger.set_run_dir(None)
    records, bad = load_ledger(str(tmp_path))
    assert bad == 0
    rep = build_report(records)
    mem = rep["memory"]
    # the exact --json shape downstream dashboards key on
    assert sorted(mem) == ["closes", "park_bytes", "parks", "reclaims",
                           "resume_bytes", "resumes", "sheds",
                           "tenants"]
    assert sorted(mem["tenants"]["a"]) == [
        "budget", "charged", "device_bytes", "reclaimed_bytes",
        "reclaims", "shed_bytes", "sheds"]
    # charged-by-class is an exact replay of the deltas
    assert mem["tenants"]["a"]["charged"] == {
        "kv_pages": 0, "prefix_pages": 200, "host_offload": 300}
    assert mem["tenants"]["a"]["budget"] == 1000
    assert mem["tenants"]["a"]["sheds"] == 1
    assert (mem["parks"], mem["resumes"], mem["closes"]) == (1, 1, 1)
    assert mem["park_bytes"] == 300 and mem["resume_bytes"] == 300
    json.dumps(rep, sort_keys=True, default=str)   # --json safe
    text = render_report(rep)
    assert "-- memory (budget & offload census) --" in text
    assert "tenant a" in text and "byte-shed" in text
