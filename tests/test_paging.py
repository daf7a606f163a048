"""Paged KV cache, prefix cache, speculative decoding (ISSUE 11).

The acceptance criteria, as tests:

* allocator edges: all-or-nothing allocation, double-free raises (the
  aliasing guard), free-list reuse after evict never aliases a live
  slot's pages;
* paged continuous batching is BIT-EQUAL to ``TransformerLM.generate``
  (learned + RoPE positions, mixed lengths, fewer slots than
  requests) — and stays so under prefix-cache hits and under
  speculative decoding (accepted tokens are the target's greedy path);
* prefix cache: the shared head is prefilled once (hit counters,
  ``serve.cache`` ledger), refcounted pages are released only when the
  last reader evicts, copy-on-write divergence leaves the shared page
  byte-identical;
* page exhaustion: a never-fit request sheds typed
  ``SlotCapacityError`` while neighbor generations stay intact; a
  token-scarce pool serves everything admitted via holdback;
* observability: ``serve.pages`` token-level occupancy, prefix hit
  rate and draft accept rate land in the ledger, ``run-report``'s
  censuses and the live metrics gauges.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu.models.transformer import TransformerLM
from bigdl_tpu.serving import (PageAllocator, PrefixCache,
                               SlotCapacityError)
from bigdl_tpu.serving.scheduler.continuous import (ContinuousGenerator,
                                                    SlotManager)

pytestmark = pytest.mark.serving


def _lm(vocab=64, max_len=96, embed=32, heads=2, layers=2, **kw):
    m = TransformerLM(vocab_size=vocab, max_len=max_len, embed_dim=embed,
                      num_heads=heads, num_layers=layers, **kw)
    params, state = m.init(jax.random.PRNGKey(0))
    return m, params, state


def _refs(m, params, state, prompts, budgets):
    return [np.asarray(m.generate(params, state, p[None], max_new=n,
                                  temperature=0.0))[0]
            for p, n in zip(prompts, budgets)]


def _truncated(m, params, state, layers=1):
    dm = TransformerLM(m.vocab_size, max_len=m.max_len,
                       embed_dim=m.embed_dim,
                       num_heads=m.blocks[0].attn.num_heads,
                       num_layers=layers)
    dparams = {"tok": params["tok"], "pos": params["pos"],
               "blocks": params["blocks"][:layers],
               "ln_f": params["ln_f"]}
    dstate = {"blocks": state["blocks"][:layers], "ln_f": state["ln_f"]}
    return dm, dparams, dstate


# -- allocator ----------------------------------------------------------------

def test_page_allocator_unit():
    a = PageAllocator(4, page_size=8)
    assert a.trash == 4 and a.capacity_tokens == 32
    assert a.pages_for(1) == 1 and a.pages_for(8) == 1
    assert a.pages_for(9) == 2 and a.pages_for(0) == 1
    p1 = a.alloc(3)
    assert len(p1) == 3 and a.free_count == 1 and a.used_count == 3
    assert a.alloc(2) is None            # all-or-nothing: 2 > 1 free
    assert a.free_count == 1             # the failed alloc took nothing
    a.free(p1[:1])
    assert a.free_count == 2
    with pytest.raises(ValueError, match="double free"):
        a.free(p1[:1])
    with pytest.raises(ValueError, match="out of range"):
        a.free([4])                      # the trash page is not freeable
    with pytest.raises(ValueError):
        PageAllocator(0, 8)
    with pytest.raises(ValueError):
        PageAllocator(4, 0)


def test_free_list_reuse_never_aliases_live_slot():
    """The satellite edge: pages freed by an evict and re-allocated to
    the next tenant must be disjoint from every page a live slot still
    holds."""
    a = PageAllocator(6, page_size=4)
    slot_a = a.alloc(3)
    slot_b = a.alloc(3)                  # pool exhausted
    assert a.alloc(1) is None
    a.free(slot_a)                       # slot A evicts
    slot_c = a.alloc(3)                  # next tenant reuses A's pages
    assert set(slot_c) == set(slot_a)
    assert not set(slot_c) & set(slot_b)  # never a live slot's pages
    a.free(slot_b)
    a.free(slot_c)
    assert a.free_count == 6


def test_slot_manager_pool_tokens_shed():
    sm = SlotManager(2, max_len=64, max_prompt=32, pool_tokens=24)
    sm.check(7, 10)                      # 16 tokens: fits the pool
    with pytest.raises(SlotCapacityError, match="page pool"):
        sm.check(7, 30)                  # 36 tokens > 24 pool tokens
    with pytest.raises(SlotCapacityError, match="overrun"):
        sm.check(40, 30)                 # max_len check still first


# -- prefix cache unit --------------------------------------------------------

def test_prefix_cache_unit():
    a = PageAllocator(8, page_size=4)
    c = PrefixCache(page_size=4)
    prompt = np.arange(1, 11, dtype=np.int32)        # 10 tokens, 2 full
    keys = c.chain_keys(prompt)
    assert len(keys) == 2
    # chain hashing: same head, different tail -> same first key only
    other = prompt.copy()
    other[5] = 63
    keys2 = c.chain_keys(other)
    assert keys2[0] == keys[0] and keys2[1] != keys[1]
    depth, pages = c.lookup(keys)
    assert depth == 0 and pages == []
    pg = a.alloc(2)
    c.insert(keys, pg, 0)
    c.acquire(keys)
    depth, pages = c.lookup(keys)
    assert depth == 2 and pages == pg
    assert c.stats()["hit_rate"] == 0.5              # 2 of 4 looked up
    # referenced entries never evict
    assert c.evict_for(2, a) == 0
    c.release(keys)
    with pytest.raises(ValueError, match="underflow"):
        c.release(keys)
    # unreferenced: leaf-first eviction frees back to the allocator
    free0 = a.free_count
    assert c.evict_for(1, a) == 1
    assert a.free_count == free0 + 1
    assert c.lookup(keys)[0] == 1                    # parent survives
    assert c.evict_for(8, a) == 1 and len(c) == 0
    with pytest.raises(KeyError):
        c.acquire(keys)                              # gone
    with pytest.raises(ValueError, match="raced"):
        c.insert(keys, pg, 0) or c.insert(keys, pg, 0)


# -- paged generation bit-equality --------------------------------------------

def test_paged_matches_generate_bit_exact():
    """Fewer slots than requests, mixed prompt lengths and budgets, two
    seq rungs, page_size smaller than most prompts — paged admit/evict
    really interleaves and output is BIT-EQUAL to generate()."""
    m, params, state = _lm(max_len=64)
    rs = np.random.RandomState(1)
    prompts = [rs.randint(1, 65, size=rs.randint(3, 14)).astype(np.int32)
               for _ in range(7)]
    budgets = [int(rs.randint(1, 12)) for _ in range(7)]
    refs = _refs(m, params, state, prompts, budgets)
    with ContinuousGenerator(m, params, state, num_slots=3,
                             max_len=64, page_size=4,
                             seq_buckets=[8, 16], steps_per_sync=3) as g:
        futs = [g.submit(p, n) for p, n in zip(prompts, budgets)]
        outs = [f.result(timeout=60) for f in futs]
        st = g.stats()
    assert st["paged"] and st["pages"]["page_size"] == 4
    assert 0 < st["pages"]["mean_token_occupancy"] <= 1
    for r, o in zip(refs, outs):
        np.testing.assert_array_equal(r, o)


def test_paged_rope_model_parity():
    m, params, state = _lm(position="rope", max_len=64)
    rs = np.random.RandomState(2)
    prompts = [rs.randint(1, 65, size=rs.randint(3, 9)).astype(np.int32)
               for _ in range(4)]
    refs = _refs(m, params, state, prompts, [5] * 4)
    with ContinuousGenerator(m, params, state, num_slots=2, max_len=64,
                             page_size=4, seq_buckets=[16],
                             steps_per_sync=2) as g:
        outs = [f.result(timeout=60)
                for f in [g.submit(p, 5) for p in prompts]]
    for r, o in zip(refs, outs):
        np.testing.assert_array_equal(r, o)


# -- prefix cache end to end --------------------------------------------------

def test_prefix_hit_bit_equal_and_cow_leaves_shared_pages_identical():
    """The shared system prompt is prefilled once: later requests hit
    the page-aligned head, their outputs stay bit-equal to generate(),
    and their divergent continuations never touch the shared pages'
    bytes (copy-on-write lands in private pages)."""
    m, params, state = _lm(max_len=96)
    rs = np.random.RandomState(3)
    head = rs.randint(1, 65, size=40).astype(np.int32)
    prompts = [np.concatenate([head,
                               rs.randint(1, 65, size=6).astype(np.int32)])
               for _ in range(4)]
    refs = _refs(m, params, state, prompts, [8] * 4)
    g = ContinuousGenerator(m, params, state, num_slots=1, page_size=8,
                            seq_buckets=[16, 48], steps_per_sync=2)
    try:
        # first request alone: publishes the head's 5 full pages
        first = g.submit(prompts[0], 8).result(timeout=60)
        np.testing.assert_array_equal(refs[0], first)
        st = g.stats()["prefix"]
        assert st["entries"] == 5 and st["inserted_pages"] == 5
        assert st["hit_pages"] == 0                  # nothing to hit yet
        # snapshot the shared pages' bytes (CPU: donation off, arrays
        # are stable jax buffers)
        entries = list(g._prefix._entries.values())
        shared_ids = sorted(e.page for e in entries)
        before = [np.asarray(layer["k"])[shared_ids].copy()
                  for layer in g._cache]
        # three more requests share the head, diverge in the tail
        outs = [g.submit(p, 8).result(timeout=60) for p in prompts[1:]]
        for r, o in zip(refs[1:], outs):
            np.testing.assert_array_equal(r, o)
        st = g.stats()["prefix"]
        assert st["hit_pages"] == 15                 # 5 pages x 3 hits
        assert st["hit_rate"] == pytest.approx(15 / 20)
        after = [np.asarray(layer["k"])[shared_ids]
                 for layer in g._cache]
        for b, a in zip(before, after):              # byte-identical
            np.testing.assert_array_equal(b, a)
    finally:
        g.drain(timeout=30)


def test_prefix_pages_released_only_when_last_reader_evicts():
    """Refcount lifecycle: while ANY reader is live the shared pages
    are pinned (evict_for reclaims nothing); once the last reader
    evicts they become reclaimable — and only via eviction, never
    eagerly."""
    m, params, state = _lm(max_len=96)
    rs = np.random.RandomState(4)
    head = rs.randint(1, 65, size=24).astype(np.int32)
    prompt = np.concatenate([head, rs.randint(1, 65, size=4)
                             .astype(np.int32)])
    g = ContinuousGenerator(m, params, state, num_slots=2, page_size=8,
                            seq_buckets=[8, 32], steps_per_sync=2,
                            warmup=False)
    try:
        g.submit(prompt, 4).result(timeout=60)
        pre = g._prefix
        alloc = g._alloc
        assert pre.held_pages == 3                   # head = 3 full pages
        held_free = alloc.free_count
        # no reader left, but pages stay cached (warm for the next hit)
        assert all(e.refs == 0 for e in pre._entries.values())
        # a reader mid-flight pins them: simulate by acquiring
        keys = pre.chain_keys(prompt)[:3]
        pre.acquire(keys)
        assert pre.evict_for(3, alloc) == 0          # pinned
        pre.release(keys)                            # last reader gone
        assert pre.evict_for(3, alloc) == 3          # now reclaimable
        assert alloc.free_count == held_free + 3
    finally:
        g.drain(timeout=30)


def test_token_occupancy_counts_shared_pages_once(tmp_path):
    """Two slots share a 2-page head in a pool sized exactly for the
    DISTINCT pages: summing raw per-slot positions would report more
    tokens held than the pool can even store (> 100% occupancy); the
    census must count each shared page once and stay within
    capacity."""
    from bigdl_tpu.observability import ledger as run_ledger
    from bigdl_tpu.observability.report import load_ledger

    m, params, state = _lm(max_len=32, layers=1)
    rs = np.random.RandomState(14)
    head = rs.randint(1, 65, size=16).astype(np.int32)
    prompts = [np.concatenate([head, rs.randint(1, 65, size=4)
                               .astype(np.int32)]) for _ in range(2)]
    run_dir = str(tmp_path / "occ")
    run_ledger.set_run_dir(run_dir)
    try:
        # 6 pages x 8 = 48 tokens; each request holds 27 positions, so
        # double-counting the 16 shared ones would report 54 > 48
        with ContinuousGenerator(m, params, state, num_slots=2,
                                 max_len=32, page_size=8, num_pages=6,
                                 seq_buckets=[8, 32],
                                 steps_per_sync=1) as g:
            for f in [g.submit(p, 8) for p in prompts]:
                assert f.result(timeout=60) is not None
    finally:
        run_ledger.set_run_dir(None)
    records, _ = load_ledger(run_dir, strict=True)
    pages = [r for r in records if r.get("type") == "serve.pages"]
    assert pages
    assert max(p["tokens_held"] for p in pages) <= 48
    assert all(0 <= p["token_occupancy"] <= 1 for p in pages)
    # both really were resident together (the double-count scenario)
    assert max(p["pages_used"] for p in pages) == 6


# -- exhaustion + holdback ----------------------------------------------------

def test_page_exhaustion_sheds_typed_neighbors_intact():
    """A request that can NEVER fit the pool sheds SlotCapacityError at
    submit while in-flight neighbor generations finish bit-equal — the
    r8 over-capacity contract, re-keyed from rows to tokens."""
    m, params, state = _lm(max_len=64)
    rs = np.random.RandomState(5)
    prompts = [rs.randint(1, 65, size=6).astype(np.int32)
               for _ in range(3)]
    refs = _refs(m, params, state, prompts, [10] * 3)
    # pool: 12 pages x 4 = 48 tokens
    with ContinuousGenerator(m, params, state, num_slots=3, max_len=64,
                             page_size=4, num_pages=12,
                             seq_buckets=[8], steps_per_sync=2) as g:
        futs = [g.submit(p, 10) for p in prompts]    # 15 tokens each
        with pytest.raises(SlotCapacityError, match="page pool"):
            g.submit(rs.randint(1, 65, size=8).astype(np.int32), 50)
        assert g.stats()["counters"]["serve.shed.over_capacity"] == 1
        outs = [f.result(timeout=60) for f in futs]
    for r, o in zip(refs, outs):                     # neighbors intact
        np.testing.assert_array_equal(r, o)


def test_token_scarce_pool_serves_all_admitted_via_holdback():
    """Pool smaller than the concurrent demand: placement holds
    requests back until pages free up (FIFO, no shed, no deadlock) and
    every admitted request still decodes bit-equal."""
    m, params, state = _lm(max_len=48, layers=1)
    rs = np.random.RandomState(6)
    prompts = [rs.randint(1, 65, size=rs.randint(3, 8)).astype(np.int32)
               for _ in range(6)]
    budgets = [int(rs.randint(2, 10)) for _ in range(6)]
    refs = _refs(m, params, state, prompts, budgets)
    # 6 pages x 4 = 24 tokens: at most ~one request resident at a time
    with ContinuousGenerator(m, params, state, num_slots=2, max_len=48,
                             page_size=4, num_pages=6, seq_buckets=[8],
                             steps_per_sync=2, queue_capacity=64) as g:
        futs = [g.submit(p, n) for p, n in zip(prompts, budgets)]
        outs = [f.result(timeout=120) for f in futs]
    for r, o in zip(refs, outs):
        np.testing.assert_array_equal(r, o)


# -- speculative decoding -----------------------------------------------------

def test_speculative_bit_equal_with_truncated_draft():
    """Accepted tokens are exactly the target's greedy path: a 1-layer
    truncated draft (imperfect proposals) still yields bit-equal
    output, with the accept rate in (0, 1] on the record."""
    m, params, state = _lm(max_len=96)
    dm, dparams, dstate = _truncated(m, params, state)
    rs = np.random.RandomState(7)
    prompts = [rs.randint(1, 65, size=rs.randint(4, 12)).astype(np.int32)
               for _ in range(5)]
    budgets = [int(rs.randint(2, 10)) for _ in range(5)]
    refs = _refs(m, params, state, prompts, budgets)
    with ContinuousGenerator(m, params, state, num_slots=2, page_size=8,
                             seq_buckets=[16], steps_per_sync=2,
                             draft_model=dm, draft_params=dparams,
                             draft_state=dstate, spec_k=3) as g:
        outs = [f.result(timeout=120)
                for f in [g.submit(p, n)
                          for p, n in zip(prompts, budgets)]]
        spec = g.stats()["spec"]
    for r, o in zip(refs, outs):
        np.testing.assert_array_equal(r, o)
    assert spec["proposed"] > 0
    assert 0 < spec["accept_rate"] <= 1


def test_speculative_self_draft_accepts_everything():
    """The target as its own draft: every proposal matches the verify
    pass, so the accept rate is exactly 1.0 — the sanity anchor for
    the accept rule.  Deep budgets on purpose: many consecutive
    full-accept rounds, so a draft cache that skips ingesting the last
    proposal (the bonus-token hole) decays the rate below 1.0 within a
    few chunks (regression — reviewer-reproduced at 0.923)."""
    m, params, state = _lm(max_len=64, layers=1)
    rs = np.random.RandomState(8)
    prompts = [rs.randint(1, 65, size=6).astype(np.int32)
               for _ in range(3)]
    refs = _refs(m, params, state, prompts, [40] * 3)
    with ContinuousGenerator(m, params, state, num_slots=2, page_size=8,
                             seq_buckets=[8], draft_model=m,
                             draft_params=params, draft_state=state,
                             spec_k=4) as g:
        outs = [f.result(timeout=120)
                for f in [g.submit(p, 40) for p in prompts]]
        spec = g.stats()["spec"]
    for r, o in zip(refs, outs):
        np.testing.assert_array_equal(r, o)
    assert spec["accept_rate"] == 1.0


def test_speculative_eos_matches_plain_paged():
    """The host-side accept walk replays the sequential eos rule: a
    speculative run with eos_id stops exactly where the plain paged
    decode does."""
    m, params, state = _lm(max_len=64, layers=1)
    rs = np.random.RandomState(9)
    prompts = [rs.randint(1, 65, size=5).astype(np.int32)
               for _ in range(3)]
    outs = {}
    for spec in (False, True):
        kw = dict(draft_model=m, draft_params=params, draft_state=state,
                  spec_k=3) if spec else {}
        with ContinuousGenerator(m, params, state, num_slots=2,
                                 page_size=8, seq_buckets=[8],
                                 steps_per_sync=2, eos_id=17, **kw) as g:
            outs[spec] = [f.result(timeout=120)
                          for f in [g.submit(p, 12) for p in prompts]]
    for a, b in zip(outs[False], outs[True]):
        np.testing.assert_array_equal(a, b)


def test_speculative_full_capacity_request_cannot_poison_neighbors():
    """Regression: a request finishing at the cache boundary
    (prompt + max_new == max_len) pushes its speculative verify rows
    PAST the learned-position table — the out-of-table embedding must
    come back finite (clipped, not NaN-filled) and the trash page must
    stay inert, or the NaN written there poisons every neighbor's
    masked attention through 0 * NaN (caught by the full-scale bench's
    cross-variant equality gate)."""
    m, params, state = _lm(max_len=32, layers=1)
    rs = np.random.RandomState(13)
    full = rs.randint(1, 65, size=6).astype(np.int32)    # 6 + 26 = 32
    neighbors = [rs.randint(1, 65, size=6).astype(np.int32)
                 for _ in range(3)]
    refs = _refs(m, params, state, [full] + neighbors, [26, 20, 20, 20])
    with ContinuousGenerator(m, params, state, num_slots=4, max_len=32,
                             page_size=8, seq_buckets=[8],
                             draft_model=m, draft_params=params,
                             draft_state=state, spec_k=3) as g:
        futs = [g.submit(full, 26)] + [g.submit(p, 20)
                                       for p in neighbors]
        outs = [f.result(timeout=120) for f in futs]
    for r, o in zip(refs, outs):
        np.testing.assert_array_equal(r, o)


def test_speculative_draft_past_its_capacity_stays_in_its_own_pages():
    """The draft is served through ``decode_pages`` on a pool of its own
    behind a fixed table (slot ``i`` owns pages ``i*Lp .. (i+1)*Lp-1``).
    A request that ends at the cache boundary drives the draft's k + 1
    proposal steps PAST its capacity (``p >= dcap``): those writes are
    gated to the trash page, so the output is still the plain
    generator's and the pages of the slot beside it, which never held a
    request, hold what they held before."""
    m, params, state = _lm(max_len=32, layers=1)
    rs = np.random.RandomState(14)
    full = rs.randint(1, 65, size=6).astype(np.int32)    # 6 + 26 = 32
    with ContinuousGenerator(m, params, state, num_slots=2, max_len=32,
                             page_size=8, seq_buckets=[8]) as g:
        plain = g.submit(full, 26).result(timeout=120)
    with ContinuousGenerator(m, params, state, num_slots=2, max_len=32,
                             page_size=8, seq_buckets=[8],
                             draft_model=m, draft_params=params,
                             draft_state=state, spec_k=4) as g:
        lp = g._lp
        np.testing.assert_array_equal(
            np.asarray(g._dtable), np.arange(2 * lp).reshape(2, lp))
        assert g._dcache[0]["k"].shape[0] == 2 * lp + 1   # + trash
        before = [np.asarray(l[kv]) for l in g._dcache for kv in "kv"]
        out = g.submit(full, 26).result(timeout=120)
        spec = g.stats()["spec"]
        after = [np.asarray(l[kv]) for l in g._dcache for kv in "kv"]
    np.testing.assert_array_equal(plain, out)
    np.testing.assert_array_equal(
        plain, _refs(m, params, state, [full], [26])[0])
    # the last rounds proposed at positions 31 + 1 .. 31 + 4 >= dcap
    assert spec["proposed"] >= 4 and spec["accept_rate"] == 1.0
    for b4, af in zip(before, after):
        assert not np.array_equal(b4[:lp], af[:lp])      # slot 0 served
        np.testing.assert_array_equal(b4[lp:2 * lp], af[lp:2 * lp])


def test_speculative_recovers_both_pools_after_a_failed_round():
    """Under donation a failed speculative round may have consumed the
    target's pool AND the draft's: both are rebuilt in the paged layout
    (the draft's behind the same fixed table), the tenants fail typed,
    and the next request is served bit-equal to ``generate()``."""
    m, params, state = _lm(max_len=32, layers=1)
    prompt = np.arange(3, 9).astype(np.int32)
    g = ContinuousGenerator(m, params, state, num_slots=2, max_len=32,
                            page_size=8, seq_buckets=[8], draft_model=m,
                            draft_params=params, draft_state=state,
                            spec_k=3, donate_cache=True)
    spec, failed = g._spec_fn, []

    def flaky(*a):
        if not failed:
            failed.append(True)
            raise RuntimeError("injected speculative failure")
        return spec(*a)

    g._spec_fn = flaky
    fresh, rebuilt = g._new_draft_cache, []
    g._new_draft_cache = lambda: rebuilt.append(fresh()) or rebuilt[-1]
    try:
        with pytest.raises(RuntimeError, match="generation failed"):
            g.submit(prompt, 10).result(timeout=120)
        out = g.submit(prompt, 10).result(timeout=120)
    finally:
        g.drain(timeout=60)
    assert len(rebuilt) == 1            # once, by the scheduler's recovery
    assert rebuilt[0][0]["k"].shape == (2 * g._lp + 1, 8, 128)
    assert g.stats()["pages"]["free"] == g.stats()["pages"]["total"]
    np.testing.assert_array_equal(
        out, _refs(m, params, state, [prompt], [10])[0])


def test_speculative_validation():
    m, params, state = _lm(layers=1)
    dm, dparams, dstate = _truncated(m, params, state)
    with pytest.raises(ValueError, match="greedy-only"):
        ContinuousGenerator(m, params, state, temperature=0.5,
                            draft_model=dm, draft_params=dparams,
                            draft_state=dstate, warmup=False)
    bad = TransformerLM(32, max_len=96, embed_dim=32, num_heads=2,
                        num_layers=1)
    with pytest.raises(ValueError, match="vocab"):
        ContinuousGenerator(m, params, state, draft_model=bad,
                            warmup=False)
    with pytest.raises(ValueError, match="spec_k"):
        ContinuousGenerator(m, params, state, draft_model=dm,
                            draft_params=dparams, draft_state=dstate,
                            spec_k=0, warmup=False)


# -- decode_pages unit parity -------------------------------------------------

@pytest.mark.parametrize("depths", [(7, 7, 7), (3, 7, 5)],
                         ids=["same_depth", "own_depths"])
def test_decode_pages_matches_decode(depths):
    """Same tokens through the paged path and the scalar ``decode`` (the
    offline path, the oracle): a prefill's logits match, then a step
    with every row at ITS OWN depth matches that row's own scalar
    decode (values, not just argmax), and an inactive row's pages stay
    untouched (the write-redirect-to-trash contract)."""
    m, params, state = _lm(layers=1, max_len=32)
    rs = np.random.RandomState(10)
    b, tp, ps = 3, 7, 4
    prompt = rs.randint(1, 65, size=(b, tp)).astype(np.int32)
    cache = m.init_cache(b, 32)
    lp_ref, cache_ref = m.decode(params, state, prompt, cache, 0)
    pcache = m.init_paged_cache(b * 8, ps)
    pages = np.stack([np.arange(r * 8, (r + 1) * 8) for r in range(b)]) \
              .astype(np.int32)
    lp_pg, pcache = m.decode_pages(params, state, prompt, pcache,
                                   jnp.asarray(pages),
                                   jnp.zeros(b, jnp.int32),
                                   jnp.ones(b, bool))
    np.testing.assert_allclose(np.asarray(lp_ref), np.asarray(lp_pg),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.argmax(np.asarray(lp_ref), -1),
                                  np.argmax(np.asarray(lp_pg), -1))
    # one step, row r at depth d_r: what lies at and beyond d_r in its
    # pages (the rest of the prompt) is overwritten or masked, so the
    # row equals a scalar decode that only ever saw its first d_r tokens
    nxt = rs.randint(1, 65, size=(b, 1)).astype(np.int32)
    lp_step, _ = m.decode_pages(params, state, nxt, pcache,
                                jnp.asarray(pages),
                                jnp.asarray(depths, jnp.int32),
                                jnp.ones(b, bool))
    for r, d in enumerate(depths):
        _, c1 = m.decode(params, state, prompt[r:r + 1, :d],
                         m.init_cache(1, 32), 0)
        want, _ = m.decode(params, state, nxt[r:r + 1], c1, d)
        np.testing.assert_allclose(np.asarray(want)[0],
                                   np.asarray(lp_step)[r],
                                   atol=1e-5, rtol=1e-5)
    # an INACTIVE row's pages must stay untouched; the write redirects
    # to the trash page
    tok = prompt[:, :1]
    active = jnp.asarray([True, False, True])
    before = np.asarray(pcache[0]["k"]).copy()
    _, c2 = m.decode_pages(params, state, tok, pcache,
                           jnp.asarray(pages),
                           jnp.full(b, tp, jnp.int32), active)
    after = np.asarray(c2[0]["k"])
    np.testing.assert_array_equal(before[8:16], after[8:16])
    assert not np.array_equal(before[0:8], after[0:8])
    # an unmapped logical page (table slot = trash) cannot reach a real
    # page: positions past the table write only the trash row
    short = np.full((b, 8), b * 8, np.int32)     # all-trash table
    short[:, 0] = pages[:, 0]
    beforep = np.asarray(c2[0]["k"])[:b * 8].copy()
    _, c3 = m.decode_pages(params, state, tok, c2, jnp.asarray(short),
                           jnp.full(b, 30, jnp.int32),
                           jnp.ones(b, bool))
    np.testing.assert_array_equal(beforep, np.asarray(c3[0]["k"])[:b * 8])


# -- the pool's layout: page, token in page, width ----------------------------

@pytest.mark.parametrize("hkv,d,width", [
    (25, 64, 1664),     # GPT-2 XL: 1,600 lanes padded to 13 tiles
    (8, 128, 1024),     # whole tiles already: nothing padded
    (2, 64, 128), (3, 64, 256), (4, 8, 128),
    (5, 96, 768),       # a chunk is 4 heads = 384 lanes: 480 -> 768
    (1, 576, 640),      # the latent pool's one head
    (1, 40, 128)])
def test_pool_is_page_token_width(hkv, d, width):
    """ONE helper says a pool's page size and width, and every pool —
    per-head K and V, the latent — has the form it reads: axis 0 the
    pages with the trash page last, a token's row whole lane tiles."""
    from bigdl_tpu.nn.attention import (LatentAttention,
                                        MultiHeadAttention, pages_rows,
                                        pages_view)
    from bigdl_tpu.ops.attention import paged_pool_dims, paged_pool_width
    assert paged_pool_width(hkv, d) == width and width % 128 == 0
    if hkv == 1:
        cache = LatentAttention(64, 4, latent_dim=d - 8, rope_dim=8) \
            .init_paged_cache(6, 16, jnp.bfloat16)
        assert set(cache) == {"k"}
    else:
        cache = MultiHeadAttention(hkv * d, hkv).init_paged_cache(
            6, 16, jnp.bfloat16)
    for pool in cache.values():
        assert pool.shape == (7, 16, width)
        assert paged_pool_dims(pool) == (16, width)
    # rows -> pool -> view is the identity on the heads, zero elsewhere
    rs = np.random.RandomState(0)
    kv = jnp.asarray(rs.randn(2, hkv, 32, d), jnp.float32)
    rows = pages_rows(kv, width)
    assert rows.shape == (2, 32, width)
    assert not np.asarray(rows[..., hkv * d:]).any()
    pool = jnp.zeros((5, 16, width)).at[:4].set(rows.reshape(4, 16, width))
    pages = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
    np.testing.assert_array_equal(pages_view(pool, pages, hkv, d), kv)
    # a trash-mapped slot reads as zero whatever the trash page holds
    pool = pool.at[4].set(jnp.nan)
    got = pages_view(pool, jnp.asarray([[0, 4], [4, 3]], jnp.int32), hkv, d)
    np.testing.assert_array_equal(got[0, :, 16:], 0)
    np.testing.assert_array_equal(got[1, :, :16], 0)
    np.testing.assert_array_equal(got[0, :, :16], kv[0, :, :16])


@pytest.mark.parametrize("case", ["inactive_row", "unmapped_page",
                                  "bucket_padding", "past_the_table"])
@pytest.mark.parametrize("layer", ["heads", "latent"])
def test_write_lands_only_in_the_rows_table_or_the_trash_page(layer, case):
    """The write's containment on the token-major pool: a token row
    goes to ``(pages[b, p // ps], p % ps)``; an inactive row, a logical
    page the host left unmapped, the part of a padded bucket beyond the
    mapped pages and positions past the table itself land in the trash
    page; no other page changes by a byte, and the padding lanes of
    what is written stay zero."""
    from bigdl_tpu.nn.attention import LatentAttention, MultiHeadAttention
    ps, lp, num_pages = 4, 4, 12
    if layer == "heads":
        attn = MultiHeadAttention(48, 3)        # 3 x 16 = 48 lanes of 128
        used = 48
    else:
        attn = LatentAttention(48, 4, latent_dim=24, rope_dim=8,
                               nope_dim=8, v_dim=8)
        used = 32
    params = attn.init_params(jax.random.PRNGKey(0))
    cache = attn.init_paged_cache(num_pages, ps)
    rs = np.random.RandomState(1)
    # every page holds a sentinel, the trash page too
    cache = {k: jnp.asarray(rs.randn(*v.shape), v.dtype)
             for k, v in cache.items()}
    pages = np.full((2, lp), num_pages, np.int32)
    pages[0, :3] = [7, 2, 9]
    pages[1, :2] = [4, 11]
    s, pos, active = 1, [5, 6], [True, True]
    if case == "inactive_row":
        active = [True, False]
        written = {0: [2]}                      # row 1 writes nothing
    elif case == "unmapped_page":
        pages[1, 1] = num_pages                 # row 1's page for pos 6
        written = {0: [2]}
    elif case == "bucket_padding":
        # 12 tokens from 0 into tables that map 3 and 2 pages: row 1's
        # last four go to the trash page
        s, pos = 12, [0, 0]
        written = {0: [7, 2, 9], 1: [4, 11]}
    else:
        # positions 14..17 of a table of 4 pages of 4: the last two
        # are past the table
        s, pos = 4, [14, 14]
        pages[0, 3] = 5
        written = {0: [5]}
    x = jnp.asarray(rs.randn(2, s, 48), jnp.float32)
    _, new = attn.apply_decode_pages(
        params, x, dict(cache), jnp.asarray(pages),
        jnp.asarray(pos, jnp.int32), jnp.asarray(active))
    touched = sorted(p for ids in written.values() for p in ids)
    for name, before in cache.items():
        after = np.asarray(new[name])
        before = np.asarray(before)
        same = [p for p in range(num_pages) if p not in touched]
        np.testing.assert_array_equal(before[same], after[same])
        for p in touched:
            assert not np.array_equal(before[p], after[p])
            changed = np.any(before[p] != after[p], axis=-1)
            # a written token's row is zero beyond the heads' lanes
            assert not after[p][changed][:, used:].any()
        # what was redirected went to the trash page
        assert not np.array_equal(before[num_pages], after[num_pages])


@pytest.mark.parametrize("pos", [[0, 8], [4, 4], [3, 8], [6, 5]])
def test_page_wise_write_is_the_token_wise_write(pos):
    """A call of a whole number of pages writes page by page where
    every row starts on a page's first token (a prefill) and token by
    token else: either way each token's row lands at ``(pages[b, p //
    ps], p % ps)``, what lies beyond the table in the trash page."""
    from bigdl_tpu.nn.attention import MultiHeadAttention, _proj, pages_rows
    ps, lp, num_pages, s = 4, 4, 12, 8
    attn = MultiHeadAttention(48, 3)
    params = attn.init_params(jax.random.PRNGKey(0))
    cache = attn.init_paged_cache(num_pages, ps)
    rs = np.random.RandomState(2)
    cache = {k: jnp.asarray(rs.randn(*v.shape), v.dtype)
             for k, v in cache.items()}
    pages = np.asarray([[7, 2, 9, 5], [4, 11, 0, 12]], np.int32)
    x = jnp.asarray(rs.randn(2, s, 48), jnp.float32)
    _, new = attn.apply_decode_pages(
        params, x, dict(cache), jnp.asarray(pages),
        jnp.asarray(pos, jnp.int32), jnp.asarray([True, True]))
    for name, w, bias in (("k", "wk", "bk"), ("v", "wv", "bv")):
        rows = np.asarray(pages_rows(attn._split(
            _proj(x, params[w], params[bias]), 3), 128))
        want = np.asarray(cache[name]).copy()
        for b in range(2):
            for i in range(s):
                page, off = divmod(pos[b] + i, ps)
                if page < lp and pages[b, page] != num_pages:
                    want[pages[b, page], off] = rows[b, i]
        got = np.asarray(new[name])
        np.testing.assert_array_equal(want[:num_pages], got[:num_pages])


def _pool_sized_ops(jaxpr, pool_size):
    """(primitive, shapes) of every equation, through every nested
    program, that takes or gives an array of at least the pool's size,
    apart from the ones that only pass it on."""
    passing = {"pjit", "jit", "closed_call", "core_call", "scan", "while",
               "cond", "custom_jvp_call", "custom_vjp_call", "remat"}
    found = []

    def walk(jp):
        for eqn in jp.eqns:
            subs = [v for v in eqn.params.values()
                    if hasattr(v, "eqns") or hasattr(v, "jaxpr")]
            subs += [b for v in eqn.params.values()
                     if isinstance(v, (tuple, list)) for b in v
                     if hasattr(b, "eqns") or hasattr(b, "jaxpr")]
            if eqn.primitive.name == "pallas_call":
                subs = []                   # the kernel's own body
            for sub in subs:
                walk(getattr(sub, "jaxpr", sub))
            big = [tuple(v.aval.shape) for v in (*eqn.invars, *eqn.outvars)
                   if hasattr(v, "aval") and hasattr(v.aval, "shape")
                   and int(np.prod(v.aval.shape)) >= pool_size]
            if big and eqn.primitive.name not in passing:
                found.append((eqn.primitive.name, big))

    walk(jaxpr.jaxpr)
    return found


@pytest.mark.parametrize("program", ["prefill", "step"])
def test_programs_meet_the_pool_only_at_the_scatter_and_the_kernel(
        program, monkeypatch):
    """What a CPU can guard of "no relayout": in the jaxpr of
    ``prefill`` and of ``step_chunk_kernel`` an array of the pool's size
    is an operand or a result of the write's ``scatter`` and of the
    ``pallas_call`` only: no ``transpose``, ``reshape``, ``gather``,
    ``pad``, ``convert_element_type`` or copy of it, whatever the
    model's head count (3 heads of 16: a padded width)."""
    monkeypatch.setenv("BIGDL_TPU_PALLAS_INTERPRET", "1")
    m, params, state = _lm(embed=48, heads=3, layers=2, max_len=64)
    g = ContinuousGenerator(m, params, state, num_slots=2, page_size=4,
                            seq_buckets=[16], steps_per_sync=2,
                            warmup=False)
    try:
        pool = g._cache[0]["k"]
        assert pool.shape == (2 * 16 + 1, 4, 128)
        key = jax.random.PRNGKey(0)
        table = jnp.asarray(g._page_table)
        if program == "prefill":
            jaxpr = jax.make_jaxpr(g._prefill_fn)(
                params, state, jnp.ones((1, 16), jnp.int32), 1, g._cache,
                table[:1], 0, key)
        else:
            jaxpr = jax.make_jaxpr(g._step_fn)(
                params, state, jnp.asarray(g._tokens), g._cache, table,
                jnp.asarray(g._pos), jnp.asarray(g._active),
                jnp.asarray(g._limit), jax.random.split(key, 2))
    finally:
        g.drain(timeout=30)
    ops = _pool_sized_ops(jaxpr, pool.size)
    names = {name for name, _ in ops}
    assert names == {"scatter", "pallas_call"}, ops
    # two layers x K and V, written once and read once (a step: the
    # scan's body holds them once; a prefill states the write twice,
    # page by page and token by token, under one cond)
    assert sum(name == "scatter" for name, _ in ops) == (
        8 if program == "prefill" else 4)
    assert sum(name == "pallas_call" for name, _ in ops) == 2


def test_stats_carry_each_programs_temporaries_beside_the_pool():
    """The gauge the layout brings: ``temp_size_in_bytes`` of every
    program compiled at warm-up, beside the pools' bytes and a row's
    width, in ``stats()["pages"]``."""
    m, params, state = _lm(embed=48, heads=3, layers=1, max_len=32)
    with ContinuousGenerator(m, params, state, num_slots=2, page_size=4,
                             seq_buckets=[8, 16],
                             steps_per_sync=2) as g:
        pg = g.stats()["pages"]
    assert set(pg["program_temp_bytes"]) == {"prefill.8", "prefill.16",
                                             "step"}
    assert all(isinstance(v, int) and v >= 0
               for v in pg["program_temp_bytes"].values())
    assert pg["pool_width"] == 128              # 3 x 16 = 48 lanes
    assert pg["page_bytes"] == 2 * 4 * 128 * 4  # K and V, float32
    assert pg["pool_bytes"] == pg["total"] * pg["page_bytes"]
    assert pg["pool_padded_bytes"] == (pg["total"] + 1) * pg["page_bytes"]
    # not warmed: nothing compiled yet, nothing recorded
    g = ContinuousGenerator(m, params, state, num_slots=2, page_size=4,
                            seq_buckets=[8], warmup=False)
    try:
        assert g.stats()["pages"]["program_temp_bytes"] == {}
    finally:
        g.drain(timeout=30)


@pytest.mark.parametrize("analysis", [None, NotImplementedError,
                                      RuntimeError])
def test_a_program_without_memory_analysis_is_recorded_as_none(analysis):
    """A backend that has no memory analysis leaves ``None`` under the
    program's name, so the gauge says that it is missing; any other
    failure of the compile is the caller's to see."""
    class Program:
        def __call__(self, x):
            return x + 1

        def lower(self, x):
            return self

        def compile(self):
            return self

        def memory_analysis(self):
            if analysis is not None:
                raise analysis("no analysis")

    m, params, state = _lm(embed=48, heads=3, layers=1, max_len=32)
    g = ContinuousGenerator(m, params, state, num_slots=2, page_size=4,
                            seq_buckets=[8], warmup=False)
    try:
        if analysis is RuntimeError:
            with pytest.raises(RuntimeError):
                g._compile("step", Program(), 1)
            assert g.stats()["pages"]["program_temp_bytes"] == {}
        else:
            assert g._compile("step", Program(), 1) == 2
            assert g.stats()["pages"]["program_temp_bytes"] == {
                "step": None}
    finally:
        g.drain(timeout=30)


# -- observability ------------------------------------------------------------

def test_paged_ledger_records_and_report(tmp_path):
    """serve.pages / serve.cache / serve.spec land on the ledger and
    run-report renders the pages census (token occupancy), prefix hit
    rate and draft accept rate — the same figures the live metrics
    gauges expose."""
    from bigdl_tpu.observability import ledger as run_ledger
    from bigdl_tpu.observability.report import (build_report, load_ledger,
                                                render_report)

    m, params, state = _lm(max_len=96, layers=1)
    dm, dparams, dstate = _truncated(m, params, state)
    rs = np.random.RandomState(11)
    head = rs.randint(1, 65, size=24).astype(np.int32)
    prompts = [np.concatenate([head, rs.randint(1, 65, size=4)
                               .astype(np.int32)]) for _ in range(4)]
    run_dir = str(tmp_path / "paged")
    run_ledger.set_run_dir(run_dir)
    try:
        with ContinuousGenerator(m, params, state, num_slots=2,
                                 page_size=8, seq_buckets=[8, 32],
                                 steps_per_sync=2, draft_model=dm,
                                 draft_params=dparams,
                                 draft_state=dstate, spec_k=3) as g:
            for f in [g.submit(p, 6) for p in prompts]:
                assert f.result(timeout=120) is not None
            gauges = g.stats()["counters"]
            assert gauges["serve.gen.prefix.hit_pages"] > 0
            assert gauges["serve.gen.spec.proposed"] > 0
    finally:
        run_ledger.set_run_dir(None)
    records, bad = load_ledger(run_dir, strict=True)
    assert bad == 0
    start = next(r for r in records if r.get("type") == "run.start")
    assert start["page_size"] == 8 and start["prefix_cache"] \
        and start["speculative"] and start["spec_k"] == 3
    pages = [r for r in records if r.get("type") == "serve.pages"]
    assert pages and all(0 <= p["token_occupancy"] <= 1 for p in pages)
    # the layout's gauges ride on the same record: each warmed program's
    # temporaries beside the pools' size and a row's width (32 -> 128)
    assert all(p["pool_width"] == 128 for p in pages)
    assert set(pages[0]["program_temp_bytes"]) == {
        "prefill.8", "prefill.32", "step", "spec"}
    assert pages[0]["pool_padded_bytes"] > 0
    admits = [r for r in records if r.get("type") == "serve.cache"
              and r.get("event") == "admit"]
    assert len(admits) == 4
    assert sum(r["hit_pages"] for r in admits) == 9   # 3 pages x 3 hits
    specs = [r for r in records if r.get("type") == "serve.spec"]
    assert specs and all(s["proposed"] >= s["accepted"] for s in specs)
    end = next(r for r in records if r.get("type") == "run.end")
    assert end["mean_token_occupancy"] > 0
    assert end["prefix_hit_rate"] == pytest.approx(9 / 12)
    assert end["draft_accept_rate"] is not None
    rep = build_report(records)["serving"]
    assert 0 < rep["pages"]["mean_token_occupancy"] <= 1
    assert rep["pages"]["capacity_tokens"] > 0
    assert rep["prefix"]["hit_rate"] == pytest.approx(9 / 12)
    assert rep["prefix"]["admits"] == 4
    assert 0 <= rep["spec"]["accept_rate"] <= 1
    txt = render_report(build_report(records))
    assert "prefix cache:" in txt and "speculative:" in txt
    assert "TOKEN occupancy" in txt
