"""Grouped-query attention whose every query reads only the keys a lightning
indexer picks (``models/hybrid.py`` mixer ``"dsa"``,
``nn.SparseGroupedQueryAttention``, ``ops/sparse_attention.py``) beside
softmax-routed held experts, against the plain reference
``benchmark/reference/keye_vl2.py`` at toy widths, with the selection
active: ``topk`` 16 over rows of 64 to 96 tokens."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import keye_vl2
from bigdl_tpu.models.hybrid import HybridLM
from bigdl_tpu.nn.attention import (GroupedQueryAttention,
                                    SparseGroupedQueryAttention)
from bigdl_tpu.observability import ledger
from bigdl_tpu.ops import sparse_attention as sa
from bigdl_tpu.serving.scheduler import ContinuousGenerator

VOCAB, TOPK, LENGTH = 97, 16, 96
TOY = dict(max_len=128, embed_dim=64, num_heads=4, num_kv_heads=2,
           num_layers=2, layers=[["dsa", "experts"], ["dsa", "experts"]],
           head_dim=16, expert_dim=24, num_experts=16, experts_per_token=4,
           experts_held=8, expert_offset=0, routed_scale=1.0,
           router="softmax", shared_expert=False, index_heads=16,
           index_dim=16, index_rope_dim=8, topk=TOPK, index_block=16,
           rope_theta=1e7, norm_eps=1e-6)
REF = dict(num_experts_per_tok=4, topk=TOPK, index_rope_dim=8)
SEQ = np.random.default_rng(0).integers(1, VOCAB + 1, LENGTH) \
    .astype(np.int32)


@pytest.fixture(scope="module")
def toy():
    model = HybridLM(VOCAB, **TOY)
    params, state = model.init(jax.random.PRNGKey(3))
    # trained norms bring their own weights
    p = params["blocks"][1]["mixer"]
    p["k_index_norm"]["bias"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(4), (16,))
    p["q_norm"]["weight"] = 1.0 + 0.2 * jax.random.normal(
        jax.random.PRNGKey(5), (16,))
    return model, params, state


def _reference(params, **kw):
    logits = keye_vl2.logits_at(params, SEQ, np.arange(LENGTH), heads=4,
                                **dict(REF, **kw))
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


def _served(model, params, state, n, slots=3, ps=4, bucket=96):
    """Log-probs after positions n-1 .. LENGTH-1 of ``SEQ`` served in slot
    1 of ``slots``: a prefill of ``n`` tokens in a bucket, then one token
    a step through the three pools; and the cache."""
    lp = 32
    cache = model.init_paged_cache(slots * lp, ps, jnp.float32,
                                   num_slots=slots)
    table = np.full((slots, lp), slots * lp, np.int32)
    table[1] = np.arange(lp) + lp
    padded = np.ones((1, bucket), np.int32)
    padded[0, :n] = SEQ[:n]
    out, cache, _ = model.decode_pages(
        params, state, padded, cache, table[1:2], jnp.zeros((1,), jnp.int32),
        jnp.ones((1,), bool), slots=jnp.asarray([1]),
        lengths=jnp.asarray([n]))
    rows = [np.asarray(out[0, 0])]
    step = jax.jit(model.decode_pages)
    active = np.arange(slots) == 1
    for t in range(n, LENGTH):
        tok = np.where(active, SEQ[t], 1).astype(np.int32)[:, None]
        out, cache, _ = step(params, state, tok, cache, table,
                             np.where(active, t, 0).astype(np.int32), active)
        rows.append(np.asarray(out[1, 0]))
    return np.stack(rows), cache


# -- (a) the layer against the reference ---------------------------------------------

def test_forward_equals_the_reference(toy):
    model, params, state = toy
    logp, _ = model.apply(params, state, SEQ[None])
    np.testing.assert_allclose(np.asarray(logp[0]), _reference(params),
                               atol=2e-5)


@pytest.mark.parametrize("n", [5, TOPK, 40, 72],
                         ids=["shorter", "the_topk", "longer", "most"])
def test_prefill_then_decode_through_the_pools_equals_the_reference(toy, n):
    """Prompts shorter than, as long as and longer than ``topk``, then
    decode steps to 96 tokens: every step past the 16th selects."""
    model, params, state = toy
    got, _ = _served(model, params, state, n)
    np.testing.assert_allclose(got, _reference(params)[n - 1:], atol=2e-5)


def test_the_prefill_blocks_agree_with_each_other():
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    q = jax.random.normal(ks[0], (1, 4, 64, 16))
    k, v = (jax.random.normal(kk, (1, 2, 64, 16)) for kk in ks[1:3])
    qi = jax.random.normal(ks[3], (1, 64, 16, 8))
    wi = jax.random.normal(ks[4], (1, 64, 16))
    ki = jax.random.normal(ks[5], (1, 64, 8))
    want = sa.sparse_prefill(q, k, v, qi, wi, ki, 12, 0.25, block=64)
    for block in (8, 16, 32):
        got = sa.sparse_prefill(q, k, v, qi, wi, ki, 12, 0.25, block=block)
        np.testing.assert_allclose(got, want, atol=2e-6)


# -- (b) the selection -------------------------------------------------------------------

def _layer_inputs(toy, length=LENGTH):
    model, params, _ = toy
    layer = model.mixers[0]
    p = params["blocks"][0]["mixer"]
    x = jax.random.normal(jax.random.PRNGKey(9), (1, length, 64))
    return layer, p, x


def test_the_selected_set_is_the_references_top_k(toy):
    """A decode step's picks, scored over its pages, and a prefill's (its
    threshold) are the reference's ``lax.top_k`` of the dense scores, at
    every position."""
    layer, p, x = _layer_inputs(toy)
    at = jnp.arange(LENGTH)
    qi, wi, ki = layer._index_qk(p, x, at[None])
    cfg = dict(keye_vl2.PUBLISHED, **REF)
    rq, rw, rk = keye_vl2.indexer(p, x[0], cfg)
    np.testing.assert_allclose(qi[0], rq, atol=2e-5)
    np.testing.assert_allclose(ki[0], rk, atol=2e-5)
    want = np.asarray(keye_vl2.selection(rq, rw, rk, at, TOPK))
    # decode: the keys as pool rows, pages out of order
    ps, lp = 4, LENGTH // 4
    order = np.random.default_rng(1).permutation(lp + 3)[:lp]
    pool = jnp.zeros((lp + 4, ps, 128)).at[order].set(
        jnp.pad(ki[0], ((0, 0), (0, 112))).reshape(lp, ps, 128))
    table = jnp.broadcast_to(jnp.asarray(order, jnp.int32), (LENGTH, lp))
    scores = sa.paged_index_scores(qi[0], wi[0], pool, table, at)
    idx, chosen = sa.select_topk(scores, TOPK)
    got = np.zeros((LENGTH, LENGTH), bool)
    for t in range(LENGTH):
        got[t, np.asarray(idx[t])[np.asarray(chosen[t])]] = True
    np.testing.assert_array_equal(got, want)
    assert (got.sum(1) == np.minimum(np.arange(LENGTH) + 1, TOPK)).all()
    # prefill: the block's threshold keeps the same keys
    s = sa.block_index_scores(qi, wi, ki, 0)[0]
    thr = sa.kth_largest(s, TOPK)
    np.testing.assert_array_equal(
        np.asarray((s >= thr[:, None]) & (s > -jnp.inf)), want)


def test_a_decode_step_reads_only_the_rows_it_selected(toy):
    """Every K and V row of the pools that the step did not select is
    NaN: the output is the same, to the bit."""
    layer, p, x = _layer_inputs(toy)
    ps, lp, b = 4, LENGTH // 4 + 1, 2
    pos = jnp.asarray([LENGTH - 1, 50], jnp.int32)
    table = jnp.asarray(np.stack([np.arange(lp), np.arange(lp) + lp]),
                        jnp.int32)
    cache = layer.init_paged_cache(2 * lp, ps)
    # the rows' contexts written by a prefill of each
    for r, n in enumerate(np.asarray(pos)):
        _, cache = layer.apply_decode_pages(
            p, x[:, :n], cache, table[r:r + 1], jnp.zeros((1,), jnp.int32),
            jnp.ones((1,), bool))
    step = x[0, np.asarray(pos)][:, None]
    want, _ = layer.apply_decode_pages(p, step, cache, table, pos,
                                       jnp.ones((b,), bool))
    # what the step selects: its indexer over the pool it will write
    qi, wi, ki = layer._index_qk(p, step, pos[:, None])
    phys = table[jnp.arange(b), pos // ps] * ps + pos % ps
    cki = cache["ki"].reshape(-1, 128).at[phys].set(
        jnp.pad(ki[:, 0], ((0, 0), (0, 112)))).reshape(cache["ki"].shape)
    idx, chosen = sa.select_topk(
        sa.paged_index_scores(qi[:, 0], wi[:, 0], cki, table, pos), TOPK)
    rows = jnp.take_along_axis(table, idx // ps, axis=1) * ps + idx % ps
    keep = np.zeros(cache["k"].shape[0] * ps, bool)
    keep[np.asarray(rows)[np.asarray(chosen)]] = True
    assert keep.sum() == 2 * TOPK
    poisoned = dict(cache)
    for name in ("k", "v"):
        flat = np.asarray(cache[name]).reshape(-1, 128).copy()
        flat[~keep] = np.nan
        poisoned[name] = jnp.asarray(flat.reshape(cache[name].shape))
    got, new = layer.apply_decode_pages(p, step, poisoned, table, pos,
                                        jnp.ones((b,), bool))
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and the step wrote its own K, V and indexer key
    assert np.isfinite(np.asarray(new["k"]).reshape(-1, 128)[phys]).all()


def test_with_topk_covering_the_context_the_layer_is_plain_gqa(toy):
    """``topk`` at least the context: every key is selected, and the layer
    is ``GroupedQueryAttention`` (roped, head-normed, no window) over the
    same weights, in the prefill and through the pages."""
    _, p, x = _layer_inputs(toy, 32)
    sparse = SparseGroupedQueryAttention(64, 4, 2, 16, 1e7, 1e-6, 16, 16, 8,
                                         topk=64, block=16)
    plain = GroupedQueryAttention(64, 4, 2, 16, None, True, 1e7, 1e-6)
    want, _ = plain.apply(p, {}, x)
    got, _ = sparse.apply(p, {}, x)
    np.testing.assert_allclose(got, want, atol=2e-5)
    table = jnp.arange(8, dtype=jnp.int32)[None]
    cache = sparse.init_paged_cache(8, 4)
    _, cache = sparse.apply_decode_pages(p, x[:, :20], cache, table,
                                         jnp.zeros((1,), jnp.int32),
                                         jnp.ones((1,), bool))
    for t in range(20, 32):
        y, cache = sparse.apply_decode_pages(
            p, x[:, t:t + 1], cache, table, jnp.asarray([t]),
            jnp.ones((1,), bool))
        np.testing.assert_allclose(y[0, 0], want[0, t], atol=2e-5)


# -- (c) the softmax router's shares ------------------------------------------------------

def test_eight_shares_of_two_experts_make_the_uncut_layer():
    """An expert layer of the uncut model (all 16 experts held) against
    the reference's, and the sum over eight chips' shares of 2 experts each
    (no shared expert: nothing is computed once)."""
    full = HybridLM(VOCAB, **dict(TOY, experts_held=16))
    params, _ = full.init(jax.random.PRNGKey(5))
    p = params["blocks"][0]
    x = jax.random.normal(jax.random.PRNGKey(6), (LENGTH, 64))
    assert set(p["ffn"]) == {"router", "experts"}
    cfg = tuple(sorted(dict(keye_vl2.PUBLISHED, **REF).items()))
    want = np.asarray(keye_vl2._ffn(p, x, cfg=cfg))
    h = full.norm.apply(p["norm2"], {}, x)[0]
    valid = jnp.ones((LENGTH,), bool)
    routed = []
    for o in range(0, 16, 2):
        chip = HybridLM(VOCAB, **dict(TOY, experts_held=2, expert_offset=o))
        share = dict(p["ffn"], experts=jax.tree_util.tree_map(
            lambda a: a[o:o + 2], p["ffn"]["experts"]))
        routed.append(chip._experts(share, h, valid)[0])
    np.testing.assert_allclose(x + sum(routed), want, atol=5e-5)
    ids, gates = full._route(p["ffn"], h)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 1.0, rtol=1e-6)
    # the reference given one share leaves out what the absent 14 add
    one = dict(p, ffn=dict(p["ffn"], experts=jax.tree_util.tree_map(
        lambda a: a[4:6], p["ffn"]["experts"])))
    part = keye_vl2._ffn(one, x, cfg=tuple(sorted(
        dict(cfg, expert_offset=4).items())))
    np.testing.assert_allclose(part, x + routed[2], atol=5e-5)


# -- the generator ---------------------------------------------------------------------

def test_generator_serves_the_references_argmax_and_counts_the_keys(
        toy, tmp_path):
    """Three pools a layer, one tree: ``ContinuousGenerator`` and its page
    allocator serve the pattern unchanged; the decode spans count the keys
    each row scored (p + 1 a layer) and attended over (min(p + 1, topk))."""
    from benchmark import spans
    model, params, state = toy
    ledger.set_run_dir(str(tmp_path))
    try:
        gen = ContinuousGenerator(model, params, state, num_slots=3,
                                  max_len=128, seq_buckets=[32, 64],
                                  cache_dtype=jnp.float32)
        try:
            st = gen.stats()
            outs = [f.result(timeout=300) for f in
                    [gen.submit(SEQ[:n], 12) for n in (40, 9, 60)]]
        finally:
            gen.drain(timeout=60)
        ledger.flush()
    finally:
        ledger.set_run_dir(None)
    for n, out in zip((40, 9, 60), outs):
        seq = np.concatenate([SEQ[:n], out]).astype(np.int32)
        logits = np.asarray(keye_vl2.logits_at(
            params, seq, np.arange(n - 1, n + 11), heads=4, **REF))
        assert (logits.argmax(-1) + 1 == out).all()
    # a page holds K, V (2 x 32 lanes, padded to 128) and the indexer's
    # key (16, padded to 128) of 16 tokens, float32, in each of 2 layers
    assert st["pages"]["page_bytes"] == 2 * 3 * 16 * 128 * 4
    records = spans.read_ledger(str(tmp_path))
    decodes = [r["attrs"] for r in spans.spans_named(records, "serve.decode")]
    assert decodes and all("keys_selected" in d for d in decodes)
    steps = [n + i for n in (40, 9, 60) for i in range(11)]
    assert sum(d["keys_indexed"] for d in decodes) \
        == 2 * sum(p + 1 for p in steps)
    assert sum(d["keys_selected"] for d in decodes) \
        == 2 * sum(min(p + 1, TOPK) for p in steps)
