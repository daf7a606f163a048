"""Window and full grouped-query attention in one pattern model
(``models/hybrid.py`` mixers ``"swa"`` and ``"full"``,
``nn.GroupedQueryAttention``): a ring a slot beside the page pool, against
the plain reference ``benchmark/reference/k_exaone.py`` at toy widths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import k_exaone
from bigdl_tpu.models.hybrid import HybridLM
from bigdl_tpu.models.transformer import TransformerLM
from bigdl_tpu.nn.attention import GroupedQueryAttention, _band_attention
from bigdl_tpu.observability import ledger
from bigdl_tpu.parallel import expert
from bigdl_tpu.serving.scheduler import ContinuousGenerator
from bigdl_tpu.serving.scheduler.membudget import MemoryBudgeter

VOCAB, WINDOW, LENGTH = 97, 8, 40
TOY = dict(max_len=64, embed_dim=64, num_heads=4, num_kv_heads=2,
           num_layers=3,
           layers=[["swa", "dense"], ["swa", "experts"], ["full", "experts"]],
           head_dim=16, ffn_dim=96, expert_dim=24, num_experts=16,
           experts_per_token=4, n_group=1, topk_group=1, routed_scale=2.5,
           experts_held=8, expert_offset=0, window=WINDOW, rope_theta=1e6,
           norm_eps=1e-5)
REF = dict(sliding_window=WINDOW, num_experts_per_tok=4,
           layer_types=("sliding_attention", "sliding_attention",
                        "full_attention"))
SEQ = np.random.default_rng(0).integers(1, VOCAB + 1, LENGTH) \
    .astype(np.int32)


@pytest.fixture(scope="module")
def toy():
    model = HybridLM(VOCAB, **TOY)
    params, state = model.init(jax.random.PRNGKey(3))
    # a trained router brings a bias, a trained norm its weights
    params["blocks"][1]["ffn"]["bias"] = 0.3 * jax.random.normal(
        jax.random.PRNGKey(2), (16,))
    params["blocks"][2]["mixer"]["q_norm"]["weight"] = 1.0 + 0.2 \
        * jax.random.normal(jax.random.PRNGKey(4), (16,))
    return model, params, state


def _reference(params, **kw):
    logits = k_exaone.logits_at(params, SEQ, np.arange(LENGTH), heads=4,
                                **dict(REF, **kw))
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


def _served(model, params, state, n, slots=3, ps=4):
    """Log-probs after positions n-1 .. LENGTH-1 of ``SEQ`` served in slot
    1 of ``slots``: a prefill of ``n`` tokens in a bucket of 32, then one
    token a step through the pages and the rings; and the cache."""
    lp = 16
    cache = model.init_paged_cache(slots * lp, ps, jnp.float32,
                                   num_slots=slots)
    table = np.full((slots, lp), slots * lp, np.int32)
    table[1] = np.arange(lp) + lp
    padded = np.ones((1, 32), np.int32)
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


# -- (a) pages and rings against the whole-sequence reference ---------------------

def test_forward_equals_the_reference(toy):
    model, params, state = toy
    logp, _ = model.apply(params, state, SEQ[None])
    np.testing.assert_allclose(np.asarray(logp[0]), _reference(params),
                               atol=2e-5)


@pytest.mark.parametrize("n", [5, WINDOW, 13, 2 * WINDOW + 3],
                         ids=["shorter", "the_window", "longer", "wrapped"])
def test_prefill_then_decode_through_pages_and_rings_equals_the_reference(
        toy, n):
    """Prompts shorter than, equal to and longer than the window, one
    that has wrapped the ring already, and 27 to 35 decode steps each:
    three to four more wraps."""
    model, params, state = toy
    got, _ = _served(model, params, state, n)
    np.testing.assert_allclose(got, _reference(params)[n - 1:], atol=2e-5)


def test_band_attention_blocks_equal_the_whole_matrix():
    """The blocked band against plain masked attention at a length several
    blocks long, windows shorter than, equal to and longer than a block,
    and a score tile that forces small blocks."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 4, 48, 16))
    k, v = (jax.random.normal(kk, (2, 2, 48, 16)) for kk in ks[1:])
    i, j = np.arange(48)[:, None], np.arange(48)[None]
    for window in (5, 16, 40):
        seen = (j <= i) & (i - j < window)
        kk, vv = (jnp.repeat(a, 2, axis=1) for a in (k, v))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kk) * 0.25
        want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(
            jnp.where(seen, s, -jnp.inf), axis=-1), vv)
        for tile in (1 << 28, 2 * 4 * 8 * 48 * 4):
            got = _band_attention(q, k, v, 0.25, window, score_bytes=tile)
            np.testing.assert_allclose(got, want, atol=2e-5)


# -- (b) three controls fail the same comparison ------------------------------------

def _worst(got, want):
    return float(np.abs(got - want).max())


def test_a_window_off_by_one_fails(toy):
    model, params, state = toy
    want = _reference(params)[12:]
    assert _worst(_served(model, params, state, 13)[0], want) < 2e-5
    wider = HybridLM(VOCAB, **dict(TOY, window=WINDOW + 1))
    assert _worst(_served(wider, params, state, 13)[0], want) > 1e-3
    # and the reference read with the other window disagrees likewise
    assert _worst(_reference(params, sliding_window=WINDOW - 1)[12:],
                  want) > 1e-3


def test_rope_on_the_full_layers_fails(toy):
    model, params, state = toy
    roped = HybridLM(VOCAB, **TOY)
    assert [m.rope for m in roped.mixers] == [True, True, False]
    roped.mixers[2].rope = True
    assert _worst(_served(roped, params, state, 13)[0],
                  _reference(params)[12:]) > 1e-3


def test_the_routers_scores_in_bf16_fail(toy, monkeypatch):
    """With a router that has made up its mind (`ROUTER_GAIN`) the best
    scores lie where bfloat16 cannot tell them apart: other experts are
    chosen, and the comparison sees it."""
    model, params, state = toy
    real = expert.sigmoid_group_route

    def rounded(scores, *a, **kw):
        return real(jax.lax.reduce_precision(scores, 8, 7), *a, **kw)

    monkeypatch.setattr("bigdl_tpu.models.hybrid.sigmoid_group_route",
                        rounded)
    got, _ = _served(HybridLM(VOCAB, **TOY), params, state, 13)
    assert _worst(got, _reference(params)[12:]) > 1e-3


# -- (c) the shares add up --------------------------------------------------------------

def test_eight_shares_with_what_every_chip_computes_once_make_the_uncut_layer():
    """An expert layer's block of the uncut model (all 16 experts held)
    against the plain reference's, and the sum over eight chips' shares of
    2 experts each of what only the held experts add, with the attention,
    the residual and the shared expert counted once."""
    full = HybridLM(VOCAB, **dict(TOY, experts_held=16))
    params, _ = full.init(jax.random.PRNGKey(5))
    p = params["blocks"][1]
    x = jax.random.normal(jax.random.PRNGKey(6), (LENGTH, 64))
    cfg = tuple(sorted({**k_exaone.PUBLISHED, **REF,
                        "layer_types": ()}.items()))
    mixed = k_exaone._mixer(p, x, heads=4, kind="sliding_attention", cfg=cfg)
    want = np.asarray(k_exaone._ffn(p, mixed, cfg=cfg))
    h = full.norm.apply(p["norm2"], {}, mixed)[0]
    valid = jnp.ones((LENGTH,), bool)
    shared = full.shared.apply(p["ffn"]["shared"], {}, h)[0]
    routed = []
    for o in range(0, 16, 2):
        chip = HybridLM(VOCAB, **dict(TOY, experts_held=2, expert_offset=o))
        share = dict(p["ffn"], experts=jax.tree_util.tree_map(
            lambda a: a[o:o + 2], p["ffn"]["experts"]))
        routed.append(chip._experts(share, h, valid)[0] - shared)
    np.testing.assert_allclose(mixed + sum(routed) + shared, want, atol=5e-5)
    # the reference given one share leaves out what the absent 14 add
    one = dict(p, ffn=dict(p["ffn"], experts=jax.tree_util.tree_map(
        lambda a: a[4:6], p["ffn"]["experts"])))
    part = k_exaone._ffn(one, mixed, cfg=tuple(sorted(
        dict(cfg, expert_offset=4).items())))
    np.testing.assert_allclose(part, mixed + routed[2] + shared, atol=5e-5)


@pytest.mark.parametrize("tokens", [64, 61])
def test_long_wide_prefills_take_the_blocked_product(monkeypatch, tokens):
    """Past `PAIR_ELEMENTS` the grouped product goes block by block of
    sorted pairs, whole blocks or not; the same sum, the same counters."""
    x = jax.random.normal(jax.random.PRNGKey(8), (tokens, 64))
    kg, kd = jax.random.split(jax.random.PRNGKey(7))
    wgu = jax.random.normal(kg, (4, 64, 48)) * 64 ** -0.5
    wd = jax.random.normal(kd, (4, 24, 64)) * 24 ** -0.5
    scores = jax.nn.sigmoid(4 * jax.random.normal(jax.random.PRNGKey(9),
                                                  (tokens, 16)))
    ids, gates = expert.sigmoid_group_route(scores, jnp.zeros(16), 4, 1, 1,
                                            2.5)
    valid = jnp.arange(tokens) < 59
    monkeypatch.setattr(expert, "DENSE_TOKENS", 0)
    want, c0 = expert.held_experts_apply(x, ids, gates, valid, wgu, wd, 4)
    monkeypatch.setattr(expert, "PAIR_ELEMENTS", 0)
    monkeypatch.setattr(expert, "PAIR_BLOCK", 16)
    got, c1 = expert.held_experts_apply(x, ids, gates, valid, wgu, wd, 4)
    assert int(c0["pairs"]) > 32            # several blocks' worth
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert {k: int(v) for k, v in c0.items()} \
        == {k: int(v) for k, v in c1.items()}


# -- (d) a free or finished slot writes nothing ----------------------------------------

def test_a_free_or_finished_slot_never_writes_a_ring_row(toy):
    model, params, state = toy
    _, cache = _served(model, params, state, 13)
    assert not model.mixers[2].window and model.mixers[0].window == WINDOW
    for entry, (kind, _) in zip(cache["slots"], model.layers):
        if kind != "swa":
            assert entry == {}
            continue
        for ring in (entry["k"], entry["v"]):
            assert ring.shape == (3, WINDOW, 2 * 16)
            assert not np.asarray(ring[jnp.asarray([0, 2])]).any()
            assert np.asarray(ring[1]).all()
    # nor a page: every write of the two other rows went to the trash page
    pool = cache["pages"][2]["k"]
    assert not np.asarray(pool[:16]).any() and not np.asarray(
        pool[32:48]).any()
    assert np.asarray(pool[16:16 + LENGTH // 4]).any(axis=(1, 2)).all()


def test_padding_of_a_prefill_bucket_reaches_neither_ring_nor_counters(toy):
    model, params, state = toy
    layer = model.mixers[0]
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 64))
    st = layer.init_slot_state(1)
    p = params["blocks"][0]["mixer"]
    _, full = layer.apply_slots(p, x[:, :11], st, jnp.zeros((1,), jnp.int32),
                                jnp.ones((1,), bool))
    _, padded = layer.apply_slots(p, x, st, jnp.zeros((1,), jnp.int32),
                                  jnp.ones((1,), bool), jnp.asarray([11]))
    for name in ("k", "v"):
        np.testing.assert_allclose(padded[name], full[name], atol=1e-6)
    # ring row r holds position r + 8 where that is a real token: 8, 9, 10
    k = layer._qkv(p, x, jnp.arange(16)[None])[1]       # (1, Hkv, S, D)
    rows = np.asarray(k.transpose(0, 2, 1, 3).reshape(16, 32))
    np.testing.assert_allclose(padded["k"][0], rows[[8, 9, 10, 3, 4, 5, 6,
                                                     7]], atol=1e-6)


# -- the generator ---------------------------------------------------------------------

def _generator(model, params, state, **kw):
    kw = dict(dict(num_slots=3, max_len=64, seq_buckets=[16, 32],
                   cache_dtype=jnp.float32), **kw)
    return ContinuousGenerator(model, params, state, **kw)


def test_generator_serves_the_references_argmax_and_counts_by_kind(
        toy, tmp_path):
    from benchmark import spans
    model, params, state = toy
    budget = MemoryBudgeter()
    ledger.set_run_dir(str(tmp_path))
    try:
        gen = _generator(model, params, state, budgeter=budget)
        try:
            st = gen.stats()
            outs = [f.result(timeout=300) for f in
                    [gen.submit(SEQ[:n], 11) for n in (13, 5, 21)]]
        finally:
            gen.drain(timeout=60)
        ledger.flush()
    finally:
        ledger.set_run_dir(None)
    for n, out in zip((13, 5, 21), outs):
        seq = np.concatenate([SEQ[:n], out]).astype(np.int32)
        logits = np.asarray(k_exaone.logits_at(
            params, seq, np.arange(n - 1, n + 10), heads=4, **REF))
        assert (logits.argmax(-1) + 1 == out).all()
    # two rings of K and V (window 8 x 2 KV heads x 16) a slot, float32;
    # one full layer's pages of 16 tokens x 128 lanes (32 padded)
    ring = 2 * 2 * WINDOW * 32 * 4
    assert st["state"] == {"bytes_per_slot": ring, "bytes": 3 * ring,
                           "bytes_per_slot_by_kind": {"swa": ring}}
    assert st["pages"]["page_bytes"] == 2 * 16 * 128 * 4
    assert st["pages"]["bytes_by_kind"] == {
        "page": {"full": 2 * 16 * 128 * 4}, "slot": {"swa": ring}}
    assert budget.charged("default", "slot_state") == 0
    records = spans.read_ledger(str(tmp_path))
    pages = [r for r in records if r.get("type") == "serve.pages"]
    assert pages and pages[-1]["bytes_by_kind"]["slot"] == {"swa": ring}
    charged = [r for r in records if r.get("type") == "mem.budget"
               and r.get("cls") == "slot_state"
               and r.get("action") == "charge"]
    assert len(charged) == 3 and all(r["bytes"] == ring for r in charged)
    # the decode spans: per chunk, over its row-steps, p + 1 keys a full
    # layer read and min(p + 1, window) a window layer
    decodes = [r["attrs"] for r in spans.spans_named(records, "serve.decode")]
    assert decodes and all(
        d["full_tokens"] == d["latent_tokens"] for d in decodes)
    assert sum(d["state_rows"] for d in decodes) == 3 * 10
    assert sum(d["full_tokens"] for d in decodes) == sum(
        n + i + 1 for n in (13, 5, 21) for i in range(10))
    assert sum(d["window_tokens"] for d in decodes) == sum(
        min(n + i + 1, WINDOW) for n in (13, 5, 21) for i in range(10))
    assert all("expert_pairs" in d for d in decodes)


# -- (e) what was there gives the numbers it gave ---------------------------------------

PINNED = {
    "transformer": [-5408.810546875, -3.3973827362060547,
                    -454.9201965332031, -5.421809196472168],
    "hybrid": [-6661.5380859375, -4.876734256744385, -4.42431640625]}


def test_transformer_lm_and_the_recurrent_pattern_give_the_numbers_they_gave():
    """Pinned at the parent commit (PR 32) with this script: the paged
    decode of a toy ``TransformerLM`` (whose layer's read is now
    ``_paged_read``) and of the recurrent pattern's toy (whose model gained
    two mixers)."""
    lm = TransformerLM(50, max_len=32, embed_dim=32, num_heads=4,
                       num_layers=2, ffn_dim=64, position="rope",
                       num_kv_heads=2)
    params, state = lm.init(jax.random.PRNGKey(0))
    cache = lm.init_paged_cache(4, 16, jnp.float32)
    table = np.asarray([[0, 1], [2, 3]], np.int32)
    tok = np.arange(1, 25, dtype=np.int32).reshape(2, 12)
    out, cache = lm.decode_pages(params, state, tok, cache, table,
                                 jnp.zeros((2,), jnp.int32),
                                 jnp.ones((2,), bool))
    nxt, _ = lm.decode_pages(params, state, tok[:, :1], cache, table,
                             jnp.full((2,), 12, jnp.int32),
                             jnp.ones((2,), bool))
    got = [float(out.sum()), float(out[1, 7, 3]), float(nxt.sum()),
           float(nxt[0, 0, 11])]
    np.testing.assert_allclose(got, PINNED["transformer"], rtol=2e-6)

    hy = HybridLM(50, max_len=64, embed_dim=64, num_heads=4, num_layers=3,
                  layers=[["kda", "dense"], ["kda", "experts"],
                          ["mla", "experts"]], head_dim=16, ffn_dim=96,
                  expert_dim=24, num_experts=16, experts_per_token=4,
                  n_group=4, topk_group=2, experts_held=8, latent_dim=32,
                  rope_dim=8, nope_dim=16, v_dim=16)
    params, state = hy.init(jax.random.PRNGKey(1))
    logp, _ = hy.apply(params, state, np.arange(1, 31)[None])
    got = [float(logp.sum()), float(logp[0, 17, 5]), float(logp[0, 29, 49])]
    np.testing.assert_allclose(got, PINNED["hybrid"], rtol=2e-6)


