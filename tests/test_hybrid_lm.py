"""The layer-pattern model with recurrent state (``models/hybrid.py``) and
what it forced: the delta-rule recurrence in its three forms, absorbed
latent attention through pages, sigmoid group-limited routing without
drops over the experts a chip holds, and two kinds of state in
``ContinuousGenerator``.  CPU, toy widths (hidden 64, 4 heads of 16, 16
experts in 4 groups, top 4 of 2 groups, latent 32 + rope 8), float32."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import ling3_flash as reference
from bigdl_tpu import nn
from bigdl_tpu.models.hybrid import HybridLM
from bigdl_tpu.observability import ledger
from bigdl_tpu.ops.delta_rule import kda_chunked, kda_naive, kda_step
from bigdl_tpu.parallel import expert
from bigdl_tpu.parallel.expert import (held_experts_apply,
                                       sigmoid_group_route)
from bigdl_tpu.serving.errors import RecurrentStateError
from bigdl_tpu.serving.scheduler import ContinuousGenerator
from bigdl_tpu.serving.scheduler.membudget import MemoryBudgeter

VOCAB = 50
TOY = dict(max_len=64, embed_dim=64, num_heads=4, num_layers=4,
           layers=[["kda", "dense"], ["kda", "experts"], ["mla", "experts"],
                   ["mla", "dense"]],
           head_dim=16, ffn_dim=96, expert_dim=24, num_experts=16,
           experts_per_token=4, n_group=4, topk_group=2, routed_scale=2.5,
           experts_held=8, expert_offset=0, latent_dim=32, rope_dim=8,
           nope_dim=16, v_dim=16, rope_theta=6e6)
REF = dict(num_experts_per_tok=4, n_group=4, topk_group=2, kv_lora_rank=32,
           qk_rope_head_dim=8)


@pytest.fixture(scope="module")
def toy():
    model = HybridLM(VOCAB, **TOY)
    params, state = model.init(jax.random.PRNGKey(1))
    # a trained router brings a bias: give the first expert layer one
    params["blocks"][1]["ffn"]["bias"] = 0.3 * jax.random.normal(
        jax.random.PRNGKey(2), (16,))
    return model, params, state


def _generator(model, params, state, **kw):
    kw = dict(dict(num_slots=3, max_len=64, seq_buckets=[16, 32],
                   cache_dtype=jnp.float32), **kw)
    return ContinuousGenerator(model, params, state, **kw)


# -- the delta rule ----------------------------------------------------------------

def _kda_inputs(t, b=2, h=3, dk=16, dv=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    return (unit(jax.random.normal(ks[0], (b, t, h, dk))) * dk ** -0.5,
            unit(jax.random.normal(ks[1], (b, t, h, dk))),
            jax.random.normal(ks[2], (b, t, h, dv)),
            -5.0 * jax.nn.sigmoid(2 * jax.random.normal(ks[3],
                                                        (b, t, h, dk))),
            jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h))),
            jax.random.normal(ks[5], (b, h, dk, dv)))


@pytest.mark.parametrize("t,chunk", [(37, 16), (64, 64), (150, 64),
                                     (150, 7), (5, 64)])
def test_kda_chunked_equals_the_token_loop(t, chunk):
    q, k, v, g, beta, s0 = _kda_inputs(t)
    want_o, want_s = kda_naive(q, k, v, g, beta, s0)
    got_o, got_s = jax.jit(lambda *a: kda_chunked(*a, chunk=chunk))(
        q, k, v, g, beta, s0)
    np.testing.assert_allclose(got_o, want_o, atol=2e-5)
    np.testing.assert_allclose(got_s, want_s, atol=2e-5)


def test_kda_step_is_the_definition():
    q, k, v, g, beta, s0 = _kda_inputs(1, b=1, h=1)
    o, s1 = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], s0)
    s = np.exp(np.asarray(g[0, 0, 0]))[:, None] * np.asarray(s0[0, 0])
    kk, vv = np.asarray(k[0, 0, 0]), np.asarray(v[0, 0, 0])
    s = s + float(beta[0, 0, 0]) * np.outer(kk, vv - s.T @ kk)
    np.testing.assert_allclose(s1[0, 0], s, atol=1e-6)
    np.testing.assert_allclose(o[0, 0], s.T @ np.asarray(q[0, 0, 0]),
                               atol=1e-6)


def test_kda_masked_tokens_leave_the_state_alone():
    q, k, v, g, beta, s0 = _kda_inputs(40)
    real = jnp.arange(40) < 23
    g = jnp.where(real[None, :, None, None], g, 0.0)
    beta = jnp.where(real[None, :, None], beta, 0.0)
    _, padded = kda_chunked(q, k, v, g, beta, s0, chunk=16)
    _, short = kda_chunked(q[:, :23], k[:, :23], v[:, :23], g[:, :23],
                           beta[:, :23], s0, chunk=16)
    np.testing.assert_allclose(padded, short, atol=1e-5)


# -- latent attention -------------------------------------------------------------

def test_absorbed_latent_attention_through_pages_equals_expanded():
    att = nn.LatentAttention(64, 4, latent_dim=32, rope_dim=8, nope_dim=16,
                             v_dim=16, rope_theta=6e6)
    params = att.init_params(jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, 64))
    want, _ = att.apply(params, {}, x)                  # expanded, causal
    ps, lp = 4, 8
    cache = att.init_paged_cache(2 * lp, ps)
    pages = jnp.arange(2 * lp, dtype=jnp.int32).reshape(2, lp)
    on = jnp.ones((2,), bool)
    # prefill 17 tokens expanded, then 7 absorbed steps through the pool
    got, cache = att.apply_decode_pages(params, x[:, :17], cache, pages,
                                        jnp.zeros((2,), jnp.int32), on)
    np.testing.assert_allclose(got, want[:, :17], atol=2e-5)
    for t in range(17, 24):
        y, cache = att.apply_decode_pages(
            params, x[:, t:t + 1], cache, pages,
            jnp.full((2,), t, jnp.int32), on)
        np.testing.assert_allclose(y[:, 0], want[:, t], atol=2e-5)


def test_latent_writes_of_inactive_rows_go_to_the_trash_page():
    att = nn.LatentAttention(64, 4, latent_dim=32, rope_dim=8, nope_dim=16,
                             v_dim=16)
    params = att.init_params(jax.random.PRNGKey(3))
    cache = att.init_paged_cache(4, 4)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 1, 64))
    _, new = att.apply_decode_pages(
        params, x, cache, jnp.asarray([[0, 1], [2, 3]], jnp.int32),
        jnp.asarray([1, 1], jnp.int32), jnp.asarray([True, False]))
    pool = np.asarray(new["k"])
    assert np.abs(pool[0]).sum() > 0            # row 0 wrote its page
    assert np.abs(pool[2:4]).sum() == 0         # row 1 touched none of its


# -- routing -------------------------------------------------------------------------

def _scores(t=64, n=16, seed=0):
    return jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(seed),
                                            (t, n)))


def test_routing_bias_moves_the_selection_and_not_the_gates():
    s = _scores()
    ids0, g0 = sigmoid_group_route(s, jnp.zeros(16), 4, 4, 2, 2.5)
    bias = jnp.zeros(16).at[5].set(10.0)
    ids1, g1 = sigmoid_group_route(s, bias, 4, 4, 2, 2.5)
    assert (np.asarray(ids1) == 5).any(axis=1).all()    # always chosen
    assert not (np.asarray(ids0) == 5).any(axis=1).all()
    chosen = np.take_along_axis(np.asarray(s), np.asarray(ids1), axis=1)
    np.testing.assert_allclose(
        g1, 2.5 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)


def test_routing_keeps_two_groups_and_normalises_over_all_chosen():
    s = _scores(seed=3)
    ids, gates = sigmoid_group_route(s, jnp.zeros(16), 4, 4, 2, 2.5)
    groups = np.asarray(ids) // 4
    assert all(len(set(row)) <= 2 for row in groups)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 2.5, rtol=1e-6)
    # the kept groups are the two with the largest sum of their two best
    rank = np.sort(np.asarray(s).reshape(-1, 4, 4), axis=-1)[..., -2:].sum(-1)
    want = np.sort(np.argsort(-rank, axis=-1)[:, :2], axis=-1)
    got = np.stack([np.unique(row) if len(set(row)) == 2
                    else want[i] for i, row in enumerate(groups)])
    np.testing.assert_array_equal(got, want)
    # and the reference routes the same way
    ref_ids, ref_gates = reference.route(
        s, jnp.zeros(16), {**reference.PUBLISHED, **REF})
    np.testing.assert_array_equal(np.sort(ids, axis=-1),
                                  np.sort(ref_ids, axis=-1))


def _expert_weights(held, e=64, f=24, seed=7):
    kg, kd = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(kg, (held, e, 2 * f)) * e ** -0.5,
            jax.random.normal(kd, (held, f, e)) * f ** -0.5)


def _dense_experts(x, ids, gates, wgu, wd, offset=0):
    """Every held expert on every token, weighted by its gate."""
    out = np.zeros(x.shape, np.float32)
    f = wgu.shape[-1] // 2
    for t in range(x.shape[0]):
        for e_id, g in zip(np.asarray(ids[t]), np.asarray(gates[t])):
            j = int(e_id) - offset
            if 0 <= j < wgu.shape[0]:
                h = np.asarray(x[t]) @ np.asarray(wgu[j])
                h = np.asarray(jax.nn.silu(h[:f])) * h[f:]
                out[t] += float(g) * (h @ np.asarray(wd[j]))
    return out


@pytest.fixture(params=["grouped", "every_expert"])
def product(request, monkeypatch):
    """Both forms of the held experts' product on the same small batches:
    the sorted grouped one (any number of tokens) and every held expert
    over every token (what a decode step's few tokens take)."""
    monkeypatch.setattr(expert, "DENSE_TOKENS",
                        0 if request.param == "grouped" else 128)
    return request.param


def test_no_token_is_dropped_when_every_token_takes_one_expert(product):
    x = jax.random.normal(jax.random.PRNGKey(8), (40, 64))
    wgu, wd = _expert_weights(8)
    ids = jnp.full((40, 4), 3, jnp.int32).at[:, 1:].set(
        jnp.asarray([9, 10, 11]))                 # 3 held here, rest absent
    gates = jnp.full((40, 4), 0.625)
    y, c = held_experts_apply(x, ids, gates, jnp.ones((40,), bool), wgu, wd)
    np.testing.assert_allclose(y, _dense_experts(x, ids, gates, wgu, wd),
                               atol=2e-5)
    assert (int(c["pairs"]), int(c["hit"]), int(c["max"])) == (40, 1, 40)


def test_counters_are_a_replay_of_the_routing(product):
    x = jax.random.normal(jax.random.PRNGKey(9), (33, 64))
    wgu, wd = _expert_weights(4)
    ids, gates = sigmoid_group_route(_scores(33, seed=5), jnp.zeros(16), 4,
                                     4, 2, 2.5)
    valid = jnp.arange(33) < 29
    _, c = held_experts_apply(x, ids, gates, valid, wgu, wd,
                              expert_offset=8)
    local = np.asarray(ids)[:29] - 8
    counts = np.bincount(local[(local >= 0) & (local < 4)], minlength=4)
    assert int(c["pairs"]) == counts.sum()
    assert int(c["hit"]) == (counts > 0).sum()
    assert int(c["max"]) == counts.max()


def test_four_shares_and_the_shared_expert_once_make_the_uncut_layer(product):
    x = jax.random.normal(jax.random.PRNGKey(10), (48, 64))
    wgu, wd = _expert_weights(16)
    ids, gates = sigmoid_group_route(_scores(48, seed=6), jnp.zeros(16), 4,
                                     4, 2, 2.5)
    valid = jnp.ones((48,), bool)
    whole, _ = held_experts_apply(x, ids, gates, valid, wgu, wd)
    parts = [held_experts_apply(x, ids, gates, valid, wgu[o:o + 4],
                                wd[o:o + 4], expert_offset=o)[0]
             for o in (0, 4, 8, 12)]
    np.testing.assert_allclose(sum(parts), whole, atol=2e-5)
    np.testing.assert_allclose(whole,
                               _dense_experts(x, ids, gates, wgu, wd),
                               atol=2e-5)
    # through the model's layer: four chips' outputs share ONE shared expert
    models = [HybridLM(VOCAB, **dict(TOY, experts_held=4, expert_offset=o))
              for o in (0, 4, 8, 12)]
    full = HybridLM(VOCAB, **dict(TOY, experts_held=16))
    p = full._init_experts(jax.random.PRNGKey(11))
    y_full, _ = full._experts(p, x, valid)
    shared = full.shared.apply(p["shared"], {}, x)[0]
    routed = []
    for m, o in zip(models, (0, 4, 8, 12)):
        share = dict(p, experts=jax.tree_util.tree_map(
            lambda a: a[o:o + 4], p["experts"]))
        routed.append(m._experts(share, x, valid)[0] - shared)
    np.testing.assert_allclose(sum(routed) + shared, y_full, atol=5e-5)


# -- the model against the plain reference -----------------------------------------

def test_forward_equals_the_reference(toy):
    model, params, state = toy
    toks = np.random.default_rng(0).integers(1, VOCAB + 1, 64)
    logp, _ = model.apply(params, state, toks[None])
    logits = reference.logits_at(params, toks, np.arange(64), heads=4, **REF)
    np.testing.assert_allclose(logp[0], jax.nn.log_softmax(logits, -1),
                               atol=2e-4)


def test_prefill_then_decode_through_the_cache_equals_the_reference(toy):
    """The two calls the generator's programs make: a right-padded,
    slot-addressed prefill from position 0, then whole-batch steps with
    the other slots inactive: LOG-PROBS against the full forward."""
    model, params, state = toy
    slots, ps, slot, tp, steps = 3, 4, 1, 21, 9
    toks = np.random.default_rng(1).integers(1, VOCAB + 1, 64)
    logits = reference.logits_at(params, toks, np.arange(64), heads=4, **REF)
    want = np.asarray(jax.nn.log_softmax(logits, -1))
    cache = model.init_paged_cache(slots * 16, ps, jnp.float32,
                                   num_slots=slots)
    table = np.full((slots, 16), slots * 16, np.int32)
    table[slot, :8] = np.arange(8)
    pages = jnp.asarray(table)
    padded = np.ones((1, 32), np.int32)
    padded[0, :tp] = toks[:tp]
    lp, cache, counts = jax.jit(model.decode_pages)(
        params, state, padded, cache, pages[slot][None],
        jnp.zeros((1,), jnp.int32), jnp.ones((1,), bool),
        slots=jnp.asarray([slot]), lengths=jnp.asarray([tp]))
    assert lp.shape == (1, 1, VOCAB)
    np.testing.assert_allclose(lp[0, 0], want[tp - 1], atol=2e-4)
    assert int(counts["expert_pairs"]) > 0
    active = jnp.arange(slots) == slot
    step = jax.jit(model.decode_pages)
    for i in range(steps):
        tok = jnp.where(active, int(toks[tp + i]), 1)[:, None]
        lp, cache, _ = step(params, state, tok, cache, pages,
                            jnp.where(active, tp + i, 0), active)
        np.testing.assert_allclose(lp[slot, 0], want[tp + i], atol=2e-4)


def test_generator_serves_the_references_argmax(toy):
    model, params, state = toy
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, VOCAB + 1, n) for n in (5, 16, 23, 9, 30)]
    gen = _generator(model, params, state)
    try:
        outs = [f.result(timeout=300)
                for f in [gen.submit(p, 10) for p in prompts]]
    finally:
        gen.drain(timeout=60)
    for p, o in zip(prompts, outs):
        seq = np.ones(64, np.int32)
        seq[:len(p)] = p
        seq[len(p):len(p) + len(o) - 1] = o[:-1]
        rows = np.arange(len(p) - 1, len(p) - 1 + len(o))
        logits = np.asarray(reference.logits_at(params, seq, rows, heads=4,
                                                **REF))
        gap = (logits.max(-1) - logits[np.arange(len(o)), o - 1]) \
            / logits.std(-1)
        assert gap.max() < 1e-3


def test_a_pattern_without_kda_layers_is_prefilled_whole_from_zero():
    """A latent-attention layer attends over a prefill's own tokens only:
    a pattern with no ``kda`` layer declares the same serving contract,
    so that no shared prefix and no verify pass reaches it."""
    kw = dict(TOY, num_layers=2, layers=[["mla", "dense"],
                                         ["mla", "experts"]])
    model = HybridLM(VOCAB, **kw)
    params, state = model.init(jax.random.PRNGKey(4))
    assert model.recurrent_state
    rng = np.random.default_rng(6)
    head = rng.integers(1, VOCAB + 1, 16)        # one whole page, shared
    prompts = [np.concatenate([head, rng.integers(1, VOCAB + 1, n)])
               for n in (5, 9)]
    gen = _generator(model, params, state, num_slots=1)
    try:
        outs = [gen.submit(p, 6).result(timeout=300) for p in prompts]
        st = gen.stats()
    finally:
        gen.drain(timeout=60)
    assert st["counters"]["serve.gen.prefix.declined"] == 1
    assert st["prefix"] is None and st["state"]["bytes"] == 0
    for p, o in zip(prompts, outs):
        seq = np.ones(64, np.int32)
        seq[:len(p)] = p
        seq[len(p):len(p) + len(o) - 1] = o[:-1]
        rows = np.arange(len(p) - 1, len(p) - 1 + len(o))
        logits = np.asarray(reference.logits_at(params, seq, rows, heads=4,
                                                **REF))
        gap = (logits.max(-1) - logits[np.arange(len(o)), o - 1]) \
            / logits.std(-1)
        assert gap.max() < 1e-3
    with pytest.raises(RecurrentStateError):
        ContinuousGenerator(model, params, state, num_slots=1, max_len=64,
                            seq_buckets=[16], draft_model=model)


# -- two kinds of state in the generator -----------------------------------------

def test_a_slots_second_tenant_starts_from_zero_state(toy):
    model, params, state = toy
    rng = np.random.default_rng(3)
    first, second = (rng.integers(1, VOCAB + 1, n) for n in (19, 11))
    gen = _generator(model, params, state, num_slots=1)
    try:
        gen.submit(first, 6).result(timeout=300)
        again = gen.submit(second, 8).result(timeout=300)   # same slot
    finally:
        gen.drain(timeout=60)
    fresh = _generator(model, params, state, num_slots=1)
    try:
        alone = fresh.submit(second, 8).result(timeout=300)
    finally:
        fresh.drain(timeout=60)
    np.testing.assert_array_equal(again, alone)


def test_an_inactive_rows_state_is_bit_equal_after_a_chunk(toy):
    model, params, state = toy
    gen = _generator(model, params, state, warmup=False)
    try:
        cache = model.init_paged_cache(gen._alloc.num_pages, 16,
                                       jnp.float32, num_slots=3)
        marked = jax.tree_util.tree_map(
            lambda a: a + 1 if a.ndim and a.shape[0] == 3 else a,
            cache["slots"])
        cache = {"pages": cache["pages"], "slots": marked}
        table = np.full((3, 4), gen._alloc.trash, np.int32)
        table[0, :2] = [0, 1]
        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        out = gen._step_fn(params, state, jnp.asarray([7, 1, 1]), cache,
                           jnp.asarray(table), jnp.asarray([5, 0, 9]),
                           jnp.asarray([True, False, False]),
                           jnp.asarray([20, 0, 0]), keys)
        for before, after in zip(jax.tree_util.tree_leaves(marked),
                                 jax.tree_util.tree_leaves(out[1]["slots"])):
            np.testing.assert_array_equal(np.asarray(before)[1:],
                                          np.asarray(after)[1:])
            assert not np.array_equal(np.asarray(before)[0],
                                      np.asarray(after)[0])
        assert set(out[6]) == set(model.decode_counters)
    finally:
        gen.drain(timeout=60)


@pytest.mark.parametrize("kind", ["transformer", "hybrid"])
def test_one_decode_program(kind, toy):
    """One cache layout and one decode program, whatever the model: after
    warm-up the generator has compiled a prefill per bucket and ``step``,
    the scan of ``decode_pages`` (the name the trace readers find it by),
    on the CPU too; the two options that chose between layouts are
    gone, the two ``stats()`` keys the benchmark reads are not."""
    import re
    if kind == "hybrid":
        model, params, state = toy
    else:
        from bigdl_tpu.models.transformer import TransformerLM
        model = TransformerLM(VOCAB, max_len=64, embed_dim=32, num_heads=2,
                              num_layers=2)
        params, state = model.init(jax.random.PRNGKey(0))
    assert hasattr(model, "init_paged_cache") \
        and hasattr(model, "decode_pages")
    for gone in ("paged", "paged_kernel"):
        with pytest.raises(TypeError, match=gone):
            _generator(model, params, state, warmup=False, **{gone: True})
    gen = _generator(model, params, state, steps_per_sync=2)
    try:
        st = gen.stats()
        assert set(st["pages"]["program_temp_bytes"]) == {
            "prefill.16", "prefill.32", "step"}
        assert st["paged"] is True and isinstance(st["paged_kernel"], bool)
        assert not st["paged_kernel"]       # plain CPU: the gather reads
        lowered = gen._step_fn.lower(
            params, state, jnp.asarray(gen._tokens), gen._cache,
            jnp.asarray(gen._page_table), jnp.asarray(gen._pos),
            jnp.asarray(gen._active), jnp.asarray(gen._limit),
            jax.random.split(jax.random.PRNGKey(0), 2))
        name = re.search(r"module @(\S+)", lowered.as_text()).group(1)
        assert "step_chunk" in name
        out = gen.generate([np.arange(1, 8)], 6)[0]
        assert out.shape == (6,)
    finally:
        gen.drain(timeout=60)


def test_what_moves_pages_only_is_declined_or_refused_typed(toy):
    model, params, state = toy
    with pytest.raises(RecurrentStateError):
        _generator(model, params, state, warmup=False, draft_model=model,
                   draft_params=params, draft_state=state)
    gen = _generator(model, params, state, warmup=False, prefix_cache=True)
    try:
        st = gen.stats()
        assert st["prefix"] is None
        assert st["counters"]["serve.gen.prefix.declined"] == 1
        with pytest.raises(RecurrentStateError):
            gen.submit(np.arange(1, 6), 4, session="s")
        with pytest.raises(RecurrentStateError):
            gen.park("s")
        assert gen.stats()["counters"]["serve.shed.recurrent_state"] == 2
        assert RecurrentStateError.reason == "recurrent_state"
    finally:
        gen.drain(timeout=60)


def test_stats_and_budget_tell_pages_from_slot_state(toy):
    from bigdl_tpu.ops.attention import paged_pool_width
    model, params, state = toy
    budget = MemoryBudgeter()
    gen = _generator(model, params, state, budgeter=budget)
    try:
        st = gen.stats()
        per_slot = 2 * (4 * 16 * 16 * 4 + 3 * 3 * 64 * 4)   # two KDA layers
        assert st["state"] == {"bytes_per_slot": per_slot,
                               "bytes": 3 * per_slot,
                               "bytes_per_slot_by_kind": {"kda": per_slot}}
        # pages stay pages: two latent pools of 16 tokens a page, their
        # 40 lanes padded to a whole tile, float32
        assert paged_pool_width(1, 40) == 128
        assert st["pages"]["page_bytes"] == 2 * 16 * 128 * 4
        assert st["pages"]["pool_width"] == 128
        assert st["pages"]["pool_bytes"] \
            == st["pages"]["total"] * st["pages"]["page_bytes"]
        fut = gen.submit(np.arange(1, 20), 24)
        deadline = 200
        while budget.charged("default", "slot_state") == 0 and deadline:
            deadline -= 1
            import time
            time.sleep(0.01)
        assert budget.charged("default", "slot_state") == per_slot
        fut.result(timeout=300)
    finally:
        gen.drain(timeout=60)
    assert budget.charged("default", "slot_state") == 0
    assert budget.charged("default", "kv_pages") == 0


def test_decode_spans_carry_the_models_counters(toy, tmp_path):
    """``serve.decode`` spans against a replay of the same request by the
    model's own calls: the counters of each chunk's steps, reduced as the
    model declares."""
    from benchmark import spans
    model, params, state = toy
    prompt = np.random.default_rng(5).integers(1, VOCAB + 1, 13)
    ledger.set_run_dir(str(tmp_path))
    try:
        gen = _generator(model, params, state, num_slots=2)
        try:
            out = gen.submit(prompt, 9).result(timeout=300)
        finally:
            gen.drain(timeout=60)
        st = gen.stats()["counters"]
        ledger.flush()
    finally:
        ledger.set_run_dir(None)
    records = spans.read_ledger(str(tmp_path))
    decodes = [r["attrs"] for r in spans.spans_named(records, "serve.decode")]
    prefill = spans.spans_named(records, "serve.prefill")[-1]["attrs"]
    assert len(decodes) == 2 and prefill["expert_pairs"] > 0
    assert [d["state_rows"] for d in decodes] == [4, 4]
    assert [d["latent_tokens"] for d in decodes] \
        == [sum(13 + i + 1 for i in range(4)),
            sum(17 + i + 1 for i in range(4))]
    # the replay
    cache = model.init_paged_cache(8, 16, jnp.float32, num_slots=2)
    table = np.full((2, 4), 8, np.int32)
    table[0, :2] = [0, 1]
    padded = np.ones((1, 16), np.int32)
    padded[0, :13] = prompt
    _, cache, c = model.decode_pages(
        params, state, padded, cache, jnp.asarray(table[:1]),
        jnp.zeros((1,), jnp.int32), jnp.ones((1,), bool),
        slots=jnp.asarray([0]), lengths=jnp.asarray([13]))
    assert {k: int(v) for k, v in c.items()} \
        == {k: prefill[k] for k in model.decode_counters}
    active = jnp.asarray([True, False])
    seen = []
    for i in range(8):
        tok = jnp.asarray([int(out[i]), 1])[:, None]
        _, cache, c = model.decode_pages(
            params, state, tok, cache, jnp.asarray(table),
            jnp.asarray([13 + i, 0]), active)
        seen.append({k: int(v) for k, v in c.items()})
    for chunk, attrs in zip((seen[:4], seen[4:]), decodes):
        for name, how in model.decode_counters.items():
            want = (max if how == "max" else sum)(s[name] for s in chunk)
            assert attrs[name] == want
    assert st["serve.moe pairs per hit expert"] >= 1.0
    assert st["serve.moe max over mean"] >= 1.0


def test_pattern_and_share_are_checked():
    with pytest.raises(ValueError, match="pattern"):
        HybridLM(VOCAB, **dict(TOY, num_layers=3))
    with pytest.raises(ValueError, match="mixers"):
        HybridLM(VOCAB, **dict(TOY, layers=[["lstm", "dense"]] * 4))
    with pytest.raises(ValueError, match="share"):
        HybridLM(VOCAB, **dict(TOY, experts_held=8, expert_offset=12))


def test_rmsnorm_and_swiglu_are_the_formulas():
    x = jax.random.normal(jax.random.PRNGKey(12), (5, 64))
    norm = nn.RMSNorm(64)
    w = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(13), (64,))
    y, _ = norm.apply({"weight": w}, {}, x)
    np.testing.assert_allclose(
        y, x / np.sqrt((np.asarray(x) ** 2).mean(-1, keepdims=True) + 1e-6)
        * w, rtol=1e-5)
    mlp = nn.GatedMLP(64, 24)
    p = mlp.init_params(jax.random.PRNGKey(14))
    y, _ = mlp.apply(p, {}, x)
    np.testing.assert_allclose(
        y, (jax.nn.silu(x @ p["w_gate"].T) * (x @ p["w_up"].T))
        @ p["w_down"].T, rtol=1e-5, atol=1e-6)
