"""State-space layers, latent experts and one-part layers in the pattern
model (``ops/ssd.py``, ``nn.Mamba2Mixer``, ``models/hybrid.py`` mixer
``"mamba2"`` and feed-forward part ``"latent_experts"``): a float32 state a
slot beside the page pool, against the plain reference
``benchmark/reference/nemotron_h.py`` at toy widths."""

import chip_smoke
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import nemotron_h
from bigdl_tpu.models.hybrid import HybridLM
from bigdl_tpu.models.transformer import TransformerLM
from bigdl_tpu.nn.state_space import Mamba2Mixer
from bigdl_tpu.observability import ledger
from bigdl_tpu.ops import ssd
from bigdl_tpu.parallel import expert
from bigdl_tpu.serving.scheduler import ContinuousGenerator

VOCAB, CHUNK, LENGTH = 97, 8, 40
M, E, A = ["mamba2", None], [None, "latent_experts"], ["full", None]
TOY = dict(max_len=64, embed_dim=64, num_heads=4, num_kv_heads=2,
           head_dim=16, num_layers=5, layers=[E, M, E, M, A],
           expert_dim=24, num_experts=16, experts_per_token=4, n_group=1,
           topk_group=1, routed_scale=5.0, experts_held=4, expert_offset=0,
           norm_eps=1e-5, qk_norm=False, ssm_heads=8, ssm_head_dim=8,
           ssm_state=16, ssm_groups=2, ssm_chunk=CHUNK, latent_size=32,
           shared_dim=48, expert_act="relu2")
REF = dict(n_groups=2, num_experts_per_tok=4)
SEQ = np.random.default_rng(0).integers(1, VOCAB + 1, LENGTH) \
    .astype(np.int32)


@pytest.fixture(scope="module")
def toy():
    model = HybridLM(VOCAB, **TOY)
    params, state = model.init(jax.random.PRNGKey(3))
    # a trained router brings a bias, a trained norm and skip their weights
    params["blocks"][2]["ffn"]["bias"] = 0.3 * jax.random.normal(
        jax.random.PRNGKey(2), (16,))
    for i, k in ((1, 4), (3, 5)):
        ks = jax.random.split(jax.random.PRNGKey(k))
        mixer = params["blocks"][i]["mixer"]
        mixer["norm"]["weight"] = 1.0 + 0.2 * jax.random.normal(ks[0], (64,))
        mixer["D"] = 1.0 + 0.3 * jax.random.normal(ks[1], (8,))
    return model, params, state


def _reference(params, **kw):
    logits = nemotron_h.logits_at(params, SEQ, np.arange(LENGTH), heads=4,
                                  **dict(REF, **kw))
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


def _served(model, params, state, n, slots=3, ps=4, cache=None):
    """Log-probs after positions n-1 .. LENGTH-1 of ``SEQ`` served in slot
    1 of ``slots``: a prefill of ``n`` tokens in a bucket of 32, then one
    token a step through the state and the pages; and the cache."""
    lp = 16
    if cache is None:
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


def _worst(got, want):
    return float(np.abs(got - want).max())


# -- (a) the recurrence: one token, chunks, the definition ------------------------

def _ssd_inputs(t, b=2, h=8, p=4, g=2, n=16, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    return dict(
        x=jax.random.normal(k[0], (b, t, h, p)),
        dt=jax.nn.softplus(jax.random.normal(k[1], (b, t, h)) - 2.0),
        a=-jnp.exp(jax.random.uniform(k[2], (h,), minval=0.0, maxval=2.7)),
        b=jax.random.normal(k[3], (b, t, g, n)),
        c=jax.random.normal(k[4], (b, t, g, n)),
        d=1.0 + 0.3 * jax.random.normal(k[5], (h,)),
        state=jax.random.normal(k[6], (b, h, p, n)))


@pytest.mark.parametrize("t", [5, CHUNK, 13, 3 * CHUNK, 37],
                         ids=["shorter", "a_chunk", "longer", "chunks",
                              "not_a_multiple"])
def test_ssd_chunked_equals_the_recurrence(t):
    v = _ssd_inputs(t)
    want, s_want = ssd.ssd_naive(**v)
    got, s_got = ssd.ssd_chunked(**v, chunk=CHUNK)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(s_got, s_want, atol=2e-5)
    # and one token at a time from the definition's own state
    s = v["state"]
    for i in range(t):
        y, s = ssd.ssd_step(v["x"][:, i], v["dt"][:, i], v["a"],
                            v["b"][:, i], v["c"][:, i], v["d"], s)
        np.testing.assert_allclose(y, want[:, i], atol=2e-5)
    np.testing.assert_allclose(s, s_want, atol=2e-5)


def test_a_token_with_no_step_leaves_the_state_bit_for_bit():
    """``dt = 0`` is how right padding and inactive rows are masked: in the
    chunked form behind the real tokens, in the one-token form alone."""
    v = _ssd_inputs(13)
    real = jnp.arange(13) < 9
    v["dt"] = jnp.where(real[None, :, None], v["dt"], 0.0)
    head = {k: (a[:, :9] if k in ("x", "dt", "b", "c") else a)
            for k, a in v.items()}
    _, want = ssd.ssd_chunked(**head, chunk=CHUNK)
    _, got = ssd.ssd_chunked(**v, chunk=CHUNK)
    np.testing.assert_allclose(got, want, atol=1e-6)
    _, s = ssd.ssd_step(v["x"][:, 0], jnp.zeros((2, 8)), v["a"],
                        v["b"][:, 0], v["c"][:, 0], v["d"], v["state"])
    assert (np.asarray(s) == np.asarray(v["state"])).all()
    _, s = ssd.ssd_chunked(**dict(v, dt=jnp.zeros((2, 13, 8))), chunk=CHUNK)
    assert (np.asarray(s) == np.asarray(v["state"])).all()


# -- (b) the mixer and the expert block against the reference ---------------------

def test_the_mixer_equals_the_reference(toy):
    model, params, _ = toy
    p = params["blocks"][1]
    x = jax.random.normal(jax.random.PRNGKey(6), (LENGTH, 64))
    cfg = tuple(sorted({**nemotron_h.PUBLISHED, **REF}.items()))
    want = nemotron_h._block(p, x, heads=4, cfg=cfg)
    h = model.norm.apply(p["norm1"], {}, x[None])[0]
    got = x + model.mixers[1].apply(p["mixer"], {}, h)[0][0]
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_a_mixers_fresh_weights_follow_the_row():
    mixer = Mamba2Mixer(64, 128, 4, 16, 8)
    p = mixer.init_params(jax.random.PRNGKey(0))
    assert p["in_proj"].shape == (2 * 512 + 2 * 8 * 16 + 128, 64)
    assert p["conv"].shape == (4, 512 + 256) and p["conv_bias"].shape == (768,)
    a = np.exp(np.asarray(p["A_log"]))
    assert 1.0 <= a.min() < 3.0 and 14.0 < a.max() <= 16.0
    dt = np.asarray(jax.nn.softplus(p["dt_bias"]))
    assert 0.001 <= dt.min() < 0.002 and 0.05 < dt.max() <= 0.1 + 1e-6
    assert (np.asarray(p["D"]) == 1.0).all()
    st = mixer.init_slot_state(3, jnp.bfloat16)
    assert st["h"].shape == (3, 128, 4, 16) and st["h"].dtype == jnp.float32
    assert st["conv"].shape == (3, 3, 768) \
        and st["conv"].dtype == jnp.bfloat16


def test_the_expert_block_equals_the_reference(toy):
    model, params, _ = toy
    p = params["blocks"][2]
    x = jax.random.normal(jax.random.PRNGKey(7), (LENGTH, 64))
    cfg = tuple(sorted({**nemotron_h.PUBLISHED, **REF}.items()))
    want = nemotron_h._block(p, x, heads=4, cfg=cfg)
    h = model.norm.apply(p["norm2"], {}, x)[0]
    got = x + model._latent_experts(p["ffn"], h, jnp.ones((LENGTH,), bool))[0]
    np.testing.assert_allclose(got, want, atol=5e-5)


def test_forward_equals_the_reference(toy):
    model, params, state = toy
    logp, _ = model.apply(params, state, SEQ[None])
    np.testing.assert_allclose(np.asarray(logp[0]), _reference(params),
                               atol=5e-5)


@pytest.mark.parametrize("n", [2, 5, CHUNK, 13, 2 * CHUNK + 3],
                         ids=["under_the_taps", "shorter", "a_chunk",
                              "longer", "chunks"])
def test_prefill_then_decode_through_state_and_pages_equals_the_reference(
        toy, n):
    """Prompts shorter than the convolution, shorter than, equal to and
    longer than a chunk, all right-padded to a bucket of 32, and 21 to 38
    decode steps each."""
    model, params, state = toy
    got, _ = _served(model, params, state, n)
    np.testing.assert_allclose(got, _reference(params)[n - 1:], atol=5e-5)


def test_a_new_tenant_forgets_the_slots_last_one(toy):
    """The same slot served twice: the second prefill starts at position 0
    and reads nothing of the state and the tail the first left."""
    model, params, state = toy
    _, cache = _served(model, params, state, 13)
    assert np.asarray(cache["slots"][1]["h"][1]).any()
    again, _ = _served(model, params, state, 5, cache=cache)
    np.testing.assert_allclose(again, _reference(params)[4:], atol=5e-5)


def test_a_free_or_finished_slot_keeps_its_state_bit_for_bit(toy):
    model, params, state = toy
    slots, lp = 3, 16
    cache = model.init_paged_cache(slots * lp, 4, jnp.float32,
                                   num_slots=slots)
    mark = jax.random.normal(jax.random.PRNGKey(9), (3, 8, 8, 16))
    for i in (1, 3):
        cache["slots"][i]["h"] = mark
        cache["slots"][i]["conv"] = cache["slots"][i]["conv"] + 0.5
    _, after = _served(model, params, state, 13, cache=cache)
    for i, (kind, _) in enumerate(model.layers):
        if kind != "mamba2":
            assert after["slots"][i] == {}
            continue
        h, tail = after["slots"][i]["h"], after["slots"][i]["conv"]
        for row in (0, 2):
            assert (np.asarray(h[row]) == np.asarray(mark[row])).all()
            assert (np.asarray(tail[row]) == 0.5).all()
        assert not (np.asarray(h[1]) == np.asarray(mark[1])).all()
    # nor a page: every write of the two other rows went to the trash page
    pool = after["pages"][4]["k"]
    assert not np.asarray(pool[:16]).any() \
        and not np.asarray(pool[32:48]).any()


def test_padding_of_a_prefill_bucket_reaches_neither_state_nor_tail(toy):
    model, params, _ = toy
    layer, p = model.mixers[1], params["blocks"][1]["mixer"]
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 64))
    st = layer.init_slot_state(1)
    zero, on = jnp.zeros((1,), jnp.int32), jnp.ones((1,), bool)
    y0, full = layer.apply_slots(p, x[:, :11], st, zero, on)
    y1, padded = layer.apply_slots(p, x, st, zero, on, jnp.asarray([11]))
    np.testing.assert_allclose(y1[:, :11], y0, atol=1e-5)
    for name in ("h", "conv"):
        np.testing.assert_allclose(padded[name], full[name], atol=1e-6)


# -- (c) controls fail the same comparison ----------------------------------------

def test_each_control_fails_the_comparison(toy, monkeypatch):
    model, params, state = toy
    want = _reference(params)[12:]
    assert _worst(_served(model, params, state, 13)[0], want) < 3e-5

    def served(**changes):
        other = HybridLM(VOCAB, **dict(TOY, **changes))
        return _served(other, params, state, 13)[0]

    # the state rounded to bfloat16 after every token
    real_step, real_chunked = ssd.ssd_step, ssd.ssd_chunked
    low = lambda out: (out[0], jax.lax.reduce_precision(out[1], 8, 7))
    with monkeypatch.context() as m:
        m.setattr("bigdl_tpu.nn.state_space.ssd_step",
                  lambda *a: low(real_step(*a)))
        m.setattr("bigdl_tpu.nn.state_space.ssd_chunked",
                  lambda *a: low(real_chunked(*a)))
        # three digits of a state that a norm follows: a tenth of what the
        # other controls read, ten times what the sound program does
        assert _worst(served(), want) > 3e-4
    # the router's scores in bfloat16 before selection and gating
    real_route = expert.sigmoid_group_route
    with monkeypatch.context() as m:
        m.setattr("bigdl_tpu.models.hybrid.sigmoid_group_route",
                  lambda scores, *a, **kw: real_route(
                      jax.lax.reduce_precision(scores, 8, 7), *a, **kw))
        assert _worst(served(), want) > 1e-3
    # no skip: y = h C alone
    no_skip = jax.tree_util.tree_map(lambda a: a, params)
    for i in (1, 3):
        no_skip["blocks"][i]["mixer"]["D"] = jnp.zeros((8,))
    assert _worst(_served(model, no_skip, state, 13)[0], want) > 1e-3
    # the gate after the norm: rmsnorm_group(y) * silu(z)
    with chip_smoke._gate_after_norm(model):
        assert _worst(served(), want) > 1e-3
    # rope on the attention block
    roped = HybridLM(VOCAB, **TOY)
    assert not roped.mixers[4].rope and not roped.mixers[4].head_norm
    roped.mixers[4].rope = True
    assert _worst(_served(roped, params, state, 13)[0], want) > 1e-3
    # a head norm on it (weights of one)
    normed = jax.tree_util.tree_map(lambda a: a, params)
    normed["blocks"][4]["mixer"].update(
        q_norm={"weight": jnp.ones((16,))}, k_norm={"weight": jnp.ones((16,))})
    assert _worst(_served(HybridLM(VOCAB, **dict(TOY, qk_norm=True)), normed,
                          state, 13)[0], want) > 1e-3


def test_a_gated_expert_fails_the_comparison(toy):
    """The same weights read as SwiGLU experts (gate and up side by side in
    the first matrix's columns) are another model."""
    model, params, _ = toy
    p = params["blocks"][2]["ffn"]
    x = jax.random.normal(jax.random.PRNGKey(7), (LENGTH, 64))
    ids, gates = model._route(p, x)
    lat = x @ p["latent_down"].T
    valid = jnp.ones((LENGTH,), bool)
    relu2, _ = expert.held_experts_apply(
        lat, ids, gates, valid, p["experts"]["w_up"],
        p["experts"]["w_down"], 0, "relu2")
    gated, _ = expert.held_experts_apply(
        lat, ids, gates, valid,
        jnp.concatenate([p["experts"]["w_up"]] * 2, axis=-1),
        p["experts"]["w_down"], 0, "swiglu")
    assert _worst(np.asarray(relu2), np.asarray(gated)) > 1e-3
    with pytest.raises(AssertionError):
        expert.held_experts_apply(lat, ids, gates, valid,
                                  p["experts"]["w_up"],
                                  p["experts"]["w_down"], 0, "gelu")


# -- (d) the shares add up -----------------------------------------------------------

def test_four_shares_with_what_every_chip_computes_once_make_the_uncut_block():
    """An expert block of the uncut model (all 16 experts held) against the
    plain reference's, and the sum over four chips' shares of 4 experts
    each of what only the held experts add (each chip projects its OWN
    partial sum back up from the latent), with the residual and the shared
    expert counted once."""
    full = HybridLM(VOCAB, **dict(TOY, experts_held=16))
    params, _ = full.init(jax.random.PRNGKey(5))
    p = params["blocks"][0]
    x = jax.random.normal(jax.random.PRNGKey(6), (LENGTH, 64))
    cfg = tuple(sorted({**nemotron_h.PUBLISHED, **REF}.items()))
    want = np.asarray(nemotron_h._block(p, x, heads=4, cfg=cfg))
    h = full.norm.apply(p["norm2"], {}, x)[0]
    valid = jnp.ones((LENGTH,), bool)
    shared = full._shared(p["ffn"]["shared"], h)
    routed = []
    for o in range(0, 16, 4):
        chip = HybridLM(VOCAB, **dict(TOY, experts_held=4, expert_offset=o))
        share = dict(p["ffn"], experts=jax.tree_util.tree_map(
            lambda a: a[o:o + 4], p["ffn"]["experts"]))
        routed.append(chip._latent_experts(share, h, valid)[0] - shared)
    np.testing.assert_allclose(x + sum(routed) + shared, want, atol=5e-5)
    # the reference given one share leaves out what the absent 12 add
    one = dict(p, ffn=dict(p["ffn"], experts=jax.tree_util.tree_map(
        lambda a: a[8:12], p["ffn"]["experts"])))
    part = nemotron_h._block(one, x, heads=4, cfg=tuple(sorted(
        dict(cfg, expert_offset=8).items())))
    np.testing.assert_allclose(part, x + routed[2] + shared, atol=5e-5)


@pytest.mark.parametrize("path", ["sorted", "blocked"])
def test_long_prefills_of_ungated_experts_take_the_grouped_products(
        monkeypatch, path):
    """Past `DENSE_TOKENS` the pairs are sorted by expert, past
    `PAIR_ELEMENTS` multiplied block by block: the same sum for experts
    that are not gated, the same counters."""
    tokens = 61
    x = jax.random.normal(jax.random.PRNGKey(8), (tokens, 32))
    ku, kd = jax.random.split(jax.random.PRNGKey(7))
    wu = jax.random.normal(ku, (4, 32, 24)) * 32 ** -0.5
    wd = jax.random.normal(kd, (4, 24, 32)) * 24 ** -0.5
    scores = jax.nn.sigmoid(4 * jax.random.normal(jax.random.PRNGKey(9),
                                                  (tokens, 16)))
    ids, gates = expert.sigmoid_group_route(scores, jnp.zeros(16), 4, 1, 1,
                                            5.0)
    valid = jnp.arange(tokens) < 59
    want, c0 = expert.held_experts_apply(x, ids, gates, valid, wu, wd, 4,
                                         "relu2")
    monkeypatch.setattr(expert, "DENSE_TOKENS", 0)
    if path == "blocked":
        monkeypatch.setattr(expert, "PAIR_ELEMENTS", 0)
        monkeypatch.setattr(expert, "PAIR_BLOCK", 16)
    got, c1 = expert.held_experts_apply(x, ids, gates, valid, wu, wd, 4,
                                        "relu2")
    assert int(c0["pairs"]) > 32
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert {k: int(v) for k, v in c0.items()} \
        == {k: int(v) for k, v in c1.items()}


# -- the pattern's own refusals ---------------------------------------------------------

@pytest.mark.parametrize("changes", [
    dict(layers=[E, M, E, M, [None, None]]),
    dict(layers=[E, M, E, M, ["mamba3", None]]),
    dict(latent_size=None), dict(expert_act="gelu")],
    ids=["an_empty_layer", "an_unknown_mixer", "no_latent_size",
         "an_unknown_form"])
def test_a_pattern_that_cannot_be_built_is_refused(changes):
    with pytest.raises(ValueError):
        HybridLM(VOCAB, **dict(TOY, **changes))


def test_a_one_part_layer_holds_one_norm(toy):
    _, params, _ = toy
    assert [sorted(b) for b in params["blocks"]] == [
        ["ffn", "norm2"], ["mixer", "norm1"], ["ffn", "norm2"],
        ["mixer", "norm1"], ["mixer", "norm1"]]
    assert sorted(params["blocks"][4]["mixer"]) == ["wk", "wo", "wq", "wv"]
    assert sorted(params["blocks"][0]["ffn"]["experts"]) \
        == ["w_down", "w_up"]


# -- the generator ---------------------------------------------------------------------

def test_generator_serves_the_references_argmax_and_counts_the_state(
        toy, tmp_path):
    from benchmark import spans
    model, params, state = toy
    ledger.set_run_dir(str(tmp_path))
    try:
        gen = ContinuousGenerator(model, params, state, num_slots=3,
                                  max_len=64, seq_buckets=[16, 32],
                                  cache_dtype=jnp.float32)
        try:
            st = gen.stats()
            outs = [f.result(timeout=300) for f in
                    [gen.submit(SEQ[:n], 11) for n in (13, 5, 21, 2)]]
        finally:
            gen.drain(timeout=60)
        ledger.flush()
    finally:
        ledger.set_run_dir(None)
    for n, out in zip((13, 5, 21, 2), outs):
        seq = np.concatenate([SEQ[:n], out]).astype(np.int32)
        logits = np.asarray(nemotron_h.logits_at(
            params, seq, np.arange(n - 1, n + 10), heads=4, **REF))
        assert (logits.argmax(-1) + 1 == out).all()
    # two states (8 heads x 8 x 16 float32) and two tails (3 x 128) a slot;
    # one attention block's pages of 16 tokens x 128 lanes (32 padded)
    slot = 2 * (8 * 8 * 16 * 4 + 3 * 128 * 4)
    assert st["state"] == {"bytes_per_slot": slot, "bytes": 3 * slot,
                           "bytes_per_slot_by_kind": {"mamba2": slot}}
    assert st["pages"]["bytes_by_kind"] == {
        "page": {"full": 2 * 16 * 128 * 4}, "slot": {"mamba2": slot}}
    records = spans.read_ledger(str(tmp_path))
    decodes = [r["attrs"] for r in spans.spans_named(records, "serve.decode")]
    assert decodes and all("window_tokens" not in d for d in decodes)
    assert sum(d["state_rows"] for d in decodes) == 4 * 10
    assert sum(d["latent_tokens"] for d in decodes) == sum(
        n + i + 1 for n in (13, 5, 21, 2) for i in range(10))
    assert all("expert_pairs" in d for d in decodes)
    prefills = [r["attrs"] for r in spans.spans_named(records,
                                                      "serve.prefill")]
    assert sorted(p["tp"] for p in prefills) == [2, 5, 13, 21]
    assert all("expert_pairs" in p for p in prefills)


# -- (e) what was there gives the numbers it gave ---------------------------------------

PINNED = {
    "transformer": [-5408.810546875, -3.3973827362060547,
                    -454.9201965332031, -5.421809196472168],
    "recurrent": [-6661.5380859375, -4.876734256744385, -4.42431640625],
    "window": [-19558.00390625, -5.91312313079834, -5.518888473510742]}


def _transformer():
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
    return [float(out.sum()), float(out[1, 7, 3]), float(nxt.sum()),
            float(nxt[0, 0, 11])]


def _recurrent():
    hy = HybridLM(50, max_len=64, embed_dim=64, num_heads=4, num_layers=3,
                  layers=[["kda", "dense"], ["kda", "experts"],
                          ["mla", "experts"]], head_dim=16, ffn_dim=96,
                  expert_dim=24, num_experts=16, experts_per_token=4,
                  n_group=4, topk_group=2, experts_held=8, latent_dim=32,
                  rope_dim=8, nope_dim=16, v_dim=16)
    params, state = hy.init(jax.random.PRNGKey(1))
    logp, _ = hy.apply(params, state, np.arange(1, 31)[None])
    return [float(logp.sum()), float(logp[0, 17, 5]), float(logp[0, 29, 49])]


def _window():
    hy = HybridLM(97, max_len=64, embed_dim=64, num_heads=4, num_kv_heads=2,
                  num_layers=3, layers=[["swa", "dense"], ["swa", "experts"],
                                        ["full", "experts"]], head_dim=16,
                  ffn_dim=96, expert_dim=24, num_experts=16,
                  experts_per_token=4, n_group=1, topk_group=1,
                  routed_scale=2.5, experts_held=8, expert_offset=0,
                  window=8, rope_theta=1e6, norm_eps=1e-5)
    params, state = hy.init(jax.random.PRNGKey(3))
    logp, _ = hy.apply(params, state, np.arange(1, 41)[None])
    return [float(logp.sum()), float(logp[0, 17, 5]), float(logp[0, 39, 96])]


@pytest.mark.parametrize("name,toy_numbers", [
    ("transformer", _transformer), ("recurrent", _recurrent),
    ("window", _window)])
def test_what_was_there_gives_the_numbers_it_gave(name, toy_numbers):
    """Pinned at the parent commit (PR 34): the paged decode of a toy
    ``TransformerLM`` and the whole-sequence log-probs of the toys of the
    two accepted pattern configurations, whose model gained a mixer, a
    feed-forward part and layers of one part, whose expert product gained
    a form and whose attention layer's head norm became an argument."""
    np.testing.assert_allclose(toy_numbers(), PINNED[name], rtol=2e-6)
