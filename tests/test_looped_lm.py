"""The looped model (``models/looped.py``: one stack applied ``loops`` times
a token, sandwich-normed, a page pool for each (pass, layer) in ONE
page-major stack) against the plain reference ``benchmark/reference/
ouro.py`` at toy widths; the structural variants the comparison must fail;
``loop_passes``; and the stacked pool's addressing in the cache write and
the paged-attention kernel."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import ouro
from bigdl_tpu.models.looped import LoopedLM
from bigdl_tpu.nn import attention
from bigdl_tpu.observability import ledger
from bigdl_tpu.ops.attention import (paged_attention, paged_pool_dims,
                                     paged_pool_width, stacked_pool)
from bigdl_tpu.serving.scheduler import ContinuousGenerator

VOCAB, LENGTH, LOOPS = 97, 40, 3
TOY = dict(max_len=64, embed_dim=64, num_heads=2, head_dim=32,
           num_layers=3, loops=LOOPS, ffn_dim=96)
SEQ = np.random.default_rng(0).integers(1, VOCAB + 1, LENGTH) \
    .astype(np.int32)
# float32 program against the float32 reference: the same operations
# summed in another order (blocked, fused) over 3 layers x 3 passes; log-
# probabilities agree to a few float32 roundings of their size (~5)
TOL = 2e-5
# a structural variant moves the log-probabilities by tenths; a thousandth
# is far above the rounding above and far below what each variant does
FAIL = 1e-3


@pytest.fixture(scope="module")
def toy():
    model = LoopedLM(VOCAB, **TOY)
    params, state = model.init(jax.random.PRNGKey(3))
    # trained norms bring their own weights: the check must see each one
    keys = jax.random.split(jax.random.PRNGKey(4), 5)
    for k, name in zip(keys, ("norm_in", "norm_in2", "norm_post",
                              "norm_post2")):
        w = params["blocks"][name]["weight"]
        params["blocks"][name]["weight"] = w * (
            1.0 + 0.2 * jax.random.normal(k, w.shape))
    params["norm_f"]["weight"] = 1.0 + 0.2 * jax.random.normal(
        keys[4], params["norm_f"]["weight"].shape)
    return model, params, state


def _reference(params, loops=LOOPS):
    logits = ouro.logits_at(params, SEQ, np.arange(LENGTH), heads=2,
                            total_ut_steps=loops)
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


def _served(model, params, state, n, slots=3, ps=4):
    """Log-probs after positions n-1 .. LENGTH-1 of ``SEQ`` served in slot
    1 of ``slots``: a prefill of ``n`` tokens in a bucket of 32, then one
    token a step through the pages; the counters of each call."""
    lp = 16
    cache = model.init_paged_cache(slots * lp, ps, jnp.float32,
                                   num_slots=slots)
    table = np.full((slots, lp), slots * lp, np.int32)
    table[1] = np.arange(lp) + lp
    padded = np.ones((1, 32), np.int32)
    padded[0, :n] = SEQ[:n]
    out, cache, c = model.decode_pages(
        params, state, padded, cache, table[1:2], jnp.zeros((1,), jnp.int32),
        jnp.ones((1,), bool), slots=jnp.asarray([1]),
        lengths=jnp.asarray([n]))
    rows, counts = [np.asarray(out[0, 0])], [int(c["loop_passes"])]
    step = jax.jit(model.decode_pages)
    active = np.arange(slots) == 1
    for t in range(n, LENGTH):
        tok = np.where(active, SEQ[t], 1).astype(np.int32)[:, None]
        out, cache, c = step(params, state, tok, cache, table,
                             np.where(active, t, 0).astype(np.int32), active)
        rows.append(np.asarray(out[1, 0]))
        counts.append(int(c["loop_passes"]))
    return np.stack(rows), counts


def _worst(got, want):
    return float(np.abs(got - want).max())


# -- the program against the reference --------------------------------------------

def test_forward_equals_the_reference(toy):
    model, params, state = toy
    logp, _ = model.apply(params, state, SEQ[None])
    np.testing.assert_allclose(np.asarray(logp[0]), _reference(params),
                               atol=TOL)


@pytest.fixture(params=["gather", "kernel"])
def read_path(request, monkeypatch):
    """The decode step's paged read through the layer's gather, or through
    the Pallas kernel in the interpreter (the chip's path)."""
    if request.param == "kernel":
        monkeypatch.setenv("BIGDL_TPU_PALLAS_INTERPRET", "1")
    return request.param


@pytest.mark.parametrize("n", [5, 16, 23], ids=["short", "whole_pages",
                                                "longer"])
def test_prefill_then_decode_through_the_pools_equals_the_reference(
        toy, read_path, n):
    """A prefill, then 17 to 35 decode steps, every one through all three
    passes, each pass's layer through its own pool of the stack."""
    model, params, state = toy
    got, counts = _served(model, params, state, n)
    np.testing.assert_allclose(got, _reference(params)[n - 1:], atol=TOL)
    # one row a call: three passes each
    assert counts == [LOOPS] * len(counts)


# -- structural variants fail the same comparison -----------------------------------

def _variant(name, model, monkeypatch):
    """The served model changed as ``name`` says; the reference is not."""
    if name == "one_pass_fewer":
        return LoopedLM(VOCAB, **dict(TOY, loops=LOOPS - 1))
    if name == "one_shared_pool":
        monkeypatch.setattr(LoopedLM, "_pool", lambda self, t, l: l)
    elif name == "no_end_of_pass_norm":
        monkeypatch.setattr(LoopedLM, "_pass_end",
                            lambda self, params, x: x)
    elif name == "no_inner_sandwich_norms":
        monkeypatch.setattr(LoopedLM, "_sandwich", lambda self, p, y: y)
    elif name == "no_rope":
        model = LoopedLM(VOCAB, **TOY)
        model.attn.rope = False
    return model


@pytest.mark.parametrize("name", [
    "one_pass_fewer", "one_shared_pool", "no_end_of_pass_norm",
    "no_inner_sandwich_norms", "no_rope"])
def test_a_structural_variant_fails_the_comparison(toy, monkeypatch, name):
    model, params, state = toy
    want = _reference(params)[12:]
    assert _worst(_served(model, params, state, 13)[0], want) < TOL
    changed = _variant(name, LoopedLM(VOCAB, **TOY), monkeypatch)
    assert _worst(_served(changed, params, state, 13)[0], want) > FAIL


# -- loop_passes and the generator --------------------------------------------------

def test_loop_passes_counts_passes_times_active_rows(toy):
    model, params, state = toy
    cache = model.init_paged_cache(8, 4, jnp.float32, num_slots=3)
    table = np.full((3, 2), 8, np.int32)
    _, _, c = model.decode_pages(
        params, state, np.ones((3, 1), np.int32), cache, table,
        np.zeros(3, np.int32), np.array([True, False, True]))
    assert int(c["loop_passes"]) == LOOPS * 2
    assert LoopedLM.decode_counters == {"loop_passes": "sum"}


def test_generator_serves_the_references_argmax_and_counts_passes(
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
                    [gen.submit(SEQ[:n], 11) for n in (13, 5, 21)]]
        finally:
            gen.drain(timeout=60)
        ledger.flush()
    finally:
        ledger.set_run_dir(None)
    for n, out in zip((13, 5, 21), outs):
        seq = np.concatenate([SEQ[:n], out]).astype(np.int32)
        logits = np.asarray(ouro.logits_at(
            params, seq, np.arange(n - 1, n + 10), heads=2,
            total_ut_steps=LOOPS))
        assert (logits.argmax(-1) + 1 == out).all()
    # a page: 16 tokens of K and V in each of 3 x 3 pools of 128 lanes
    # (2 heads of 32, padded to a whole tile), float32; nothing a slot
    assert st["pages"]["page_bytes"] == LOOPS * 3 * 2 * 16 * 128 * 4
    assert st["pages"]["pool_width"] == 128
    assert st["state"]["bytes_per_slot"] == 0
    assert st["counters"].get("serve.gen.prefix.declined") == 1
    records = spans.read_ledger(str(tmp_path))
    decodes = [r["attrs"] for r in spans.spans_named(records, "serve.decode")]
    assert decodes and all(
        d["loop_passes"] == LOOPS * d["state_rows"] for d in decodes)
    assert sum(d["state_rows"] for d in decodes) == 3 * 10
    assert sum(d["latent_tokens"] for d in decodes) == sum(
        n + i + 1 for n in (13, 5, 21) for i in range(10))
    prefills = [r["attrs"] for r in spans.spans_named(records,
                                                      "serve.prefill")]
    assert len(prefills) == 3 and all(
        p["loop_passes"] == LOOPS for p in prefills)


# -- the stacked pool ----------------------------------------------------------------

def test_the_cache_is_one_page_major_stack(toy):
    model, _, _ = toy
    cache = model.init_paged_cache(10, 16, jnp.bfloat16, num_slots=4)
    assert cache["slots"] == {}
    for a in cache["pages"].values():
        assert a.shape == (11, LOOPS * 3, 16, 128) and a.dtype == jnp.bfloat16
        assert paged_pool_dims(a) == (16, 128)


def _pools(n=5, pages=7, ps=16, hkv=2, d=64, seed=0):
    w = paged_pool_width(hkv, d)
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    return (jax.random.normal(ks[0], (pages + 1, n, ps, w)),
            jax.random.normal(ks[1], (pages + 1, n, ps, w)))


@pytest.mark.parametrize("path", ["gather", "kernel"])
def test_the_indexed_call_on_a_stack_equals_the_plain_call_on_its_pool(
        path, monkeypatch):
    """Pool ``i`` of a stack through ``stacked_pool`` and pool ``i`` taken
    out of it on its own give the same bits, trash slots included."""
    if path == "kernel":
        monkeypatch.setenv("BIGDL_TPU_PALLAS_INTERPRET", "1")
    k, v = _pools()
    q = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 1, 64))
    pages = np.array([[3, 0, 7, 5], [6, 2, 1, 7]], np.int32)   # 7 is trash
    pos = np.array([[50], [40]], np.int32)
    for i in (0, 3, 4):
        fk, table = stacked_pool(k, pages, i)
        fv, _ = stacked_pool(v, pages, i)
        assert fk.shape == (8 * 5, 16, 128)
        assert int(table[0, 2]) == int(table[1, 3]) == fk.shape[0] - 1
        got = attention._paged_read(q, fk, fv, table, pos, 0.125, 2)
        want = attention._paged_read(q, k[:, i], v[:, i], pages, pos,
                                     0.125, 2)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        if path == "kernel":
            direct = paged_attention(q, fk, fv, table, pos, 0.125,
                                     num_kv_heads=2)
            np.testing.assert_array_equal(np.asarray(direct),
                                          np.asarray(got))


def test_a_layer_writes_and_reads_its_own_pool_of_the_stack_alone():
    """``GroupedQueryAttention`` with ``pool``: the token lands in pool
    ``i`` of the stack and nowhere else; an inactive row writes only the
    trash page."""
    layer = attention.GroupedQueryAttention(64, 2, 2, 32, None, True, 1e6,
                                            1e-6, False)
    p = layer.init_params(jax.random.PRNGKey(0))
    one = layer.init_paged_cache(6, 4, jnp.float32)
    cache = {k: jnp.zeros((7, 5) + a.shape[1:]) for k, a in one.items()}
    pages = np.array([[2, 4], [6, 6]], np.int32)             # 6 is trash
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 1, 64))
    _, new = layer.apply_decode_pages(p, x, cache, pages,
                                      np.array([5, 1], np.int32),
                                      np.array([True, False]), pool=3)
    k = np.asarray(new["k"])
    written = np.argwhere(np.abs(k).sum(-1) > 0)
    # position 5 of row 0 is page 4 (its second), offset 1; row 1 goes to
    # the trash page of the stack (pool 4's, the row's last page)
    assert {tuple(r) for r in written[:, :3]} <= {(4, 3, 1), (6, 4, 1)}
    assert (4, 3, 1) in {tuple(r) for r in written[:, :3]}
    # the same token through the pool alone
    _, alone = layer.apply_decode_pages(
        p, x[:1], {kk: a[:, 3] for kk, a in cache.items()}, pages[:1],
        np.array([5], np.int32), np.array([True]))
    np.testing.assert_array_equal(k[:, 3], np.asarray(alone["k"]))


def test_a_prefill_writes_by_tokens_what_it_would_write_by_pages():
    """``_write_rows(by_page=False)``: the stacked pool's prefill keeps to
    the token rows (a ``cond`` on a pool carried by a loop costs a copy of
    it a branch) and writes the same bits as the page-by-page form."""
    pool = jax.random.normal(jax.random.PRNGKey(0), (9, 4, 128))
    new = jax.random.normal(jax.random.PRNGKey(1), (2, 2, 8, 64))
    phys = np.array([[1] * 4 + [5] * 4, [3] * 4 + [8] * 4], np.int32)
    offs = np.tile(np.arange(8) % 4, (2, 1)).astype(np.int32)
    a = attention._write_rows(pool, new, phys, offs)
    b = attention._write_rows(pool, new, phys, offs, by_page=False)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# Pinned at the parent commit (PR 40), the kernel in the interpreter: a
# plain call at a GPT-2-like group of 1 (5 heads of 64) and at a K-EXAONE-
# like group of 4 (8 query heads over 2 KV heads of 128), one trash slot.
PINNED = {
    "gpt2": [-1.6871689558029175, -0.35843366384506226, 141.06114196777344],
    "exaone": [6.357942581176758, 0.33158165216445923, 429.3160095214844]}


@pytest.mark.parametrize("name,b,h,hkv,d,seed", [
    ("gpt2", 2, 5, 5, 64, 0), ("exaone", 2, 8, 2, 128, 1)])
def test_the_plain_kernel_call_gives_what_it_gave(monkeypatch, name, b, h,
                                                  hkv, d, seed):
    monkeypatch.setenv("BIGDL_TPU_PALLAS_INTERPRET", "1")
    ps, lp = 16, 4
    w = paged_pool_width(hkv, d)
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    n = b * lp
    k = jax.random.normal(ks[0], (n + 1, ps, w))
    v = jax.random.normal(ks[1], (n + 1, ps, w))
    q = jax.random.normal(ks[2], (b, h, 1, d))
    pages = np.arange(n, dtype=np.int32).reshape(b, lp)
    pages[0, -1] = n
    pos = np.array([lp * ps - 3, ps + 2][:b], np.int32)[:, None]
    out = paged_attention(q, k, v, pages, pos, d ** -0.5, num_kv_heads=hkv)
    got = [float(out.sum()), float(out[b - 1, h - 1, 0, d - 1]),
           float(jnp.abs(out).sum())]
    np.testing.assert_allclose(got, PINNED[name], rtol=1e-6)
