"""The held experts' product over the experts a call HITS
(``ops/experts.py``, ``held_experts_apply``'s hit form): the same sum as
the dense form and as a float32 reference, the same counters, chosen by
the call's shapes alone, lowered under the caller's scope, and what the
generator reports of it.  CPU, the kernel in the Pallas interpreter."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.ops as ops
from bigdl_tpu.models.hybrid import HybridLM
from bigdl_tpu.ops import experts as experts_ops
from bigdl_tpu.parallel import expert
from bigdl_tpu.parallel.expert import held_experts_apply
from bigdl_tpu.serving.scheduler import ContinuousGenerator

E, F, HELD, N, OFFSET = 64, 24, 8, 32, 4


@pytest.fixture
def kernel_on(monkeypatch):
    """The kernel in the interpreter, as on a chip it is compiled."""
    monkeypatch.setattr(experts_ops, "_interpret", lambda: True)


def _weights(form, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    first = 2 * F if form == "swiglu" else F
    return (jax.random.normal(k1, (HELD, E, first)) * E ** -0.5,
            jax.random.normal(k2, (HELD, F, E)) * F ** -0.5)


def _routing(case, t=6, k=4, seed=3):
    """(ids, gates, valid) of ``t`` rows over ``N`` experts, of which
    ``[OFFSET, OFFSET + HELD)`` are held, hitting none, one, some or all
    of the held ones; the last row is not valid."""
    rng = np.random.default_rng(seed)
    absent = [e for e in range(N) if not OFFSET <= e < OFFSET + HELD]
    ids = np.stack([rng.choice(absent, k, replace=False) for _ in range(t)])
    if case == "one":
        ids[:4, 0] = OFFSET + 5
    elif case == "some":
        ids[:, 1] = OFFSET + np.asarray([0, 2, 2, 6, 7, 3])[:t]
    elif case == "all":
        ids[:, :2] = OFFSET + np.arange(2 * t).reshape(t, 2) % HELD
    gates = rng.random((t, k)).astype(np.float32) + 0.1
    valid = np.arange(t) < t - 1
    return jnp.asarray(ids, jnp.int32), jnp.asarray(gates), jnp.asarray(valid)


def _reference(x, ids, gates, valid, w1, w2, form):
    """Every (valid row, held expert) pair alone, in float32."""
    x, w1, w2 = (np.asarray(a, np.float64) for a in (x, w1, w2))
    out = np.zeros(x.shape)
    for r in np.flatnonzero(np.asarray(valid)):
        for e, g in zip(np.asarray(ids[r]), np.asarray(gates[r])):
            j = int(e) - OFFSET
            if 0 <= j < HELD:
                h = x[r] @ w1[j]
                h = np.square(np.maximum(h, 0)) if form == "relu2" \
                    else h[:F] / (1 + np.exp(-h[:F])) * h[F:]
                out[r] += float(g) * (h @ w2[j])
    return out


@pytest.mark.parametrize("form", ["swiglu", "relu2"])
@pytest.mark.parametrize("case,n_hit", [("none", 0), ("one", 1),
                                        ("some", 4), ("all", HELD)])
def test_hit_form_is_the_dense_sum(kernel_on, monkeypatch, form, case,
                                   n_hit):
    x = jax.random.normal(jax.random.PRNGKey(7), (6, E))
    w1, w2 = _weights(form)
    ids, gates, valid = _routing(case)

    def apply():
        return held_experts_apply(x, ids, gates, valid, w1, w2, OFFSET, form,
                                  n_experts=N)

    assert expert.hit_form(6, 4, N)
    # a fresh function each time: a trace is cached by its function
    assert "pallas_call" in str(jax.make_jaxpr(lambda: apply())())
    hit, c_hit = apply()
    monkeypatch.setattr(expert, "HIT_PAIRS", 0)
    assert "pallas_call" not in str(jax.make_jaxpr(lambda: apply())())
    dense, c_dense = apply()
    assert {k: int(v) for k, v in c_hit.items()} \
        == {k: int(v) for k, v in c_dense.items()}
    assert int(c_hit["hit"]) == n_hit
    np.testing.assert_allclose(hit, dense, atol=2e-5)
    np.testing.assert_allclose(hit, _reference(x, ids, gates, valid, w1, w2,
                                               form), atol=2e-5)
    if n_hit == 0:
        assert not np.asarray(hit).any()


@pytest.mark.parametrize("t,k,n,hit", [(64, 8, 512, True),
                                       (96, 8, 128, False),
                                       (128, 22, 512, False),
                                       (128, 8, 512, True),
                                       (129, 1, 512, False)])
def test_the_form_follows_the_pairs_a_held_expert(t, k, n, hit):
    """``ling3_flash_vl``'s decode step (1 pair a held expert) takes the
    hit experts alone; ``k_exaone_236b``'s (6) and
    ``nemotron3_super_120b``'s (5.5) every held expert; past
    `DENSE_TOKENS` the sorted pairs."""
    assert expert.hit_form(t, k, n) is hit


def test_the_kernel_serves_what_fits_its_vmem(kernel_on):
    s = jax.ShapeDtypeStruct
    # ling3_flash_vl's expert: 7.9 + 3.9 MB
    assert experts_ops.hit_kernel_fits(
        64, s((128, 2560, 1536), jnp.bfloat16), s((128, 768, 2560),
                                                   jnp.bfloat16))
    # k_exaone_236b's: 50 + 25 MB, twice, does not
    assert not experts_ops.hit_kernel_fits(
        96, s((16, 6144, 4096), jnp.bfloat16), s((16, 2048, 6144),
                                                  jnp.bfloat16))


# -- the lowered program ---------------------------------------------------

def _parent_dense(x, ids, gates, valid, w_gate_up, w_down, expert_offset):
    """The decode form as it was before the hit form existed."""
    t, k = ids.shape
    held, f = w_gate_up.shape[0], w_down.shape[1]
    local = ids - expert_offset
    here = (local >= 0) & (local < held) & valid[:, None]
    key = jnp.where(here, local, held).reshape(-1)
    sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
    n_here = jnp.sum(sizes)
    counters = {"pairs": n_here.astype(jnp.int32),
                "hit": jnp.sum(sizes > 0).astype(jnp.int32),
                "max": jnp.max(sizes).astype(jnp.int32)}
    dense = jnp.zeros((t, held), jnp.float32).at[
        jnp.arange(t)[:, None], jnp.clip(local, 0, held - 1)].add(
        jnp.where(here, gates, 0.0))
    h = jnp.einsum("te,gef->tgf", x, w_gate_up,
                   preferred_element_type=jnp.float32)
    h = (jax.nn.silu(h[..., :f]) * h[..., f:]) * dense[..., None]
    y = jnp.dot(h.astype(x.dtype).reshape(t, -1),
                w_down.reshape(held * f, -1),
                preferred_element_type=jnp.float32)
    return y.astype(x.dtype), counters


def _lowered(impl, t, k, debug=False):
    """``impl`` under ``moe/experts``, lowered for a TPU (no chip needed)."""
    s = jax.ShapeDtypeStruct

    def step(x, ids, gates, valid, w1, w2):
        with jax.named_scope("moe"), jax.named_scope("experts"):
            return impl(x, ids, gates, valid, w1, w2)

    args = (s((t, 128), jnp.bfloat16), s((t, k), jnp.int32),
            s((t, k), jnp.float32), s((t,), jnp.bool_),
            s((HELD, 128, 256), jnp.bfloat16), s((HELD, 128, 128),
                                                 jnp.bfloat16))
    lowered = jax.jit(step).trace(*args).lower(lowering_platforms=("tpu",))
    return lowered.as_text(debug_info=debug)


@pytest.fixture
def tpu_kernels(monkeypatch):
    """The dispatch as it reads on a chip (the kernel compiled, not
    interpreted)."""
    monkeypatch.setattr(ops, "pallas_enabled", lambda: True)


def test_the_dense_side_lowers_to_the_form_as_it_was(tpu_kernels):
    t, k = 12, 8                     # 3 pairs a held expert: dense
    assert not expert.hit_form(t, k, 32)
    now = _lowered(lambda *a: held_experts_apply(*a, 0, "swiglu", 32), t, k)
    assert "tpu_custom_call" not in now
    assert now == _lowered(lambda *a: _parent_dense(*a, 0), t, k)


def test_the_hit_kernel_lowers_under_the_callers_scope(tpu_kernels):
    """Its custom call's location, which becomes the instruction's
    ``op_name`` (what ``hybrid_trace.scope_seconds`` reads), holds
    ``moe/experts``."""
    t, k = 8, 4                      # half a pair a held expert: hit
    text = _lowered(lambda *a: held_experts_apply(*a, 0, "swiglu", 64), t, k,
                    debug=True)
    call = next(l for l in text.splitlines() if "tpu_custom_call" in l)
    locs = dict(re.findall(r"^(#loc\d+) = (.*)$", text, re.M))

    def names(ref, seen=()):
        body = locs.get(ref, "")
        return re.findall(r'"([^"]*)"', body) + [
            n for r in re.findall(r"#loc\d+", body) if r not in seen
            for n in names(r, seen + (ref,))]

    where = names(re.findall(r"loc\((#loc\d+)\)\s*$", call)[0])
    assert any("moe/experts/" in n and "held_experts_hit" in n
               for n in where), where


# -- through the model and the generator ------------------------------------

TOY = dict(max_len=64, embed_dim=64, num_heads=4, num_layers=3,
           layers=[["kda", "experts"], ["mla", "experts"], ["kda", "dense"]],
           head_dim=16, ffn_dim=96, expert_dim=24, num_experts=16,
           experts_per_token=4, n_group=4, topk_group=2, routed_scale=2.5,
           experts_held=8, expert_offset=4, latent_dim=32, rope_dim=8,
           nope_dim=16, v_dim=16, rope_theta=6e6)


@pytest.fixture(scope="module")
def toy():
    model = HybridLM(50, **TOY)
    params, state = model.init(jax.random.PRNGKey(1))
    return model, params, state


def _serve(model, params, state):
    gen = ContinuousGenerator(model, params, state, num_slots=3, max_len=64,
                              seq_buckets=[16, 32],
                              cache_dtype=jnp.float32)
    rng = np.random.default_rng(5)
    try:
        futures = [gen.submit(rng.integers(1, 51, n), 9) for n in (13, 7)]
        out = [list(f.result(timeout=300)) for f in futures]
    finally:
        gen.drain(timeout=60)
    return out, gen.stats()["counters"]


def test_a_toy_model_generates_the_same_tokens_both_ways(kernel_on,
                                                         monkeypatch, toy):
    """A decode step of 3 rows of 4 choices over 16 experts (0.75 pairs a
    held expert) takes the hit form; forced dense it gives the same
    greedy tokens, and the gauge tells the two apart."""
    model, params, state = toy
    assert model.held_experts_total == 16
    assert model.streams_hit_experts(params, 3)
    assert not model.streams_hit_experts(params, 12)   # 3 pairs: dense
    hit, st_hit = _serve(model, params, state)
    monkeypatch.setattr(expert, "HIT_PAIRS", 0)
    assert not model.streams_hit_experts(params, 3)
    dense, st_dense = _serve(model, params, state)
    assert hit == dense
    assert 0 < st_hit["serve.moe streamed share"] < 1
    assert st_dense["serve.moe streamed share"] == 1.0
