"""Each plain reference against the program's model on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import costs
from benchmark.reference import gpt2, inception_v1


@pytest.fixture(scope="module")
def inception():
    from bigdl_tpu.models.inception import Inception_v1
    model = Inception_v1(10)
    params, state = jax.jit(model.init)(jax.random.PRNGKey(3))
    return model, params, state


def test_inception_reference_agrees_with_the_model(inception):
    # the architecture has no toy width: the toy is 2 images, 10 classes
    model, params, state = inception
    x = jnp.asarray(np.random.default_rng(0)
                    .random((2, 3, 224, 224), dtype=np.float32))
    got, _ = jax.jit(lambda p, s, im: model.apply(p, s, im, training=False)
                     )(params, state, x)
    want = jax.jit(inception_v1.forward)(params, x)
    # float32 on both sides: only the order of sums differs
    assert float(jnp.abs(got - want).max()) < 1e-4
    assert float(jnp.abs(want - want.mean()).max()) > 1e-2   # not flat
    y = jnp.asarray([3.0, 7.0])
    from bigdl_tpu.nn import ClassNLLCriterion
    assert float(inception_v1.nll_loss(want, y)) == pytest.approx(
        float(ClassNLLCriterion().apply(want, y)), rel=1e-6)
    # the reference reads the parameters it is given
    bumped = jax.tree_util.tree_map(lambda a: a, params)
    bumped[0] = {"weight": params[0]["weight"] * 1.5,
                 "bias": params[0]["bias"]}
    other = jax.jit(inception_v1.forward)(bumped, x)
    assert float(jnp.abs(other - want).max()) > 1e-4


def test_inception_layer_shapes_give_the_published_operation_count(inception):
    _model, params, _state = inception
    layers = inception_v1.mxu_layers(params, (3, 224, 224))
    assert len(layers) == 58                 # 57 convolutions and the head
    assert layers[0] == {"cin": 3, "cout": 64, "kh": 7, "kw": 7, "hin": 224,
                         "win": 224, "hout": 112, "wout": 112}
    forward_macs = sum(costs.conv_pass_costs(l, 1)["flops"]
                       for l in layers) / 2
    # GoogLeNet is "about 1.5 billion multiply-adds" (arXiv:1409.4842)
    assert 1.5e9 < forward_macs < 1.65e9


def test_gpt2_reference_agrees_with_the_model_at_toy_widths():
    from bigdl_tpu.models.transformer import TransformerLM
    model = TransformerLM(97, max_len=64, embed_dim=32, num_heads=4,
                          num_layers=2, ffn_dim=64, position="learned")
    params, state = jax.jit(model.init)(jax.random.PRNGKey(0))
    toks = np.random.default_rng(1).integers(1, 98, 40)
    got, _ = model.apply(params, state, toks[None])
    want = jax.nn.log_softmax(
        gpt2.logits_at(params, toks, np.arange(40), heads=4), axis=-1)
    assert float(jnp.abs(got[0] - want).max()) < 1e-4
    # padding after the rows asked for changes nothing (causal)
    padded = np.ones(64, np.int64)
    padded[:40] = toks
    again = jax.nn.log_softmax(
        gpt2.logits_at(params, padded, np.arange(40), heads=4), axis=-1)
    assert float(jnp.abs(again - want).max()) < 1e-5


def test_the_int8_control_is_seen_by_the_statistic_at_toy_widths(monkeypatch):
    """``benchmark/control.py`` at a size a test can hold: the reference
    with int8 weights puts another token first at some position and the
    harness's statistic reads it above 0, which the same weights left as
    they are read exactly (the chip's readings at GPT-2 XL's size against
    the configuration's limit are in PERF.md)."""
    from benchmark import control
    from bigdl_tpu.models.transformer import TransformerLM
    cfg = {"reference": "benchmark.reference.gpt2",
           "model": {"args": [997], "kwargs": {"num_heads": 4}},
           "server": {"max_len": 64}, "tolerance": {"rows": 32}}
    model = TransformerLM(997, max_len=64, embed_dim=64, num_heads=4,
                          num_layers=4, ffn_dim=128, position="learned")
    params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16),
        jax.jit(model.init)(jax.random.PRNGKey(1))[0])
    low = control.int8_weights(params)
    w = params["blocks"][0]["fc1"]["weight"]
    q = low["blocks"][0]["fc1"]["weight"]
    assert q.dtype == w.dtype and not bool(jnp.array_equal(q, w))
    levels = np.unique(np.asarray(q[0], np.float32))
    assert len(levels) <= 255
    assert bool(jnp.array_equal(low["ln_f"]["bias"], params["ln_f"]["bias"]))
    assert control.reading(cfg, params, 1) > 0.005
    monkeypatch.setattr(control, "int8_weights", lambda p: p)
    assert control.reading(cfg, params, 1) == 0.0
