"""Inception-v1 (GoogLeNet without auxiliary heads), Szegedy et al.,
arXiv:1409.4842, Table 1, as BigDL's ``models/inception/Inception_v1.scala``
lays it out.  Input NCHW 3x224x224; output log-probabilities over the
classes.  Parameters are read from the program's tree: a list indexed by
the position of the layer in the sequential model, an inception block
being ``[[1x1], [3x3 reduce, _, 3x3], [5x5 reduce, _, 5x5], [_, pool
proj]]``, each convolution ``{"weight": OIHW, "bias": O}``.

Departures from the paper, as in BigDL: LRN is Torch's cross-map form
``x / (1 + alpha/size * sum x^2)^beta``; pooling rounds the output size
up (caffe); dropout is off (the reference is the deterministic forward).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# positions of the layers that own parameters or change shape, in the
# sequential model (ReLU and the other parameter-free layers sit between)
_STEM = (("conv", 0, 2, 3), ("pool", 3, 2), ("lrn",),
         ("conv", 4, 1, 0), ("conv", 6, 1, 1), ("lrn",), ("pool", 3, 2))
_BLOCKS = ((10, 11), (13, 14, 15, 16, 17), (19, 20))
_CLASSIFIER = 24


def _conv(p, x, stride, pad, record):
    w = p["weight"].astype(jnp.float32)
    y = lax.conv_general_dilated(
        x, w, (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    if record is not None:
        record.append({"cin": w.shape[1], "cout": w.shape[0],
                       "kh": w.shape[2], "kw": w.shape[3],
                       "hin": x.shape[2], "win": x.shape[3],
                       "hout": y.shape[2], "wout": y.shape[3]})
    return jnp.maximum(y + p["bias"].astype(jnp.float32)[None, :, None, None],
                       0.0)


def _max_pool(x, k, stride, pad=0):
    """Max pooling with the output size rounded up (caffe / ``.ceil()``)."""
    h = x.shape[2]
    out = -(-(h + 2 * pad - k) // stride) + 1
    if pad and (out - 1) * stride >= h + pad:
        out -= 1                        # last window must start inside
    extra = max(0, (out - 1) * stride + k - h - 2 * pad)
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, 1, k, k), (1, 1, stride, stride),
        ((0, 0), (0, 0), (pad, pad + extra), (pad, pad + extra)))


def _lrn(x, size=5, alpha=1e-4, beta=0.75, k=1.0):
    sq = jnp.pad(x * x, ((0, 0), (size // 2, size // 2), (0, 0), (0, 0)))
    window = sum(sq[:, i:i + x.shape[1]] for i in range(size))
    return x / (k + alpha / size * window) ** beta


def _inception(p, x, record):
    b1 = _conv(p[0][0], x, 1, 0, record)
    b3 = _conv(p[1][2], _conv(p[1][0], x, 1, 0, record), 1, 1, record)
    b5 = _conv(p[2][2], _conv(p[2][0], x, 1, 0, record), 1, 2, record)
    bp = _conv(p[3][1], _max_pool(x, 3, 1, 1), 1, 0, record)
    return jnp.concatenate([b1, b3, b5, bp], axis=1)


def forward(params, images, record=None):
    """Log-probabilities ``(N, classes)`` of float images ``(N, 3, H, W)``.
    ``record``, when a list, receives one shape entry per MXU layer."""
    with jax.default_matmul_precision("highest"):
        x = images.astype(jnp.float32)
        for op in _STEM:
            if op[0] == "conv":
                x = _conv(params[op[1]], x, op[2], op[3], record)
            elif op[0] == "pool":
                x = _max_pool(x, op[1], op[2])
            else:
                x = _lrn(x)
        for stage, blocks in enumerate(_BLOCKS):
            if stage:
                x = _max_pool(x, 3, 2)
            for i in blocks:
                x = _inception(params[i], x, record)
        x = jnp.mean(x, axis=(2, 3))                  # 7x7 average pool
        w = params[_CLASSIFIER]["weight"].astype(jnp.float32)
        if record is not None:
            record.append({"cin": w.shape[1], "cout": w.shape[0], "kh": 1,
                           "kw": 1, "hin": 1, "win": 1, "hout": 1, "wout": 1})
        logits = x @ w.T + params[_CLASSIFIER]["bias"].astype(jnp.float32)
        return jax.nn.log_softmax(logits, axis=-1)


def nll_loss(log_probs, labels_1based):
    """``ClassNLLCriterion``: mean of -log p[label], labels 1-based."""
    idx = labels_1based.astype(jnp.int32) - 1
    picked = jnp.take_along_axis(log_probs, idx[:, None], axis=1)[:, 0]
    return -jnp.mean(picked)


def mxu_layers(params, input_shape):
    """Shapes of every convolution and the classifier, in forward order,
    for ``costs.train_flops_per_sample``: the reference is traced on
    abstract values, nothing runs."""
    record: list = []
    x = jax.ShapeDtypeStruct((1,) + tuple(input_shape), jnp.float32)
    jax.eval_shape(lambda p, im: forward(p, im, record), params, x)
    return record
