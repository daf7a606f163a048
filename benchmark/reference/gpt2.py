"""GPT-2 (Radford et al. 2019): pre-LayerNorm decoder blocks, learned
positions, tanh GELU, logits tied to the token embedding.  Parameters are
read from the program's ``TransformerLM`` tree (``tok``, ``pos``,
``blocks[i]{ln1, attn{wq,bq,...}, ln2, fc1, fc2}``, ``ln_f``; every
projection is ``y = x @ w.T + b``).  Token ids are 1-based, as the
program's are.

One sequence at a time, one block per call, so that at GPT-2 XL's size
only one block's float32 copy (123 MB) exists beside the served weights.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

_F32 = jnp.float32


def _ln(p, x, eps=1e-5):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["weight"].astype(_F32) \
        + p["bias"].astype(_F32)


def _linear(w, b, x):
    return x @ w.astype(_F32).T + b.astype(_F32)


@partial(jax.jit, static_argnames=("heads",))
def block(p, x, *, heads):
    """One decoder block on ``x`` (T, E), causal over the whole sequence."""
    with jax.default_matmul_precision("highest"):
        t, e = x.shape
        d = e // heads
        h = _ln(p["ln1"], x)
        a = p["attn"]
        q = _linear(a["wq"], a["bq"], h).reshape(t, heads, d)
        k = _linear(a["wk"], a["bk"], h).reshape(t, heads, d)
        v = _linear(a["wv"], a["bv"], h).reshape(t, heads, d)
        s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(_F32(d))
        causal = jnp.tril(jnp.ones((t, t), bool))
        w = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", w, v).reshape(t, e)
        x = x + _linear(a["wo"], a["bo"], o)
        h = _ln(p["ln2"], x)
        h = _linear(p["fc1"]["weight"], p["fc1"]["bias"], h)
        h = 0.5 * h * (1.0 + jnp.tanh(
            jnp.sqrt(2.0 / jnp.pi) * (h + 0.044715 * h ** 3)))
        return x + _linear(p["fc2"]["weight"], p["fc2"]["bias"], h)


@jax.jit
def _embed(tok, pos, ids):
    return tok.astype(_F32)[ids] + pos.astype(_F32)[:ids.shape[0]]


@jax.jit
def _logits(ln_f, tok, x, rows):
    with jax.default_matmul_precision("highest"):
        return _ln(ln_f, x[rows]) @ tok.astype(_F32).T


def logits_at(params, tokens_1based, rows, *, heads):
    """Float32 logits ``(len(rows), vocab)`` after positions ``rows`` of
    the sequence ``tokens_1based`` (T,): row r predicts token r+1."""
    ids = jnp.asarray(tokens_1based, jnp.int32) - 1
    x = _embed(params["tok"], params["pos"], ids)
    for p in params["blocks"]:
        x = block(p, x, heads=heads)
    return _logits(params["ln_f"], params["tok"], x,
                   jnp.asarray(rows, jnp.int32))
