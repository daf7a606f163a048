"""Ouro-2.6B (``model_type`` ``ouro``, the LoopLM of *Scaling Latent
Reasoning via Looped Language Models*, 2025): a plain float32 forward pass
over one whole sequence, with nothing of ``bigdl_tpu/`` but the names of
the parameter tree.  No cache, no page, no kernel: every pass is computed
again over the whole prefix.

The widths are read from the shapes, the rest from ``PUBLISHED`` below
(the catalog row's keys).  Every projection is ``y = x @ w.T``; the layers'
weights are stacked on a leading axis.  Token ids are 1-based.  For pass
``t = 1 .. total_ut_steps``, for layer ``l``, with the SAME weights in
every pass::

    x = x + rms_in2(attn(rms_in(x)))          # attention over the keys and
    x = x + rms_post2(mlp(rms_post(x)))       # values of this pass's layer l

and after the pass's last layer ``x = rms_f(x)``, which the next pass
starts from; the logits are ``head @ x`` after the last pass.  Attention:
q, k, v projections of ``heads`` heads (as many KV heads, no bias), rope by
pairs ``(i, i + D/2)`` at the token's index, the same index in every pass;
causal softmax of ``q.k / sqrt(D)``.  The MLP is ``(silu(x Wg) * (x Wu))
Wd``.

Departure from the public model: its early-exit gate (one linear of the
hidden size to 1 after every pass) is not computed.  At the row's
``early_exit_threshold`` of 1 it decides nothing: every token runs every
pass and the logits are the last pass's.

One pass per jitted call, a scan over the layers inside it, and each
layer's weights read up to float32 inside the scan's body, so that only one
layer's weights exist in float32 beside the served ones; a sequence is cut
behind the last row asked for (to whole `SEQ_STEP`s, so that few lengths
ever compile)."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

_F32 = jnp.float32

PUBLISHED = {"rms_norm_eps": 1e-6, "rope_theta": 1e6, "total_ut_steps": 4}

SEQ_STEP = 128              # a sequence is cut to a whole number of these


def _rms(w, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(_F32)


def _mm(x, w):
    return x @ w.astype(_F32).T


def _rope(x, theta):
    """Pairs ``(x[i], x[i + D/2])`` over the last axis of (T, H, D),
    position = index along the first axis."""
    t, half = x.shape[0], x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=_F32) / half)
    ang = jnp.arange(t, dtype=_F32)[:, None, None] * freqs
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            a * jnp.sin(ang) + b * jnp.cos(ang)], axis=-1)


def attention(p, x, *, heads, theta):
    t, _ = x.shape
    d = p["wq"].shape[0] // heads
    q = _rope(_mm(x, p["wq"]).reshape(t, heads, d), theta)
    k = _rope(_mm(x, p["wk"]).reshape(t, heads, d), theta)
    v = _mm(x, p["wv"]).reshape(t, heads, d)
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(_F32(d))
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return _mm(jnp.einsum("hqk,khd->qhd", w, v).reshape(t, heads * d),
               p["wo"])


def mlp(p, x):
    return _mm(jax.nn.silu(_mm(x, p["w_gate"])) * _mm(x, p["w_up"]),
               p["w_down"])


@partial(jax.jit, static_argnames=("heads", "eps", "theta"))
def one_pass(blocks, norm_f, x, *, heads, eps, theta):
    """One pass of the whole stack over ``x`` (T, E), float32."""
    def layer(x, p):
        p = jax.tree_util.tree_map(lambda a: a.astype(_F32), p)
        x = x + _rms(p["norm_in2"]["weight"], attention(
            p["attn"], _rms(p["norm_in"]["weight"], x, eps), heads=heads,
            theta=theta), eps)
        x = x + _rms(p["norm_post2"]["weight"], mlp(
            p["mlp"], _rms(p["norm_post"]["weight"], x, eps)), eps)
        return x, None

    with jax.default_matmul_precision("highest"):
        x, _ = jax.lax.scan(layer, x, blocks)
        return _rms(norm_f["weight"], x, eps)


@jax.jit
def _logits(head, x, rows):
    with jax.default_matmul_precision("highest"):
        return _mm(x[rows], head)


def logits_at(params, tokens_1based, rows, *, heads, **published):
    """Float32 logits ``(len(rows), vocab)`` after positions ``rows`` of
    the sequence ``tokens_1based`` (T,): row r predicts token r+1.
    ``published`` overrides ``PUBLISHED`` (a test's toy sizes)."""
    cfg = {**PUBLISHED, **published}
    rows = jnp.asarray(rows, jnp.int32)
    ids = jnp.asarray(tokens_1based, jnp.int32) - 1
    # causal: nothing behind the last row asked for reaches it
    need = int(rows.max()) + 1
    ids = ids[:min(ids.shape[0], -(-need // SEQ_STEP) * SEQ_STEP)]
    x = params["tok"][ids].astype(_F32)
    for _ in range(int(cfg["total_ut_steps"])):
        x = one_pass(params["blocks"], params["norm_f"], x, heads=heads,
                     eps=float(cfg["rms_norm_eps"]),
                     theta=float(cfg["rope_theta"]))
    return _logits(params["head"], x, rows)
