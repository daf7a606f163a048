"""Keye-VL-2.0-30B-A3B's language model, the share of it that the
configuration ``keye_vl2_30b_a3b`` holds: a plain float32 forward pass over
one whole sequence, with nothing of ``bigdl_tpu/`` but the names of the
parameter tree.  No cache, no kernel, no batching.

The widths are read from the shapes (KV heads from ``wk``, the indexer's
heads from ``w_index``), what shapes cannot say from ``PUBLISHED`` below:
the catalog row's keys and the configuration's ``assumed`` list.  Every
projection is ``y = x @ w.T``; the held experts' weights are ``(expert, in,
out)`` with gate and up side by side.  Token ids are 1-based.

* attention (DeepSeek Sparse Attention, DeepSeek-V3.2 arXiv:2512.02556
  section 2.1, on a GQA layer): q, k, v projections; RMS norm over each
  head's query and key channels; rope by pairs ``(i, i + D/2)``.  The
  lightning indexer: ``qI_j = rope(WqI_j x)`` (16 heads of 64), ONE key
  ``kI = rope(LayerNorm(WkI x))``, weights ``wI = WwI x / sqrt(16 * 64)``,
  its rope on the first 32 channels; ``I[t, s] = sum_j wI_tj ReLU(qI_tj .
  kI_s)`` for ``s <= t``, dense; query ``t`` attends, softmax, over the
  ``lax.top_k`` of its row (every ``s <= t`` while ``t < topk``).  Query
  head ``a`` reads KV head ``a // (H / Hkv)``.
* experts: a float32 softmax over all experts, the best k, gates the
  chosen probabilities over their sum; every HELD expert applied to every
  token, weighted by its gate (zero where it was not chosen), one expert
  at a time; no shared expert.  What absent experts would add is left out,
  as their chips add it.

One layer part per jitted call, one expert at a time inside it and a block
of queries at a time in attention, so that only that much exists in
float32 beside the served weights; a sequence is cut behind the last row
asked for (to whole `SEQ_STEP`s, so that few lengths ever compile).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

_F32 = jnp.float32

PUBLISHED = {
    "rms_norm_eps": 1e-6, "rope_theta": 1e7, "num_experts_per_tok": 8,
    "expert_offset": 0, "topk": 2048, "index_rope_dim": 32}

QUERY_BLOCK = 64            # query rows scored at a time
SEQ_STEP = 2048             # a sequence is cut to a whole number of these


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


def _rope_part(x, theta, r):
    """Rope on the first ``r`` channels of (T, H, D); the rest pass."""
    return jnp.concatenate([_rope(x[..., :r], theta), x[..., r:]], axis=-1)


def indexer(p, x, cfg):
    """``qI`` (T, Hi, Di), ``wI`` (T, Hi) with its scales and ``kI`` (T, Di)
    of the tokens ``x`` (T, E)."""
    t, _ = x.shape
    hi = p["w_index"].shape[0]
    di = p["wk_index"].shape[0]
    qi = _rope_part(_mm(x, p["wq_index"]).reshape(t, hi, di),
                    cfg["rope_theta"], cfg["index_rope_dim"])
    ki = _mm(x, p["wk_index"])
    mu = ki.mean(-1, keepdims=True)
    ki = (ki - mu) * jax.lax.rsqrt(((ki - mu) ** 2).mean(-1, keepdims=True)
                                   + cfg["rms_norm_eps"]) \
        * p["k_index_norm"]["weight"].astype(_F32) \
        + p["k_index_norm"]["bias"].astype(_F32)
    ki = _rope_part(ki[:, None], cfg["rope_theta"], cfg["index_rope_dim"])[:, 0]
    wi = _mm(x, p["w_index"]) / jnp.sqrt(_F32(hi * di))
    return qi, wi, ki


def selection(qi, wi, ki, at, topk):
    """Which keys each query at ``at`` (Q,) picks: (Q, T) bool, the
    ``lax.top_k`` of its dense scores among ``s <= at``."""
    t = ki.shape[0]
    scores = jnp.einsum("qhd,sd->qhs", qi, ki)
    scores = jnp.einsum("qhs,qh->qs", jax.nn.relu(scores), wi)
    seen = jnp.arange(t)[None] <= at[:, None]
    scores = jnp.where(seen, scores, -jnp.inf)
    vals, idx = jax.lax.top_k(scores, min(topk, t))
    picked = jnp.zeros((at.shape[0], t), bool).at[
        jnp.arange(at.shape[0])[:, None], idx].set(True)
    return picked & seen


def attention(p, x, *, heads, cfg):
    t, _ = x.shape
    d = p["wq"].shape[0] // heads
    hkv = p["wk"].shape[0] // d
    q = _mm(x, p["wq"]).reshape(t, heads, d)
    k = _mm(x, p["wk"]).reshape(t, hkv, d)
    v = _mm(x, p["wv"]).reshape(t, hkv, d)
    q = _rope(_rms(p["q_norm"]["weight"], q, cfg["rms_norm_eps"]),
              cfg["rope_theta"])
    k = _rope(_rms(p["k_norm"]["weight"], k, cfg["rms_norm_eps"]),
              cfg["rope_theta"])
    qi, wi, ki = indexer(p, x, cfg)
    q = q.reshape(t, hkv, heads // hkv, d)
    block = next(c for c in (QUERY_BLOCK, 32, 16, 8, 4, 2, 1) if t % c == 0)

    def rows(i):
        at = i * block + jnp.arange(block)
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, i * block, block)
        picked = selection(cut(qi), cut(wi), ki, at, cfg["topk"])
        s = jnp.einsum("qkgd,skd->kgqs", cut(q), k) / jnp.sqrt(_F32(d))
        w = jax.nn.softmax(jnp.where(picked, s, -jnp.inf), axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", w, v)

    o = jax.lax.map(rows, jnp.arange(t // block)).reshape(t, heads * d)
    return _mm(o, p["wo"])


# -- the expert layer ----------------------------------------------------------------

def route(logits, cfg):
    """(ids (T, k), gates (T, k)): the best k of the softmax over all
    experts, gates renormalised over them."""
    k = cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(logits, axis=-1)
    ids = jnp.argsort(-probs, axis=-1)[:, :k]
    chosen = jnp.take_along_axis(probs, ids, axis=1)
    return ids, chosen / chosen.sum(-1, keepdims=True)


def experts(p, x, *, cfg):
    t, _ = x.shape
    ids, gates = route(_mm(x, p["router"]), cfg)
    wgu, wd = p["experts"]["w_gate_up"], p["experts"]["w_down"]
    held, _, f2 = wgu.shape
    # (T, held): a held expert's gate for a token, zero where not chosen
    local = ids - cfg["expert_offset"]
    dense = jnp.zeros((t, held), _F32).at[
        jnp.arange(t)[:, None], jnp.clip(local, 0, held - 1)].add(
        jnp.where((local >= 0) & (local < held), gates, 0.0))

    def one(g, y):
        h = x @ wgu[g].astype(_F32)
        h = jax.nn.silu(h[:, :f2 // 2]) * h[:, f2 // 2:]
        return y + (h @ wd[g].astype(_F32)) * dense[:, g][:, None]

    return jax.lax.fori_loop(0, held, one, jnp.zeros_like(x))


# -- the model -------------------------------------------------------------------

@partial(jax.jit, static_argnames=("heads", "cfg"))
def _mixer(p, x, *, heads, cfg):
    cfg = dict(cfg)
    with jax.default_matmul_precision("highest"):
        h = _rms(p["norm1"]["weight"], x, cfg["rms_norm_eps"])
        return x + attention(p["mixer"], h, heads=heads, cfg=cfg)


@partial(jax.jit, static_argnames=("cfg",))
def _ffn(p, x, *, cfg):
    cfg = dict(cfg)
    with jax.default_matmul_precision("highest"):
        h = _rms(p["norm2"]["weight"], x, cfg["rms_norm_eps"])
        return x + experts(p["ffn"], h, cfg=cfg)


@partial(jax.jit, static_argnames=("eps",))
def _logits(norm_f, head, x, rows, *, eps):
    with jax.default_matmul_precision("highest"):
        return _mm(_rms(norm_f["weight"], x[rows], eps), head)


def logits_at(params, tokens_1based, rows, *, heads, **published):
    """Float32 logits ``(len(rows), rows held)`` after positions ``rows``
    of the sequence ``tokens_1based`` (T,): row r predicts token r+1.
    ``published`` overrides ``PUBLISHED`` (a test's toy widths)."""
    cfg = tuple(sorted({**PUBLISHED, **published}.items()))
    rows = jnp.asarray(rows, jnp.int32)
    ids = jnp.asarray(tokens_1based, jnp.int32) - 1
    # causal: nothing behind the last row asked for reaches it
    need = int(rows.max()) + 1
    ids = ids[:min(ids.shape[0], -(-need // SEQ_STEP) * SEQ_STEP)]
    x = params["tok"][ids].astype(_F32)
    for p in params["blocks"]:
        x = _mixer(p, x, heads=heads, cfg=cfg)
        x = _ffn(p, x, cfg=cfg)
    return _logits(params["norm_f"], params["head"], x, rows,
                   eps=dict(cfg)["rms_norm_eps"])
