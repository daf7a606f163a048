"""Ling-3.0-flash's language model (``bailing_hybrid``), the share of it
that the configuration ``ling3_flash_vl`` holds: a plain float32 forward
pass over one whole sequence, with nothing of ``bigdl_tpu/`` but the names
of the parameter tree.

The layer pattern is read from the tree (a mixer with ``wqkv`` is a KDA
layer, one with ``wkva`` a latent-attention layer; a feed-forward part
with ``router`` is an expert layer), the widths from the shapes, and what
shapes cannot say from ``PUBLISHED`` below (the catalog row's keys).  Every
projection is ``y = x @ w.T``; the held experts' weights are ``(expert,
in, out)`` with gate and up side by side.  Token ids are 1-based.

* KDA (arXiv:2510.26692): the recurrence token by token, exactly as
  defined: ``S <- diag(exp(g)) S``, ``S <- S + beta k (v - S^T k)^T``,
  ``o = S^T q``, after a causal depthwise convolution and SiLU on q, k, v.
* MLA (DeepSeek-V2): the EXPANDED form, per-head keys and values from the
  normed latent, causal softmax over the whole sequence, in query blocks.
* experts: sigmoid scores over all experts, group-limited selection on
  the biased scores, gates from the unbiased ones normalised over all
  chosen; every HELD expert applied to every token, weighted by its gate
  (zero where it was not chosen), in blocks of experts; the shared expert
  once.  What absent experts would add is left out, as their chips add it.

One sequence and one layer part per jitted call, so that only one part's
float32 copy exists beside the served weights.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

_F32 = jnp.float32

PUBLISHED = {
    "rms_norm_eps": 1e-6, "rope_theta": 6e6, "kda_lower_bound": -5.0,
    "num_experts_per_tok": 8, "n_group": 8, "topk_group": 4,
    "routed_scaling_factor": 2.5, "expert_offset": 0,
    "kv_lora_rank": 512, "qk_rope_head_dim": 64}

EXPERT_BLOCK = 8            # held experts applied at a time
QUERY_BLOCK = 512           # MLA query rows scored at a time


def _rms(w, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(_F32)


def _mm(x, w):
    return x @ w.astype(_F32).T


def _swiglu(p, x):
    return _mm(jax.nn.silu(_mm(x, p["w_gate"])) * _mm(x, p["w_up"]),
               p["w_down"])


# -- mixers ----------------------------------------------------------------------

def kda(p, x, *, heads, cfg):
    t, _ = x.shape
    d = p["wqkv"].shape[0] // (3 * heads)
    u = _mm(x, p["wqkv"])                                   # (T, 3HD)
    taps = p["conv"].shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, u.shape[1]), _F32), u])
    conv = p["conv"].astype(_F32)
    y = sum(padded[j:j + t] * conv[j] for j in range(taps))
    y = jax.nn.silu(y).reshape(t, 3, heads, d)

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    q, k, v = unit(y[:, 0]) / jnp.sqrt(_F32(d)), unit(y[:, 1]), y[:, 2]
    f = (_mm(x, p["wf"]) + p["dt_bias"].astype(_F32)).reshape(t, heads, d)
    g = cfg["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(p["A_log"].astype(_F32))[:, None] * f)
    beta = jax.nn.sigmoid(_mm(x, p["wb"]))                  # (T, H)
    gate = jax.nn.sigmoid(_mm(x, p["wg"]))

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = jnp.exp(g_t)[..., None] * s                     # (H, dk, dv)
        r = v_t - jnp.einsum("hkv,hk->hv", s, k_t)
        s = s + jnp.einsum("hk,hv->hkv", b_t[:, None] * k_t, r)
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((heads, d, d), _F32),
                        (q, k, v, g, beta))
    o = _rms(p["o_norm"]["weight"], o, cfg["rms_norm_eps"]) * gate[..., None]
    return _mm(o.reshape(t, heads * d), p["wo"])


def _rope(x, theta):
    """Interleaved pairs over the last axis of (T, ..., D), position =
    index along the first axis."""
    t, half = x.shape[0], x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=_F32) / half)
    ang = jnp.arange(t, dtype=_F32).reshape((t,) + (1,) * (x.ndim - 1)) \
        * freqs
    pair = x.reshape(x.shape[:-1] + (half, 2))
    a, b = pair[..., 0], pair[..., 1]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      a * jnp.sin(ang) + b * jnp.cos(ang)],
                     axis=-1).reshape(x.shape)


def mla(p, x, *, heads, cfg):
    t, _ = x.shape
    c, r = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    n = p["wq"].shape[0] // heads - r
    dv = p["wkvb"].shape[0] // heads - n
    q = _mm(x, p["wq"]).reshape(t, heads, n + r)
    kva = _mm(x, p["wkva"])
    lat = _rms(p["kv_norm"]["weight"], kva[:, :c], cfg["rms_norm_eps"])
    k_r = _rope(kva[:, c:], cfg["rope_theta"])              # (T, r), shared
    q_r = _rope(q[..., n:], cfg["rope_theta"])
    kv = _mm(lat, p["wkvb"]).reshape(t, heads, n + dv)
    k_n, v = kv[..., :n], kv[..., n:]
    scale = 1.0 / jnp.sqrt(_F32(n + r))
    block = min(QUERY_BLOCK, t)
    assert t % block == 0, (t, block)

    def rows(i):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * block, block)
        s = (jnp.einsum("qhd,khd->hqk", sl(q[..., :n]), k_n)
             + jnp.einsum("qhd,kd->hqk", sl(q_r), k_r)) * scale
        seen = (i * block + jnp.arange(block))[:, None] \
            >= jnp.arange(t)[None]
        w = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", w, v)

    o = jax.lax.map(rows, jnp.arange(t // block)).reshape(t, heads * dv)
    return _mm(o, p["wo"])


# -- the expert layer ----------------------------------------------------------------

def route(scores, bias, cfg):
    """(ids (T, k), gates (T, k)) of group-limited top-k on the biased
    scores; gates from the unbiased ones."""
    t, n = scores.shape
    k, groups = cfg["num_experts_per_tok"], cfg["n_group"]
    biased = scores + bias
    per = biased.reshape(t, groups, n // groups)
    rank = jnp.sort(per, axis=-1)[..., -2:].sum(-1)         # two best
    best = jnp.argsort(-rank, axis=-1)[:, :cfg["topk_group"]]
    kept = (best[..., None] == jnp.arange(groups)).any(axis=1)
    masked = jnp.where(jnp.repeat(kept, n // groups, axis=1), biased,
                       -jnp.inf)
    ids = jnp.argsort(-masked, axis=-1)[:, :k]
    chosen = jnp.take_along_axis(scores, ids, axis=1)
    return ids, chosen / chosen.sum(-1, keepdims=True) \
        * cfg["routed_scaling_factor"]


def experts(p, x, *, cfg):
    t, e = x.shape
    scores = jax.nn.sigmoid(_mm(x, p["router"]))
    ids, gates = route(scores, p["bias"].astype(_F32), cfg)
    wgu, wd = p["experts"]["w_gate_up"], p["experts"]["w_down"]
    held, _, f2 = wgu.shape
    # (T, held): a held expert's gate for a token, zero where not chosen
    local = ids - cfg["expert_offset"]
    dense = jnp.zeros((t, held), _F32).at[
        jnp.arange(t)[:, None], jnp.clip(local, 0, held - 1)].add(
        jnp.where((local >= 0) & (local < held), gates, 0.0))
    block = min(EXPERT_BLOCK, held)
    assert held % block == 0, (held, block)

    def some(i):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * block, block)
        h = jnp.einsum("te,gef->gtf", x, sl(wgu).astype(_F32))
        h = jax.nn.silu(h[..., :f2 // 2]) * h[..., f2 // 2:]
        y = jnp.einsum("gtf,gfe->gte", h, sl(wd).astype(_F32))
        return jnp.einsum("gte,tg->te", y, sl(dense.T).T)

    y = jax.lax.map(some, jnp.arange(held // block)).sum(axis=0)
    return y + _swiglu(p["shared"], x)


# -- the model -------------------------------------------------------------------

@partial(jax.jit, static_argnames=("heads", "cfg"))
def _mixer(p, x, *, heads, cfg):
    cfg = dict(cfg)
    with jax.default_matmul_precision("highest"):
        h = _rms(p["norm1"]["weight"], x, cfg["rms_norm_eps"])
        part = kda if "wqkv" in p["mixer"] else mla
        return x + part(p["mixer"], h, heads=heads, cfg=cfg)


@partial(jax.jit, static_argnames=("cfg",))
def _ffn(p, x, *, cfg):
    cfg = dict(cfg)
    with jax.default_matmul_precision("highest"):
        h = _rms(p["norm2"]["weight"], x, cfg["rms_norm_eps"])
        if "router" in p["ffn"]:
            return x + experts(p["ffn"], h, cfg=cfg)
        return x + _swiglu(p["ffn"], h)


@partial(jax.jit, static_argnames=("eps",))
def _logits(norm_f, head, x, rows, *, eps):
    with jax.default_matmul_precision("highest"):
        return _mm(_rms(norm_f["weight"], x[rows], eps), head)


def logits_at(params, tokens_1based, rows, *, heads, **published):
    """Float32 logits ``(len(rows), rows held)`` after positions ``rows``
    of the sequence ``tokens_1based`` (T,): row r predicts token r+1.
    ``published`` overrides ``PUBLISHED`` (a test's toy widths)."""
    cfg = tuple(sorted({**PUBLISHED, **published}.items()))
    ids = jnp.asarray(tokens_1based, jnp.int32) - 1
    x = params["tok"][ids].astype(_F32)
    for p in params["blocks"]:
        x = _mixer(p, x, heads=heads, cfg=cfg)
        x = _ffn(p, x, cfg=cfg)
    return _logits(params["norm_f"], params["head"], x,
                   jnp.asarray(rows, jnp.int32),
                   eps=dict(cfg)["rms_norm_eps"])
