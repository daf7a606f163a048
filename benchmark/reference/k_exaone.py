"""K-EXAONE-236B-A23B's language model (``exaone_moe``), the share of it
that the configuration ``k_exaone_236b`` holds: a plain float32 forward
pass over one whole sequence, with nothing of ``bigdl_tpu/`` but the names
of the parameter tree.  No cache, no ring, no kernel.

The widths are read from the shapes (KV heads from ``wk``, a feed-forward
part with ``router`` is an expert layer), what shapes cannot say from
``PUBLISHED`` below: the catalog row's keys and, since a window layer's
and a full layer's weights have the same shapes, the kinds of the layers
HELD (the row's ``layer_types`` at layers 0 and 4-7).  Every projection is
``y = x @ w.T``; the held experts' weights are ``(expert, in, out)`` with
gate and up side by side.  Token ids are 1-based.

* attention (both kinds): q, k, v projections; RMS norm over each head's
  query and key channels; on a ``sliding_attention`` layer rope by pairs
  ``(i, i + D/2)`` and the window ``i - j < sliding_window``, on a
  ``full_attention`` layer neither; causal softmax over the whole
  sequence, in query blocks; query head ``a`` reads KV head ``a // (H /
  Hkv)``.
* experts: sigmoid scores over all experts, the best k by ``scores +
  bias`` (``n_group`` 1: no groups to limit), gates from the unbiased
  scores normalised over the chosen x the scaling factor; every HELD
  expert applied to every token, weighted by its gate (zero where it was
  not chosen), one expert at a time; the shared expert once.  What absent
  experts would add is left out, as their chips add it.

One layer part per jitted call and one expert (or 2,048 columns of a dense
part) at a time inside it, so that only that much of the weights exists in
float32 beside the served ones; a sequence is cut behind the last row
asked for (to whole `SEQ_STEP`s, so that few lengths ever compile).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

_F32 = jnp.float32

PUBLISHED = {
    "rms_norm_eps": 1e-5, "rope_theta": 1e6, "sliding_window": 128,
    "num_experts_per_tok": 8, "routed_scaling_factor": 2.5,
    "expert_offset": 0,
    "layer_types": ("sliding_attention",) * 4 + ("full_attention",)}

QUERY_BLOCK = 128           # query rows scored at a time
FFN_BLOCK = 2048            # columns of a dense part multiplied at a time
SEQ_STEP = 2048             # a sequence is cut to a whole number of these


def _rms(w, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(_F32)


def _mm(x, w):
    return x @ w.astype(_F32).T


def _swiglu(p, x):
    """``(silu(x Wg) * (x Wu)) Wd``, `FFN_BLOCK` hidden columns a turn."""
    f = p["w_gate"].shape[0]
    block = next(c for c in (FFN_BLOCK, f) if f % c == 0)

    def turn(i, y):
        rows = lambda w: jax.lax.dynamic_slice_in_dim(w, i * block, block, 0)
        h = jax.nn.silu(_mm(x, rows(p["w_gate"]))) * _mm(x, rows(p["w_up"]))
        return y + h @ jax.lax.dynamic_slice_in_dim(
            p["w_down"], i * block, block, 1).astype(_F32).T

    return jax.lax.fori_loop(0, f // block, turn, jnp.zeros_like(x))


# -- attention ---------------------------------------------------------------------

def _rope(x, theta):
    """Pairs ``(x[i], x[i + D/2])`` over the last axis of (T, H, D),
    position = index along the first axis."""
    t, half = x.shape[0], x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=_F32) / half)
    ang = jnp.arange(t, dtype=_F32)[:, None, None] * freqs
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            a * jnp.sin(ang) + b * jnp.cos(ang)], axis=-1)


def attention(p, x, *, heads, kind, cfg):
    t, _ = x.shape
    d = p["wq"].shape[0] // heads
    hkv = p["wk"].shape[0] // d
    q = _mm(x, p["wq"]).reshape(t, heads, d)
    k = _mm(x, p["wk"]).reshape(t, hkv, d)
    v = _mm(x, p["wv"]).reshape(t, hkv, d)
    q = _rms(p["q_norm"]["weight"], q, cfg["rms_norm_eps"])
    k = _rms(p["k_norm"]["weight"], k, cfg["rms_norm_eps"])
    window = None
    if kind == "sliding_attention":
        window = cfg["sliding_window"]
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    q = q.reshape(t, hkv, heads // hkv, d)
    block = next(c for c in (QUERY_BLOCK, 64, 32, 16, 8, 4, 2, 1)
                 if t % c == 0)

    def rows(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * block, block)
        s = jnp.einsum("qkgd,skd->kgqs", qs, k) / jnp.sqrt(_F32(d))
        at = (i * block + jnp.arange(block))[:, None]
        key = jnp.arange(t)[None]
        seen = key <= at
        if window is not None:
            seen &= at - key < window
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", w, v)

    o = jax.lax.map(rows, jnp.arange(t // block)).reshape(t, heads * d)
    return _mm(o, p["wo"])


# -- the expert layer ----------------------------------------------------------------

def route(scores, bias, cfg):
    """(ids (T, k), gates (T, k)): the best k of the biased scores; gates
    from the unbiased ones."""
    k = cfg["num_experts_per_tok"]
    ids = jnp.argsort(-(scores + bias), axis=-1)[:, :k]
    chosen = jnp.take_along_axis(scores, ids, axis=1)
    return ids, chosen / chosen.sum(-1, keepdims=True) \
        * cfg["routed_scaling_factor"]


def experts(p, x, *, cfg):
    t, _ = x.shape
    scores = jax.nn.sigmoid(_mm(x, p["router"]))
    ids, gates = route(scores, p["bias"].astype(_F32), cfg)
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

    return jax.lax.fori_loop(0, held, one, jnp.zeros_like(x)) \
        + _swiglu(p["shared"], x)


# -- the model -------------------------------------------------------------------

@partial(jax.jit, static_argnames=("heads", "kind", "cfg"))
def _mixer(p, x, *, heads, kind, cfg):
    cfg = dict(cfg)
    with jax.default_matmul_precision("highest"):
        h = _rms(p["norm1"]["weight"], x, cfg["rms_norm_eps"])
        return x + attention(p["mixer"], h, heads=heads, kind=kind, cfg=cfg)


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
    ``published`` overrides ``PUBLISHED`` (a test's toy widths and its
    pattern)."""
    cfg = {**PUBLISHED, **published}
    kinds = tuple(cfg.pop("layer_types"))
    assert len(kinds) == len(params["blocks"]), (kinds, len(params["blocks"]))
    cfg = tuple(sorted(cfg.items()))
    rows = jnp.asarray(rows, jnp.int32)
    ids = jnp.asarray(tokens_1based, jnp.int32) - 1
    # causal: nothing behind the last row asked for reaches it
    need = int(rows.max()) + 1
    ids = ids[:min(ids.shape[0], -(-need // SEQ_STEP) * SEQ_STEP)]
    x = params["tok"][ids].astype(_F32)
    for kind, p in zip(kinds, params["blocks"]):
        x = _mixer(p, x, heads=heads, kind=kind, cfg=cfg)
        x = _ffn(p, x, cfg=cfg)
    return _logits(params["norm_f"], params["head"], x, rows,
                   eps=dict(cfg)["rms_norm_eps"])
