"""NVIDIA-Nemotron-3-Super-120B-A12B's language model (``nemotron_h``), the
share of it that the configuration ``nemotron3_super_120b`` holds: a plain
float32 forward pass over one whole sequence, with nothing of ``bigdl_tpu/``
but the names of the parameter tree.  No cache, no state kept, no chunked
scan, no kernel.

Every block is ONE part, ``x + part(rmsnorm(x))``: a block with ``mixer``
whose weights hold ``in_proj`` is a Mamba-2 block (``M``), one with ``wq``
an attention block (``*``), a block with ``ffn`` an expert block (``E``).
The widths are read from the shapes (Mamba heads from ``A_log``, taps from
``conv``, KV heads from ``wk``), what shapes cannot say from ``PUBLISHED``
below: the catalog row's keys.  Every projection is ``y = x @ w.T``; the
held experts' weights are ``(expert, in, out)``.  Token ids are 1-based.

* Mamba-2: ``[z, xBC, dt] = W_in u``; a causal depthwise convolution of 4
  taps over time on ``xBC`` plus its bias, SiLU, split into ``x`` (H, P),
  ``B`` (G, N), ``C`` (G, N); ``dt = softplus(dt + dt_bias)``; the state
  ``h`` (H, P, N), token by token (``lax.scan``): ``h = exp(dt A) h + dt x
  (x) B[g]``, ``y = h C[g] + D x``, head ``i`` reading group ``i // (H /
  G)``; ``y * silu(z)``, THEN an RMS norm over each of the G groups of
  channels; ``W_out``.
* attention: q, k, v projections, no rope, no head norm; causal softmax
  over the whole sequence, in query blocks; query head ``a`` reads KV head
  ``a // (H / Hkv)``.
* experts: sigmoid scores over all experts, the best k by ``scores +
  bias`` (``n_group`` 1: no groups to limit), gates from the unbiased
  scores normalised over the chosen x the scaling factor; the token
  projected down to the latent; every HELD expert (``relu(l W1)^2 W2``, not
  gated) applied to every token, weighted by its gate (zero where it was
  not chosen), one expert at a time; the sum projected back up; the shared
  expert (``relu2`` too, on the full hidden size) once.  What absent
  experts would add is left out, as their chips add it.

Departures from the published description:
* the share: experts ``expert_offset`` .. +held of every expert block, the
  embedding and head rows held, the blocks held (the configuration's
  ``reduced``); no multi-token-prediction head.
* no rotary embedding in the attention block, although the row lists
  ``rope_theta`` and ``partial_rotary_factor``: the family's public
  modelling code applies none (the configuration's ``assumed`` says so, and
  ``models/hybrid.py``'s docstring; all three change together).
* the state is float32 and the gate comes before the group norm, as the
  family's code has them (``assumed`` again).

One block per jitted call and one expert (or `FFN_BLOCK` columns of a wide
projection) at a time inside it, so that only that much of the weights
exists in float32 beside the served ones; a sequence is cut behind the last
row asked for (to whole `SEQ_STEP`s, so that few lengths ever compile).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

_F32 = jnp.float32

PUBLISHED = {
    "norm_eps": 1e-5, "n_groups": 8, "num_experts_per_tok": 22,
    "routed_scaling_factor": 5.0, "expert_offset": 0}

QUERY_BLOCK = 128           # query rows scored at a time
FFN_BLOCK = 2048            # columns of a wide projection at a time
SEQ_STEP = 2048             # a sequence is cut to a whole number of these


def _rms(w, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(_F32)


def _mm(x, w):
    """``x @ w.T`` with `FFN_BLOCK` rows of ``w`` in float32 at a time."""
    out = w.shape[0]
    if out <= FFN_BLOCK or out % FFN_BLOCK:
        return x @ w.astype(_F32).T

    def turn(i, y):
        rows = jax.lax.dynamic_slice_in_dim(w, i * FFN_BLOCK, FFN_BLOCK, 0)
        return jax.lax.dynamic_update_slice_in_dim(
            y, x @ rows.astype(_F32).T, i * FFN_BLOCK, 1)

    return jax.lax.fori_loop(0, out // FFN_BLOCK, turn,
                             jnp.zeros((x.shape[0], out), _F32))


def _relu2(p, x):
    """``relu(x Wu)^2 Wd``: the family's feed-forward part is not gated."""
    return _mm(jnp.square(jax.nn.relu(_mm(x, p["w_up"]))), p["w_down"])


# -- Mamba-2 ---------------------------------------------------------------------

def mamba2(p, u, *, cfg):
    t, _ = u.shape
    h = p["A_log"].shape[0]
    taps, conv_dim = p["conv"].shape
    inner = p["out_proj"].shape[1]
    g = cfg["n_groups"]
    n = (conv_dim - inner) // (2 * g)
    hp = inner // h
    # W_in's rows are [z | xBC | dt]; z is projected where it is used, so
    # that a long sequence's three parts never exist side by side
    xbc = _mm(u, p["in_proj"][inner:inner + conv_dim])
    dt = _mm(u, p["in_proj"][inner + conv_dim:])
    # causal: token t sees xBC rows t - (taps - 1) .. t, zeros before 0
    seq = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    cw = p["conv"].astype(_F32)
    xbc = jax.nn.silu(sum(seq[j:j + t] * cw[j] for j in range(taps))
                      + p["conv_bias"].astype(_F32))
    x = xbc[:, :inner].reshape(t, h, hp)
    b = xbc[:, inner:inner + g * n].reshape(t, g, n)
    c = xbc[:, inner + g * n:].reshape(t, g, n)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(_F32))     # (T, H)
    a = -jnp.exp(p["A_log"].astype(_F32))
    d = p["D"].astype(_F32)

    def token(state, v):
        x_t, b_t, c_t, dt_t = v
        b_h = jnp.repeat(b_t, h // g, axis=0)                # (H, N)
        c_h = jnp.repeat(c_t, h // g, axis=0)
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :]
        y = jnp.sum(state * c_h[:, None, :], axis=-1) + d[:, None] * x_t
        return state, y

    _, y = jax.lax.scan(token, jnp.zeros((h, hp, n), _F32), (x, b, c, dt))
    y = y.reshape(t, inner) * jax.nn.silu(_mm(u, p["in_proj"][:inner]))
    y = y.reshape(t, g, inner // g)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + cfg["norm_eps"])
    return _mm(y.reshape(t, inner) * p["norm"]["weight"].astype(_F32),
               p["out_proj"])


# -- attention ---------------------------------------------------------------------

def attention(p, x, *, heads):
    t, _ = x.shape
    d = p["wq"].shape[0] // heads
    hkv = p["wk"].shape[0] // d
    q = _mm(x, p["wq"]).reshape(t, hkv, heads // hkv, d)
    k = _mm(x, p["wk"]).reshape(t, hkv, d)
    v = _mm(x, p["wv"]).reshape(t, hkv, d)
    block = next(c for c in (QUERY_BLOCK, 64, 32, 16, 8, 4, 2, 1)
                 if t % c == 0)

    def rows(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * block, block)
        s = jnp.einsum("qkgd,skd->kgqs", qs, k) / jnp.sqrt(_F32(d))
        at = (i * block + jnp.arange(block))[:, None]
        w = jax.nn.softmax(jnp.where(jnp.arange(t)[None] <= at, s, -jnp.inf),
                           axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", w, v)

    o = jax.lax.map(rows, jnp.arange(t // block)).reshape(t, heads * d)
    return _mm(o, p["wo"])


# -- the expert block ----------------------------------------------------------------

def route(scores, bias, cfg):
    """(ids (T, k), gates (T, k)): the best k of the biased scores; gates
    from the unbiased ones."""
    k = cfg["num_experts_per_tok"]
    ids = jnp.argsort(-(scores + bias), axis=-1)[:, :k]
    chosen = jnp.take_along_axis(scores, ids, axis=1)
    return ids, chosen / chosen.sum(-1, keepdims=True) \
        * cfg["routed_scaling_factor"]


def latent_experts(p, x, *, cfg):
    t, _ = x.shape
    scores = jax.nn.sigmoid(_mm(x, p["router"]))
    ids, gates = route(scores, p["bias"].astype(_F32), cfg)
    w1, w2 = p["experts"]["w_up"], p["experts"]["w_down"]
    held = w1.shape[0]
    # (T, held): a held expert's gate for a token, zero where not chosen
    local = ids - cfg["expert_offset"]
    dense = jnp.zeros((t, held), _F32).at[
        jnp.arange(t)[:, None], jnp.clip(local, 0, held - 1)].add(
        jnp.where((local >= 0) & (local < held), gates, 0.0))
    lat = _mm(x, p["latent_down"])

    def one(g, y):
        h = jnp.square(jax.nn.relu(lat @ w1[g].astype(_F32)))
        return y + (h @ w2[g].astype(_F32)) * dense[:, g][:, None]

    routed = jax.lax.fori_loop(0, held, one, jnp.zeros_like(lat))
    return _mm(routed, p["latent_up"]) + _relu2(p["shared"], x)


# -- the model -------------------------------------------------------------------

@partial(jax.jit, static_argnames=("heads", "cfg"))
def _block(p, x, *, heads, cfg):
    cfg = dict(cfg)
    with jax.default_matmul_precision("highest"):
        if "ffn" in p:
            h = _rms(p["norm2"]["weight"], x, cfg["norm_eps"])
            return x + latent_experts(p["ffn"], h, cfg=cfg)
        h = _rms(p["norm1"]["weight"], x, cfg["norm_eps"])
        if "in_proj" in p["mixer"]:
            return x + mamba2(p["mixer"], h, cfg=cfg)
        return x + attention(p["mixer"], h, heads=heads)


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
        x = _block(p, x, heads=heads, cfg=cfg)
    return _logits(params["norm_f"], params["head"], x, rows,
                   eps=dict(cfg)["norm_eps"])
