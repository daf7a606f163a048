"""Delta-rule linear attention with a per-channel decay (Kimi Delta
Attention, arXiv:2510.26692): a fixed-size state per sequence instead of
a cache that grows with it.

Per head, with keys of width ``dk`` and values of width ``dv``, the state
``S`` (dk, dv) float32 goes through, for every token ``t``::

    S <- diag(exp(g_t)) S                     # per-channel decay, g_t <= 0
    S <- S + beta_t k_t (v_t - S^T k_t)^T     # the delta rule
    o_t = S^T q_t

``kda_step`` is that recurrence for one token of every row (decode);
``kda_chunked`` is the same map over a whole sequence in chunks (prefill):
inside a chunk the updates are solved at once as a unit lower-triangular
system (the WY form, the inverse by repeated squaring), between chunks the
state is carried.  Both keep the
state and every product with it in float32 by element-wise arithmetic or
``precision="highest"`` matmuls: on a TPU a default float32 matmul rounds
its operands to bfloat16, which a state that lives for thousands of
tokens does not forgive.

Exponents are only ever taken of differences ``G_t - G_i`` with ``i <=
t`` of the cumulative log-decay (never of ``-G_i`` alone), so no chunk
length can overflow them; that costs one (C, C, dk) block of
element-wise work per chunk and head instead of a matmul.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


def kda_step(q, k, v, g, beta, state):
    """One token of every row.  ``q, k, g`` (B, H, dk), ``v`` (B, H, dv),
    ``beta`` (B, H), ``state`` (B, H, dk, dv) float32.  Returns
    (``o`` (B, H, dv) float32, the new state)."""
    q, k, v, g, beta = (a.astype(_F32) for a in (q, k, v, g, beta))
    s = jnp.exp(g)[..., None] * state
    r = v - jnp.sum(s * k[..., None], axis=-2)
    s = s + (beta[..., None] * k)[..., None] * r[..., None, :]
    return jnp.sum(s * q[..., None], axis=-2), s


def kda_naive(q, k, v, g, beta, state):
    """The recurrence token by token over (B, T, H, ·) inputs: the
    definition the chunked form is tested against."""
    def one(s, x):
        o, s = kda_step(*x, s)
        return s, o

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    state, o = jax.lax.scan(one, state.astype(_F32), xs)
    return jnp.moveaxis(o, 0, 1), state


def _unit_lower_inverse(l):
    """``(I + l)^-1`` for strictly lower-triangular ``l`` (..., C, C), by
    ``(I - l)(I + l^2)(I + l^4)...``: ``l`` is nilpotent, so the product
    ends after ``log2 C`` squarings.  Every product at the highest matmul
    precision: XLA's triangular solve carries no precision, and on a TPU
    its inner float32 products then round their operands to bfloat16
    (first chip run of PR 27: the prefilled state came out three digits
    wide)."""
    c = l.shape[-1]
    inv = jnp.eye(c, dtype=l.dtype) - l
    power, reach = l, 2
    while reach < c:
        power = jnp.matmul(power, power, precision=_HI)
        inv = inv + jnp.matmul(inv, power, precision=_HI)
        reach *= 2
    return inv


def kda_chunked(q, k, v, g, beta, state, chunk: int = 64):
    """The recurrence over whole sequences.  ``q, k, g`` (B, T, H, dk),
    ``v`` (B, T, H, dv), ``beta`` (B, T, H), ``state`` (B, H, dk, dv).
    A token with ``beta = 0`` and ``g = 0`` leaves the state as it was
    (how a caller masks right-padding); ``T`` need not be a multiple of
    ``chunk``.  Returns (``o`` (B, T, H, dv) float32, the state after the
    last token)."""
    b, t, h, dk = k.shape
    dv = v.shape[-1]
    pad = -t % chunk
    n = (t + pad) // chunk

    def chunks(a):
        a = a.astype(_F32)
        if pad:
            a = jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        # (B, T, H, d) -> (N, B, H, C, d)
        a = a.reshape(b, n, chunk, h, -1)
        return a.transpose(1, 0, 3, 2, 4)

    q, k, v, g = chunks(q), chunks(k), chunks(v), chunks(g)
    beta = chunks(beta[..., None])[..., 0]               # (N, B, H, C)
    gc = jnp.cumsum(g, axis=-2)                          # G_t, inclusive
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))

    def pairwise(args):
        # A[t, i] = sum_c k_t k_i exp(G_t - G_i), P likewise with q_t
        qc, kc, gg = args                                # (B, H, C, dk)
        diff = gg[..., :, None, :] - gg[..., None, :, :]
        e = jnp.exp(jnp.where(lower[..., None], diff, -jnp.inf))
        ke = kc[..., None, :, :] * e                     # (B, H, C, C, dk)
        return (jnp.sum(kc[..., :, None, :] * ke, axis=-1),
                jnp.sum(qc[..., :, None, :] * ke, axis=-1))

    a, p = jax.lax.map(pairwise, (q, k, gc))             # (N, B, H, C, C)
    # (I + diag(beta) strict(A)) U = diag(beta) (V - K^ S0), K^ = k exp(G)
    k_in = k * jnp.exp(gc)
    rhs = beta[..., None] * jnp.concatenate([v, k_in], axis=-1)
    sol = jnp.matmul(
        _unit_lower_inverse(beta[..., None] * jnp.tril(a, -1)), rhs,
        precision=_HI)
    u0, w = sol[..., :dv], sol[..., dv:]
    q_in = q * jnp.exp(gc)
    g_end = gc[..., -1:, :]                              # (N, B, H, 1, dk)
    k_out = k * jnp.exp(g_end - gc)

    def carry(s, x):
        u0_, w_, q_, p_, kout_, gend_ = x
        u = u0_ - jnp.matmul(w_, s, precision=_HI)       # (B, H, C, dv)
        o = jnp.matmul(q_, s, precision=_HI) \
            + jnp.matmul(p_, u, precision=_HI)
        s = jnp.exp(gend_)[..., 0, :, None] * s + jnp.matmul(
            jnp.swapaxes(kout_, -1, -2), u, precision=_HI)
        return s, o

    state, o = jax.lax.scan(carry, state.astype(_F32),
                            (u0, w, q_in, p, k_out, g_end))
    o = o.transpose(1, 0, 3, 2, 4).reshape(b, n * chunk, h, dv)
    return o[:, :t], state
