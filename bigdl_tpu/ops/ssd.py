"""The Mamba-2 state-space recurrence (state-space duality, arXiv:2405.21060):
a fixed-size float32 state per sequence with ONE scalar decay a head.

Per head, with channels of width ``P`` and a state of width ``N``, the
state ``h`` (P, N) float32 goes through, for every token ``t``::

    h <- exp(dt_t A) h + dt_t x_t (x) B_t        # A < 0 a scalar a head
    y_t = h C_t + D x_t                           # D a scalar a head

``B_t`` and ``C_t`` (N,) are shared by the ``H / G`` heads of a group: head
``i`` reads group ``i // (H / G)``.  ``ssd_step`` is that recurrence for one
token of every row (decode); ``ssd_chunked`` is the same map over a whole
sequence, the SSD block decomposition: inside a chunk the masked ``C B^T``
product against ``dt x``, between chunks the state carried by the product
of the chunk's decays, plus what the state that entered the chunk gives
each of its tokens.  ``ssd_naive`` is the definition, token by token, that
both are tested against.

The state and every product with it stay in float32, by element-wise
arithmetic or ``precision="highest"`` matmuls (``ops/delta_rule.py`` says
why: on a TPU a default float32 matmul rounds its operands to bfloat16).
Exponents are only ever taken of differences ``cum_t - cum_s`` with ``s <=
t`` of the cumulative log-decay, so no chunk length overflows them.  A
token with ``dt = 0`` leaves the state as it was, bit for bit (how a
caller masks right padding and inactive rows).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


def ssd_step(x, dt, a, b, c, d, state):
    """One token of every row.  ``x`` (B, H, P), ``dt`` (B, H) (after its
    softplus), ``a`` and ``d`` (H,), ``b`` and ``c`` (B, G, N), ``state``
    (B, H, P, N) float32.  Returns (``y`` (B, H, P) float32, the new
    state).  One pass over the state: read, decayed, added to, read out."""
    x, dt, a, b, c, d = (v.astype(_F32) for v in (x, dt, a, b, c, d))
    bsz, h, p = x.shape
    g = b.shape[1]
    s = state.reshape(bsz, g, h // g, p, -1)
    decay = jnp.exp(dt * a).reshape(bsz, g, h // g, 1, 1)
    dtx = (dt[..., None] * x).reshape(bsz, g, h // g, p, 1)
    s = decay * s + dtx * b[:, :, None, None, :]
    y = jnp.sum(s * c[:, :, None, None, :], axis=-1).reshape(bsz, h, p)
    return y + d[:, None] * x, s.reshape(state.shape)


def ssd_naive(x, dt, a, b, c, d, state):
    """The recurrence token by token over ``x`` (B, T, H, P), ``dt`` (B, T,
    H), ``b`` and ``c`` (B, T, G, N): the definition."""
    def one(s, v):
        y, s = ssd_step(v[0], v[1], a, v[2], v[3], d, s)
        return s, y

    xs = tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c))
    state, y = jax.lax.scan(one, state.astype(_F32), xs)
    return jnp.moveaxis(y, 0, 1), state


def ssd_chunked(x, dt, a, b, c, d, state, chunk: int = 128):
    """The recurrence over whole sequences, ``chunk`` tokens a turn.  ``x``
    (B, T, H, P), ``dt`` (B, T, H), ``a`` and ``d`` (H,), ``b`` and ``c``
    (B, T, G, N), ``state`` (B, H, P, N); ``T`` need not be a multiple of
    ``chunk`` (the pad has ``dt = 0``).  Returns (``y`` (B, T, H, P)
    float32, the state after the last token)."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    r = h // g
    pad = -t % chunk
    nc = (t + pad) // chunk

    def chunks(v):
        v = v.astype(_F32)
        if pad:
            v = jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
        return jnp.moveaxis(v.reshape(bsz, nc, chunk, *v.shape[2:]), 1, 0)

    a, d = a.astype(_F32), d.astype(_F32)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))

    def turn(s, v):
        xc, dtc, bc, cc = v         # (B, L, H, P) (B, L, H) (B, L, G, N) x 2
        # inclusive cumulative log-decay of the chunk, (B, G, R, L)
        cum = jnp.cumsum(dtc * a, axis=1).transpose(0, 2, 1) \
            .reshape(bsz, g, r, chunk)
        dtx = (dtc[..., None] * xc).transpose(0, 2, 1, 3) \
            .reshape(bsz, g, r, chunk, p)
        # inside the chunk: token l takes token s <= l's dt x (x) B through
        # the decays between them and reads it with its own C
        cb = jnp.einsum("blgn,bsgn->bgls", cc, bc, precision=_HI)
        between = jnp.exp(jnp.where(
            lower, cum[..., :, None] - cum[..., None, :], -jnp.inf))
        y = jnp.einsum("bgrls,bgrsp->bgrlp", cb[:, :, None] * between, dtx,
                       precision=_HI)
        # what the state that entered the chunk gives each token
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "blgn,bgrpn->bgrlp", cc, s, precision=_HI)
        # the state that leaves it
        last = cum[..., -1:]                                # (B, G, R, 1)
        s = jnp.exp(last)[..., None] * s + jnp.einsum(
            "bgrlp,blgn->bgrpn", dtx * jnp.exp(last - cum)[..., None], bc,
            precision=_HI)
        y = y.reshape(bsz, h, chunk, p).transpose(0, 2, 1, 3)
        return s, y + d[:, None] * xc

    s0 = state.astype(_F32).reshape(bsz, g, r, p, n)
    s, y = jax.lax.scan(turn, s0, tuple(chunks(v) for v in (x, dt, b, c)))
    y = jnp.moveaxis(y, 0, 1).reshape(bsz, nc * chunk, h, p)
    return y[:, :t], s.reshape(bsz, h, p, n)
