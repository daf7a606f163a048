"""A state-space sequence mixer with a fixed-size recurrent state: the
Mamba-2 layer (arXiv:2405.21060) as a module.

Like :class:`~bigdl_tpu.nn.DeltaAttention` it keeps, per sequence, a float32
state whose size does not depend on the sequence (``(H, P, N)`` a layer) and
the last ``taps - 1`` inputs of a short causal convolution, addressed by the
serving SLOT and not through a page table; the recurrence is another one
(``ops/ssd.py``: a scalar decay a head, no delta rule, ``B`` and ``C``
shared by the heads of a group, a skip ``D``), and so is the output norm.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bigdl_tpu.core.module import Module
from bigdl_tpu.ops import quant
from bigdl_tpu.ops.ssd import ssd_chunked, ssd_step

_F32 = jnp.float32
#: a fresh layer's ``-A`` a head is drawn uniform over `A_RANGE` (Mamba-2's
#: own convention) and its step ``dt`` log-uniform over `DT_RANGE`, never
#: under `DT_FLOOR` (the published ``time_step_min``, ``time_step_max`` and
#: ``time_step_floor``): decays of ``exp(-dt A)`` a token, memories of one
#: to a thousand tokens
A_RANGE = (1.0, 16.0)
DT_RANGE = (0.001, 0.1)
DT_FLOOR = 1e-4


class Mamba2Mixer(Module):
    """Per token, ``u`` the normed input: ``[z (HP), xBC (HP + 2GN), dt (H)]
    = W_in u``; ``xBC <- silu(causal depthwise convolution of ``taps`` over
    time (xBC) + conv bias)``, split into ``x`` (H, P), ``B`` (G, N), ``C``
    (G, N); ``dt <- softplus(dt + dt_bias)``, ``A = -exp(A_log)`` one scalar
    a head; the recurrence of ``ops/ssd.py`` on a float32 state (H, P, N),
    head ``i`` reading group ``i // (H / G)``; ``y <- rmsnorm_group(y *
    silu(z))``, the gate FIRST and then an RMS norm over each of the ``G``
    groups of ``HP / G`` channels (one weight of ``HP``); ``out = W_out y``.
    No positions, no biases but the convolution's.

    ``A_log``, ``D``, ``dt_bias``, the convolution and the norm's weight may
    arrive in bfloat16 (a served tree cast whole): they are read up to
    float32 here, and the state is float32 whatever the tree's dtype."""

    def __init__(self, embed_dim: int, num_heads: int = 128,
                 head_dim: int = 64, state_dim: int = 128, groups: int = 8,
                 taps: int = 4, chunk: int = 128, eps: float = 1e-5):
        super().__init__()
        assert num_heads % groups == 0, (num_heads, groups)
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.head_dim = head_dim
        self.state_dim = state_dim
        self.groups = groups
        self.taps = taps
        self.chunk = chunk
        self.eps = eps
        self.inner = num_heads * head_dim
        #: channels the convolution runs over: x, B and C side by side
        self.conv_dim = self.inner + 2 * groups * state_dim

    def init_params(self, rng):
        ks = jax.random.split(rng, 6)
        e, h = self.embed_dim, self.num_heads

        def w(k, out, fan_in):
            return jax.random.normal(k, (out, fan_in)) * fan_in ** -0.5

        lo, hi = DT_RANGE
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            ks[3], (h,), minval=math.log(lo), maxval=math.log(hi))),
            DT_FLOOR)
        bound = self.taps ** -0.5       # a depthwise Conv1d's usual draw
        return {
            "in_proj": w(ks[0], 2 * self.inner + 2 * self.groups
                         * self.state_dim + h, e),
            "conv": jax.random.uniform(ks[1], (self.taps, self.conv_dim),
                                       minval=-bound, maxval=bound),
            "conv_bias": jax.random.uniform(ks[2], (self.conv_dim,),
                                            minval=-bound, maxval=bound),
            "A_log": jnp.log(jax.random.uniform(
                ks[4], (h,), minval=A_RANGE[0], maxval=A_RANGE[1])),
            "D": jnp.ones((h,), _F32),
            # softplus(dt_bias) = dt
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "norm": {"weight": jnp.ones((self.inner,), _F32)},
            "out_proj": w(ks[5], e, self.inner),
        }

    def init_slot_state(self, num_slots: int, dtype=jnp.float32):
        """The state of ``num_slots`` sequences: ``h`` the state-space
        matrices, always float32; ``conv`` the convolution's tail (the last
        ``taps - 1`` projected ``xBC`` rows) in ``dtype``."""
        return {"h": jnp.zeros((num_slots, self.num_heads, self.head_dim,
                                self.state_dim), _F32),
                "conv": jnp.zeros((num_slots, self.taps - 1, self.conv_dim),
                                  dtype)}

    def apply_slots(self, params, x, st, pos, active, lengths=None):
        """``DeltaAttention.apply_slots``'s contract: ``x`` (B, S, E) at
        positions ``[pos_b, pos_b + S)`` against the rows' states ``st``
        (``init_slot_state``'s tree, B rows).  A row at position 0 starts
        from the zero state whatever ``st`` holds (the slot's last tenant);
        tokens at or past ``lengths_b`` (right padding of a prefill bucket)
        leave state and tail alone (``dt`` is 0 there); an inactive row's
        state comes back bit for bit.  ``S == 1`` takes the one-token
        recurrence, longer inputs the chunked one.  Returns (y, st')."""
        b, s, _ = x.shape
        h, p, g, n = (self.num_heads, self.head_dim, self.groups,
                      self.state_dim)
        taps, inner = self.taps, self.inner
        fresh = jnp.asarray(pos) == 0
        h0 = jnp.where(fresh[:, None, None, None], 0.0, st["h"])
        tail = jnp.where(fresh[:, None, None], 0, st["conv"])
        keep = jnp.asarray(active)
        real = jnp.arange(s)[None] < (
            jnp.full((b,), s, jnp.int32) if lengths is None
            else jnp.asarray(lengths, jnp.int32))[:, None]       # (B, S)
        n_real = jnp.sum(real, axis=1)
        with jax.named_scope("in_proj"):
            w_in = params["in_proj"]
            zx = quant.matmul_or_observe(x, w_in[:inner + self.conv_dim])
            z, xbc = zx[..., :inner], zx[..., inner:]
            # the step comes out in float32 (the product accumulates there
            # anyway): exp(dt A) is applied to the state at every token
            dt = jax.nn.softplus(
                jnp.dot(x, jnp.asarray(w_in[inner + self.conv_dim:]).T,
                        preferred_element_type=_F32)
                + params["dt_bias"].astype(_F32))
            dt = jnp.where(real[..., None] & keep[:, None, None], dt, 0.0)
        with jax.named_scope("conv"):
            seq = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
            cw = params["conv"].astype(_F32)
            y = sum(seq[:, j:j + s].astype(_F32) * cw[j]
                    for j in range(taps)) + params["conv_bias"].astype(_F32)
            y = jax.nn.silu(y)
            xs = y[..., :inner].reshape(b, s, h, p)
            bs = y[..., inner:inner + g * n].reshape(b, s, g, n)
            cs = y[..., inner + g * n:].reshape(b, s, g, n)
            # the last taps-1 REAL rows of xBC: rows n-(taps-1) .. n-1,
            # which sit taps-1 later in seq
            idx = n_real[:, None] + jnp.arange(taps - 1)[None]
            new_tail = jnp.take_along_axis(seq, idx[..., None], axis=1)
            new_tail = jnp.where(keep[:, None, None],
                                 new_tail.astype(st["conv"].dtype),
                                 st["conv"])
        with jax.named_scope("state"):
            a = -jnp.exp(params["A_log"].astype(_F32))
            skip = params["D"].astype(_F32)
            if s == 1:
                o, h1 = ssd_step(xs[:, 0], dt[:, 0], a, bs[:, 0], cs[:, 0],
                                 skip, h0)
                o = o[:, None]
            else:
                o, h1 = ssd_chunked(xs, dt, a, bs, cs, skip, h0, self.chunk)
            h1 = jnp.where(keep[:, None, None, None], h1, st["h"])
        with jax.named_scope("gate_norm"):
            o = self._gate_norm(params, o.reshape(b, s, inner), z)
        with jax.named_scope("out"):
            out = quant.matmul_or_observe(o.astype(x.dtype),
                                          params["out_proj"])
        return out, {"h": h1, "conv": new_tail}

    def _gate_norm(self, params, o, z):
        """``rmsnorm_group(o * silu(z))`` of (B, S, HP) float32 ``o``: the
        gate first, then the norm over each group's channels."""
        b, s, inner = o.shape
        o = (o * jax.nn.silu(z.astype(_F32))).reshape(b, s, self.groups, -1)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + self.eps)
        return o.reshape(b, s, inner) * params["norm"]["weight"].astype(_F32)

    def apply(self, params, state, input, *, training=False, rng=None):
        """A whole sequence from the zero state."""
        b = input.shape[0]
        y, _ = self.apply_slots(
            params, input, self.init_slot_state(b, input.dtype),
            jnp.zeros((b,), jnp.int32), jnp.ones((b,), bool))
        return y, state
