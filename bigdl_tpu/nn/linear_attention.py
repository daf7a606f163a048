"""Linear attention with a fixed-size recurrent state: the delta-rule
mixer (Kimi Delta Attention, arXiv:2510.26692) as a module.

Where softmax attention keeps every key and value it has seen, this mixer
keeps, per head, one ``(dk, dv)`` float32 matrix and the last
``taps - 1`` inputs of a short causal convolution: a state whose size does
not depend on the sequence, addressed by the serving SLOT and not through
a page table.  ``ops/delta_rule.py`` holds the recurrence (one-token form
for decode, chunked form for prefill).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bigdl_tpu.core.module import Module
from bigdl_tpu.ops import quant
from bigdl_tpu.ops.delta_rule import kda_chunked, kda_step

_F32 = jnp.float32
#: the per-token decay rates ``-g`` a fresh layer's channels are drawn from
DECAY_RATES = (2e-4, 5e-3)


def _l2norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


class DeltaAttention(Module):
    """Per token, ``x`` the normed input: ``[q~, k~, v~] = Wqkv x``, a
    causal depthwise convolution of ``taps`` over time on each channel,
    SiLU; ``q = l2norm(q) / sqrt(d)``, ``k = l2norm(k)`` per head; the
    per-channel log-decay ``g = floor * sigmoid(exp(A_log_h) * (Wf x +
    dt_bias))`` in ``[floor, 0)`` (the bounded, "safe" gate); ``beta =
    sigmoid(Wb x)`` per head; the delta rule of ``ops/delta_rule.py``;
    the output ``Wo [rmsnorm_head(o) * sigmoid(Wg x)_h]``, one gate scalar
    a head.  No positions, no biases.

    ``A_log``, ``dt_bias`` and the norm's weight may arrive in bfloat16
    (a served tree cast whole): they are read up to float32 here, and the
    state is float32 whatever the tree's dtype."""

    def __init__(self, embed_dim: int, num_heads: int, head_dim: int,
                 taps: int = 4, decay_floor: float = -5.0,
                 eps: float = 1e-6):
        super().__init__()
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.head_dim = head_dim
        self.taps = taps
        self.decay_floor = float(decay_floor)
        self.eps = eps

    def init_params(self, rng):
        ks = jax.random.split(rng, 8)
        e, h, d = self.embed_dim, self.num_heads, self.head_dim
        hd = h * d

        def w(k, out, fan_in=e):
            return jax.random.normal(k, (out, fan_in)) * fan_in ** -0.5

        # A state that is worth keeping: with unit-gain gates a channel
        # would forget within a token or two (g anywhere in (floor, 0))
        # and the state would hold nothing a rounding could harm.  The
        # decay rates are drawn log-uniform over DECAY_RATES a token, as
        # the mixer's authors initialise theirs (memories of hundreds to
        # thousands of tokens), set through ``dt_bias`` with ``A_log`` 0;
        # ``Wf`` is small, so that a token moves its channel's rate by a
        # factor near one, and ``Wb`` large, so that a token either
        # writes (beta near 1) or mostly leaves the state alone.
        lo, hi = DECAY_RATES
        rate = jnp.exp(jax.random.uniform(ks[6], (hd,), minval=math.log(lo),
                                          maxval=math.log(hi)))
        share = rate / -self.decay_floor          # sigmoid(dt_bias) of it
        return {
            "wqkv": w(ks[0], 3 * hd),
            "conv": jax.random.normal(ks[1], (self.taps, 3 * hd))
            * self.taps ** -0.5,
            "wf": 0.3 * w(ks[2], hd), "wb": 4.0 * w(ks[3], h),
            "wg": w(ks[4], h),
            "A_log": jnp.zeros((h,), _F32),
            "dt_bias": jnp.log(share / (1.0 - share)),
            "o_norm": {"weight": jnp.ones((d,), _F32)},
            "wo": w(ks[7], e, hd),
        }

    def init_slot_state(self, num_slots: int, dtype=jnp.float32):
        """The state of ``num_slots`` sequences: ``s`` the delta-rule
        matrices, always float32; ``conv`` the convolution's tail (the
        last ``taps - 1`` projected inputs) in ``dtype``."""
        h, d = self.num_heads, self.head_dim
        return {"s": jnp.zeros((num_slots, h, d, d), _F32),
                "conv": jnp.zeros((num_slots, self.taps - 1, 3 * h * d),
                                  dtype)}

    def _gates(self, params, x):
        """(g (B, S, H, D) log-decay, beta (B, S, H), out gate (B, S, H))."""
        b, s, _ = x.shape
        h, d = self.num_heads, self.head_dim
        # the three gate projections come out in float32 (the matmul
        # accumulates there anyway): exp(A_log) multiplies whatever
        # rounding Wf x carries by up to 16 before the sigmoid, and the
        # decay it sets is applied to the state at every token
        def proj(w):
            return jnp.dot(x, jnp.asarray(w).T, preferred_element_type=_F32)

        f = proj(params["wf"]) + params["dt_bias"].astype(_F32)
        rate = jnp.exp(params["A_log"].astype(_F32))[:, None]
        g = self.decay_floor * jax.nn.sigmoid(rate * f.reshape(b, s, h, d))
        beta = jax.nn.sigmoid(proj(params["wb"]))
        gate = jax.nn.sigmoid(proj(params["wg"]))
        return g, beta, gate

    def apply_slots(self, params, x, st, pos, active, lengths=None):
        """``x`` (B, S, E) at positions ``[pos_b, pos_b + S)`` against the
        rows' states ``st`` (``init_slot_state``'s tree, B rows).  A row at
        position 0 starts from the zero state whatever ``st`` holds (the
        slot's last tenant); tokens at or past ``lengths_b`` (right
        padding of a prefill bucket) leave the state alone; an inactive
        row's state comes back bit for bit.  ``S == 1`` takes the one-token
        recurrence, longer inputs the chunked one.  Returns (y, st')."""
        b, s, _ = x.shape
        h, d, taps = self.num_heads, self.head_dim, self.taps
        fresh = jnp.asarray(pos) == 0
        s0 = jnp.where(fresh[:, None, None, None], 0.0, st["s"])
        tail = jnp.where(fresh[:, None, None], 0, st["conv"])
        n = jnp.full((b,), s, jnp.int32) if lengths is None \
            else jnp.asarray(lengths, jnp.int32)
        with jax.named_scope("conv"):
            u = quant.matmul_or_observe(x, params["wqkv"])     # (B, S, 3HD)
            seq = jnp.concatenate([tail.astype(u.dtype), u], axis=1)
            cw = params["conv"].astype(_F32)
            y = sum(seq[:, j:j + s].astype(_F32) * cw[j]
                    for j in range(taps))
            y = jax.nn.silu(y).reshape(b, s, 3, h, d)
            q = _l2norm(y[:, :, 0]) * d ** -0.5
            k = _l2norm(y[:, :, 1])
            v = y[:, :, 2]
            # the last taps-1 REAL inputs: rows n-(taps-1) .. n-1 of u,
            # which sit taps-1 later in seq
            idx = n[:, None] + jnp.arange(taps - 1)[None]
            new_tail = jnp.take_along_axis(seq, idx[..., None], axis=1)
        with jax.named_scope("gates"):
            g, beta, gate = self._gates(params, x)
            real = (jnp.arange(s)[None] < n[:, None])           # (B, S)
            g = jnp.where(real[..., None, None], g, 0.0)
            beta = jnp.where(real[..., None], beta, 0.0)
        with jax.named_scope("state"):
            if s == 1:
                o, s1 = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                 beta[:, 0], s0)
                o = o[:, None]
            else:
                o, s1 = kda_chunked(q, k, v, g, beta, s0)
            keep = jnp.asarray(active)
            s1 = jnp.where(keep[:, None, None, None], s1, st["s"])
            new_tail = jnp.where(keep[:, None, None],
                                 new_tail.astype(st["conv"].dtype),
                                 st["conv"])
        # per-head RMS norm of the output, the head-wise gate, Wo
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + self.eps)
        o = o * params["o_norm"]["weight"].astype(_F32) * gate[..., None]
        out = quant.matmul_or_observe(
            o.reshape(b, s, h * d).astype(x.dtype), params["wo"])
        return out, {"s": s1, "conv": new_tail}

    def apply(self, params, state, input, *, training=False, rng=None):
        """A whole sequence from the zero state."""
        b = input.shape[0]
        y, _ = self.apply_slots(
            params, input, self.init_slot_state(b, input.dtype),
            jnp.zeros((b,), jnp.int32), jnp.ones((b,), bool))
        return y, state
