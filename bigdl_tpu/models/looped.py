"""A looped decoder-only language model (Ouro's "LoopLM", *Scaling Latent
Reasoning via Looped Language Models*, 2025): ONE stack of ``num_layers``
layers applied ``loops`` times to every token, all passes with the same
weights, each pass ended by the final RMS norm and the next pass started
from it; the head reads the last pass.

A layer is sandwich-normed, four RMS norms and no biases::

    x = x + norm_in2(attn(norm_in(x)))
    x = x + norm_post2(mlp(norm_post(x)))

``attn`` is :class:`~bigdl_tpu.nn.GroupedQueryAttention` without a window
and without a head norm, roped (half-split pairs) at the token's position,
the same position in every pass; ``mlp`` is :class:`~bigdl_tpu.nn.GatedMLP`.
Pass ``t``'s layer ``l`` attends over the keys and values that pass ``t``
and layer ``l`` wrote for the earlier tokens: the cache is kept by (pass,
layer), ``loops x num_layers`` page pools for ``num_layers`` layers'
weights, pool ``t x num_layers + l`` (the index of the public modelling
code's cache).

The program applies ONE compiled layer body: the layers' weights are
stacked on a leading axis and scanned, and the passes are a loop around
the scan.  The pools are one page-major stack ``(P + 1, loops x
num_layers, ps, W)`` carried through both loops, so a page's bytes across
all the pools are one row of it (what the generator counts a page by), and
each layer writes and reads its own pool of the stack in place
(``ops.attention.stacked_pool``): no pool is sliced out of it.

It speaks ``ContinuousGenerator``'s interface (``init``, ``max_len``,
``vocab_size``, ``init_paged_cache``, ``decode_pages``) and declares
``recurrent_state`` and ``decode_counters`` as ``HybridLM`` does.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

import bigdl_tpu.nn as nn
from bigdl_tpu.core.module import Module, child_rng
from bigdl_tpu.models.transformer import _embed_rows

_F32 = jnp.float32
NORMS = ("norm_in", "norm_in2", "norm_post", "norm_post2")


class LoopedLM(Module):
    """See the module's docstring.  Every width is an argument, so that a
    configuration file states the published ones; ``vocab_size`` is the
    number of embedding and head rows."""

    #: passes run, summed over the active row-steps of a call (a decode
    #: chunk sums its steps): ``loops`` times the row-steps
    decode_counters = {"loop_passes": "sum"}

    #: ``HybridLM``'s contract: the serving tree is ``{"pages", "slots"}``
    #: and a prefill is the prompt whole from position 0.  A looped layer
    #: attends over its own tokens in a prefill, so no shared prefix and no
    #: verify pass may be handed to it (``slots`` is empty: 0 bytes)
    recurrent_state = True

    def __init__(self, vocab_size: int, max_len: int = 4096,
                 embed_dim: int = 2048, num_heads: int = 16,
                 num_kv_heads: Optional[int] = None, head_dim: int = 128,
                 num_layers: int = 48, loops: int = 4, ffn_dim: int = 5632,
                 rope_theta: float = 1e6, norm_eps: float = 1e-6,
                 sandwich_gain: float = 1.0, outlier_gain: float = 0.0):
        super().__init__()
        if loops < 1:
            raise ValueError(f"loops must be >= 1, got {loops}")
        self.vocab_size = vocab_size
        self.max_len = max_len
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.num_layers = num_layers
        self.loops = int(loops)
        self.sandwich_gain = float(sandwich_gain)
        self.outlier_gain = float(outlier_gain)
        self.norm = nn.RMSNorm(embed_dim, norm_eps)
        self.attn = nn.GroupedQueryAttention(
            embed_dim, num_heads, num_kv_heads or num_heads, head_dim,
            None, True, rope_theta, norm_eps, False)
        self.mlp = nn.GatedMLP(embed_dim, ffn_dim)

    # -- parameters ------------------------------------------------------------

    def _outliers(self, w, rng):
        """``w`` (out, in) with ONE entry a row, at a random column, set to
        ``+-outlier_gain`` times the row's scale ``in ** -0.5``."""
        if not self.outlier_gain:
            return w
        kc, ks = jax.random.split(rng)
        col = jax.random.randint(kc, w.shape[:1], 0, w.shape[1])
        big = jnp.where(jax.random.bernoulli(ks, 0.5, w.shape[:1]), 1.0,
                        -1.0) * self.outlier_gain * w.shape[1] ** -0.5
        return jnp.where(jnp.arange(w.shape[1])[None] == col[:, None],
                         big[:, None], w)

    def _init_layer(self, rng):
        # Two scales are chosen where the random weights meet the
        # comparison with the reference (the configuration's
        # ``assumed.init`` says why): what a half adds to the residual
        # enters at ``sandwich_gain`` (its outer norm's weight), and a
        # layer's matrices carry one outlier a row, as trained weights do,
        # so that a row's int8 step is set by it
        layer = {k: self.norm.init_params(None) for k in NORMS}
        for k in ("norm_in2", "norm_post2"):
            layer[k] = {"weight": layer[k]["weight"] * self.sandwich_gain}
        parts = {"attn": self.attn.init_params(child_rng(rng, 0)),
                 "mlp": self.mlp.init_params(child_rng(rng, 1))}
        for i, (name, part) in enumerate(sorted(parts.items())):
            layer[name] = {k: self._outliers(w, child_rng(rng, 2 + 8 * i + j))
                           for j, (k, w) in enumerate(sorted(part.items()))}
        return layer

    def init_params(self, rng):
        keys = jax.vmap(lambda i: child_rng(rng, i))(
            jnp.arange(self.num_layers))
        kt, kh = jax.random.split(child_rng(rng, self.num_layers))
        e = self.embed_dim
        return {"tok": jax.random.normal(kt, (self.vocab_size, e)),
                # every layer's weights on a leading axis of num_layers
                "blocks": jax.vmap(self._init_layer)(keys),
                "norm_f": self.norm.init_params(None),
                "head": jax.random.normal(kh, (self.vocab_size, e))
                * e ** -0.5}

    # -- serving state ---------------------------------------------------------

    def init_paged_cache(self, num_pages: int, page_size: int,
                         dtype=jnp.float32, num_slots: int = 1):
        """``{"pages": {"k", "v"}, "slots": {}}``: K and V of every (pass,
        layer) pool in one page-major stack ``(num_pages + 1, loops x
        num_layers, page_size, W)``, the trash page last; nothing a
        slot."""
        del num_slots
        one = self.attn.init_paged_cache(num_pages, page_size, dtype)
        pools = self.loops * self.num_layers
        return {"pages": {k: jnp.zeros((a.shape[0], pools) + a.shape[1:],
                                       a.dtype) for k, a in one.items()},
                "slots": {}}

    # -- the layers --------------------------------------------------------------

    def _pool(self, t, l):
        """The pool of pass ``t``'s layer ``l`` in the stack."""
        return t * self.num_layers + l

    def _sandwich(self, p, y):
        """A half's outer norm, on what the half adds to the residual."""
        return self.norm.apply(p, {}, y)[0]

    def _pass_end(self, params, x):
        """The final norm, at the end of every pass."""
        return self.norm.apply(params["norm_f"], {}, x)[0]

    def _block(self, p, x, kv, pool, pages, pos, active):
        with jax.named_scope("norm_in"):
            h = self.norm.apply(p["norm_in"], {}, x)[0]
        with jax.named_scope("attn"):
            y, kv = self.attn.apply_decode_pages(p["attn"], h, kv, pages,
                                                 pos, active, pool=pool)
        with jax.named_scope("norm_in"):
            x = x + self._sandwich(p["norm_in2"], y)
        with jax.named_scope("norm_post"):
            h = self.norm.apply(p["norm_post"], {}, x)[0]
        with jax.named_scope("mlp"):
            y = self.mlp.apply(p["mlp"], {}, h)[0]
        with jax.named_scope("norm_post"):
            x = x + self._sandwich(p["norm_post2"], y)
        return x, kv

    def _forward(self, params, ids, cache, pages, pos, active, lengths):
        b, s = ids.shape
        with jax.named_scope("embed"):
            x = _embed_rows(params["tok"], ids)
        pages = jnp.asarray(pages, jnp.int32)
        pos = jnp.asarray(pos, jnp.int32)
        active = jnp.asarray(active)
        rows = jnp.sum(active, dtype=jnp.int32)
        layer_ids = jnp.arange(self.num_layers, dtype=jnp.int32)

        def one_pass(t, carry):
            x, kv, passes = carry

            def layer(c, xs):
                p, l = xs
                with jax.named_scope("block"):
                    return self._block(p, *c, self._pool(t, l), pages, pos,
                                       active), None

            with jax.named_scope("pass"):
                (x, kv), _ = jax.lax.scan(layer, (x, kv),
                                          (params["blocks"], layer_ids))
                with jax.named_scope("norm_f"):
                    x = self._pass_end(params, x)
            return x, kv, passes + rows

        x, kv, passes = jax.lax.fori_loop(
            0, self.loops, one_pass,
            (x, cache["pages"], jnp.zeros((), jnp.int32)))
        with jax.named_scope("logits"):
            if lengths is not None:
                # a prefill wants the logits after its last real token
                n = jnp.asarray(lengths, jnp.int32)
                x = jnp.take_along_axis(x, (n - 1)[:, None, None], axis=1)
            # float32 log-probs from the bf16 product, as HybridLM's head
            logp = jax.nn.log_softmax(
                jnp.dot(x, jnp.asarray(params["head"]).T,
                        preferred_element_type=_F32), axis=-1)
        return logp, {"pages": kv, "slots": cache["slots"]}, \
            {"loop_passes": passes}

    def decode_pages(self, params, state, tokens, cache, pages, pos, active,
                     slots=None, lengths=None):
        """``HybridLM.decode_pages``'s contract: ``tokens`` (B, S) 1-based
        at positions ``[pos_b, pos_b + S)``; an input longer than one token
        is a prefill from position 0, right-padded, whose log-probs come
        back for the last of its ``lengths`` real tokens alone (B, 1,
        vocab); ``slots`` names rows' slots, and there is no per-slot
        state to address.  Returns (log-probs, cache', counters)."""
        del state, slots
        ids = jnp.asarray(tokens, jnp.int32) - 1
        return self._forward(params, ids, cache, pages, pos, active, lengths)

    def apply(self, params, state, input, *, training=False, rng=None):
        """Log-probs (B, T, vocab) of whole sequences from position 0."""
        ids = jnp.asarray(input, jnp.int32) - 1
        b = ids.shape[0]
        cache = self.init_paged_cache(0, 1, params["tok"].dtype, b)
        logp, _, _ = self._forward(
            params, ids, cache, jnp.zeros((b, 1), jnp.int32),
            jnp.zeros((b,), jnp.int32), jnp.ones((b,), bool), None)
        return logp, state
