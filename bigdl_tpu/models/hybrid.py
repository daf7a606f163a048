"""A decoder-only language model built from a LAYER PATTERN: each layer
names its sequence mixer and its feed-forward part, or ONE of the two
(``(mixer, None)``, ``(None, ffn)``: a block that is a mixer or a
feed-forward part alone, ``x + part(rmsnorm(x))`` with one norm), and the
model's serving state follows from the mixers it holds.

* mixers: ``"kda"`` (:class:`~bigdl_tpu.nn.DeltaAttention`: delta-rule
  linear attention, a fixed float32 state per sequence), ``"mla"``
  (:class:`~bigdl_tpu.nn.LatentAttention`: softmax attention over a paged
  pool of latents), and ``"swa"`` and ``"full"``
  (:class:`~bigdl_tpu.nn.GroupedQueryAttention` with and without a
  window: a ring of ``window`` keys and values per sequence, or pages of
  them; the window layers carry the rope and the full layers none; the
  RMS norm over each head's query and key channels is ``qk_norm``, which a
  pattern whose state-space layers carry the order switches off: its one
  kind of attention layer, ``"full"``, then has NO rope and NO head norm),
  ``"mamba2"`` (:class:`~bigdl_tpu.nn.Mamba2Mixer`: the Mamba-2
  state-space recurrence, a float32 state ``(H, P, N)`` and a convolution
  tail per sequence), and ``"dsa"``
  (:class:`~bigdl_tpu.nn.SparseGroupedQueryAttention`: roped, head-normed
  GQA whose every query reads only the ``topk`` keys a lightning indexer
  picks, pages of K, V and the indexer's keys);
* feed-forward parts: ``"dense"`` (:class:`~bigdl_tpu.nn.GatedMLP`),
  ``"experts"`` (``router`` ``"sigmoid"``: group-limited routing over ALL
  the layer's experts, or ``"softmax"``: the top ``experts_per_token`` of
  a float32 softmax over all of them, gates renormalised over those; the
  grouped product over the ``experts_held`` this chip holds from
  ``expert_offset`` on, plus a shared expert added once where
  ``shared_expert``) and
  ``"latent_experts"`` (the same routing on the hidden state, but the
  routed experts work in a LATENT of ``latent_size``: one projection
  down in front of them and one back up behind their gated sum, both
  linear, so that the chips' partial sums still add up; the shared expert
  stays on the full hidden size, at its own width ``shared_dim``); an
  expert of such a part, routed or shared, is ``expert_act``: ``"swiglu"``
  (``silu(x Wg) * (x Wu)`` through ``Wd``, what an ``"experts"`` part's
  always are) or ``"relu2"`` (not gated: ``relu(x Wu)^2`` through ``Wd``);
* RMSNorm before each part, no biases, an untied head, no position
  table; ``vocab_size`` is the number of embedding and head rows HELD
  (a chip of a vocabulary-parallel deployment holds a slice).

It speaks ``ContinuousGenerator``'s interface (``init``, ``max_len``,
``vocab_size``, ``init_paged_cache``, ``decode_pages``) and declares what
the generator cannot see from outside: ``recurrent_state`` (state is
kept per SLOT, beside or instead of pages, and a prefill starts at 0) and
``decode_counters`` (small integers ``decode_pages`` returns beside the
cache, and how a chunk of steps reduces each).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

import bigdl_tpu.nn as nn
from bigdl_tpu.core.module import Module, child_rng
from bigdl_tpu.models.transformer import _embed_rows
from bigdl_tpu.nn.attention import _proj
from bigdl_tpu.parallel.expert import (EXPERT_FORMS, held_experts_apply,
                                       sigmoid_group_route, streams_hit,
                                       topk_route)

_F32 = jnp.float32
MIXERS = ("kda", "mla", "swa", "full", "mamba2", "dsa")
PAGED = ("mla", "full", "dsa")  # mixers whose cache is pages; others' a slot's
ROUTERS = ("sigmoid", "softmax")
ROUTER_GAIN = 4.0           # standard deviation of a fresh router's logits
EXPERT_GAIN = 0.1           # a routed expert's output, of unit gain
FFNS = ("dense", "experts", "latent_experts")


class HybridLM(Module):
    """See the module's docstring.  ``layers`` is the pattern, one
    ``(mixer, ffn)`` pair a layer, either of them None in a layer that is
    the other part alone; every width is an argument, so that a
    configuration file states the published ones."""

    #: how a chunk of decode steps reduces what ``decode_pages`` counts
    decode_counters = {"expert_pairs": "sum", "experts_hit": "sum",
                       "expert_pairs_max": "max"}

    def __init__(self, vocab_size: int, max_len: int = 4096,
                 embed_dim: int = 2560, num_heads: int = 32,
                 num_layers: int = 2,
                 layers: Sequence[Sequence[str]] = (("kda", "dense"),
                                                    ("mla", "experts")),
                 head_dim: int = 128, ffn_dim: int = 6144,
                 expert_dim: int = 768, num_experts: int = 512,
                 experts_per_token: int = 8, n_group: int = 8,
                 topk_group: int = 4, routed_scale: float = 2.5,
                 experts_held: Optional[int] = None, expert_offset: int = 0,
                 latent_dim: int = 512, rope_dim: int = 64,
                 nope_dim: int = 128, v_dim: int = 128,
                 rope_theta: float = 10000.0, conv_taps: int = 4,
                 decay_floor: float = -5.0, norm_eps: float = 1e-6,
                 num_kv_heads: Optional[int] = None,
                 window: Optional[int] = None,
                 router_gain: float = ROUTER_GAIN,
                 expert_gain: float = EXPERT_GAIN, qk_norm: bool = True,
                 ssm_heads: int = 128, ssm_head_dim: int = 64,
                 ssm_state: int = 128, ssm_groups: int = 8,
                 ssm_chunk: int = 128, latent_size: Optional[int] = None,
                 shared_dim: Optional[int] = None,
                 expert_act: str = "swiglu", router: str = "sigmoid",
                 shared_expert: bool = True, index_heads: int = 16,
                 index_dim: int = 64, index_rope_dim: int = 32,
                 topk: int = 2048, index_block: int = 512):
        super().__init__()
        layers = [tuple(l) for l in layers]
        if len(layers) != num_layers:
            raise ValueError(f"num_layers {num_layers} but the pattern "
                             f"holds {len(layers)} layers")
        for mixer, ffn in layers:
            if (mixer, ffn) == (None, None) \
                    or mixer not in MIXERS + (None,) \
                    or ffn not in FFNS + (None,):
                raise ValueError(f"layer ({mixer!r}, {ffn!r}): mixers are "
                                 f"{MIXERS}, feed-forward parts {FFNS}, "
                                 f"one of the two may be None")
        if expert_act not in EXPERT_FORMS:
            raise ValueError(f"expert_act {expert_act!r}: {EXPERT_FORMS}")
        if router not in ROUTERS:
            raise ValueError(f"router {router!r}: {ROUTERS}")
        if not latent_size and any(f == "latent_experts" for _, f in layers):
            raise ValueError("a pattern with 'latent_experts' layers states "
                             "its latent_size")
        self.vocab_size = vocab_size
        self.max_len = max_len
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.num_layers = num_layers
        self.layers = layers
        self.expert_dim = expert_dim
        self.num_experts = num_experts
        self.experts_per_token = experts_per_token
        self.n_group = n_group
        self.topk_group = topk_group
        self.routed_scale = float(routed_scale)
        self.experts_held = num_experts if experts_held is None \
            else int(experts_held)
        self.expert_offset = int(expert_offset)
        self.router_gain, self.expert_gain = router_gain, expert_gain
        self.latent_size = latent_size
        self.shared_dim = shared_dim or expert_dim
        self.expert_act = expert_act
        self.router = router
        self.shared_expert = bool(shared_expert)
        if any(m == "dsa" for m, _ in layers):
            # what a decode step's sparse layers read: the keys each row
            # scored and the keys it then attended over
            self.decode_counters = dict(HybridLM.decode_counters,
                                        keys_indexed="sum",
                                        keys_selected="sum")
        self.topk = int(topk)
        if not 0 <= self.expert_offset \
                <= num_experts - self.experts_held:
            raise ValueError(
                f"experts [{self.expert_offset}, {self.expert_offset} + "
                f"{self.experts_held}) are not a share of {num_experts}")
        #: a window layer's keys a sequence (declared: the generator's
        #: decode spans then count what each kind of layer read)
        self.window = window
        self.norm = nn.RMSNorm(embed_dim, norm_eps)
        if not window and any(m == "swa" for m, _ in layers):
            raise ValueError("a pattern with 'swa' layers states its window")

        def mixer(kind):
            if kind is None:
                return None
            if kind == "mamba2":
                return nn.Mamba2Mixer(embed_dim, ssm_heads, ssm_head_dim,
                                      ssm_state, ssm_groups, conv_taps,
                                      ssm_chunk, norm_eps)
            if kind == "kda":
                return nn.DeltaAttention(embed_dim, num_heads, head_dim,
                                         conv_taps, decay_floor, norm_eps)
            if kind == "mla":
                return nn.LatentAttention(embed_dim, num_heads, latent_dim,
                                          rope_dim, nope_dim, v_dim,
                                          rope_theta, norm_eps)
            if kind == "dsa":
                return nn.SparseGroupedQueryAttention(
                    embed_dim, num_heads, num_kv_heads or num_heads,
                    head_dim, rope_theta, norm_eps, index_heads, index_dim,
                    index_rope_dim, topk, index_block)
            return nn.GroupedQueryAttention(
                embed_dim, num_heads, num_kv_heads or num_heads, head_dim,
                window if kind == "swa" else None, kind == "swa",
                rope_theta, norm_eps, qk_norm)

        self.mixers = [mixer(m) for m, _ in layers]
        self.dense = nn.GatedMLP(embed_dim, ffn_dim)
        self.shared = nn.GatedMLP(embed_dim, self.shared_dim)

    #: what the generator is told, for EVERY pattern: the serving tree is
    #: ``{"pages", "slots"}``, addressed by slot beside the page table, and
    #: a prefill is the prompt whole from position 0.  A ``kda`` layer needs
    #: that for its state and a ``swa`` layer for its ring; an ``mla`` or a
    #: ``full`` layer because a longer input attends over its own tokens
    #: only (their contract), so a pattern of paged layers alone must not
    #: be handed a shared prefix or a verify pass either (its ``slots``
    #: entries are empty: 0 bytes).
    recurrent_state = True

    # -- parameters ------------------------------------------------------------

    def _init_experts(self, rng):
        kr, kg, kd, ks = jax.random.split(rng, 4)
        e, f, g = self.embed_dim, self.expert_dim, self.experts_held
        # Two scales are chosen, not unit gain (the configuration's
        # ``assumed.init`` says why at length).  The router's logits have
        # a standard deviation of ROUTER_GAIN: a router that has made up
        # its mind, whose best scores lie where bfloat16 has a hundred
        # values left and float32 millions.  A routed expert's output
        # projection is EXPERT_GAIN of unit gain, so that ONE expert
        # exchanged for its runner-up (which a hidden state rounded to
        # bfloat16 does to a token in ten a layer, in any implementation)
        # moves the logits less than the rounding itself does.
        out = {
            "router": jax.random.normal(kr, (self.num_experts, e))
            * self.router_gain * e ** -0.5,
            # (in, out) per expert, as the grouped product reads them;
            # gate and up side by side
            "experts": {
                "w_gate_up": jax.random.normal(kg, (g, e, 2 * f))
                * e ** -0.5,
                "w_down": jax.random.normal(kd, (g, f, e))
                * self.expert_gain * f ** -0.5}}
        if self.router == "sigmoid":
            out["bias"] = jnp.zeros((self.num_experts,), _F32)
        if self.shared_expert:
            out["shared"] = self.shared.init_params(ks)
        return out

    def _init_shared(self, rng):
        if self.expert_act == "swiglu":
            return self.shared.init_params(rng)
        ku, kd = jax.random.split(rng)
        e, f = self.embed_dim, self.shared_dim
        return {"w_up": jax.random.normal(ku, (f, e)) * e ** -0.5,
                "w_down": jax.random.normal(kd, (e, f)) * f ** -0.5}

    def _init_latent_experts(self, rng):
        """``_init_experts``'s tree and scales with the routed experts on
        the latent: ``latent_down`` and ``latent_up`` at unit gain around
        them, and their first matrix as wide as ``expert_act`` makes it."""
        kr, kg, kd, ks, kl, ku = jax.random.split(rng, 6)
        e, l, f, g = (self.embed_dim, self.latent_size, self.expert_dim,
                      self.experts_held)
        gated = self.expert_act == "swiglu"
        return {
            "router": jax.random.normal(kr, (self.num_experts, e))
            * self.router_gain * e ** -0.5,
            "bias": jnp.zeros((self.num_experts,), _F32),
            "latent_down": jax.random.normal(kl, (l, e)) * e ** -0.5,
            "latent_up": jax.random.normal(ku, (e, l)) * l ** -0.5,
            "experts": {
                ("w_gate_up" if gated else "w_up"): jax.random.normal(
                    kg, (g, l, 2 * f if gated else f)) * l ** -0.5,
                "w_down": jax.random.normal(kd, (g, f, l))
                * self.expert_gain * f ** -0.5},
            "shared": self._init_shared(ks),
        }

    def init_params(self, rng):
        e = self.embed_dim
        init_ffn = {"dense": self.dense.init_params,
                    "experts": self._init_experts,
                    "latent_experts": self._init_latent_experts}
        blocks = []
        for i, ((_, ffn), mixer) in enumerate(zip(self.layers,
                                                  self.mixers)):
            k = child_rng(rng, i)
            # a layer that is one part alone holds that part's norm alone
            block = {}
            if mixer is not None:
                block.update(norm1=self.norm.init_params(None),
                             mixer=mixer.init_params(child_rng(k, 0)))
            if ffn is not None:
                block.update(norm2=self.norm.init_params(None),
                             ffn=init_ffn[ffn](child_rng(k, 1)))
            blocks.append(block)
        kt, kh = jax.random.split(child_rng(rng, self.num_layers))
        return {"tok": jax.random.normal(kt, (self.vocab_size, e)),
                "blocks": blocks,
                "norm_f": self.norm.init_params(None),
                "head": jax.random.normal(kh, (self.vocab_size, e))
                * e ** -0.5}

    # -- serving state ---------------------------------------------------------

    def init_paged_cache(self, num_pages: int, page_size: int,
                         dtype=jnp.float32, num_slots: int = 1):
        """``{"pages": [...], "slots": [...]}``, a list entry a layer:
        the pools of an ``mla`` or a ``full`` layer, ``(num_pages + 1,
        page_size, W)`` with the trash page last (``{}`` for a layer
        without one), and what a ``kda``, a ``mamba2`` or a ``swa`` layer
        keeps per slot, its state or its ring (likewise)."""
        return {
            "pages": [m.init_paged_cache(num_pages, page_size, dtype)
                      if kind in PAGED else {}
                      for (kind, _), m in zip(self.layers, self.mixers)],
            "slots": [m.init_slot_state(num_slots, dtype)
                      if m is not None and kind not in PAGED else {}
                      for (kind, _), m in zip(self.layers, self.mixers)]}

    def state_bytes(self, cache) -> dict:
        """Bytes of ONE page and of ONE slot's state in ``cache``, by kind
        of mixer: ``{"page": {"full": ...}, "slot": {"swa": ...}}``."""
        out = {"page": {}, "slot": {}}
        for name, entries in (("page", cache["pages"]),
                              ("slot", cache["slots"])):
            for (kind, _), entry in zip(self.layers, entries):
                n = sum(a.size // a.shape[0] * a.dtype.itemsize
                        for a in jax.tree_util.tree_leaves(entry))
                if n:
                    out[name][kind] = out[name].get(kind, 0) + n
        return out

    @property
    def held_experts_total(self) -> int:
        """Held experts over all the expert layers: what a decode step
        reads in the dense form."""
        return self.experts_held * sum(
            f in ("experts", "latent_experts") for _, f in self.layers)

    def streams_hit_experts(self, params, rows: int) -> bool:
        """Whether a decode step of ``rows`` rows reads, in every expert
        layer, only the held experts its rows hit (``held_experts_apply``'s
        hit form, chosen by the shapes alone): what the step compiles to,
        so that the generator can tell what share of the held experts'
        weights a step streams (``experts_hit`` counts them)."""
        layers = [b["ffn"]["experts"] for (_, f), b
                  in zip(self.layers, params["blocks"])
                  if f in ("experts", "latent_experts")]
        return bool(layers) and all(
            streams_hit(rows, self.experts_per_token, self.num_experts,
                        w.get("w_gate_up", w.get("w_up")), w["w_down"])
            for w in layers)

    # -- the layers --------------------------------------------------------------

    def _route(self, p, x):
        """(ids, gates) (T, k) of ``x`` (T, E) over ALL the layer's
        experts: router product, scores and gates in float32."""
        with jax.default_matmul_precision("highest"):
            logits = x.astype(_F32) @ p["router"].astype(_F32).T
        if self.router == "softmax":
            ids, gates = topk_route(logits, self.experts_per_token)
            return ids.astype(jnp.int32), gates * self.routed_scale
        scores = jax.nn.sigmoid(logits)
        return sigmoid_group_route(
            scores, p["bias"].astype(_F32), self.experts_per_token,
            self.n_group, self.topk_group, self.routed_scale)

    def _experts(self, p, x, valid):
        """The expert layer on ``x`` (T, E): (y, counters)."""
        with jax.named_scope("router"):
            ids, gates = self._route(p, x)
        with jax.named_scope("experts"):
            y, counters = held_experts_apply(
                x, ids, gates, valid, p["experts"]["w_gate_up"],
                p["experts"]["w_down"], self.expert_offset,
                n_experts=self.num_experts)
        if self.shared_expert:
            with jax.named_scope("shared"):
                y = y + self.shared.apply(p["shared"], {}, x)[0]
        return y, counters

    def _shared(self, p, x):
        if self.expert_act == "swiglu":
            return self.shared.apply(p, {}, x)[0]
        return _proj(jnp.square(jax.nn.relu(_proj(x, p["w_up"]))),
                     p["w_down"])

    def _latent_experts(self, p, x, valid):
        """The expert layer whose routed experts work in the latent, on
        ``x`` (T, E): (y, counters).  The projection back up is applied to
        THIS chip's partial sum: it is linear, so the chips' shares still
        add up to the uncut layer's."""
        with jax.named_scope("router"):
            ids, gates = self._route(p, x)
        with jax.named_scope("latent.down"):
            lat = _proj(x, p["latent_down"])
        with jax.named_scope("experts"):
            w = p["experts"]
            y, counters = held_experts_apply(
                lat, ids, gates, valid,
                w["w_gate_up" if self.expert_act == "swiglu" else "w_up"],
                w["w_down"], self.expert_offset, self.expert_act,
                n_experts=self.num_experts)
        with jax.named_scope("latent.up"):
            y = _proj(y, p["latent_up"])
        with jax.named_scope("shared"):
            y = y + self._shared(p["shared"], x)
        return y, counters

    def _forward(self, params, ids, cache, pages, pos, active, slots,
                 lengths):
        b, s = ids.shape
        with jax.named_scope("embed"):
            x = _embed_rows(params["tok"], ids)
        n = jnp.full((b,), s, jnp.int32) if lengths is None \
            else jnp.asarray(lengths, jnp.int32)
        valid = (jnp.asarray(active)[:, None]
                 & (jnp.arange(s)[None] < n[:, None])).reshape(-1)
        zero = jnp.zeros((), jnp.int32)
        counts = {"expert_pairs": zero, "experts_hit": zero,
                  "expert_pairs_max": zero}
        sparse = sum(m == "dsa" for m, _ in self.layers)
        if sparse:
            # a token at position p scores the p + 1 keys up to it and
            # attends over min(p + 1, topk) of them, in every sparse layer
            seen = (jnp.asarray(pos, jnp.int32)[:, None]
                    + jnp.arange(s, dtype=jnp.int32) + 1).reshape(-1)
            counts.update(
                keys_indexed=sparse * jnp.sum(jnp.where(valid, seen, 0)),
                keys_selected=sparse * jnp.sum(jnp.where(
                    valid, jnp.minimum(seen, self.topk), 0)))
        new_pages, new_slots = list(cache["pages"]), list(cache["slots"])
        for i, ((kind, ffn), mixer) in enumerate(zip(self.layers,
                                                     self.mixers)):
            p = params["blocks"][i]
            with jax.named_scope(f"block_{i}"):
                if kind is not None:
                    h = self.norm.apply(p["norm1"], {}, x)[0]
                    with jax.named_scope(kind):
                        if kind in PAGED:
                            y, new_pages[i] = mixer.apply_decode_pages(
                                p["mixer"], h, cache["pages"][i], pages,
                                pos, active)
                        else:
                            st = cache["slots"][i]
                            rows = st if slots is None else \
                                jax.tree_util.tree_map(
                                    lambda a: a[slots], st)
                            y, rows = mixer.apply_slots(
                                p["mixer"], h, rows, pos, active, lengths)
                            new_slots[i] = rows if slots is None else \
                                jax.tree_util.tree_map(
                                    lambda a, r: a.at[slots].set(r), st,
                                    rows)
                    x = x + y
                if ffn is None:
                    continue
                h = self.norm.apply(p["norm2"], {}, x)[0]
                if ffn == "dense":
                    with jax.named_scope("mlp"):
                        x = x + self.dense.apply(p["ffn"], {}, h)[0]
                else:
                    with jax.named_scope("moe"):
                        y, c = (self._latent_experts
                                if ffn == "latent_experts"
                                else self._experts)(
                            p["ffn"], h.reshape(b * s, -1), valid)
                        x = x + y.reshape(b, s, -1)
                    counts.update(
                        expert_pairs=counts["expert_pairs"] + c["pairs"],
                        experts_hit=counts["experts_hit"] + c["hit"],
                        expert_pairs_max=jnp.maximum(
                            counts["expert_pairs_max"], c["max"]))
        with jax.named_scope("logits"):
            if lengths is not None:
                # a prefill wants the logits after its last real token
                x = jnp.take_along_axis(x, (n - 1)[:, None, None], axis=1)
            x = self.norm.apply(params["norm_f"], {}, x)[0]
            # the product accumulates in float32 anyway: rounding the
            # logits to the weights' dtype first would put the largest of
            # a position on a grid of 0.016 of their standard deviation
            logp = jax.nn.log_softmax(
                jnp.dot(x, jnp.asarray(params["head"]).T,
                        preferred_element_type=_F32), axis=-1)
        return logp, {"pages": new_pages, "slots": new_slots}, counts

    def decode_pages(self, params, state, tokens, cache, pages, pos, active,
                     slots=None, lengths=None):
        """``TransformerLM.decode_pages``'s contract with two additions a
        model with per-slot state needs.  ``slots`` (B,) int32 names the
        slot of each row (None: row ``b`` is slot ``b``, the decode
        chunk's layout); ``lengths`` (B,) the real tokens of each row of
        a right-padded prefill: tokens past them touch neither the
        recurrent state nor the counters, and the log-probs come back
        for the last real token only, ``(B, 1, vocab)``.  An input longer
        than one token is a prefill from position 0 (``LatentAttention``'s
        contract).  Returns (log-probs, cache', counters): the counters
        are ``decode_counters``'s keys, int32 scalars, the expert layers'
        ``expert_pairs`` and ``experts_hit`` summed and
        ``expert_pairs_max`` their largest; with ``"dsa"`` layers also
        ``keys_indexed`` and ``keys_selected`` over the valid tokens."""
        del state
        ids = jnp.asarray(tokens, jnp.int32) - 1
        return self._forward(params, ids, cache, pages, pos, active, slots,
                             lengths)

    def apply(self, params, state, input, *, training=False, rng=None):
        """Log-probs (B, T, vocab) of whole sequences from empty state."""
        ids = jnp.asarray(input, jnp.int32) - 1
        b = ids.shape[0]
        cache = self.init_paged_cache(0, 1, params["tok"].dtype, b)
        logp, _, _ = self._forward(
            params, ids, cache, jnp.zeros((b, 1), jnp.int32),
            jnp.zeros((b,), jnp.int32), jnp.ones((b,), bool), None, None)
        return logp, state
