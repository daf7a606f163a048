"""Decoder-only transformer language model — the long-context flagship.

No reference analogue: BigDL of this vintage has no attention at all
(SURVEY.md §5.7; its sequence model is ``Recurrent``+``RnnCell``).  This
family is the TPU-native extension that exercises the framework's
long-context machinery end to end:

* ``nn.MultiHeadAttention`` blocks — locally fused on one chip, or
  sequence-parallel by injecting ``ring_attention``/``ulysses_attention``
  (``sequence_parallel=...``);
* pre-LayerNorm residual blocks (the trainable-at-depth layout);
* optional mixture-of-experts FFN (``moe_every``) wired to
  ``nn.MixtureOfExperts`` — expert-parallel under an "expert" mesh axis;
* weight-tied embedding/output head, learned positions;
* optional per-block gradient rematerialisation (``remat=True``) —
  ``jax.checkpoint`` around each residual block trades FLOPs for HBM so
  activation memory scales with one block instead of ``num_layers``
  (the standard long-context/deep-stack memory lever on TPU).

Built entirely from the module protocol, so it composes with every
trainer (Local/Distri optimizers, mixed precision, sharded checkpoints).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

import bigdl_tpu.nn as nn
from bigdl_tpu.core.module import Module, child_rng
from bigdl_tpu.ops import quant


def _embed_rows(tok_p, ids):
    """Token embedding lookup, packed-rung-aware: a ``tok`` table packed
    by ``quant.quantize_params(..., extra_keys=("tok",))`` — int8, the
    r14 two-nibble int4, or scaled e4m3 — gathers packed rows + per-row
    scales (the (vocab, E) table, the dominant residual tenant of a
    quantized LM, stays packed in HBM at 1x/0.25x/0.5x int8's bytes)."""
    if quant.is_quantized(tok_p):
        return quant.int8_gather_rows(tok_p, ids)
    return jnp.asarray(tok_p)[ids]


def _tied_logits(x, tok_p):
    """Weight-tied output head, packed-rung-aware: the same per-row
    scales that dequantize the gather dequantize the logit matmul
    (axis 0 of the stored table is the vocab axis in both roles);
    ``quant.int8_matmul`` dispatches on the leaf kind (q8/q4/f8)."""
    if quant.is_quantized(tok_p):
        return quant.int8_matmul(x, tok_p)
    return x @ jnp.asarray(tok_p).T


class TransformerBlock(Module):
    """Pre-LN residual block: x + attn(ln(x)); x + ffn(ln(x))."""

    def __init__(self, embed_dim: int, num_heads: int, ffn_dim: int,
                 dropout: float = 0.0, causal: bool = True,
                 attention_fn=None, moe: Optional[nn.MixtureOfExperts] = None,
                 num_kv_heads: Optional[int] = None, rope: bool = False):
        super().__init__()
        self.ln1 = nn.LayerNorm(embed_dim)
        self.attn = nn.MultiHeadAttention(embed_dim, num_heads,
                                          causal=causal,
                                          attention_fn=attention_fn,
                                          num_kv_heads=num_kv_heads,
                                          rope=rope)
        self.ln2 = nn.LayerNorm(embed_dim)
        self.moe = moe
        if moe is None:
            self.fc1 = nn.Linear(embed_dim, ffn_dim)
            self.fc2 = nn.Linear(ffn_dim, embed_dim)
        self.dropout = nn.Dropout(dropout) if dropout > 0 else None

    def init(self, rng):
        ks = jax.random.split(rng, 6)
        parts = {"ln1": self.ln1.init(ks[0]),
                 "attn": self.attn.init(ks[1]),
                 "ln2": self.ln2.init(ks[2])}
        if self.moe is None:
            parts["fc1"] = self.fc1.init(ks[3])
            parts["fc2"] = self.fc2.init(ks[4])
        else:
            parts["moe"] = self.moe.init(ks[5])
        return ({k: v[0] for k, v in parts.items()},
                {k: v[1] for k, v in parts.items()})

    def apply(self, params, state, input, *, training=False, rng=None,
              pos_offset=0, key_padding_mask=None):
        with jax.named_scope("attn"):
            h, _ = self.ln1.apply(params["ln1"], state["ln1"], input)
            # training must reach the attention layer: it selects the
            # fwd+bwd kernel dispatch vs the measured fwd-only (eval)
            # policy
            a, _ = self.attn.apply(params["attn"], state["attn"], h,
                                   training=training,
                                   pos_offset=pos_offset,
                                   key_padding_mask=key_padding_mask)
            if self.dropout is not None and training:
                a, _ = self.dropout.apply((), (), a, training=True,
                                          rng=child_rng(rng, 0))
            x = input + a
        with jax.named_scope("mlp"):
            h, _ = self.ln2.apply(params["ln2"], state["ln2"], x)
            new_state = state
            if self.moe is None:
                h, _ = self.fc1.apply(params["fc1"], state["fc1"], h)
                h = jax.nn.gelu(h)
                h, _ = self.fc2.apply(params["fc2"], state["fc2"], h)
            else:
                h, moe_state = self.moe.apply(params["moe"], state["moe"],
                                              h, training=training)
                # thread the routing stats (aux load-balance loss, drop
                # rate) so trainers can collect them from the state tree
                new_state = dict(state)
                new_state["moe"] = moe_state
            if self.dropout is not None and training:
                h, _ = self.dropout.apply((), (), h, training=True,
                                          rng=child_rng(rng, 1))
            return x + h, new_state

    def _decode_block(self, params, state, x_t, attend):
        """What the two incremental variants share: ``attend(attn
        params, normed x)`` -> ``(attention output, cache')`` through
        whichever cache layout, then the FFN/MoE as in eval.  The
        ``attn`` and ``mlp`` scopes name the two halves in the device
        trace."""
        with jax.named_scope("attn"):
            h, _ = self.ln1.apply(params["ln1"], state["ln1"], x_t)
            a, cache = attend(params["attn"], h)
            x = x_t + a
        with jax.named_scope("mlp"):
            h, _ = self.ln2.apply(params["ln2"], state["ln2"], x)
            if self.moe is None:
                h, _ = self.fc1.apply(params["fc1"], state["fc1"], h)
                h = jax.nn.gelu(h)
                h, _ = self.fc2.apply(params["fc2"], state["fc2"], h)
            else:
                h, _ = self.moe.apply(params["moe"], state["moe"], h,
                                      training=False)
            return x + h, cache

    def decode_step(self, params, state, cache, x_t, pos):
        """Incremental block application for tokens at [pos, pos+S) —
        attention through the KV cache, FFN/MoE as in eval.  Returns
        (y (B, S, E), cache')."""
        return self._decode_block(
            params, state, x_t,
            lambda p, h: self.attn.apply_decode(p, h, cache, pos))

    def decode_step_pages(self, params, state, cache, x_t, pages, pos,
                          active):
        """Page-table :meth:`decode_step`: ``pos`` (B,) is each row's
        own depth, ``active`` (B,) gates its write, and the per-row
        cache is an indirection through ``pages`` (B, Lp) into a shared
        page pool — the per-decode-step unit of the continuous-batching
        scheduler (``serving/scheduler/continuous.py``)."""
        return self._decode_block(
            params, state, x_t,
            lambda p, h: self.attn.apply_decode_pages(p, h, cache, pages,
                                                      pos, active))


class TransformerLM(Module):
    """Token ids (B, T), 1-based -> logits (B, T, vocab) as log-softmax.

    ``sequence_parallel``: None for local attention, or an attention
    kernel like ``functools.partial(ring_attention, axis_name="seq")`` —
    apply the model inside ``shard_map`` with inputs sharded over that
    axis (see ``tests/test_transformer.py``).
    """

    def __init__(self, vocab_size: int, max_len: int = 512,
                 embed_dim: int = 256, num_heads: int = 4,
                 num_layers: int = 4, ffn_dim: Optional[int] = None,
                 dropout: float = 0.0, causal: bool = True,
                 sequence_parallel=None,
                 moe_experts: int = 0, moe_every: int = 2,
                 remat: bool = False,
                 num_kv_heads: Optional[int] = None,
                 position: str = "learned"):
        super().__init__()
        self.vocab_size = vocab_size
        self.max_len = max_len
        self.embed_dim = embed_dim
        ffn_dim = ffn_dim or 4 * embed_dim
        assert position in ("learned", "rope"), position
        self.position = position
        self.blocks = []
        for i in range(num_layers):
            moe = None
            if moe_experts and (i % moe_every == moe_every - 1):
                moe = nn.MixtureOfExperts(embed_dim, ffn_dim, moe_experts)
            self.blocks.append(TransformerBlock(
                embed_dim, num_heads, ffn_dim, dropout=dropout,
                causal=causal, attention_fn=sequence_parallel, moe=moe,
                num_kv_heads=num_kv_heads,
                rope=(position == "rope")))
        self.ln_f = nn.LayerNorm(embed_dim)
        self.remat = remat

    def init(self, rng):
        ks = jax.random.split(rng, len(self.blocks) + 3)
        scale = 1.0 / math.sqrt(self.embed_dim)
        params = {
            "tok": jax.random.normal(
                ks[0], (self.vocab_size, self.embed_dim)) * scale,
        }
        if self.position == "learned":
            params["pos"] = jax.random.normal(
                ks[1], (self.max_len, self.embed_dim)) * scale
        state = {}
        blocks_p, blocks_s = [], []
        for i, b in enumerate(self.blocks):
            p, s = b.init(ks[2 + i])
            blocks_p.append(p)
            blocks_s.append(s)
        params["blocks"] = blocks_p
        state["blocks"] = blocks_s
        params["ln_f"], state["ln_f"] = self.ln_f.init(ks[-1])
        return params, state

    def apply(self, params, state, input, *, training=False, rng=None,
              pos_offset=0, key_padding_mask=None):
        """``pos_offset``: global position of this shard's first token —
        pass ``axis_index * T_local`` under sequence parallelism so
        learned positions stay correct on sequence shards.

        ``key_padding_mask``: optional (B, T) boolean, True = real
        token — for batches padded to fixed length
        (``dataset/text.py``; ``Transformer.scala:77-241`` pads the
        same way).  Padded KEY positions are excluded from every
        attention row (streaming-kernel path, no (B,H,T,T) mask
        tensor); padded QUERY rows still emit (garbage) logits — mask
        them in the loss (``TimeDistributedCriterion`` supports
        per-token weights)."""
        ids = jnp.asarray(input, jnp.int32) - 1          # 1-based tokens
        b, t = ids.shape
        if self.position == "learned":
            assert jnp.ndim(pos_offset) == 0, \
                "per-token position vectors need position='rope'"
            if not isinstance(pos_offset, jax.core.Tracer):
                # static offsets are checkable; traced ones (axis_index
                # under shard_map) rely on the caller keeping global
                # T <= max_len — dynamic_slice would silently CLAMP an
                # overrun otherwise
                assert int(pos_offset) + t <= self.max_len, \
                    f"positions {pos_offset}+{t} exceed max_len " \
                    f"{self.max_len}"
            else:
                assert t <= self.max_len, \
                    f"shard length {t} exceeds max_len {self.max_len}"
            with jax.named_scope("embed"):
                x = _embed_rows(params["tok"], ids) + \
                    jax.lax.dynamic_slice_in_dim(
                        params["pos"], pos_offset, t, axis=0)[None]
        else:
            # rope: positions enter through the attention q/k rotation
            # (relative, unbounded — no table, no max_len constraint)
            with jax.named_scope("embed"):
                x = _embed_rows(params["tok"], ids)
        new_blocks = list(state["blocks"])
        for i, blk in enumerate(self.blocks):

            def block_call(p, s, xx, r, off, kpm, _blk=blk):
                return _blk.apply(p, s, xx, training=training, rng=r,
                                  pos_offset=off, key_padding_mask=kpm)

            if self.remat:
                # recompute this block's activations in the backward pass
                # instead of keeping them live across the whole stack
                block_call = jax.checkpoint(block_call)
            with jax.named_scope(f"block_{i}"):
                x, new_blocks[i] = block_call(
                    params["blocks"][i], state["blocks"][i], x,
                    child_rng(rng, i), pos_offset, key_padding_mask)
        new_state = dict(state)
        new_state["blocks"] = new_blocks
        return self._head(params, state, x), new_state

    def _embed(self, params, ids, pos):
        """Token rows of ``ids`` (B, S) plus, for learned positions, the
        table's rows at ``[pos_b, pos_b + S)`` per row, gathered CLIPPED:
        an out-of-table position (a
        right-pad garbage token, or a speculative verify row past a
        finishing slot's limit) must yield a garbage-but-FINITE
        embedding.  jnp.take's default out-of-bounds mode fills NaN, and
        a NaN hidden state written to the pool's trash page would poison
        every OTHER slot's attention through 0 * NaN in the masked
        softmax-weighted sum."""
        with jax.named_scope("embed"):
            x = _embed_rows(params["tok"], ids)
            if self.position == "learned":
                positions = jnp.asarray(pos)[:, None] \
                    + jnp.arange(ids.shape[1])
                x = x + jnp.take(jnp.asarray(params["pos"]), positions,
                                 axis=0, mode="clip")
            return x

    def _head(self, params, state, x):
        """Final norm, the weight-tied logits and their log-softmax."""
        with jax.named_scope("logits"):
            x, _ = self.ln_f.apply(params["ln_f"], state["ln_f"], x)
            return jax.nn.log_softmax(_tied_logits(x, params["tok"]),
                                      axis=-1)

    # -- autoregressive inference (KV cache) ----------------------------

    def init_cache(self, batch: int, max_len: Optional[int] = None,
                   dtype=jnp.float32):
        """Per-layer KV caches for ``decode``/``generate`` (GQA models
        cache only the KV heads)."""
        ml = max_len or self.max_len
        return [b.attn.init_cache(batch, ml, dtype) for b in self.blocks]

    def decode(self, params, state, tokens, cache, pos):
        """Incremental forward: ``tokens`` (B, S) 1-based ids at
        positions [pos, pos+S) against a cache holding [0, pos).
        Returns (log-probs (B, S, vocab), cache').  One call with
        S=prompt_len is the prefill; S=1 calls are generation steps.
        ``pos`` may be traced (it is the ``lax.scan`` carry in
        ``generate``), so the whole decode loop stays on device.

        CALLER-ENFORCED capacity bound: ``pos + S`` must not exceed the
        cache length (and, for ``position="learned"``, ``max_len``) —
        ``pos`` can be traced, so decode() cannot check it; an overrun
        dynamic_update_slice-CLAMPS into the last cache slot and
        silently corrupts it.  ``generate()`` raises ValueError up
        front for this; the continuous-batching slot manager
        (``serving/scheduler/continuous.py``) sheds an over-capacity
        admit with a typed ``SlotCapacityError`` for the same reason;
        any other direct caller must bound it themselves."""
        ids = jnp.asarray(tokens, jnp.int32) - 1
        b, s = ids.shape
        # snapshot-loaded params are host numpy arrays; _embed_rows
        # lifts the table so traced ids (the lax.scan carry in
        # generate) can index it — int8-packed tables gather + matmul
        # through their per-row scales
        with jax.named_scope("embed"):
            x = _embed_rows(params["tok"], ids)
            if self.position == "learned":
                # dynamic_slice CLAMPS an overrun silently; generate()
                # bounds pos statically, direct callers must too
                x = x + jax.lax.dynamic_slice_in_dim(
                    params["pos"], jnp.asarray(pos), s, axis=0)[None]
        new_cache = list(cache)
        for i, blk in enumerate(self.blocks):
            with jax.named_scope(f"block_{i}"):
                x, new_cache[i] = blk.decode_step(
                    params["blocks"][i], state["blocks"][i], cache[i], x,
                    pos)
        return self._head(params, state, x), new_cache

    def init_paged_cache(self, num_pages: int, page_size: int,
                         dtype=jnp.float32):
        """Per-layer block-paged KV pools for :meth:`decode_pages` —
        each ``(num_pages + 1, page_size, W)`` (page, token in page,
        every KV head of the token side by side), the last page
        being the write-redirect trash page (see
        ``nn.MultiHeadAttention.init_paged_cache``)."""
        return [b.attn.init_paged_cache(num_pages, page_size, dtype)
                for b in self.blocks]

    def decode_pages(self, params, state, tokens, cache, pages, pos,
                     active):
        """Page-table :meth:`decode`, the SERVED path: every batch row
        is an independent slot at its own depth, whose cache positions
        live in the shared page pool at
        ``pages[b, p // page_size]``.  ``tokens`` (B, S) 1-based ids at
        positions ``[pos_b, pos_b + S)``, ``pages`` (B, Lp) int32 page
        table, ``pos`` (B,), ``active`` (B,) — inactive rows and
        positions whose logical page the table leaves unmapped write to
        the pool's trash page, never to a page another slot (or a
        shared read-only prefix) owns.  Returns
        (log-probs (B, S, vocab), cache').

        Capacity contract: unlike :meth:`decode`, an over-table
        position cannot corrupt the cache — it lands in trash — but
        its READ view is garbage-masked only up to the table's mapped
        range, so the scheduler still bounds positions eagerly at admit
        (typed ``SlotCapacityError``) and deactivates rows in-graph."""
        ids = jnp.asarray(tokens, jnp.int32) - 1
        b, s = ids.shape
        x = self._embed(params, ids, pos)
        new_cache = list(cache)
        for i, blk in enumerate(self.blocks):
            with jax.named_scope(f"block_{i}"):
                x, new_cache[i] = blk.decode_step_pages(
                    params["blocks"][i], state["blocks"][i], cache[i], x,
                    pages, pos, active)
        return self._head(params, state, x), new_cache

    def generate(self, params, state, prompt, max_new: int,
                 temperature: float = 0.0, rng=None,
                 max_len: Optional[int] = None, cache_dtype=jnp.float32,
                 top_k: int = 0, top_p: float = 1.0):
        """Autoregressive generation, fully on device: ONE prefill call
        over the prompt, then ``lax.scan`` of single-token decode steps
        (greedy at ``temperature=0``, else categorical sampling,
        optionally truncated to the ``top_k`` highest-probability
        tokens and/or the ``top_p`` nucleus — both static, both
        jit-compatible; the first token of the nucleus is always kept).
        ``prompt`` (B, Tp) 1-based; returns (B, max_new) 1-based ids.
        Wrap in ``jax.jit`` (static: max_new/temperature/top_k/top_p) —
        XLA compiles prefill + the scanned step into one program; the
        KV cache is a scan carry, so it never round-trips to host.
        """
        prompt = jnp.asarray(prompt, jnp.int32)
        b, tp = prompt.shape
        ml = max_len or self.max_len
        # KV-cache capacity bound holds for BOTH position modes — an
        # overrun would dynamic_update_slice-CLAMP into the last slot,
        # silently corrupting the cache (rope has no table to save it).
        # ValueError, not assert: must survive ``python -O`` (same
        # convention as ops/attention.py / nn/attention.py).
        if tp + max_new > ml:
            raise ValueError(
                f"prompt {tp} + max_new {max_new} exceeds cache length {ml}")
        if self.position == "learned" and tp + max_new > self.max_len:
            raise ValueError(
                f"prompt {tp} + max_new {max_new} exceeds learned-position "
                f"table length {self.max_len}")
        if max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {max_new} "
                             "(the prefill always samples one token)")
        if not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p} "
                             "(top_p<=0 would mask every logit to -inf)")
        if top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {top_k}")
        if temperature > 0 and rng is None:
            raise ValueError("sampling (temperature>0) needs an rng")
        rng = rng if rng is not None else jax.random.PRNGKey(0)

        cache = self.init_cache(b, ml, cache_dtype)
        lp, cache = self.decode(params, state, prompt, cache, 0)

        def pick(logp, r):
            if temperature <= 0:
                return jnp.argmax(logp, axis=-1).astype(jnp.int32) + 1
            lp = logp / temperature
            if top_k and top_k < lp.shape[-1]:
                kth = jax.lax.top_k(lp, top_k)[0][..., -1:]
                lp = jnp.where(lp < kth, -jnp.inf, lp)
            if top_p < 1.0:
                # nucleus: keep the smallest prefix of the sorted
                # distribution whose mass reaches top_p (first token
                # always kept), expressed as a per-row logit threshold
                srt = jnp.sort(lp, axis=-1)[..., ::-1]
                probs = jax.nn.softmax(srt, axis=-1)
                exclusive = jnp.cumsum(probs, axis=-1) - probs
                kept = jnp.where(exclusive < top_p, srt, jnp.inf)
                thresh = jnp.min(kept, axis=-1, keepdims=True)
                lp = jnp.where(lp < thresh, -jnp.inf, lp)
            return jax.random.categorical(
                r, lp, axis=-1).astype(jnp.int32) + 1

        rng, r0 = jax.random.split(rng)
        first = pick(lp[:, -1], r0)

        def step(carry, r):
            tok, cache, pos = carry
            logp, cache = self.decode(params, state, tok[:, None],
                                      cache, pos)
            nxt = pick(logp[:, -1], r)
            return (nxt, cache, pos + 1), tok

        keys = jax.random.split(rng, max(max_new - 1, 1))
        (last, _, _), toks = jax.lax.scan(
            step, (first, cache, jnp.asarray(tp, jnp.int32)),
            keys[:max_new - 1])
        out = jnp.concatenate([toks.T, last[:, None]], axis=1) \
            if max_new > 1 else first[:, None]
        return out


def train_main(argv=None):
    """CLI train entry for the transformer LM on a text corpus — the
    long-context counterpart of ``models/rnn`` Train (same tokenizer,
    flags, checkpoint/validation wiring; ``models/rnn/Train.scala:35-105``
    is the flag-parity source)."""
    import argparse

    from bigdl_tpu.dataset.dataset import DataSet
    from bigdl_tpu.dataset.text import (LabeledSentenceToTokens,
                                        WordTokenizer, load_in_data)
    from bigdl_tpu.dataset.transformer import SampleToBatch
    from bigdl_tpu.engine import Engine
    from bigdl_tpu.nn import ClassNLLCriterion, TimeDistributedCriterion
    from bigdl_tpu.optim import (Adam, Loss, Optimizer, SGD, Trigger,
                                 Warmup)
    from bigdl_tpu.utils.compile_cache import enable_compile_cache
    from bigdl_tpu.utils.log import init_logging

    p = argparse.ArgumentParser("transformer-train")
    p.add_argument("-f", "--folder", default="./")
    p.add_argument("--model", default=None, help="model snapshot location")
    p.add_argument("--state", default=None, help="state snapshot location")
    p.add_argument("--checkpoint", default=None)
    p.add_argument("-r", "--learningRate", type=float, default=0.01)
    p.add_argument("-m", "--momentum", type=float, default=0.0)
    p.add_argument("--optim", choices=["sgd", "adam"], default="sgd")
    p.add_argument("--warmup", type=int, default=0,
                   help="linear LR warmup iterations (0 = off)")
    p.add_argument("--vocab", type=int, default=4000)
    p.add_argument("--embed", type=int, default=128)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--maxLen", type=int, default=256)
    p.add_argument("-e", "--nEpochs", type=int, default=10)
    p.add_argument("-b", "--batchSize", type=int, default=8)
    args = p.parse_args(argv)
    if args.optim == "adam" and args.momentum:
        p.error("--momentum applies to sgd only (Adam's beta1 is the "
                "analogous knob)")

    init_logging()
    enable_compile_cache()
    Engine.init()
    dictionary_length = args.vocab + 1
    WordTokenizer(f"{args.folder}/input.txt", args.folder,
                  dictionary_length=dictionary_length).process()
    train, val, train_max, val_max = load_in_data(
        args.folder, dictionary_length)
    fix = min(max(train_max, val_max), args.maxLen)

    train_set = DataSet.array(train) >> LabeledSentenceToTokens(fix) >> \
        SampleToBatch(args.batchSize, drop_last=True)
    val_set = DataSet.array(val) >> LabeledSentenceToTokens(fix) >> \
        SampleToBatch(args.batchSize, drop_last=True)

    # max_len comes from the FLAG, not the corpus: the position table's
    # shape must be corpus-independent or snapshot resume on an extended
    # corpus would restore a mismatched pos embedding
    model = TransformerLM(dictionary_length + 1, max_len=args.maxLen,
                          embed_dim=args.embed, num_heads=args.heads,
                          num_layers=args.layers)
    if args.model:
        from bigdl_tpu.utils.file import load_model_snapshot
        load_model_snapshot(model, args.model)

    criterion = TimeDistributedCriterion(ClassNLLCriterion(),
                                         size_average=True)
    optimizer = Optimizer(model=model, dataset=train_set,
                          criterion=criterion)
    sched = Warmup(args.warmup) if args.warmup > 0 else None
    if args.optim == "adam":
        optimizer.set_optim_method(Adam(learning_rate=args.learningRate,
                                        learning_rate_schedule=sched))
    else:
        optimizer.set_optim_method(SGD(learning_rate=args.learningRate,
                                       momentum=args.momentum,
                                       learning_rate_schedule=sched))
    if args.state:
        from bigdl_tpu.utils.file import File
        optimizer.set_state(File.load(args.state))
    optimizer.set_end_when(Trigger.max_epoch(args.nEpochs))
    optimizer.set_validation(Trigger.every_epoch(), val_set,
                             [Loss(criterion)])
    if args.checkpoint:
        optimizer.set_checkpoint(args.checkpoint, Trigger.every_epoch())
    return optimizer.optimize()


def generate_main(argv=None):
    """CLI generation entry (the transformer counterpart of
    ``models/rnn/Test.scala:39-92``): extend each ``test.txt`` sentence
    by ``--words`` tokens through the on-device KV-cache ``generate``
    loop — one jitted prefill+scan program per prompt shape, instead of
    the RNN CLI's re-run-the-whole-forward-per-token host loop."""
    import argparse

    import jax
    import numpy as np

    from bigdl_tpu.dataset.text import Dictionary, read_sentence
    from bigdl_tpu.engine import Engine
    from bigdl_tpu.utils.file import load_model_snapshot
    from bigdl_tpu.utils.compile_cache import enable_compile_cache
    from bigdl_tpu.utils.log import init_logging

    p = argparse.ArgumentParser("transformer-generate")
    p.add_argument("-f", "--folder", default="./")
    p.add_argument("--model", required=True)
    p.add_argument("--words", type=int, required=True)
    p.add_argument("--vocab", type=int, default=4000)
    p.add_argument("--embed", type=int, default=128)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--maxLen", type=int, default=256)
    p.add_argument("--temperature", type=float, default=1.0,
                   help="0 = greedy")
    p.add_argument("--topK", type=int, default=0)
    p.add_argument("--topP", type=float, default=1.0)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    init_logging()
    enable_compile_cache()
    Engine.init()
    dictionary_length = args.vocab + 1
    vocab = Dictionary(args.folder)
    model = TransformerLM(dictionary_length + 1, max_len=args.maxLen,
                          embed_dim=args.embed, num_heads=args.heads,
                          num_layers=args.layers)
    load_model_snapshot(model, args.model)
    model.evaluate()

    sentences = [[float(vocab.get_index(t)) for t in line]
                 for line in read_sentence(args.folder)]
    results = []
    for i, seq in enumerate(sentences):
        prompt = jnp.asarray(np.asarray(seq, np.int32)[None] + 1)
        out = model.generate(model.params, model.state, prompt,
                             max_new=args.words,
                             temperature=args.temperature,
                             top_k=args.topK, top_p=args.topP,
                             rng=jax.random.PRNGKey(args.seed + i))
        grown = seq + [float(t - 1) for t in np.asarray(out[0])]
        results.append(" ".join(vocab.get_word(t) for t in grown))
    for line in results:
        print(line)
    return results


if __name__ == "__main__":
    import sys
    if sys.argv[1:2] == ["generate"]:
        generate_main(sys.argv[2:])
    else:
        train_main()
