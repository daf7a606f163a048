"""Attention layers.

The reference has NO attention ops (SURVEY.md section 5.7 — its sequence
story is ``Recurrent``/``RnnCell``); these layers are the TPU-native
extension that makes long-context work first-class.  They follow the same
module protocol as every other layer and plug directly into the
context-parallel kernels in ``bigdl_tpu/parallel/sequence.py``:

* locally (single chip), ``MultiHeadAttention`` runs the fused Pallas
  attention kernel on TPU (``ops/attention.py`` — scores stay in VMEM;
  ``BIGDL_TPU_DISABLE_PALLAS=1`` reverts to plain XLA attention, which is
  also the path on non-TPU backends and beyond the kernel's VMEM budget);
* under ``shard_map`` with sequence-sharded inputs, pass
  ``attention_fn=partial(ring_attention, axis_name="seq")`` (or
  ``ulysses_attention``) and the same module computes exact full-sequence
  attention over the mesh.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from bigdl_tpu.core import init as init_methods
from bigdl_tpu.core.module import Module
from bigdl_tpu.ops import quant


def _proj(x, w, b=None):
    """Quant-aware projection (the shared ``quant.matmul_or_observe``
    dispatch): packed int8 weights route through the fused
    dequant-matmul so the zoo's qkv/ffn/out projections serve from
    int8-resident params; the fp path doubles as the calibration
    observation point."""
    return quant.matmul_or_observe(x, w, b)


def apply_rope(x, pos, theta: float = 10000.0):
    """Rotary position embedding (RoFormer) over (B, H, T, D) with
    positions ``pos`` (T,) — the half-split pairing convention.  Scores
    after rotating q and k depend only on RELATIVE positions, so causal
    attention is invariant to a global position shift (tested); a
    contiguous sequence shard passes its global offset, a non-contiguous
    layout (e.g. the zigzag causal ring's chunk pairs) passes its
    per-token global position vector — no learned table, no max_len.
    ``pos`` may also be (B, T): per-ROW positions, the slot-addressable
    decode layout where every cache slot sits at its own depth."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[..., None] * freqs   # (..., T, half)
    if ang.ndim == 3:                   # (B, T, half): per-row positions
        cos = jnp.cos(ang)[:, None]
        sin = jnp.sin(ang)[:, None]
    else:
        cos = jnp.cos(ang)[None, None]
        sin = jnp.sin(ang)[None, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


def pages_view(pool, pages, num_kv_heads: int, head_dim: int):
    """The rows' pages of a pool ``(P + 1, ps, W)`` gathered through
    their tables ``pages`` (B, Lp) into the contiguous per-head view
    ``(B, Hkv, Lp * ps, D)`` the slot layout has, the padding lanes of
    the width dropped.  Trash-mapped positions read as ZERO: the -inf
    validity mask hides them from the softmax, but the weighted sum
    still multiplies their V by 0 — and 0 * NaN is NaN, so a single
    non-finite value ever written to the trash page (any slot's
    redirected garbage) would poison EVERY row whose table holds a
    trash entry.  Zeroing makes trash inert regardless of what was
    dumped there."""
    from bigdl_tpu.ops.attention import paged_pool_dims
    ps, _ = paged_pool_dims(pool)
    b, lp = pages.shape
    view = pool[pages][..., :num_kv_heads * head_dim] \
        .reshape(b, lp * ps, num_kv_heads, head_dim).transpose(0, 2, 1, 3)
    tmask = jnp.repeat(pages == pool.shape[0] - 1, ps, axis=1)
    return jnp.where(tmask[:, None, :, None], 0, view)


def pages_rows(view, width: int):
    """The inverse of :func:`pages_view`'s un-merging on a per-head
    ``(B, Hkv, T, D)`` array: its tokens as pool rows ``(B, T, W)``,
    zero in the padding lanes."""
    b, hkv, t, d = view.shape
    rows = view.transpose(0, 2, 1, 3).reshape(b, t, hkv * d)
    return jnp.pad(rows, ((0, 0), (0, 0), (0, width - hkv * d)))


def _page_slots(pages, positions, ps: int, trash: int, active):
    """Where each token of ``positions`` (B, S) is written: its physical
    page and its offset in it, both (B, S).  A logical page past the
    table, one the host left unmapped (its table slot names the trash
    page already) and every token of an inactive row go to ``trash``."""
    lp = pages.shape[1]
    logical = positions // ps
    phys = jnp.take_along_axis(pages, jnp.clip(logical, 0, lp - 1), axis=1)
    phys = jnp.where(logical >= lp, trash, phys)
    phys = jnp.where(jnp.asarray(active)[:, None], phys, trash)
    return phys, positions % ps


@functools.partial(jax.jit, inline=True, static_argnames=("by_page",))
def _write_rows(pool, new, phys, offs, by_page=True):
    """The cache write: the tokens ``new`` (B, Hkv, S, D) as rows of a
    pool ``(P + 1, ps, W)`` at ``(phys, offs)`` (B, S), the page and the
    offset in it of each token — a scatter whose window is one
    contiguous row of the pool.  Traced once for all the layers of a
    model (and for K and V): they share its shapes.  ``by_page=False``
    keeps to the token rows: inside a loop that carries the pool, the
    page-by-page choice below (a ``cond`` on the pool) makes the compiler
    copy the whole pool into each of its branches."""
    from bigdl_tpu.ops.attention import paged_pool_dims
    ps, width = paged_pool_dims(pool)
    b, _, s, _ = new.shape
    flat = pages_rows(new.astype(pool.dtype), width).reshape(b * s, width)

    def by_tokens(c):
        return c.at[phys.reshape(-1), offs.reshape(-1)].set(flat)

    if s % ps or not by_page:
        return by_tokens(pool)

    # a whole number of pages (a prefill bucket): where every row starts
    # on a page's first token (a prefill does: shared prefixes are whole
    # pages) the same tokens go page by page, S / ps windows of ps x W
    # in place of S serial rows
    def by_pages(c):
        return c.at[phys[:, ::ps].reshape(-1)].set(
            flat.reshape(b * s // ps, ps, width))

    return jax.lax.cond(jnp.all(offs[:, 0] == 0), by_pages, by_tokens, pool)


def _paged_read(q, ck, cv, pages, positions, scale, num_kv_heads):
    """Attention of ``q`` (B, H, S, D) over the rows' pages of the pools
    ``ck``, ``cv`` through their tables ``pages`` (B, Lp), key slot ``l``
    visible to a token at ``positions`` (B, S) iff ``l <= position``:
    the paged-attention kernel where it is on, else the gather path,
    which is also the kernel's oracle.  (B, H, S, D)."""
    from bigdl_tpu.ops.attention import (expand_kv_heads, paged_attention,
                                         paged_attention_enabled,
                                         paged_pool_dims)
    if paged_attention_enabled():
        # r14: gather + masked attention in ONE Pallas kernel — the
        # page table rides in as a scalar-prefetch operand and the
        # index map does the gather, so the contiguous (B, H, L, D)
        # view below never exists in HBM.  Same math operation for
        # operation (trash zeroing, validity mask, f32 softmax):
        # parity with the gather path is regression-gated.
        with jax.named_scope("attn.paged"):
            return paged_attention(q, ck, cv, pages, positions, scale,
                                   num_kv_heads=num_kv_heads)
    # gather the row's pages into a contiguous (B, H, L, D) view
    # (L = Lp * ps), trash-mapped positions zeroed — the jnp fallback
    # (non-Pallas backends) and the kernel's parity oracle
    head_dim = q.shape[-1]
    lp, ps = pages.shape[1], paged_pool_dims(ck)[0]
    with jax.named_scope("attn.paged"):
        kk = pages_view(ck, pages, num_kv_heads, head_dim)
        vv = pages_view(cv, pages, num_kv_heads, head_dim)
    kk, vv = expand_kv_heads(q, kk, vv)         # (B, H, L, D)
    scores = jnp.einsum("bhsd,bhld->bhsl", q, kk) * scale
    valid = (jnp.arange(lp * ps)[None, None, :]
             <= positions[:, :, None])          # (B, S, L)
    scores = jnp.where(valid[:, None], scores, -jnp.inf)
    w = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhsl,bhld->bhsd", w.astype(vv.dtype), vv)


class MultiHeadAttention(Module):
    """Multi-head self-attention over (batch, seq, embed) inputs.

    ``attention_fn(q, k, v, causal=...)`` — q/k/v shaped (B, H, T, D) —
    defaults to local softmax attention; override with a context-parallel
    kernel from ``parallel.sequence`` to shard the sequence axis across the
    mesh.  The module always passes its own ``causal`` flag into the call,
    so a ``partial(ring_attention, axis_name="seq")`` needs no (and must
    not disagree with) its own causal binding.
    """

    def __init__(self, embed_dim: int, num_heads: int,
                 causal: bool = False, with_bias: bool = True,
                 attention_fn: Optional[Callable] = None,
                 init_method: str = init_methods.XAVIER,
                 num_kv_heads: Optional[int] = None,
                 rope: bool = False, rope_theta: float = 10000.0):
        super().__init__()
        assert embed_dim % num_heads == 0
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.head_dim = embed_dim // num_heads
        self.causal = causal
        self.with_bias = with_bias
        self.attention_fn = attention_fn
        self.init_method = init_method
        # GQA/MQA: K/V project to num_kv_heads * head_dim; each KV head
        # serves num_heads // num_kv_heads query heads (the Pallas
        # kernels share KV blocks via index maps, no materialised
        # repeat).  num_kv_heads=1 is multi-query attention.
        self.num_kv_heads = num_kv_heads or num_heads
        assert num_heads % self.num_kv_heads == 0, \
            (num_heads, self.num_kv_heads)
        self.rope = rope
        self.rope_theta = rope_theta
        if rope:
            assert self.head_dim % 2 == 0, self.head_dim

    def init_params(self, rng):
        keys = jax.random.split(rng, 4)
        e = self.embed_dim

        ekv = self.num_kv_heads * self.head_dim

        def proj(k, out=e):
            return init_methods.init_weight(self.init_method, k, (out, e),
                                            fan_in=e, fan_out=out)

        p = {"wq": proj(keys[0]), "wk": proj(keys[1], ekv),
             "wv": proj(keys[2], ekv), "wo": proj(keys[3])}
        if self.with_bias:
            z = jnp.zeros((e,), jnp.float32)
            zkv = jnp.zeros((ekv,), jnp.float32)
            p.update({"bq": z, "bk": zkv, "bv": zkv, "bo": z})
        return p

    def _split(self, x, heads=None):
        b, t, _ = x.shape
        return x.reshape(b, t, heads or self.num_heads, self.head_dim) \
                .transpose(0, 2, 1, 3)          # (B, H, T, D)

    def _merge(self, x):
        b, h, t, d = x.shape
        return x.transpose(0, 2, 1, 3).reshape(b, t, h * d)

    # -- autoregressive decode (KV cache) --------------------------------

    def init_cache(self, batch: int, max_len: int, dtype=jnp.float32):
        """Zeroed KV cache for ``apply_decode`` — (B, H_kv, max_len, D)
        per tensor.  GQA caches only the KV heads (num_kv_heads), the
        memory win that motivates GQA at decode time."""
        shape = (batch, self.num_kv_heads, max_len, self.head_dim)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    def apply_decode(self, params, x_t, cache, pos):
        """Incremental attention: ``x_t`` (B, S, E) are the tokens at
        positions [pos, pos+S) (S = prompt length for prefill, 1 for
        generation steps); attends to every cached position <= its own.
        Returns (y (B, S, E), cache') — cache' holds this call's K/V
        written at [pos, pos+S).

        Decode is HBM-bound (one q row against the cache), so this is
        plain XLA einsum math — the flash kernels exist for the O(T^2)
        training regime, not for S=1 rows.  ``pos`` may be traced
        (lax.scan carry), enabling fully on-device generation loops.
        """
        bias = self.with_bias
        q = _proj(x_t, params["wq"], params["bq"] if bias else None)
        k = _proj(x_t, params["wk"], params["bk"] if bias else None)
        v = _proj(x_t, params["wv"], params["bv"] if bias else None)
        q = self._split(q)                          # (B, H, S, D)
        k = self._split(k, self.num_kv_heads)       # (B, Hkv, S, D)
        v = self._split(v, self.num_kv_heads)
        s = q.shape[2]
        positions = jnp.asarray(pos) + jnp.arange(s)
        if self.rope:
            # k is cached POST-rotation: each position's rotation is
            # absolute, and scores depend only on relative offsets
            q = apply_rope(q, positions, self.rope_theta)
            k = apply_rope(k, positions, self.rope_theta)
        dt = cache["k"].dtype
        with jax.named_scope("kv.write"):
            ck = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(dt), (0, 0, jnp.asarray(pos), 0))
            cv = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(dt), (0, 0, jnp.asarray(pos), 0))
        from bigdl_tpu.ops.attention import expand_kv_heads
        kk, vv = expand_kv_heads(q, ck, cv)         # (B, H, L, D)
        scale = 1.0 / math.sqrt(self.head_dim)
        scores = jnp.einsum("bhsd,bhld->bhsl", q, kk) * scale
        # causal-banded validity: key slot l visible to local row i iff
        # l <= pos + i (unwritten cache slots are > pos+S-1, so the same
        # predicate also masks them out)
        valid = jnp.arange(ck.shape[2])[None, :] <= positions[:, None]
        scores = jnp.where(valid[None, None], scores, -jnp.inf)
        w = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
        o = jnp.einsum("bhsl,bhld->bhsd", w.astype(vv.dtype), vv)
        y = _proj(self._merge(o), params["wo"],
                  params["bo"] if self.with_bias else None)
        return y, {"k": ck, "v": cv}

    def init_paged_cache(self, num_pages: int, page_size: int,
                         dtype=jnp.float32):
        """Block-paged KV cache for ``apply_decode_pages`` —
        ``(num_pages + 1, page_size, W)`` per tensor: page, token in
        page, width.  A token's K (or V) of every head is one
        contiguous row, KV head ``j`` in lanes ``[j * D, (j + 1) * D)``;
        ``W`` is ``H_kv * D`` rounded up to whole 128-lane tiles
        (``ops.attention.paged_pool_width``: GPT-2 XL's 25 x 64 = 1,600
        -> 1,664; the padding lanes stay zero), so that the program's
        boundary, the write's scatter and the paged-attention kernel
        all take the pool in the one tiling it has.  The extra LAST
        page (id ``num_pages``) is the **trash page**: unallocated
        page-table slots and inactive rows write there, so no in-graph
        write can ever land in a page another slot owns.  Physical
        pages carry no sequence identity; the host-side page table
        (``serving/scheduler/paging.py``) is the only map from a slot's
        logical positions to pool rows."""
        from bigdl_tpu.ops.attention import paged_pool_width
        shape = (num_pages + 1, page_size,
                 paged_pool_width(self.num_kv_heads, self.head_dim))
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    def apply_decode_pages(self, params, x_t, cache, pages, pos, active):
        """Page-table incremental attention: every batch row is an
        independent SLOT at its own depth, its cache an indirection
        through ``pages`` (B, Lp) int32 — logical page ``l`` of row
        ``b`` lives in pool page ``pages[b, l]``.  ``x_t`` (B, S, E)
        holds each slot's next ``S`` tokens at positions ``[pos_b,
        pos_b + S)``; ``active`` (B,) gates writes: an inactive (free /
        finished) slot computes garbage but must never mutate a page,
        or an admit into that slot later would inherit a corrupted
        prefix.

        Writes are a scatter of token rows ``(W,)`` at ``(pages[b, p //
        ps], p % ps)`` of the pool ``(P + 1, ps, W)``; an inactive row,
        and any position whose logical page the host left unmapped, is
        redirected to the TRASH page (the pool's last row) — O(S) per
        row, and a write can never reach a page outside the row's own
        table.  Reads go through the paged-attention kernel where it is
        on, else gather the row's pages into a contiguous ``(B, H,
        Lp*ps, D)`` view (``pages_view``); garbage in trash-mapped
        or unwritten pages is hidden by the per-row validity
        predicate (``l <= positions``).  Shared
        read-only prefix pages are safe under this contract by
        construction: a reader's write positions start at the end of
        its shared prefix, so its scatter indices never land in a
        shared page (the ``page-aliasing`` graftlint rule guards the
        host bookkeeping that keeps it true).  Returns
        (y (B, S, E), cache')."""
        bias = self.with_bias
        q = _proj(x_t, params["wq"], params["bq"] if bias else None)
        k = _proj(x_t, params["wk"], params["bk"] if bias else None)
        v = _proj(x_t, params["wv"], params["bv"] if bias else None)
        q = self._split(q)                          # (B, H, S, D)
        k = self._split(k, self.num_kv_heads)       # (B, Hkv, S, D)
        v = self._split(v, self.num_kv_heads)
        b, _, s, _ = q.shape
        positions = jnp.asarray(pos)[:, None] + jnp.arange(s)   # (B, S)
        if self.rope:
            q = apply_rope(q, positions, self.rope_theta)
            k = apply_rope(k, positions, self.rope_theta)
        from bigdl_tpu.ops.attention import paged_pool_dims
        ps, _ = paged_pool_dims(cache["k"])
        trash = cache["k"].shape[0] - 1
        pages = jnp.asarray(pages, jnp.int32)

        phys, offs = _page_slots(pages, positions, ps, trash, active)

        # the write and the read are scoped apart, so that a trace says
        # which of the two owns a relayout of the pool
        with jax.named_scope("kv.write"):
            ck = _write_rows(cache["k"], k, phys, offs)
            cv = _write_rows(cache["v"], v, phys, offs)
        o = _paged_read(q, ck, cv, pages, positions,
                        1.0 / math.sqrt(self.head_dim), self.num_kv_heads)
        y = _proj(self._merge(o), params["wo"],
                  params["bo"] if self.with_bias else None)
        return y, {"k": ck, "v": cv}

    def apply(self, params, state, input, *, training=False, rng=None,
              pos_offset=0, key_padding_mask=None):
        bias = self.with_bias
        q = _proj(input, params["wq"], params["bq"] if bias else None)
        k = _proj(input, params["wk"], params["bk"] if bias else None)
        v = _proj(input, params["wv"], params["bv"] if bias else None)
        q = self._split(q)
        k = self._split(k, self.num_kv_heads)
        v = self._split(v, self.num_kv_heads)
        if self.rope:
            # pos_offset: scalar global offset of a CONTIGUOUS shard, or
            # a (T,) per-token global position vector for non-contiguous
            # layouts (zigzag ring chunk pairs)
            off = jnp.asarray(pos_offset)
            pos = off if off.ndim == 1 else jnp.arange(q.shape[2]) + off
            q = apply_rope(q, pos, self.rope_theta)
            k = apply_rope(k, pos, self.rope_theta)
        if self.attention_fn is not None:
            # context-parallel kernels take full-head K/V; they shard
            # the sequence axis, so a (B, T_global) padding mask has no
            # per-shard meaning here — pad to the shard multiple instead.
            # ValueError, not assert: silently dropping the mask under
            # python -O would attend to padding
            if key_padding_mask is not None:
                raise ValueError(
                    "key_padding_mask is not supported with a context-"
                    "parallel attention_fn")
            from bigdl_tpu.ops.attention import expand_kv_heads
            k, v = expand_kv_heads(q, k, v)
            o = self.attention_fn(q, k, v, causal=self.causal)
        else:
            # fused Pallas kernel on TPU (scores never touch HBM); the
            # identical-math jnp reference elsewhere.  Eval mode
            # (training=False) signals no backward: the dispatcher then
            # uses the measured fwd-only policy (BENCH_attn: XLA wins
            # forward-only through T=8k, streaming flash beyond)
            from bigdl_tpu.ops import fused_attention
            o = fused_attention(q, k, v, causal=self.causal,
                                needs_backward=training,
                                key_padding_mask=key_padding_mask)
        y = _proj(self._merge(o), params["wo"],
                  params["bo"] if self.with_bias else None)
        return y, state


def apply_rope_interleaved(x, pos, theta: float = 10000.0):
    """Rotary embedding over the last axis of ``x`` (..., T, D) by
    INTERLEAVED pairs ``(x[2i], x[2i+1])`` (the DeepSeek convention;
    :func:`apply_rope` pairs ``x[i]`` with ``x[i + D/2]``).  ``pos``
    broadcasts against ``x``'s leading axes up to T."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.asarray(pos, jnp.float32)[..., None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (half, 2))
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _causal_attention(q, k, v, scale, block_q: int = 256):
    """Exact causal attention over (B, H, T, ·) whose values may be
    narrower than its keys, a block of query rows at a time (one (B, H,
    block, T) score tile live, float32 softmax)."""
    b, h, t, _ = q.shape
    block = next(c for c in (block_q, 128, 64, 32, 16, 8, 4, 2, 1)
                 if t % c == 0)

    def rows(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * block, block, axis=2)
        s = jnp.einsum("bhqd,bhkd->bhqk", qs, k) * scale
        seen = (i * block + jnp.arange(block))[:, None] \
            >= jnp.arange(t)[None]
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf)
                           .astype(jnp.float32), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", w.astype(v.dtype), v)

    out = jax.lax.map(rows, jnp.arange(t // block))      # (N, B, H, blk, dv)
    return out.transpose(1, 2, 0, 3, 4).reshape(b, h, t, v.shape[-1])


class LatentAttention(Module):
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434) with a
    full-rank query: per token ``q = Wq x`` (H x (nope | rope)),
    ``[c~, k_r] = Wkva x`` (latent | rope), ``c = rmsnorm(c~)``, rope by
    interleaved pairs on ``q_r`` and the ONE shared ``k_r``, ``[k_n, v] =
    Wkvb c`` per head; scores ``(q_n . k_n + q_r . k_r) / sqrt(nope +
    rope)``, causal softmax, ``Wo`` on the heads' weighted ``v``.

    The cache holds the latent, not the heads: a page pool ``(P + 1,
    page_size, W)`` of ``[c, k_r]`` rows (``MultiHeadAttention``'s pool
    with ONE head of ``latent + rope``, padded to whole 128-lane tiles
    like it: 576 -> 640), read as K and, by
    its first ``latent`` lanes, as V.  A decode step (``S == 1``) ABSORBS
    ``Wkvb``: ``q' = q_n Wkvb^K`` (H x latent) scores the latents
    directly and the weighted sum of latents goes through ``Wkvb^V`` — one
    KV head of width ``latent + rope`` shared by all H query heads, which
    the paged-attention kernel serves as H query rows of one head.  A
    longer input is a prefill FROM POSITION 0 (the caller's contract):
    it writes its latents and attends over its own tokens in the expanded
    form, which costs a third of the absorbed form's operations."""

    def __init__(self, embed_dim: int, num_heads: int, latent_dim: int = 512,
                 rope_dim: int = 64, nope_dim: int = 128, v_dim: int = 128,
                 rope_theta: float = 10000.0, eps: float = 1e-6):
        super().__init__()
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.latent_dim = latent_dim
        self.rope_dim = rope_dim
        self.nope_dim = nope_dim
        self.v_dim = v_dim
        self.rope_theta = float(rope_theta)
        self.eps = eps
        self.scale = 1.0 / math.sqrt(nope_dim + rope_dim)

    def init_params(self, rng):
        ks = jax.random.split(rng, 4)
        e, h, c = self.embed_dim, self.num_heads, self.latent_dim

        def w(k, out, fan_in):
            return jax.random.normal(k, (out, fan_in)) * fan_in ** -0.5

        return {"wq": w(ks[0], h * (self.nope_dim + self.rope_dim), e),
                "wkva": w(ks[1], c + self.rope_dim, e),
                "kv_norm": {"weight": jnp.ones((c,), jnp.float32)},
                "wkvb": w(ks[2], h * (self.nope_dim + self.v_dim), c),
                "wo": w(ks[3], e, h * self.v_dim)}

    def init_paged_cache(self, num_pages: int, page_size: int,
                         dtype=jnp.float32):
        """The latent page pool, under ``"k"``; its last page is the
        write-redirect trash page, as in ``MultiHeadAttention``'s."""
        from bigdl_tpu.ops.attention import paged_pool_width
        return {"k": jnp.zeros((num_pages + 1, page_size, paged_pool_width(
            1, self.latent_dim + self.rope_dim)), dtype)}

    def _project(self, params, x, positions):
        """Per-head queries ``q_n`` (B, H, S, nope) and roped ``q_r``
        (B, H, S, rope), the tokens' latents (B, S, latent + rope) and
        ``Wkvb`` by head (H, nope + v, latent)."""
        b, s, _ = x.shape
        h, n, r = self.num_heads, self.nope_dim, self.rope_dim
        q = _proj(x, params["wq"]).reshape(b, s, h, n + r)
        q_r = apply_rope_interleaved(q[..., n:].transpose(0, 2, 1, 3),
                                     positions[:, None], self.rope_theta)
        wkvb = jnp.asarray(params["wkvb"]).reshape(h, n + self.v_dim,
                                                   self.latent_dim)
        return q[..., :n].transpose(0, 2, 1, 3), q_r, \
            self._latents(params, x, positions), wkvb

    def _latents(self, params, x, positions):
        """``[c, k_r]`` (B, S, latent + rope) of the tokens of ``x``."""
        c = self.latent_dim
        kva = _proj(x, params["wkva"])
        lat = kva[..., :c].astype(jnp.float32)
        lat = lat * jax.lax.rsqrt(jnp.mean(lat * lat, axis=-1,
                                           keepdims=True) + self.eps) \
            * params["kv_norm"]["weight"].astype(jnp.float32)
        k_r = apply_rope_interleaved(kva[..., c:], positions,
                                     self.rope_theta)
        return jnp.concatenate([lat.astype(x.dtype), k_r], axis=-1)

    def apply_decode_pages(self, params, x_t, cache, pages, pos, active):
        """``MultiHeadAttention.apply_decode_pages``'s contract on the
        latent pool (writes of inactive rows and unmapped positions go to
        the trash page).  Returns (y (B, S, E), cache')."""
        b, s, _ = x_t.shape
        h, c, r = self.num_heads, self.latent_dim, self.rope_dim
        n, dv = self.nope_dim, self.v_dim
        positions = jnp.asarray(pos)[:, None] + jnp.arange(s)    # (B, S)
        from bigdl_tpu.ops.attention import (paged_attention,
                                             paged_attention_enabled,
                                             paged_pool_dims)
        pool = cache["k"]
        ps, trash = paged_pool_dims(pool)[0], pool.shape[0] - 1
        pages = jnp.asarray(pages, jnp.int32)
        with jax.named_scope("absorb"):
            q_n, q_r, lat, wkvb = self._project(params, x_t, positions)
        with jax.named_scope("kv.write"):
            pool = _write_rows(pool, lat[:, None], *_page_slots(
                pages, positions, ps, trash, active))
        if s > 1:
            # prefill from position 0: expanded heads over the call's own
            # tokens, causal
            with jax.named_scope("attn.expand"):
                o = self._expanded(q_n, q_r, lat, wkvb)
        else:
            with jax.named_scope("absorb"):
                # q' = q_n Wkvb^K: the H heads become H query rows of the
                # one latent head
                qa = jnp.einsum("bhsn,hnc->bhsc", q_n, wkvb[:, :n])
                qa = jnp.concatenate([qa.astype(x_t.dtype), q_r],
                                     axis=-1)[:, :, 0][:, None]  # (B,1,H,c+r)
                rows = jnp.broadcast_to(positions, (b, h))
            with jax.named_scope("attn.paged"):
                if paged_attention_enabled():
                    ctx = paged_attention(qa, pool, pool, pages, rows,
                                          self.scale,
                                          num_kv_heads=1)[..., :c]
                else:
                    ctx = self._gathered(qa, pool, pages, rows)
            with jax.named_scope("absorb"):
                o = jnp.einsum("bhc,hdc->bhd", ctx[:, 0], wkvb[:, n:]
                               )[:, :, None]                     # (B,H,1,dv)
        y = _proj(o.transpose(0, 2, 1, 3).reshape(b, s, h * dv)
                  .astype(x_t.dtype), params["wo"])
        return y, {"k": pool}

    def _expanded(self, q_n, q_r, lat, wkvb):
        """Causal attention of a call's tokens over themselves with
        per-head keys and values expanded from their latents:
        (B, H, S, v)."""
        b, h, s, n = q_n.shape
        c, r = self.latent_dim, self.rope_dim
        kv = jnp.einsum("bsc,hdc->bhsd", lat[..., :c], wkvb)
        k = jnp.concatenate(
            [kv[..., :n], jnp.broadcast_to(lat[:, None, :, c:],
                                           (b, h, s, r))], axis=-1)
        return _causal_attention(jnp.concatenate([q_n, q_r], axis=-1), k,
                                 kv[..., n:], self.scale)

    def _gathered(self, q, pool, pages, rows):
        """The jnp form of the absorbed read, ``apply_decode_pages``'s
        gather path on the one latent head: (B, 1, H, latent)."""
        c = self.latent_dim
        kk = pages_view(pool, pages, 1,
                        c + self.rope_dim)[:, 0]          # (B, L, c + r)
        s = jnp.einsum("bhd,bld->bhl", q[:, 0], kk) * self.scale
        valid = jnp.arange(kk.shape[1])[None, None] <= rows[:, :, None]
        w = jax.nn.softmax(jnp.where(valid, s, -jnp.inf)
                           .astype(jnp.float32), axis=-1)
        return jnp.einsum("bhl,blc->bhc", w.astype(kk.dtype),
                          kk[..., :c])[:, None]

    def apply(self, params, state, input, *, training=False, rng=None):
        """A whole sequence from position 0, causal, expanded; no cache."""
        b, s, _ = input.shape
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        o = self._expanded(*self._project(params, input, positions))
        y = _proj(o.transpose(0, 2, 1, 3).reshape(b, s, -1)
                  .astype(input.dtype), params["wo"])
        return y, state


def _band_attention(q, k, v, scale, window: int,
                    score_bytes: int = 1 << 27):
    """Exact causal grouped-query attention inside a WINDOW of a call's
    tokens over themselves, ``q`` (B, H, T, D) against ``k``, ``v`` (B,
    Hkv, T, D) (query head ``a`` reads KV head ``a // (H / Hkv)``): token
    ``i`` sees the keys ``j <= i`` with ``i - j < window``.  A block of
    query rows at a time, each multiplying only the ``block + window``
    keys its band touches (one ``(B, H, block, block + window)`` float32
    score tile live, at most ``score_bytes``); float32 softmax."""
    b, h, t, d = q.shape
    hkv = k.shape[1]
    block = next(c for c in (256, 128, 64, 32, 16, 8, 4, 2, 1)
                 if t % c == 0
                 and (b * h * c * (c + window) * 4 <= score_bytes or c == 1))
    span = block + window
    qg = q.reshape(b, hkv, h // hkv, t, d)
    # keys in front of position 0: masked, there so that every block
    # slices the same number of keys
    k, v = (jnp.pad(a, ((0, 0), (0, 0), (window, 0), (0, 0)))
            for a in (k, v))

    def rows(i):
        qs = jax.lax.dynamic_slice_in_dim(qg, i * block, block, axis=3)
        ks, vs = (jax.lax.dynamic_slice_in_dim(a, i * block, span, axis=2)
                  for a in (k, v))
        at = i * block + jnp.arange(block)[:, None]          # query positions
        key = i * block - window + jnp.arange(span)[None]
        seen = (key <= at) & (at - key < window) & (key >= 0)
        s = jnp.einsum("bkgqd,bksd->bkgqs", qs, ks,
                       preferred_element_type=jnp.float32) * scale
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bkgqs,bksd->bkgqd", w.astype(vs.dtype), vs)

    out = jax.lax.map(rows, jnp.arange(t // block))   # (N, B, Hkv, G, blk, D)
    return out.transpose(1, 2, 3, 0, 4, 5).reshape(b, h, t, d)


class GroupedQueryAttention(Module):
    """Grouped-query softmax attention with a STATED head size, one module
    for the two kinds of layer a window/full pattern model mixes.  Per
    token, ``x`` the normed input: ``q = Wq x`` as ``H`` heads of ``D``,
    ``k = Wk x``, ``v = Wv x`` as ``Hkv`` heads of ``D``; with
    ``head_norm`` an RMS norm over each head's ``D`` query and key channels
    (one weight vector of ``D`` each, shared by the heads; a layer without
    it holds no such weights); with ``rope`` the rotary
    embedding by half-split pairs ``(i, i + D/2)`` on q and k; scores
    ``q_i . k_j / sqrt(D)`` where ``j <= i`` and, with a ``window``,
    ``i - j < window`` (a token and the ``window - 1`` before it);
    softmax in float32; query head ``a`` reads KV head ``a // (H /
    Hkv)``; ``Wo`` on the heads' weighted values.  No biases.

    What is kept between calls follows from the kind.  WITHOUT a window
    the layer serves through the page pool exactly as
    ``MultiHeadAttention`` does (``init_paged_cache``,
    ``apply_decode_pages``: the same write, the same paged-attention
    kernel at ``Hkv`` heads).  WITH a window it holds a RING a slot
    (``init_slot_state``, ``apply_slots``): K and V ``(slots, window,
    Hkv x D)``, token ``p`` at ring row ``p % window`` — keys are stored
    roped, so their order in the ring does not matter — and maps no
    page, whatever the row's length.  Either way an input longer than
    one token is a prefill FROM POSITION 0 that attends over its own
    tokens: inside the band (``_band_attention``) or, without a window,
    through ``ops.fused_attention`` (on a TPU the streaming kernel: an
    8,192-token prompt's scores, 17 GB in float32, never exist)."""

    def __init__(self, embed_dim: int, num_heads: int, num_kv_heads: int,
                 head_dim: int = 128, window: Optional[int] = None,
                 rope: bool = True, rope_theta: float = 1e6,
                 eps: float = 1e-5, head_norm: bool = True):
        super().__init__()
        assert num_heads % num_kv_heads == 0, (num_heads, num_kv_heads)
        self.head_norm = head_norm
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.window = None if window is None else int(window)
        self.rope = rope
        self.rope_theta = float(rope_theta)
        self.eps = eps
        self.scale = 1.0 / math.sqrt(head_dim)

    def init_params(self, rng):
        ks = jax.random.split(rng, 4)
        e, d = self.embed_dim, self.head_dim

        def w(k, out, fan_in):
            return jax.random.normal(k, (out, fan_in)) * fan_in ** -0.5

        params = {"wq": w(ks[0], self.num_heads * d, e),
                  "wk": w(ks[1], self.num_kv_heads * d, e),
                  "wv": w(ks[2], self.num_kv_heads * d, e),
                  "wo": w(ks[3], e, self.num_heads * d)}
        if self.head_norm:
            params.update(
                q_norm={"weight": jnp.ones((d,), jnp.float32)},
                k_norm={"weight": jnp.ones((d,), jnp.float32)})
        return params

    # -- what the layer keeps -------------------------------------------------

    def init_paged_cache(self, num_pages: int, page_size: int,
                         dtype=jnp.float32):
        """A full layer's page pools, ``MultiHeadAttention``'s layout:
        ``(num_pages + 1, page_size, W)`` K and V, the trash page last."""
        from bigdl_tpu.ops.attention import paged_pool_width
        shape = (num_pages + 1, page_size,
                 paged_pool_width(self.num_kv_heads, self.head_dim))
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    def init_slot_state(self, num_slots: int, dtype=jnp.float32):
        """A window layer's rings: K and V ``(num_slots, window, Hkv x
        D)``, a token's heads side by side as in a pool's row."""
        shape = (num_slots, self.window, self.num_kv_heads * self.head_dim)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    # -- the layer --------------------------------------------------------------

    def _norm_heads(self, x, weight):
        xf = x.astype(jnp.float32)
        xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                                + self.eps) * weight.astype(jnp.float32)
        return xf.astype(x.dtype)

    def _qkv(self, params, x, positions):
        """q (B, H, S, D), k and v (B, Hkv, S, D) of the tokens of ``x``
        at ``positions`` (B, S): normed and roped as the layer says."""
        b, s, _ = x.shape
        d = self.head_dim
        q = _proj(x, params["wq"]).reshape(b, s, self.num_heads, d)
        k = _proj(x, params["wk"]).reshape(b, s, self.num_kv_heads, d)
        v = _proj(x, params["wv"]).reshape(b, s, self.num_kv_heads, d)
        if self.head_norm:
            q = self._norm_heads(q, params["q_norm"]["weight"])
            k = self._norm_heads(k, params["k_norm"]["weight"])
        q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
        if self.rope:
            q = apply_rope(q, positions, self.rope_theta)
            k = apply_rope(k, positions, self.rope_theta)
        return q, k, v

    def _own_tokens(self, q, k, v):
        """Causal attention of a call's tokens over themselves."""
        if self.window is not None:
            return _band_attention(q, k, v, self.scale, self.window)
        from bigdl_tpu.ops import fused_attention
        return fused_attention(q, k, v, causal=True, scale=self.scale,
                               needs_backward=False)

    def _out(self, params, o):
        b, h, s, d = o.shape
        with jax.named_scope("out"):
            return _proj(o.transpose(0, 2, 1, 3).reshape(b, s, h * d),
                         params["wo"])

    def apply_decode_pages(self, params, x_t, cache, pages, pos, active,
                           pool=None):
        """A FULL layer: ``MultiHeadAttention.apply_decode_pages``'s
        contract (writes of inactive rows and unmapped positions go to
        the trash page; one token reads its row's pages through the
        kernel or the gather).  With ``pool`` (an int32 scalar, may be
        traced) ``cache`` holds a page-major STACK of pools ``(P + 1, N,
        ps, W)`` and the layer writes and reads pool ``pool`` of it in
        place (``ops.attention.stacked_pool``).  Returns (y (B, S, E),
        cache')."""
        from bigdl_tpu.ops.attention import paged_pool_dims, stacked_pool
        s = x_t.shape[1]
        positions = jnp.asarray(pos)[:, None] + jnp.arange(s)    # (B, S)
        with jax.named_scope("qkv"):
            q, k, v = self._qkv(params, x_t, positions)
        pk, pv = cache["k"], cache["v"]
        pages = jnp.asarray(pages, jnp.int32)
        if pool is not None:
            pk, table = stacked_pool(pk, pages, pool)
            pv, _ = stacked_pool(pv, pages, pool)
            pages = table
        ps, trash = paged_pool_dims(pk)[0], pk.shape[0] - 1
        phys, offs = _page_slots(pages, positions, ps, trash, active)
        with jax.named_scope("kv.write"):
            ck = _write_rows(pk, k, phys, offs, by_page=pool is None)
            cv = _write_rows(pv, v, phys, offs, by_page=pool is None)
        if s > 1:
            with jax.named_scope("attn.prefill"):
                o = self._own_tokens(q, k, v)
        else:
            o = _paged_read(q, ck, cv, pages, positions, self.scale,
                            self.num_kv_heads)
        if pool is not None:
            ck, cv = ck.reshape(cache["k"].shape), cv.reshape(cache["v"].shape)
        return self._out(params, o.astype(x_t.dtype)), {"k": ck, "v": cv}

    def apply_slots(self, params, x, st, pos, active, lengths=None):
        """A WINDOW layer: ``x`` (B, S, E) at positions ``[pos_b, pos_b +
        S)`` against the rows' rings ``st`` (``init_slot_state``'s tree, B
        rows).  One token is written at ring row ``pos % window`` and
        attends over the ring's rows ``j <= pos`` (all of them once the
        ring has wrapped).  A longer input is a prefill from position 0:
        it attends inside the band over its own tokens and leaves the
        last ``min(n, window)`` of its ``n = lengths_b`` REAL tokens in
        the ring (a bucket's padding never reaches it).  An inactive
        row's ring comes back bit for bit.  Returns (y, st')."""
        b, s, _ = x.shape
        w, hkv, d = self.window, self.num_kv_heads, self.head_dim
        pos = jnp.asarray(pos, jnp.int32)
        positions = pos[:, None] + jnp.arange(s)
        keep = jnp.asarray(active)
        with jax.named_scope("qkv"):
            q, k, v = self._qkv(params, x, positions)
        dt = st["k"].dtype
        rk, rv = (a.transpose(0, 2, 1, 3).reshape(b, s, hkv * d).astype(dt)
                  for a in (k, v))
        if s == 1:
            with jax.named_scope("kv.write"):
                # an inactive row's index lies past the ring: dropped
                at = jnp.where(keep, pos % w, w)
                ring_k, ring_v = (
                    ring.at[jnp.arange(b), at].set(new[:, 0], mode="drop")
                    for ring, new in ((st["k"], rk), (st["v"], rv)))
            with jax.named_scope("attn.ring"):
                qg = q[:, :, 0].reshape(b, hkv, self.num_heads // hkv, d)
                sc = jnp.einsum("bkgd,bskd->bkgs", qg,
                                ring_k.reshape(b, w, hkv, d),
                                preferred_element_type=jnp.float32) \
                    * self.scale
                seen = jnp.arange(w)[None] <= pos[:, None]       # (B, W)
                wt = jax.nn.softmax(
                    jnp.where(seen[:, None, None], sc, -jnp.inf), axis=-1)
                o = jnp.einsum("bkgs,bskd->bkgd", wt.astype(dt),
                               ring_v.reshape(b, w, hkv, d),
                               preferred_element_type=jnp.float32)
                o = o.reshape(b, self.num_heads, 1, d)
        else:
            n = jnp.full((b,), s, jnp.int32) if lengths is None \
                else jnp.asarray(lengths, jnp.int32)
            with jax.named_scope("kv.write"):
                # ring row r holds the last real position congruent to it
                r = jnp.arange(w)[None]
                src = r + w * ((n[:, None] - 1 - r) // w)        # (B, W)
                held = (r < n[:, None]) & keep[:, None]
                ring_k, ring_v = (
                    jnp.where(held[..., None], jnp.take_along_axis(
                        new, jnp.clip(src, 0, s - 1)[..., None], axis=1),
                        ring)
                    for ring, new in ((st["k"], rk), (st["v"], rv)))
            with jax.named_scope("attn.prefill"):
                o = self._own_tokens(q, k, v)
        return self._out(params, o.astype(x.dtype)), \
            {"k": ring_k, "v": ring_v}

    def apply(self, params, state, input, *, training=False, rng=None):
        """A whole sequence from position 0; nothing kept."""
        b, s, _ = input.shape
        q, k, v = self._qkv(params, input,
                            jnp.broadcast_to(jnp.arange(s), (b, s)))
        return self._out(params, self._own_tokens(q, k, v)
                         .astype(input.dtype)), state


class SparseGroupedQueryAttention(GroupedQueryAttention):
    """Grouped-query attention whose every query reads only the ``topk``
    keys a LIGHTNING INDEXER picks (DeepSeek Sparse Attention, DeepSeek-V3.2
    arXiv:2512.02556 section 2.1).  Queries, keys and values are
    :class:`GroupedQueryAttention`'s (head norm, rope by half-split pairs,
    no window).  Beside them, per token ``x`` (the normed input): ``Hi``
    indexer query heads ``qI_j = rope(WqI_j x)`` of ``Di`` channels, ONE
    indexer key ``kI = rope(LayerNorm(WkI x))`` shared by them, and the
    heads' weights ``wI = WwI x`` times ``Hi^-1/2 Di^-1/2``; the rope turns
    the first ``index_rope_dim`` channels of each (half-split pairs), the
    rest pass.  Query ``t`` scores key ``s <= t`` by ``I[t, s] = sum_j
    wI_tj ReLU(qI_tj . kI_s)`` and attends, softmax as usual, over the
    ``topk`` keys of largest ``I`` (all of them while ``t < topk``).

    Served through the page pool with a THIRD pool beside K and V: each
    token's roped ``kI``, ``(P + 1, ps, 128)`` (its ``Di`` lanes padded to
    one tile).  A decode step writes K, V and ``kI``, scores its row's
    ``kI`` over its pages, selects the exact top ``topk`` and gathers only
    those K and V rows (``ops.sparse_attention``).  A longer input is a
    prefill FROM POSITION 0 over its own tokens, ``block`` queries at a
    time (``sparse_prefill``)."""

    def __init__(self, embed_dim: int, num_heads: int, num_kv_heads: int,
                 head_dim: int = 128, rope_theta: float = 1e7,
                 eps: float = 1e-6, index_heads: int = 16,
                 index_dim: int = 64, index_rope_dim: int = 32,
                 topk: int = 2048, block: int = 512):
        super().__init__(embed_dim, num_heads, num_kv_heads, head_dim,
                         None, True, rope_theta, eps, True)
        self.index_heads = index_heads
        self.index_dim = index_dim
        self.index_rope_dim = index_rope_dim
        self.topk = int(topk)
        self.block = int(block)

    def init_params(self, rng):
        k0, k1 = jax.random.split(rng)
        params = super().init_params(k0)
        ks = jax.random.split(k1, 3)
        e, hi, di = self.embed_dim, self.index_heads, self.index_dim

        def w(k, out):
            return jax.random.normal(k, (out, e)) * e ** -0.5

        params.update(
            wq_index=w(ks[0], hi * di), wk_index=w(ks[1], di),
            w_index=w(ks[2], hi),
            k_index_norm={"weight": jnp.ones((di,), jnp.float32),
                          "bias": jnp.zeros((di,), jnp.float32)})
        return params

    def init_paged_cache(self, num_pages: int, page_size: int,
                         dtype=jnp.float32):
        """K and V as a full layer keeps them, and the indexer's keys
        ``"ki"`` ``(num_pages + 1, page_size, 128)``, the trash page last
        in all three."""
        from bigdl_tpu.ops.attention import paged_pool_width
        cache = super().init_paged_cache(num_pages, page_size, dtype)
        cache["ki"] = jnp.zeros((num_pages + 1, page_size, paged_pool_width(
            1, self.index_dim)), dtype)
        return cache

    def _index_rope(self, x, positions):
        r = self.index_rope_dim
        return jnp.concatenate([apply_rope(x[..., :r], positions,
                                           self.rope_theta), x[..., r:]],
                               axis=-1)

    def _index_qk(self, params, x, positions):
        """The indexer's queries (B, S, Hi, Di), the heads' weights (B, S,
        Hi) float32 with both scales in, and the keys (B, S, Di)."""
        b, s, _ = x.shape
        hi, di = self.index_heads, self.index_dim
        qi = _proj(x, params["wq_index"]).reshape(b, s, hi, di)
        qi = self._index_rope(qi.transpose(0, 2, 1, 3),
                              positions).transpose(0, 2, 1, 3)
        ki = _proj(x, params["wk_index"]).astype(jnp.float32)
        mu = jnp.mean(ki, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(ki - mu), axis=-1, keepdims=True)
        norm = params["k_index_norm"]
        ki = ((ki - mu) * jax.lax.rsqrt(var + self.eps)
              * norm["weight"].astype(jnp.float32)
              + norm["bias"].astype(jnp.float32)).astype(x.dtype)
        ki = self._index_rope(ki[:, None], positions)[:, 0]
        wi = _proj(x, params["w_index"]).astype(jnp.float32) \
            * (hi * di) ** -0.5
        return qi, wi, ki

    def apply_decode_pages(self, params, x_t, cache, pages, pos, active):
        """``GroupedQueryAttention.apply_decode_pages``'s contract with the
        third pool (writes of inactive rows and unmapped positions go to
        the trash page in all three).  One token a row reads its row's
        ``kI`` over its pages, and of K and V only the rows it selected.
        Returns (y (B, S, E), cache')."""
        from bigdl_tpu.ops import sparse_attention as sa
        from bigdl_tpu.ops.attention import paged_pool_dims
        s = x_t.shape[1]
        positions = jnp.asarray(pos)[:, None] + jnp.arange(s)    # (B, S)
        with jax.named_scope("qkv"):
            q, k, v = self._qkv(params, x_t, positions)
        with jax.named_scope("index.qk"):
            qi, wi, ki = self._index_qk(params, x_t, positions)
        pages = jnp.asarray(pages, jnp.int32)
        ps, trash = paged_pool_dims(cache["k"])[0], cache["k"].shape[0] - 1
        phys, offs = _page_slots(pages, positions, ps, trash, active)
        with jax.named_scope("kv.write"):
            ck = _write_rows(cache["k"], k, phys, offs)
            cv = _write_rows(cache["v"], v, phys, offs)
            cki = _write_rows(cache["ki"], ki[:, None], phys, offs)
        if s > 1:
            o = sa.sparse_prefill(q, k, v, qi, wi, ki, self.topk,
                                  self.scale, self.block)
        else:
            with jax.named_scope("indexer"):
                scores = sa.paged_index_scores(qi[:, 0], wi[:, 0], cki,
                                               pages, positions[:, 0])
            with jax.named_scope("select"):
                idx, chosen = sa.select_topk(scores, self.topk)
            with jax.named_scope("attn.sparse"):
                o = sa.gathered_attention(q[:, :, 0], ck, cv, pages, idx,
                                          chosen, positions[:, 0],
                                          self.scale,
                                          self.num_kv_heads)[:, :, None]
        return self._out(params, o.astype(x_t.dtype)), \
            {"k": ck, "v": cv, "ki": cki}

    def apply(self, params, state, input, *, training=False, rng=None):
        """A whole sequence from position 0; nothing kept."""
        from bigdl_tpu.ops.sparse_attention import sparse_prefill
        b, s, _ = input.shape
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        q, k, v = self._qkv(params, input, positions)
        qi, wi, ki = self._index_qk(params, input, positions)
        o = sparse_prefill(q, k, v, qi, wi, ki, self.topk, self.scale,
                           self.block)
        return self._out(params, o.astype(input.dtype)), state
