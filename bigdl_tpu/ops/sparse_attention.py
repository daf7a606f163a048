"""DeepSeek Sparse Attention's three steps (DeepSeek-V3.2, arXiv:2512.02556,
section 2.1), over the page pools a serving layer keeps:

* the lightning indexer's scores of a query against keys: ``I[t, s] =
  sum_j w_tj * ReLU(q_tj . k_s)``, ``Hi`` query heads against ONE key head,
  a weighted sum over the heads and no softmax (``index_scores`` for one
  query a row against that row's pages, ``block_index_scores`` for a block
  of a prompt's queries against the prompt's own keys);
* the exact top-``k`` of a row's scores (``select_topk``: the indices a
  decode step gathers; ``kth_largest``: the threshold a prefill masks by,
  the same set except at exact ties);
* softmax attention over the SELECTED keys only: ``gathered_attention``
  reads token rows ``pages[s // ps] * ps + s % ps`` of the pools and no
  other row, ``sparse_prefill`` runs a block of queries over its keys in
  chunks, a key counted where the indexer chose it for that query.

Everything is plain ``jax.numpy``; scores in float32."""

from __future__ import annotations

import jax
import jax.numpy as jnp

_F32 = jnp.float32
_FLOOR = -1e30              # a masked score inside the online softmax


def index_scores(qi, wi, ki, visible):
    """One query a row against that row's keys: ``qi`` (B, Hi, Di), ``wi``
    (B, Hi) float32 (the heads' weights, scales folded in), ``ki`` (B, L,
    Di), ``visible`` (B, L) bool.  (B, L) float32, ``-inf`` where not
    visible (a NaN in an invisible key stays there)."""
    s = jnp.einsum("bhd,bld->bhl", qi, ki, preferred_element_type=_F32)
    scores = jnp.einsum("bhl,bh->bl", jax.nn.relu(s), wi)
    return jnp.where(visible, scores, -jnp.inf)


def paged_index_scores(qi, wi, pool, pages, positions):
    """``index_scores`` of one query a row at ``positions`` (B,) against
    the keys of its pages: the pool ``(P + 1, ps, W)`` of the indexer's
    keys, read through ``pages`` (B, Lp), key slot ``l`` visible iff
    ``l <= position``.  The query is padded to the pool's ``W`` lanes (the
    padding lanes are zero in both), so that the rows are multiplied as
    they were gathered.  (B, Lp * ps) float32."""
    b, lp = pages.shape
    ps, w = pool.shape[1], pool.shape[2]
    keys = pool[pages].reshape(b, lp * ps, w)
    qi = jnp.pad(qi, ((0, 0), (0, 0), (0, w - qi.shape[-1])))
    visible = jnp.arange(lp * ps)[None] <= positions[:, None]
    return index_scores(qi, wi, keys, visible)


def select_topk(scores, k: int):
    """The ``k`` largest of each row of ``scores`` (B, L): their indices
    (B, k) int32 and whether each is a visible key (B, k) bool (a row with
    fewer than ``k`` visible keys selects them all and pads with invisible
    ones).  Exact: ``lax.top_k`` (on a v5e 0.8 ms for 32 rows of 32,768;
    a threshold by `kth_largest` and a compaction of the keys at or above
    it by a binary search over their running count took ten times as
    long inside the decode step)."""
    vals, idx = jax.lax.top_k(scores, min(k, scores.shape[-1]))
    return idx.astype(jnp.int32), vals > -jnp.inf


def gathered_attention(q, k_pool, v_pool, pages, idx, chosen, positions,
                       scale, num_kv_heads: int):
    """Attention of ``q`` (B, H, D), one query a row at ``positions`` (B,),
    over the keys ``idx`` (B, K) of its row and no other: token ``s`` is
    row ``pages[b, s // ps] * ps + s % ps`` of the pools ``(P + 1, ps,
    W)`` seen as token rows, gathered.  A pick where ``chosen`` is false (a
    row with fewer than K visible keys) reads the row's own token, which
    it always chose, and is masked.  Query head ``a`` reads KV head ``a //
    (H / Hkv)``; float32 softmax.  (B, H, D)."""
    b, h, d = q.shape
    ps, w = k_pool.shape[1], k_pool.shape[2]
    idx = jnp.where(chosen, idx, positions[:, None])
    page = jnp.take_along_axis(pages, idx // ps, axis=1)
    rows = page * ps + idx % ps                               # (B, K)

    def gather(pool):
        got = pool.reshape(-1, w)[rows][..., :num_kv_heads * d]
        return got.reshape(b, -1, num_kv_heads, d)

    kk, vv = gather(k_pool), gather(v_pool)
    qg = q.reshape(b, num_kv_heads, h // num_kv_heads, d)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, kk,
                   preferred_element_type=_F32) * scale
    p = jax.nn.softmax(jnp.where(chosen[:, None, None], s, -jnp.inf),
                       axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p.astype(vv.dtype), vv,
                   preferred_element_type=_F32)
    return o.reshape(b, h, d)


# -- a prompt from position 0 ----------------------------------------------------

def block_index_scores(qi, wi, ki, start):
    """A block of queries at positions ``start + [0, Q)`` against all ``T``
    keys of their prompt: ``qi`` (B, Q, Hi, Di), ``wi`` (B, Q, Hi), ``ki``
    (B, T, Di).  (B, Q, T) float32, ``-inf`` where the key lies after the
    query.  One head at a time, so that a ``(Q, T)`` float32 sum is the
    largest thing live, never a ``(Q, Hi, T)``."""
    b, q, hi, _ = qi.shape
    t = ki.shape[1]

    def head(j, acc):
        qj = jax.lax.dynamic_index_in_dim(qi, j, axis=2, keepdims=False)
        wj = jax.lax.dynamic_index_in_dim(wi, j, axis=2, keepdims=True)
        s = jnp.einsum("bqd,btd->bqt", qj, ki, preferred_element_type=_F32)
        return acc + wj * jax.nn.relu(s)

    acc = jax.lax.fori_loop(0, hi, head, jnp.zeros((b, q, t), _F32))
    seen = jnp.arange(t)[None] <= (start + jnp.arange(q))[:, None]
    return jnp.where(seen[None], acc, -jnp.inf)


def kth_largest(scores, k: int):
    """The ``k``-th largest value of each row of ``scores`` (..., L) float32,
    or ``-inf`` where a row holds fewer than ``k`` values: ``x >= `` it
    keeps exactly the row's top ``k`` (more only at exact ties).  A binary
    search over the 32 bits of the values' order-preserving integer image,
    one count of a row's values at or above a candidate a bit: on a v5e
    0.6 ms for 512 rows of 30,720 where ``lax.top_k`` takes 16.6 ms."""
    if k > scores.shape[-1]:
        return jnp.full(scores.shape[:-1], -jnp.inf, scores.dtype)
    bits = jax.lax.bitcast_convert_type(scores.astype(_F32), jnp.int32)
    # negative floats order backwards: flip them; then move the sign
    # bit so that unsigned order is the floats' order
    key = jax.lax.bitcast_convert_type(
        jnp.where(bits < 0, ~bits, bits | jnp.int32(-2 ** 31)), jnp.uint32)

    def bit(j, found):
        cand = found | (jnp.uint32(1) << (31 - j).astype(jnp.uint32))
        enough = jnp.sum(key >= cand[..., None], axis=-1) >= k
        return jnp.where(enough, cand, found)

    found = jax.lax.fori_loop(0, 32, bit,
                              jnp.zeros(scores.shape[:-1], jnp.uint32))
    back = jax.lax.bitcast_convert_type(found, jnp.int32)
    return jax.lax.bitcast_convert_type(
        jnp.where(back < 0, back & jnp.int32(2 ** 31 - 1), ~back), _F32)


def sparse_prefill(q, k, v, qi, wi, ki, topk: int, scale,
                   block: int = 512):
    """Causal attention of a prompt over itself from position 0 in which
    query ``t`` counts key ``s`` only where the indexer put ``s`` among
    its ``topk`` best (every ``s <= t`` while ``t < topk``).  ``q`` (B, H,
    T, D), ``k``, ``v`` (B, Hkv, T, D); the indexer's ``qi`` (B, T, Hi,
    Di), ``wi`` (B, T, Hi), ``ki`` (B, T, Di).  A block of ``block``
    queries at a time: its scores against the prompt's keys, its
    thresholds, then an online softmax over the key chunks at or before
    it (``block`` keys a chunk), the chosen keys counted.  No ``(T, T)``
    array exists.  (B, H, T, D)."""
    b, h, t, d = q.shape
    hkv = k.shape[1]
    g = h // hkv
    block = next(c for c in (block, 256, 128, 64, 32, 16, 8, 4, 2, 1)
                 if t % c == 0)
    qg = q.reshape(b, hkv, g, t, d)

    def rows(i):
        lo = i * block
        with jax.named_scope("indexer"):
            scores = block_index_scores(
                jax.lax.dynamic_slice_in_dim(qi, lo, block, axis=1),
                jax.lax.dynamic_slice_in_dim(wi, lo, block, axis=1), ki, lo)
        with jax.named_scope("select"):
            thr = kth_largest(scores, topk)                   # (B, Q)
        qs = jax.lax.dynamic_slice_in_dim(qg, lo, block, axis=3)

        def chunk(j, carry):
            m, l, acc = carry
            ks, vs = (jax.lax.dynamic_slice_in_dim(a, j * block, block,
                                                   axis=2) for a in (k, v))
            got = jax.lax.dynamic_slice_in_dim(scores, j * block, block,
                                               axis=2)        # (B, Q, C)
            keep = (got >= thr[..., None]) & (got > -jnp.inf)
            s = jnp.einsum("bkgqd,bkcd->bkgqc", qs, ks,
                           preferred_element_type=_F32) * scale
            # a finite floor: a query may have chosen no key of a chunk
            s = jnp.where(keep[:, None, None], s, _FLOOR)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.where(keep[:, None, None],
                          jnp.exp(s - m_new[..., None]), 0.0)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + p.sum(axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bkgqc,bkcd->bkgqd", p.astype(vs.dtype), vs,
                preferred_element_type=_F32)
            return m_new, l, acc

        with jax.named_scope("attn.prefill"):
            init = (jnp.full((b, hkv, g, block), _FLOOR, _F32),
                    jnp.zeros((b, hkv, g, block), _F32),
                    jnp.zeros((b, hkv, g, block, d), _F32))
            _, l, acc = jax.lax.fori_loop(0, i + 1, chunk, init)
            return (acc / l[..., None]).astype(q.dtype)

    out = jax.lax.map(rows, jnp.arange(t // block))   # (N, B, Hkv, G, Q, D)
    return out.transpose(1, 2, 3, 0, 4, 5).reshape(b, h, t, d)
