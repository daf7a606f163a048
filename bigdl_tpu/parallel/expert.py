"""Expert parallelism — mixture-of-experts with all_to_all token routing.

The reference's closest concept is the LOCAL mixture (``nn/MixtureTable``,
gates x experts summed on one node); there is no expert parallelism at that
version (SURVEY.md section 2.7).  This module adds the distributed form
that completes the dp/tp/sp/pp/ep mesh story: experts live one-per-device
on an "expert" mesh axis, tokens are routed to their top-k experts with a
pair of ``lax.all_to_all``s (dispatch + return), and everything is static-
shaped via the standard capacity-factor design so XLA compiles one program.

Design (Switch-Transformer top-1 / GShard top-k, sized for ICI):

1. router: logits = x @ Wg -> top-k expert ids + combine gates per token
   (k=1: the raw softmax prob, Switch style; k>=2: probs renormalised
   over the k winners, GShard/Mixtral style)
2. capacity C = ceil(tokens/experts * capacity_factor); per-expert
   position by cumulative count over the SLOT-MAJOR queue (all first
   choices rank ahead of any second choice, so overflow drops k-th
   choices first); slots beyond C are DROPPED (their contribution is the
   zero vector, scaled residual streams pass them through) — drops keep
   shapes static, the XLA-first tradeoff
3. dispatch: scatter the k*T slots into an (experts, C, d) buffer,
   all_to_all so each device receives its expert's buffer from every
   peer -> (peers * C, d) local expert batch
4. expert FFN on local batch (one matmul chain, MXU-friendly)
5. return: all_to_all back, gather each slot's result, scale by its
   gate, sum a token's k slots

Everything is differentiable; the router gets gradients through the gate
scaling (straight-through on the hard assignment, the standard
estimator).  ``router_z_loss`` (ST-MoE) is available beside the Switch
load-balance aux loss.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from bigdl_tpu.ops.experts import (EXPERT_FORMS, expert_hidden,
                                   held_experts_hit, hit_kernel_fits)


def top1_route(logits: jnp.ndarray):
    """Softmax router, hard top-1 assignment.

    logits (T, E) -> (expert_id (T,), gate (T,)) with gate = softmax prob
    of the chosen expert (carries router gradients).
    """
    probs = jax.nn.softmax(logits, axis=-1)
    expert_id = jnp.argmax(logits, axis=-1)
    gate = jnp.take_along_axis(probs, expert_id[:, None], axis=1)[:, 0]
    return expert_id, gate


def topk_route(logits: jnp.ndarray, k: int):
    """Softmax router, top-k assignment with normalized combine weights.

    logits (T, E) -> (expert_ids (T, k), gates (T, k)); gates are the
    softmax probabilities of the chosen experts renormalised over the k
    winners (GShard/Mixtral convention).  For k=1 use ``top1_route``
    instead: the normalised top-1 gate is identically 1.0 and would cut
    the router out of the gradient path (Switch keeps the raw prob).
    """
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, ids = jax.lax.top_k(probs, k)          # softmax is monotone
    gates = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return ids, gates


def _route(x, router_w, k):
    """(ids (T, k), gates (T, k)) for any k (top1 keeps the raw prob)."""
    logits = x @ router_w
    if k == 1:
        eid, gate = top1_route(logits)
        return eid[:, None], gate[:, None]
    return topk_route(logits, k)


def _flatten_slots(ids, gates, x):
    """Slot-major flatten of (T, k) routing: ALL first choices rank ahead
    of any second choice in the capacity queue, so overflow drops
    k-th choices first (GShard dispatch order)."""
    k = ids.shape[1]
    flat_ids = ids.T.reshape(-1)                   # (k*T,)
    flat_gates = gates.T.reshape(-1)
    xk = jnp.tile(x, (k, 1))                       # (k*T, d)
    return flat_ids, flat_gates, xk


def router_z_loss(logits, axis_name: Optional[str] = None):
    """ST-MoE router z-loss: mean(logsumexp(logits)^2) over the (global)
    token batch — keeps router logits small so the softmax stays out of
    saturation.  Same psum convention as ``load_balance_loss`` (every
    device returns the identical global value; see that docstring for
    the gradient-scaling argument)."""
    z = jax.nn.logsumexp(logits, axis=-1)
    s = jnp.sum(z * z)
    t = jnp.asarray(z.shape[0], z.dtype)
    if axis_name is not None:
        s = lax.psum(s, axis_name)
        t = lax.psum(t, axis_name)
    return s / t


def dispatch_indices(expert_id: jnp.ndarray, n_experts: int, capacity: int):
    """Per-token slot in its expert's capacity buffer.

    Returns (position (T,), keep (T,)): position = rank of the token among
    same-expert tokens (arrival order); keep = position < capacity.
    """
    one_hot = jax.nn.one_hot(expert_id, n_experts, dtype=jnp.int32)
    # rank within expert: exclusive cumsum over tokens of the one-hot
    ranks = jnp.cumsum(one_hot, axis=0) - one_hot
    position = jnp.sum(ranks * one_hot, axis=-1)
    keep = position < capacity
    return position, keep


def load_balance_loss(probs, expert_id, n_experts: int,
                      axis_name: Optional[str] = None):
    """Switch-Transformer auxiliary load-balancing loss.

    ``L = E * sum_e f_e * P_e`` where ``f_e`` is the fraction of tokens
    hard-routed to expert e and ``P_e`` the mean router probability for
    e.  Minimised (= 1) at a uniform load; differentiable through
    ``P_e``.  Under expert parallelism (``axis_name``), ``f``/``P`` are
    the global-batch means (psum over the shard axis).

    Gradient-scaling note: every device returns the identical GLOBAL aux
    value, and jax transposes ``psum`` to ``psum``, so each device's
    gradient of this loss is n x (its local pathway's true sensitivity).
    A trainer that averages per-device gradients over the n-device axis
    (ours does — ``make_zero1_step`` reduce-scatters with ``count=n``)
    therefore recovers exactly the full global aux gradient: reported
    loss weight and optimized gradient weight agree at ``aux_loss_weight``
    with NO hidden 1/n.  Locked by
    ``tests/test_expert_parallel.py::test_aux_loss_gradient_scaling`` so a
    jax change to psum transpose semantics cannot silently re-weight it.
    """
    one_hot = jax.nn.one_hot(expert_id, n_experts, dtype=probs.dtype)
    f_sum = jnp.sum(one_hot, axis=0)          # (E,) hard counts
    p_sum = jnp.sum(probs, axis=0)            # (E,) prob mass
    t = jnp.asarray(probs.shape[0], probs.dtype)
    if axis_name is not None:
        f_sum = lax.psum(f_sum, axis_name)
        p_sum = lax.psum(p_sum, axis_name)
        t = lax.psum(t, axis_name)
    return n_experts * jnp.sum((f_sum / t) * (p_sum / t))


def routing_stats(x, router_w, n_experts: int, capacity: int,
                  axis_name: Optional[str] = None, k: int = 1):
    """(aux_load_balance_loss, drop_rate) for this batch's routing.

    The load-balance loss always uses the FIRST (argmax) choice — the
    Switch/GShard convention for any k.  The drop rate counts dropped
    (token, slot) pairs over all k slots, mirroring the dispatch path's
    slot-major capacity queue.  Recomputes the (tiny) router matmul —
    inside one jit XLA CSEs it with the dispatch path's, so this costs
    nothing extra at runtime.
    """
    logits = x @ router_w
    probs = jax.nn.softmax(logits, axis=-1)
    expert_id = jnp.argmax(logits, axis=-1)
    aux = load_balance_loss(probs, expert_id, n_experts, axis_name)
    if k == 1:
        _, keep = dispatch_indices(expert_id, n_experts, capacity)
    else:
        ids, _ = topk_route(logits, k)
        _, keep = dispatch_indices(ids.T.reshape(-1), n_experts, capacity)
    dropped = jnp.mean(1.0 - keep.astype(probs.dtype))
    if axis_name is not None:
        dropped = lax.pmean(dropped, axis_name)
    return aux, lax.stop_gradient(dropped)


def moe_apply_local(x, router_w, expert_fn, expert_params, n_experts: int,
                    capacity_factor: float = 1.25, k: int = 1):
    """Single-device MoE (all experts local) — the dense-mesh fallback and
    the numerical reference for the expert-parallel path.

    x (T, d); expert_params: pytree with leading expert axis (E, ...);
    expert_fn(params_e, x_block) -> y_block.  ``k``: top-k routing
    (k=1 Switch gate, k>=2 normalised GShard gates; per-expert capacity
    is unchanged by k, so higher k drops more under skew unless
    ``capacity_factor`` is raised).  Matches the expert-parallel path
    exactly only in the no-drop regime (see
    ``moe_apply_expert_parallel`` on capacity semantics).
    """
    t = x.shape[0]
    capacity = max(1, math.ceil(t / n_experts * capacity_factor))
    ids, gates = _route(x, router_w, k)
    flat_ids, flat_gates, xk = _flatten_slots(ids, gates, x)
    position, keep = dispatch_indices(flat_ids, n_experts, capacity)

    buf = jnp.zeros((n_experts, capacity, x.shape[-1]), x.dtype)
    buf = buf.at[flat_ids, position].add(
        jnp.where(keep[:, None], xk, 0.0))
    y_buf = jax.vmap(expert_fn)(expert_params, buf)      # (E, C, d)
    y = y_buf[flat_ids, position]
    y = jnp.where(keep[:, None], y * flat_gates[:, None], 0.0)
    return y.reshape(k, t, -1).sum(axis=0)


def moe_apply_expert_parallel(x, router_w, expert_fn, expert_params,
                              axis_name: str,
                              capacity_factor: float = 1.25, k: int = 1):
    """Expert-parallel MoE inside ``shard_map``: one expert per device on
    ``axis_name``; ``x`` (T_local, d) is this device's token shard;
    ``expert_params`` are this device's expert weights (leading expert
    axis of local size 1, squeezed here).

    Two all_to_alls move only the capacity buffers (E * C * d per device
    each way) over ICI — the token batch itself never gathers.

    Capacity semantics: C = ceil(T_local / E * factor) is PER SOURCE
    DEVICE — each device may send at most C tokens to any one expert (an
    expert's total batch is n_devices * C).  With skewed routing this
    drops a different token set than ``moe_apply_local`` over the gathered
    batch, whose single capacity is computed from the global count; the
    two match exactly only when nothing is dropped (e.g. factor >= E).
    Per-source capacity is the standard distributed-MoE choice: it keeps
    every all_to_all message statically shaped.
    """
    n_experts = lax.psum(1, axis_name)
    expert_params = jax.tree_util.tree_map(lambda p: p[0], expert_params)
    t = x.shape[0]
    capacity = max(1, int(math.ceil(
        t / n_experts * capacity_factor)))

    ids, gates = _route(x, router_w, k)
    flat_ids, flat_gates, xk = _flatten_slots(ids, gates, x)
    position, keep = dispatch_indices(flat_ids, n_experts, capacity)

    # local dispatch buffer: slot [e, c] = this device's token for expert e
    buf = jnp.zeros((n_experts, capacity, x.shape[-1]), x.dtype)
    buf = buf.at[flat_ids, position].add(
        jnp.where(keep[:, None], xk, 0.0))

    # all_to_all: device d sends buf[e] to device e; receives each peer's
    # buffer for ITS expert -> (n_peers, capacity, d_model)
    recv = lax.all_to_all(buf, axis_name, split_axis=0, concat_axis=0,
                          tiled=True)
    y_local = expert_fn(expert_params,
                        recv.reshape(n_experts * capacity, -1))
    y_send = y_local.reshape(n_experts, capacity, -1)
    # return trip: results go back to the owning devices
    y_buf = lax.all_to_all(y_send, axis_name, split_axis=0, concat_axis=0,
                           tiled=True)
    y = y_buf[flat_ids, position]
    y = jnp.where(keep[:, None], y * flat_gates[:, None], 0.0)
    return y.reshape(k, t, -1).sum(axis=0)


# -- module surface -----------------------------------------------------------

from bigdl_tpu.core import init as init_methods            # noqa: E402
from bigdl_tpu.core.module import Module                   # noqa: E402


def _ffn(params, x):
    h = jnp.maximum(x @ params["w1"].T + params["b1"], 0.0)
    return h @ params["w2"].T + params["b2"]


class MixtureOfExperts(Module):
    """Top-k routed MoE FFN over (batch, seq, embed) or (tokens, embed).

    ``k=1`` (default) is the Switch gate (raw softmax prob); ``k>=2``
    uses normalised GShard/Mixtral combine weights, second choices
    dropping first under capacity pressure.  Local by default (every
    expert on-device, the distributed analogue of ``nn/MixtureTable``);
    pass ``axis_name`` and apply inside shard_map with expert-sharded
    params for expert parallelism.  ``router_z_loss_weight`` adds the
    ST-MoE z-loss beside the Switch load-balance aux loss.
    """

    def __init__(self, embed_dim: int, hidden_dim: int, n_experts: int,
                 capacity_factor: float = 1.25,
                 axis_name: Optional[str] = None,
                 init_method: str = init_methods.XAVIER,
                 aux_loss_weight: float = 0.01,
                 k: int = 1,
                 router_z_loss_weight: float = 0.0):
        super().__init__()
        assert 1 <= k <= n_experts, (k, n_experts)
        self.embed_dim = embed_dim
        self.hidden_dim = hidden_dim
        self.n_experts = n_experts
        self.capacity_factor = capacity_factor
        self.axis_name = axis_name
        self.init_method = init_method
        # Switch-Transformer default; without it a top-1 router collapses
        # onto few experts and the capacity drop rate explodes
        self.aux_loss_weight = aux_loss_weight
        self.k = k
        self.router_z_loss_weight = router_z_loss_weight

    def init_state(self):
        # per-batch routing health, threaded like BN running stats; the
        # weighted aux_loss is picked up by the trainers' loss via
        # ``core.module.collect_aux_losses``
        return {"aux_loss": jnp.zeros((), jnp.float32),
                "drop_rate": jnp.zeros((), jnp.float32)}

    def init_params(self, rng):
        ks = jax.random.split(rng, 5)
        e, d, h = self.n_experts, self.embed_dim, self.hidden_dim

        def w(k, shape, fi, fo):
            return init_methods.init_weight(self.init_method, k, shape,
                                            fan_in=fi, fan_out=fo)

        return {
            "router": w(ks[0], (d, e), d, e),
            "experts": {
                "w1": jax.vmap(lambda k: w(k, (h, d), d, h))(
                    jax.random.split(ks[1], e)),
                "b1": jnp.zeros((e, h), jnp.float32),
                "w2": jax.vmap(lambda k: w(k, (d, h), h, d))(
                    jax.random.split(ks[2], e)),
                "b2": jnp.zeros((e, d), jnp.float32),
            },
        }

    def apply(self, params, state, input, *, training=False, rng=None):
        x = input
        shape = x.shape
        x2 = x.reshape(-1, shape[-1])
        if self.axis_name is None:
            y = moe_apply_local(x2, params["router"], _ffn,
                                params["experts"], self.n_experts,
                                self.capacity_factor, self.k)
        else:
            y = moe_apply_expert_parallel(x2, params["router"], _ffn,
                                          params["experts"], self.axis_name,
                                          self.capacity_factor, self.k)
        capacity = max(1, math.ceil(
            x2.shape[0] / self.n_experts * self.capacity_factor))
        aux, drop = routing_stats(x2, params["router"], self.n_experts,
                                  capacity, self.axis_name, self.k)
        aux = self.aux_loss_weight * aux
        if self.router_z_loss_weight:
            aux = aux + self.router_z_loss_weight * router_z_loss(
                x2 @ params["router"], self.axis_name)
        new_state = {"aux_loss": aux.astype(jnp.float32),
                     "drop_rate": drop.astype(jnp.float32)}
        return y.reshape(shape), new_state


# -- routing without drops: the experts one chip HOLDS -------------------------
#
# A served expert layer of a model too large for one chip: the router
# scores every expert of the layer, this chip holds the contiguous share
# ``[expert_offset, expert_offset + experts_held)`` and computes what
# those give for the tokens routed to them; what the absent experts would
# add is left out (their chips add it), the gates are normalised over all
# of a token's chosen experts wherever they live.  No capacity, no drops;
# three forms of one sum, chosen by the call's shapes:
#
# * sorted pairs (past `DENSE_TOKENS` tokens: a prefill): the token-expert
#   pairs sorted by expert and multiplied group by group
#   (``lax.ragged_dot``), whole or block by block (`PAIR_ELEMENTS`);
# * every held expert over every token, a gate of zero where the token
#   was not routed there (a decode step whose tokens hit nearly every
#   held expert: more than `HIT_PAIRS` pairs a held expert expected): it
#   reads each held expert's weights once, which is all a step that hits
#   them all must read, and its time does not follow the routing;
# * the HIT experts alone (a decode step of fewer pairs a held expert,
#   where a third or more of the held experts get none): the Pallas
#   kernel of ``ops/experts.py`` walks the held experts with a pair and
#   reads no other's weights; its time follows how many a step hits.

#: at most this many tokens go through every held expert, or through the
#: hit ones (the product then reads each expert's weights once whatever
#: its tokens; the multiplications it adds stay under the time of that
#: read up to about 240 tokens at these widths on a v5e)
DENSE_TOKENS = 128
#: a call of at most `DENSE_TOKENS` tokens whose pairs a held expert, under
#: even routing (tokens x k / the router's experts), are at most this many
#: reads the hit experts alone: at 1 pair a held expert (``ling3_flash_vl``'s
#: 64 rows of 8 over 512 experts) 37% of them get none, at 2 pairs 14%.
#: Each alone on a v5e at that model's widths (128 held experts of 2560 x
#: 768), dense against hit, ms a layer: 0.5 pairs 2.05 -> 0.92, 1:
#: 2.10 -> 1.40, 2: 2.09 -> 1.94, 3: 2.07 -> 2.02, 4: 2.11 -> 2.07, 6:
#: 2.12 -> 2.11.  Past 2 the kernel spares 1-3% and brings back a time
#: that follows the routing, where the dense form reads every held expert
#: at its floor (``k_exaone_236b`` at 6 pairs, ``nemotron3_super_120b`` at
#: 5.5: 83% and 94% of it), so those take the dense form
HIT_PAIRS = 2
#: past this many elements in the sorted pairs' gathered inputs (T x k x E)
#: the grouped product goes BLOCK by block of sorted pairs and stops at the
#: last pair routed here: a long prefill of wide tokens (8,192 x 8 x 6,144)
#: would otherwise hold every pair's input, hidden and output row at once
#: (3.2 GB) when an eighth of them belong to experts held here.  Below it
#: the whole-sorted product stays, because the blocked one's loop and
#: scatter-add cost more than the rows they spare: each alone on a v5e
#: (PR 33) the blocked one is 4-19% SLOWER at 2^24.3-2^24.6 elements
#: (5.25 -> 5.46, 5.72 -> 6.04, 4.71 -> 5.59 ms) and 11-28% faster from
#: 2^25.3 up (7.60 -> 6.75, 9.34 -> 6.98, 15.5 -> 11.1, 26.9 -> 19.8 ms)
PAIR_ELEMENTS = 1 << 26
PAIR_BLOCK = 4096           # sorted pairs a block


def sigmoid_group_route(scores, bias, k: int, n_group: int, topk_group: int,
                        scale: float = 1.0):
    """Group-limited top-k over sigmoid scores (DeepSeek-V3 form).
    ``scores`` (T, N) float32 in (0, 1); ``bias`` (N,) moves the
    SELECTION only.  The ``n_group`` groups of ``N / n_group`` experts
    are ranked by the sum of their two best biased scores, the best
    ``topk_group`` kept, the best ``k`` experts chosen among those.
    Returns (ids (T, k) int32, gates (T, k) float32): a token's gates are
    its chosen experts' UNBIASED scores over their sum, times ``scale``."""
    t, n = scores.shape
    biased = scores + bias.astype(scores.dtype)
    per = biased.reshape(t, n_group, n // n_group)
    rank = jnp.sum(lax.top_k(per, 2)[0], axis=-1)            # (T, groups)
    keep = lax.top_k(rank, topk_group)[1]
    kept = jnp.zeros((t, n_group), bool).at[
        jnp.arange(t)[:, None], keep].set(True)
    masked = jnp.where(jnp.repeat(kept, n // n_group, axis=1), biased,
                       -jnp.inf)
    ids = lax.top_k(masked, k)[1].astype(jnp.int32)
    chosen = jnp.take_along_axis(scores, ids, axis=1)
    gates = chosen / jnp.sum(chosen, axis=-1, keepdims=True) * scale
    return ids, gates


def hit_form(t: int, k: int, n_experts: int) -> bool:
    """Whether ``held_experts_apply`` takes a call of ``t`` tokens of ``k``
    choices among the router's ``n_experts`` through the hit experts
    alone: few tokens (`DENSE_TOKENS`) sending at most `HIT_PAIRS` pairs a
    held expert under even routing.  A trained router is less even than
    that, so it hits fewer experts than the estimate: the rule errs
    towards the dense form."""
    return t <= DENSE_TOKENS and t * k <= HIT_PAIRS * n_experts


def streams_hit(t: int, k: int, n_experts: int, w_gate_up, w_down) -> bool:
    """`hit_form` where the kernel serves these weights
    (``ops.experts.hit_kernel_fits``): what a program of such a call
    compiles to, and so what a model tells its generator."""
    return hit_form(t, k, n_experts) \
        and hit_kernel_fits(t, w_gate_up, w_down)


def held_experts_apply(x, ids, gates, valid, w_gate_up, w_down,
                       expert_offset: int = 0, form: str = "swiglu",
                       n_experts: Optional[int] = None):
    """What the experts held here add for ``x`` (T, E): ``ids``/``gates``
    (T, k) from the router over ALL experts, ``valid`` (T,) masks padding
    and inactive rows, ``w_gate_up`` (held, E, 2F) and ``w_down`` (held,
    F, E) the held experts' SwiGLU weights (``silu(x Wg) * (x Wu)``
    through ``Wd``); with ``form`` ``"relu2"`` the experts are not gated
    and ``w_gate_up`` is ``Wu`` alone, (held, E, F): ``relu(x Wu)^2``
    through ``Wd``.  Pairs whose expert is absent (or whose token is not
    valid) sort behind every held group and contribute zero.
    ``n_experts``: the router's count of experts (by default the held
    ones are all there are); with the call's shapes it chooses the form
    (see the comment above `DENSE_TOKENS`).

    Returns (y (T, E) in ``x``'s dtype, counters): ``pairs`` routed to
    held experts, ``hit`` held experts with at least one, ``max`` the
    most loaded one's — int32 scalars."""
    assert form in EXPERT_FORMS, form
    t, k = ids.shape
    held, f = w_gate_up.shape[0], w_down.shape[1]
    local = ids - expert_offset
    here = (local >= 0) & (local < held) & valid[:, None]
    key = jnp.where(here, local, held).reshape(-1)           # (T*k,)
    sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
    n_here = jnp.sum(sizes)
    counters = {"pairs": n_here.astype(jnp.int32),
                "hit": jnp.sum(sizes > 0).astype(jnp.int32),
                "max": jnp.max(sizes).astype(jnp.int32)}
    if t <= DENSE_TOKENS:
        # (T, held): a held expert's gate for a token, zero where the
        # token was not routed to it; the gates go in BEFORE the second
        # product, which then contracts over (expert, F) at once
        dense = jnp.zeros((t, held), jnp.float32).at[
            jnp.arange(t)[:, None], jnp.clip(local, 0, held - 1)].add(
            jnp.where(here, gates, 0.0))
        if streams_hit(t, k, n_experts or held, w_gate_up, w_down):
            # the held experts with a pair first: their weights alone
            # come through the kernel
            hit = jnp.argsort(sizes == 0, stable=True)
            y = held_experts_hit(x, dense, hit, counters["hit"], w_gate_up,
                                 w_down, form)
            return y.astype(x.dtype), counters
        h = jnp.einsum("te,gef->tgf", x, w_gate_up,
                       preferred_element_type=jnp.float32)
        h = expert_hidden(h, f, form) * dense[..., None]
        y = jnp.dot(h.astype(x.dtype).reshape(t, -1),
                    w_down.reshape(held * f, -1),
                    preferred_element_type=jnp.float32)
        return y.astype(x.dtype), counters
    order = jnp.argsort(key, stable=True)
    if t * k * x.shape[1] > PAIR_ELEMENTS:
        return _held_pairs_blocked(x, order, sizes, n_here,
                                   jnp.where(here, gates, 0.0), w_gate_up,
                                   w_down, form), counters
    xs = x[order // k]                                       # sorted pairs
    h = lax.ragged_dot(xs, w_gate_up, sizes,
                       preferred_element_type=jnp.float32)
    h = expert_hidden(h, f, form).astype(x.dtype)
    ys = lax.ragged_dot(h, w_down, sizes,
                        preferred_element_type=jnp.float32)
    # rows past the last held group belong to no group: whatever the
    # grouped product left there is not a number anyone asked for
    ys = jnp.where((jnp.arange(t * k) < n_here)[:, None], ys, 0.0)
    y = ys[jnp.argsort(order)].reshape(t, k, -1)             # unsort
    y = jnp.sum(y * jnp.where(here, gates, 0.0)[..., None], axis=1)
    return y.astype(x.dtype), counters


def _held_pairs_blocked(x, order, sizes, n_here, gates, w_gate_up, w_down,
                        form: str = "swiglu"):
    """``held_experts_apply``'s grouped product over the FIRST ``n_here``
    of the pairs sorted by expert (``order``; the pairs of absent experts
    and of padding sort behind them), `PAIR_BLOCK` pairs a turn: a block's
    inputs gathered, multiplied group by group with the part of each
    expert's group that falls inside it, gated and added onto their
    tokens' rows of a float32 sum.  The turns are as many as the pairs
    routed here need, so neither the time nor the temporaries follow the
    pairs routed elsewhere.  ``order`` is padded to whole blocks: a pad
    lies past ``n_here`` and adds nothing."""
    t, k = gates.shape
    f = w_down.shape[1]
    order = jnp.pad(order, (0, -(t * k) % PAIR_BLOCK))
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    flat = gates.reshape(-1)

    def turn(i, y):
        lo = i * PAIR_BLOCK
        pair = jax.lax.dynamic_slice_in_dim(order, lo, PAIR_BLOCK)
        token = pair // k
        part = (jnp.clip(ends, lo, lo + PAIR_BLOCK)
                - jnp.clip(starts, lo, lo + PAIR_BLOCK)).astype(jnp.int32)
        h = lax.ragged_dot(x[token], w_gate_up, part,
                           preferred_element_type=jnp.float32)
        h = expert_hidden(h, f, form).astype(x.dtype)
        ys = lax.ragged_dot(h, w_down, part,
                            preferred_element_type=jnp.float32)
        live = lo + jnp.arange(PAIR_BLOCK) < n_here
        ys = jnp.where(live[:, None], ys * flat[pair][:, None], 0.0)
        return y.at[token].add(ys)

    y = jax.lax.fori_loop(0, -(-n_here // PAIR_BLOCK), turn,
                          jnp.zeros((t, x.shape[1]), jnp.float32))
    return y.astype(x.dtype)
