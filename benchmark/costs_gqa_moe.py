"""Operations and bytes of a decode step of the window/full grouped-query
pattern model with held experts (``bigdl_tpu/models/hybrid.py`` mixers
``swa`` and ``full``; the configuration ``k_exaone_236b``), from the
configuration's shapes and the counters the program puts on its
``serve.decode`` spans.  Every count is a floor from BELOW: what any
implementation of the same layers must move or multiply, never what this
one happens to (``costs.py`` says why: a share over 100% means the
numerator counted too much).

Read by ``benchmark/gqa_trace.py`` for the metrics
``gqa_paged_decode_roofline``, ``swa_ring_decode_roofline``,
``held_experts_decode_roofline``, ``gqa_moe_step_mfu_pct``,
``gqa_moe_step_hbm_pct`` and ``gqa_moe_prefill_mfu_pct``."""

from __future__ import annotations

BF16 = 2


def dims(kw: dict) -> dict:
    """The widths the costs need, from the configuration's ``model.kwargs``
    (``vocab`` from ``model.args[0]`` goes in as ``kw["vocab"]``)."""
    layers = [tuple(l) for l in kw["layers"]]
    return {
        "e": kw["embed_dim"], "h": kw["num_heads"],
        "hkv": kw.get("num_kv_heads") or kw["num_heads"],
        "d": kw["head_dim"], "f_dense": kw["ffn_dim"], "f": kw["expert_dim"],
        "n_experts": kw["num_experts"], "vocab": kw["vocab"],
        "window": kw["window"],
        "swa": sum(1 for m, _ in layers if m == "swa"),
        "full": sum(1 for m, _ in layers if m == "full"),
        "dense": sum(1 for _, f in layers if f == "dense"),
        "experts": sum(1 for _, f in layers if f == "experts")}


def resident_matmul_params(d: dict) -> int:
    """Parameters of the matrices EVERY row of a decode step multiplies
    by, whichever experts it is routed to: the attention layers' four
    projections, the dense feed-forward parts, the routers, the shared
    experts and the head's rows held.  (The embedding is a gather of one
    row a token.)"""
    e = d["e"]
    attention = 2 * e * d["h"] * d["d"] + 2 * e * d["hkv"] * d["d"]
    return ((d["swa"] + d["full"]) * attention
            + d["dense"] * 3 * e * d["f_dense"]
            + d["experts"] * (d["n_experts"] * e + 3 * e * d["f"])
            + d["vocab"] * e)


def expert_bytes(d: dict) -> int:
    """One routed expert's weights: gate, up and down."""
    return 3 * d["e"] * d["f"] * BF16


def expert_pair_flops(d: dict) -> int:
    """One token through one routed expert."""
    return 6 * d["e"] * d["f"]


def kv_bytes_per_token(d: dict) -> int:
    """One token's keys and values in one attention layer."""
    return 2 * d["hkv"] * d["d"] * BF16


def attention_flops_per_key(d: dict) -> int:
    """One query token against one key in one layer: every query head's
    score and its weighted value."""
    return 4 * d["h"] * d["d"]


def gqa_read_floor_s(full_tokens: int, d: dict, peaks: dict) -> float:
    """The keys and values of ``full_tokens`` visible context tokens read
    once a full layer."""
    return full_tokens * d["full"] * kv_bytes_per_token(d) \
        / peaks["hbm_bytes_per_s"]


def ring_read_floor_s(window_tokens: int, d: dict, peaks: dict) -> float:
    """The keys and values of ``window_tokens`` visible ring rows read once
    a window layer."""
    return window_tokens * d["swa"] * kv_bytes_per_token(d) \
        / peaks["hbm_bytes_per_s"]


def held_experts_floor_s(pairs: int, hit: int, d: dict, peaks: dict) -> float:
    """The held experts' product of the steps that counted ``pairs``
    token-expert pairs on ``hit`` experts (both summed over layers and
    steps): the larger of reading each hit expert's weights once and of
    multiplying the pairs."""
    return max(hit * expert_bytes(d) / peaks["hbm_bytes_per_s"],
               pairs * expert_pair_flops(d) / peaks["bf16_flops"])


def step_matmul_flops(row_steps: int, pairs: int, full_tokens: int,
                      window_tokens: int, d: dict) -> float:
    """Multiply-adds x 2 of ``row_steps`` decode rows: the resident
    matrices, the routed pairs, and attention over the keys each kind of
    layer read."""
    keys = full_tokens * d["full"] + window_tokens * d["swa"]
    return (2.0 * row_steps * resident_matmul_params(d)
            + pairs * expert_pair_flops(d)
            + keys * attention_flops_per_key(d))


def step_min_bytes(steps: int, hit: int, full_tokens: int,
                   window_tokens: int, d: dict) -> float:
    """Bytes ``steps`` decode steps cannot avoid reading: the resident
    matrices once a step, each hit expert once, the keys and values each
    kind of layer read."""
    keys = full_tokens * d["full"] + window_tokens * d["swa"]
    return (steps * resident_matmul_params(d) * BF16
            + hit * expert_bytes(d) + keys * kv_bytes_per_token(d))


def prefill_matmul_flops(tokens: int, pairs: int, d: dict) -> float:
    """Multiply-adds x 2 of ONE prefill of ``tokens`` real prompt tokens
    from position 0: the resident matrices a token (the head's for the
    last token alone), the routed pairs the program counted, and causal
    attention over a token's whole prefix in a full layer and inside the
    band in a window layer."""
    w = min(tokens, d["window"])
    band = w * (w + 1) // 2 + (tokens - w) * d["window"]
    keys = tokens * (tokens + 1) // 2 * d["full"] + band * d["swa"]
    head = d["vocab"] * d["e"]
    return (2.0 * tokens * (resident_matmul_params(d) - head) + 2.0 * head
            + pairs * expert_pair_flops(d)
            + keys * attention_flops_per_key(d))
