"""Operations and bytes of the sparse-attention pattern model with held
experts (``bigdl_tpu/models/hybrid.py`` mixer ``dsa`` with a softmax
``experts`` part; the configuration ``keye_vl2_30b_a3b``), from the
configuration's shapes and the counters the program puts on its
``serve.decode`` and ``serve.prefill`` spans.  Every count is a floor from
BELOW: what any implementation of the same layers must move or multiply,
never what this one happens to (``costs.py`` says why: a share over 100%
means the numerator counted too much).  Attention is counted over the keys
a query SELECTED (``keys_selected``), the indexer over the keys it scored
(``keys_indexed``): a layer that reads or multiplies more, such as dense
attention masked to the selection, reads low.

Read by ``benchmark/dsa_trace.py`` for the metrics
``dsa_indexer_decode_roofline``, ``dsa_sparse_decode_roofline``,
``dsa_moe_step_mfu_pct``, ``dsa_moe_step_hbm_pct`` and
``dsa_prefill_mfu_pct``."""

from __future__ import annotations

BF16 = 2
LANES = 128                 # a page pool's row is whole 128-lane tiles


def dims(kw: dict) -> dict:
    """The widths the costs need, from the configuration's ``model.kwargs``
    (``vocab`` from ``model.args[0]`` goes in as ``kw["vocab"]``)."""
    layers = [tuple(l) for l in kw["layers"]]
    return {
        "e": kw["embed_dim"], "h": kw["num_heads"],
        "hkv": kw.get("num_kv_heads") or kw["num_heads"],
        "d": kw["head_dim"], "f": kw["expert_dim"],
        "n_experts": kw["num_experts"], "vocab": kw["vocab"],
        "hi": kw["index_heads"], "di": kw["index_dim"], "topk": kw["topk"],
        "dsa": sum(1 for m, _ in layers if m == "dsa"),
        "experts": sum(1 for _, f in layers if f == "experts")}


def resident_matmul_params(d: dict) -> int:
    """Parameters of the matrices EVERY row multiplies by, whichever
    experts it is routed to: the attention layers' four projections, the
    indexer's three, the routers and the head's rows held.  (The embedding
    is a gather of one row a token.)"""
    e = d["e"]
    attention = 2 * e * d["h"] * d["d"] + 2 * e * d["hkv"] * d["d"]
    indexer = e * (d["hi"] * d["di"] + d["di"] + d["hi"])
    return (d["dsa"] * (attention + indexer)
            + d["experts"] * d["n_experts"] * e + d["vocab"] * e)


def expert_bytes(d: dict) -> int:
    """One routed expert's weights: gate, up and down."""
    return 3 * d["e"] * d["f"] * BF16


def expert_pair_flops(d: dict) -> int:
    """One token through one routed expert."""
    return 6 * d["e"] * d["f"]


def index_key_bytes(d: dict) -> int:
    """One token's indexer key as its pool keeps it: ``di`` channels in
    whole 128-lane tiles (64 -> 128)."""
    return -(-d["di"] // LANES) * LANES * BF16


def kv_bytes_per_token(d: dict) -> int:
    """One token's keys and values in one layer."""
    return 2 * d["hkv"] * d["d"] * BF16


def index_flops_per_key(d: dict) -> int:
    """One query against one key in the indexer: every head's product."""
    return 2 * d["hi"] * d["di"]


def attention_flops_per_key(d: dict) -> int:
    """One query token against one SELECTED key in one layer: every query
    head's score and its weighted value."""
    return 4 * d["h"] * d["d"]


def indexer_read_floor_s(c: dict, d: dict, peaks: dict) -> float:
    """The indexer keys of the ``keys_indexed`` visible tokens read once,
    and each row-step's indexer query, over HBM."""
    q = c["state_rows"] * d["dsa"] * d["hi"] * d["di"] * BF16
    return (c["keys_indexed"] * index_key_bytes(d) + q) \
        / peaks["hbm_bytes_per_s"]


def sparse_read_floor_s(c: dict, d: dict, peaks: dict) -> float:
    """The keys and values of the ``keys_selected`` tokens read once, and
    each row-step's query and output, over HBM."""
    qo = c["state_rows"] * d["dsa"] * 2 * d["h"] * d["d"] * BF16
    return (c["keys_selected"] * kv_bytes_per_token(d) + qo) \
        / peaks["hbm_bytes_per_s"]


def step_matmul_flops(c: dict, d: dict) -> float:
    """Multiply-adds x 2 of the counted decode row-steps: the resident
    matrices, the routed pairs, the indexer over the keys it scored and
    attention over the keys it selected."""
    return (2.0 * c["state_rows"] * resident_matmul_params(d)
            + c["expert_pairs"] * expert_pair_flops(d)
            + c["keys_indexed"] * index_flops_per_key(d)
            + c["keys_selected"] * attention_flops_per_key(d))


def step_min_bytes(c: dict, d: dict) -> float:
    """Bytes the counted decode steps cannot avoid reading: the resident
    matrices once a step, each hit expert once, the indexer keys scored
    and the keys and values selected."""
    return (c["steps"] * resident_matmul_params(d) * BF16
            + c["experts_hit"] * expert_bytes(d)
            + c["keys_indexed"] * index_key_bytes(d)
            + c["keys_selected"] * kv_bytes_per_token(d))


def causal_keys(tokens: int) -> int:
    """Keys a prompt of ``tokens`` scores in one layer's indexer."""
    return tokens * (tokens + 1) // 2


def selected_keys(tokens: int, topk: int) -> int:
    """Keys a prompt of ``tokens`` attends over in one layer: ``min(t + 1,
    topk)`` for each of its queries."""
    full = min(tokens, topk)
    return full * (full + 1) // 2 + (tokens - full) * topk


def prefill_sparse_flops(tokens: int, pairs: int, d: dict) -> float:
    """Multiply-adds x 2 of ONE prefill of ``tokens`` real prompt tokens
    from position 0 as a sparse layer defines it: the resident matrices a
    token (the head's for the last token alone), the routed pairs the
    program counted, the causal indexer and attention over each query's
    selected keys only."""
    head = d["vocab"] * d["e"]
    return (2.0 * tokens * (resident_matmul_params(d) - head) + 2.0 * head
            + pairs * expert_pair_flops(d)
            + d["dsa"] * causal_keys(tokens) * index_flops_per_key(d)
            + d["dsa"] * selected_keys(tokens, d["topk"])
            * attention_flops_per_key(d))
