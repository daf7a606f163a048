"""Operations and bytes of the state-space / latent-expert pattern model
(``bigdl_tpu/models/hybrid.py`` mixers ``mamba2`` and ``full``, feed-forward
part ``latent_experts``; the configuration ``nemotron3_super_120b``), from
the configuration's shapes and the counters the program puts on its
``serve.decode`` and ``serve.prefill`` spans.  Every count is a floor from
BELOW: what any implementation of the same layers must move or multiply,
never what this one happens to (``costs.py`` says why: a share over 100%
means the numerator counted too much).

Read by ``benchmark/ssm_trace.py`` for the metrics
``ssm_state_decode_roofline``, ``latent_experts_decode_roofline``,
``gqa2_paged_decode_roofline``, ``ssm_moe_step_mfu_pct``,
``ssm_moe_step_hbm_pct`` and ``ssm_moe_prefill_mfu_pct``; the chunked
scan's own counts (``ssd_chunked_flops``, ``ssd_chunked_bytes``) are for the
builder's measurement of it alone (PERF.md section 5)."""

from __future__ import annotations

BF16, F32 = 2, 4


def dims(kw: dict) -> dict:
    """The widths the costs need, from the configuration's ``model.kwargs``
    (``vocab`` from ``model.args[0]`` goes in as ``kw["vocab"]``)."""
    layers = [tuple(l) for l in kw["layers"]]
    return {
        "e": kw["embed_dim"], "h": kw["num_heads"],
        "hkv": kw.get("num_kv_heads") or kw["num_heads"],
        "d": kw["head_dim"], "f": kw["expert_dim"],
        "f_shared": kw.get("shared_dim") or kw["expert_dim"],
        "latent": kw["latent_size"], "n_experts": kw["num_experts"],
        "held": kw["experts_held"], "vocab": kw["vocab"],
        "ssm_h": kw["ssm_heads"], "ssm_p": kw["ssm_head_dim"],
        "ssm_n": kw["ssm_state"], "ssm_g": kw["ssm_groups"],
        "taps": kw.get("conv_taps", 4),
        "mamba2": sum(1 for m, _ in layers if m == "mamba2"),
        "full": sum(1 for m, _ in layers if m == "full"),
        "experts": sum(1 for _, f in layers if f == "latent_experts")}


# -- parameters ----------------------------------------------------------------

def mamba_matmul_params(d: dict) -> int:
    """``W_in`` (z, xBC and dt) and ``W_out`` of one Mamba-2 block."""
    inner = d["ssm_h"] * d["ssm_p"]
    conv = inner + 2 * d["ssm_g"] * d["ssm_n"]
    return d["e"] * (inner + conv + d["ssm_h"]) + inner * d["e"]


def attention_matmul_params(d: dict) -> int:
    return 2 * d["e"] * d["h"] * d["d"] + 2 * d["e"] * d["hkv"] * d["d"]


def expert_block_resident_params(d: dict) -> int:
    """What every token of an expert block multiplies by: the router, the
    two latent projections and the shared expert (not gated: two matrices)."""
    return (d["n_experts"] * d["e"] + 2 * d["e"] * d["latent"]
            + 2 * d["e"] * d["f_shared"])


def expert_params(d: dict) -> int:
    """One routed expert, not gated: up and down on the latent."""
    return 2 * d["latent"] * d["f"]


def block_params(d: dict) -> dict:
    """Every parameter of one block of each kind, the norms and the small
    vectors too, and of the embedding, the head and the final norm: what
    the configuration's arithmetic states, to the digit."""
    inner = d["ssm_h"] * d["ssm_p"]
    conv = inner + 2 * d["ssm_g"] * d["ssm_n"]
    return {
        "mamba2": mamba_matmul_params(d) + conv * (d["taps"] + 1)
        + 3 * d["ssm_h"] + inner + d["e"],
        "full": attention_matmul_params(d) + d["e"],
        "experts": expert_block_resident_params(d) + d["n_experts"]
        + d["held"] * expert_params(d) + d["e"],
        "ends": 2 * d["vocab"] * d["e"] + d["e"]}


def total_params(d: dict) -> int:
    per = block_params(d)
    return sum(d[k] * per[k] for k in ("mamba2", "full", "experts")) \
        + per["ends"]


def resident_matmul_params(d: dict) -> int:
    """Parameters of the matrices EVERY row of a decode step multiplies
    by, whichever experts it is routed to.  (The embedding is a gather of
    one row a token.)"""
    return (d["mamba2"] * mamba_matmul_params(d)
            + d["full"] * attention_matmul_params(d)
            + d["experts"] * expert_block_resident_params(d)
            + d["vocab"] * d["e"])


# -- a decode step ---------------------------------------------------------------

def expert_bytes(d: dict) -> int:
    return expert_params(d) * BF16


def expert_pair_flops(d: dict) -> int:
    """One token's latent through one routed expert."""
    return 2 * expert_params(d)


def state_bytes_per_row_layer(d: dict) -> int:
    """One row's state-space state in one block, float32."""
    return d["ssm_h"] * d["ssm_p"] * d["ssm_n"] * F32


def state_flops_per_row_layer(d: dict) -> int:
    """One token of the recurrence in one block: a multiply-add a state
    element for the update and one for the read-out."""
    return 4 * d["ssm_h"] * d["ssm_p"] * d["ssm_n"]


def kv_bytes_per_token(d: dict) -> int:
    """One token's keys and values in one attention block."""
    return 2 * d["hkv"] * d["d"] * BF16


def attention_flops_per_key(d: dict) -> int:
    """One query token against one key in one block: every query head's
    score and its weighted value."""
    return 4 * d["h"] * d["d"]


def ssm_state_floor_s(state_rows: int, d: dict, peaks: dict) -> float:
    """``state_rows`` row-steps, each reading and writing its state in
    every Mamba-2 block."""
    return state_rows * d["mamba2"] * 2 * state_bytes_per_row_layer(d) \
        / peaks["hbm_bytes_per_s"]


def latent_experts_floor_s(pairs: int, hit: int, d: dict,
                           peaks: dict) -> float:
    """The held experts' product of the steps that counted ``pairs``
    token-expert pairs on ``hit`` experts (both summed over blocks and
    steps): the larger of reading each hit expert's weights once and of
    multiplying the pairs."""
    return max(hit * expert_bytes(d) / peaks["hbm_bytes_per_s"],
               pairs * expert_pair_flops(d) / peaks["bf16_flops"])


def gqa2_read_floor_s(latent_tokens: int, d: dict, peaks: dict) -> float:
    """The keys and values of ``latent_tokens`` visible context tokens read
    once an attention block."""
    return latent_tokens * d["full"] * kv_bytes_per_token(d) \
        / peaks["hbm_bytes_per_s"]


def step_matmul_flops(row_steps: int, pairs: int, latent_tokens: int,
                      d: dict) -> float:
    """Multiply-adds x 2 of ``row_steps`` decode rows: the resident
    matrices, the routed pairs, the recurrence, and attention over the
    keys the attention block read."""
    return (2.0 * row_steps * resident_matmul_params(d)
            + pairs * expert_pair_flops(d)
            + row_steps * d["mamba2"] * state_flops_per_row_layer(d)
            + latent_tokens * d["full"] * attention_flops_per_key(d))


def step_min_bytes(steps: int, state_rows: int, hit: int, latent_tokens: int,
                   d: dict) -> float:
    """Bytes ``steps`` decode steps cannot avoid reading or writing: the
    resident matrices once a step, each hit expert once, each active row's
    state read and written, the keys and values read."""
    return (steps * resident_matmul_params(d) * BF16
            + hit * expert_bytes(d)
            + state_rows * d["mamba2"] * 2 * state_bytes_per_row_layer(d)
            + latent_tokens * d["full"] * kv_bytes_per_token(d))


# -- a prefill ------------------------------------------------------------------

def prefill_matmul_flops(tokens: int, pairs: int, d: dict) -> float:
    """Multiply-adds x 2 of ONE prefill of ``tokens`` real prompt tokens
    from position 0: the resident matrices a token (the head's for the last
    token alone), the routed pairs the program counted, the recurrence a
    token (the chunked form multiplies more: a floor), and causal attention
    over a token's whole prefix."""
    head = d["vocab"] * d["e"]
    return (2.0 * tokens * (resident_matmul_params(d) - head) + 2.0 * head
            + pairs * expert_pair_flops(d)
            + tokens * d["mamba2"] * state_flops_per_row_layer(d)
            + tokens * (tokens + 1) // 2 * d["full"]
            * attention_flops_per_key(d))


def ssd_chunked_flops(tokens: int, chunk: int, d: dict) -> float:
    """What the chunked scan of ONE block multiplies over ``tokens``
    (whole chunks): a chunk's ``C B^T`` a group, the masked product against
    ``dt x``, the entering state read out, the leaving state summed."""
    h, p, n, g = d["ssm_h"], d["ssm_p"], d["ssm_n"], d["ssm_g"]
    chunks = -(-tokens // chunk)
    per = 2 * chunk * chunk * n * g + 2 * chunk * chunk * p * h \
        + 2 * 2 * chunk * p * n * h
    return float(chunks * per)


def ssd_chunked_bytes(tokens: int, chunk: int, d: dict) -> float:
    """What the chunked scan of ONE block cannot avoid moving: ``x``,
    ``B``, ``C``, ``dt`` read and ``y`` written once (float32, as the
    scan takes and gives them), and the state read and written a chunk."""
    h, p, n, g = d["ssm_h"], d["ssm_p"], d["ssm_n"], d["ssm_g"]
    chunks = -(-tokens // chunk)
    return float(tokens * (2 * h * p + 2 * g * n + h) * F32
                 + chunks * 2 * state_bytes_per_row_layer(d))
