"""Operations and bytes of the layer-pattern model's decode step
(``bigdl_tpu/models/hybrid.py``), from the configuration's shapes and the
counters the program puts on its ``serve.decode`` spans.  Every count is a
floor from BELOW: what any implementation of the same layers must move or
multiply, never what this one happens to (``costs.py`` says why: a share
over 100% means the numerator counted too much)."""

from __future__ import annotations

BF16, F32 = 2, 4


def dims(kw: dict) -> dict:
    """The widths the costs need, from the configuration's ``model.kwargs``
    (``vocab`` from ``model.args[0]`` goes in as ``kw["vocab"]``)."""
    layers = [tuple(l) for l in kw["layers"]]
    return {
        "e": kw["embed_dim"], "h": kw["num_heads"], "d": kw["head_dim"],
        "f_dense": kw["ffn_dim"], "f": kw["expert_dim"],
        "n_experts": kw["num_experts"], "vocab": kw["vocab"],
        "latent": kw["latent_dim"], "rope": kw["rope_dim"],
        "nope": kw["nope_dim"], "v": kw["v_dim"],
        "taps": kw.get("conv_taps", 4),
        "kda": sum(1 for m, _ in layers if m == "kda"),
        "mla": sum(1 for m, _ in layers if m == "mla"),
        "dense": sum(1 for _, f in layers if f == "dense"),
        "experts": sum(1 for _, f in layers if f == "experts")}


def resident_matmul_params(d: dict) -> int:
    """Parameters of the matrices EVERY row of a decode step multiplies
    by, whichever experts it is routed to: the mixers' projections, the
    dense feed-forward parts, the routers, the shared experts and the
    head's rows held.  (The embedding is a gather of one row a token.)"""
    e, hd = d["e"], d["h"] * d["d"]
    kda = 3 * hd * e + hd * e + 2 * d["h"] * e + e * hd
    mla = (d["h"] * (d["nope"] + d["rope"]) * e
           + (d["latent"] + d["rope"]) * e
           + d["h"] * (d["nope"] + d["v"]) * d["latent"]
           + e * d["h"] * d["v"])
    return (d["kda"] * kda + d["mla"] * mla
            + d["dense"] * 3 * e * d["f_dense"]
            + d["experts"] * (d["n_experts"] * e + 3 * e * d["f"])
            + d["vocab"] * e)


def expert_bytes(d: dict) -> int:
    """One routed expert's weights: gate, up and down."""
    return 3 * d["e"] * d["f"] * BF16


def expert_pair_flops(d: dict) -> int:
    """One token through one routed expert."""
    return 6 * d["e"] * d["f"]


def state_bytes_per_row_layer(d: dict) -> int:
    """One row's delta-rule state in one layer, float32."""
    return d["h"] * d["d"] * d["d"] * F32


def latent_bytes_per_token(d: dict) -> int:
    return (d["latent"] + d["rope"]) * BF16


def moe_experts_floor_s(pairs: int, hit: int, d: dict, peaks: dict) -> float:
    """The grouped expert product of the steps that counted ``pairs``
    token-expert pairs on ``hit`` experts (both summed over layers and
    steps): the larger of reading each hit expert's weights once and of
    multiplying the pairs."""
    return max(hit * expert_bytes(d) / peaks["hbm_bytes_per_s"],
               pairs * expert_pair_flops(d) / peaks["bf16_flops"])


def kda_state_floor_s(state_rows: int, d: dict, peaks: dict) -> float:
    """``state_rows`` row-steps, each reading and writing its state in
    every delta-rule layer."""
    return state_rows * d["kda"] * 2 * state_bytes_per_row_layer(d) \
        / peaks["hbm_bytes_per_s"]


def mla_read_floor_s(latent_tokens: int, d: dict, peaks: dict) -> float:
    """The latents of ``latent_tokens`` context tokens read once a
    latent-attention layer."""
    return latent_tokens * d["mla"] * latent_bytes_per_token(d) \
        / peaks["hbm_bytes_per_s"]


def step_matmul_flops(row_steps: int, pairs: int, latent_tokens: int,
                      d: dict) -> float:
    """Multiply-adds x 2 of ``row_steps`` decode rows: the resident
    matrices, the routed pairs, and the absorbed latent attention (each
    head scores and sums ``latent_tokens`` latents, and carries its query
    and its context through Wkvb)."""
    absorbed = row_steps * d["mla"] * 2 * d["h"] * d["latent"] \
        * (d["nope"] + d["v"])
    attend = latent_tokens * d["mla"] * 2 * d["h"] \
        * (2 * d["latent"] + d["rope"])
    return (2.0 * row_steps * resident_matmul_params(d)
            + pairs * expert_pair_flops(d) + absorbed + attend)


def step_min_bytes(steps: int, state_rows: int, hit: int, latent_tokens: int,
                   d: dict) -> float:
    """Bytes ``steps`` decode steps cannot avoid reading or writing: the
    resident matrices once a step, each hit expert once, each active
    row's state read and written, the latents read."""
    return (steps * resident_matmul_params(d) * BF16
            + hit * expert_bytes(d)
            + state_rows * d["kda"] * 2 * state_bytes_per_row_layer(d)
            + latent_tokens * d["mla"] * latent_bytes_per_token(d))
