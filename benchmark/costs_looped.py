"""Operations and bytes of a decode step of the looped model
(``bigdl_tpu/models/looped.py``; the configuration ``ouro_2_6b``), from the
configuration's shapes and the counters the program puts on its
``serve.decode`` spans.  Every count is a floor from BELOW: what any
implementation of the same passes must move or multiply, never what this
one happens to (``costs.py`` says why: a share over 100% means the
numerator counted too much).

A pass reads the whole stack's weights once whatever the batch, so a step
of ``P`` passes reads them ``P`` times; the head is read once, after the
last pass.  ``passes`` below is the passes a decode step runs, the
program's ``loop_passes`` over its ``state_rows`` (the row-steps it ran).

Read by ``benchmark/looped_trace.py`` for the metrics
``looped_paged_decode_roofline``, ``looped_step_hbm_pct``,
``looped_step_mfu_pct`` and ``loop_pass_device_ms``."""

from __future__ import annotations

BF16 = 2


def dims(kw: dict) -> dict:
    """The widths the costs need, from the configuration's ``model.kwargs``
    (``vocab`` from ``model.args[0]`` goes in as ``kw["vocab"]``)."""
    return {"e": kw["embed_dim"], "h": kw["num_heads"],
            "hkv": kw.get("num_kv_heads") or kw["num_heads"],
            "d": kw["head_dim"], "f": kw["ffn_dim"],
            "layers": kw["num_layers"], "loops": kw["loops"],
            "vocab": kw["vocab"]}


def layer_matmul_params(d: dict) -> int:
    """Parameters of one layer's matrices: q, k, v, o and the gated MLP's
    three."""
    e = d["e"]
    return (2 * e * d["h"] * d["d"] + 2 * e * d["hkv"] * d["d"]
            + 3 * e * d["f"])


def layer_params(d: dict) -> int:
    """One layer's parameters: its matrices and its four norms."""
    return layer_matmul_params(d) + 4 * d["e"]


def model_params(d: dict) -> int:
    """The whole model: the stack once (every pass reads the same weights),
    the final norm, the embedding and the untied head."""
    return d["layers"] * layer_params(d) + d["e"] + 2 * d["vocab"] * d["e"]


def stack_bytes(d: dict) -> int:
    """The weights one pass reads: every layer's."""
    return d["layers"] * layer_params(d) * BF16


def head_bytes(d: dict) -> int:
    return d["vocab"] * d["e"] * BF16


def kv_bytes_per_token(d: dict) -> int:
    """One token's keys and values in ONE pool (one pass's one layer)."""
    return 2 * d["hkv"] * d["d"] * BF16


def kv_bytes_per_context_token(d: dict) -> int:
    """One token's keys and values across every (pass, layer) pool: what a
    token of context costs the cache."""
    return d["loops"] * d["layers"] * kv_bytes_per_token(d)


def kv_read_bytes(latent_tokens: int, passes: float, d: dict) -> float:
    """The keys and values of ``latent_tokens`` visible context tokens
    (summed over the row-steps) read once in every layer of every pass
    run."""
    return latent_tokens * passes * d["layers"] * kv_bytes_per_token(d)


def step_min_bytes(steps: int, latent_tokens: int, passes: float,
                   d: dict) -> float:
    """Bytes ``steps`` decode steps of ``passes`` passes cannot avoid: the
    stack's weights once a pass, the head once a step, the keys and values
    read."""
    return (steps * (passes * stack_bytes(d) + head_bytes(d))
            + kv_read_bytes(latent_tokens, passes, d))


def step_matmul_flops(row_steps: int, latent_tokens: int, passes: float,
                      d: dict) -> float:
    """Multiply-adds x 2 of ``row_steps`` decode rows of ``passes`` passes:
    every layer's matrices in every pass, the head once, and attention
    (every query head's score and weighted value) over the keys each layer
    of each pass read."""
    attention = 4 * d["h"] * d["d"] * d["layers"] * passes
    return (2.0 * row_steps * (passes * d["layers"] * layer_matmul_params(d)
                               + d["vocab"] * d["e"])
            + latent_tokens * attention)
