"""What the readers of the state-space / latent-expert pattern model's
metrics share (``nemotron3_super_120b``): ``floor_share`` over the traced
decode chunks.  The chunks are matched with the program's ``serve.decode``
spans by ``hybrid_trace.traced_chunks`` as it is (it asks for
``latent_tokens``, which this model's spans carry: the context tokens the
attention block read), the device seconds under a pair of scopes are
``hybrid_trace.scope_seconds`` as it is, and the window's prefills
``gqa_trace.window_prefills``; only the widths are this model's own.  A
program without the counters or the scopes gives nothing, and the readers
report nothing."""

from __future__ import annotations

from typing import Optional, Tuple

from benchmark import costs_ssm_moe, hybrid_trace

def dims(run) -> Optional[dict]:
    """This model's widths, or None for a configuration that has no
    state-space layer (another model's cell)."""
    model = run.cell.config["model"]
    kw = dict(model["kwargs"], vocab=model["args"][0])
    if "ssm_heads" not in kw or "latent_size" not in kw:
        return None
    return costs_ssm_moe.dims(kw)


def floor_share(run, floor_s, pair: Optional[Tuple[str, str]] = None
                ) -> Optional[float]:
    """100 x ``floor_s(chunks, dims, peaks)`` over the traced time under
    ``pair`` (or over the chunks' device busy time)."""
    d = dims(run)
    chunks = hybrid_trace.traced_chunks(run) if d else None
    if chunks is None:
        return None
    traced = hybrid_trace.scope_seconds(run, chunks["runs"], pair) if pair \
        else chunks["busy_s"]
    if not traced:
        return None
    return 100.0 * floor_s(chunks, d, run.peaks) / traced
