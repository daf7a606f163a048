"""Controls of the sparse-attention cell's ``correct``: ``python
benchmark/dsa_control.py --variant last_keys --workload
keye_vl2_30b_a3b.longdoc_closed --seed 11 --seconds 10``.

The cell as ``run.py`` runs it, with the SERVED model changed in one way
that the plain reference does not share, so that the run's own comparison
has to read ``correct: false``:

* ``last_keys``: every query attends over the LAST ``topk`` keys of its
  row in place of the indexer's picks (the indexer's scores replaced by
  the keys' positions, in the prefill and in decode);
* ``dense``: every query attends over EVERY key before it (the prefill
  keeps all of them, a decode step gathers all of its row's keys and
  values, 2 GB of temporaries at 32 rows of 32,768);
* ``router_bf16``: the router's logits and softmax taken in bfloat16, the
  served dtype, in place of float32 (ties broken as ``lax.top_k`` breaks
  them, by the lower expert).

Not part of a benchmark run: the builder of the configuration runs it on
the chip (``PERF.md``), and ``tests/benchmark_harness`` holds each variant
at a toy size."""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

VARIANTS = ("last_keys", "dense", "router_bf16")


def apply_variant(name: str) -> None:
    """Change the served model as ``name`` says."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.models import hybrid
    from bigdl_tpu.ops import sparse_attention as sa

    if name == "last_keys":
        def by_position(visible):
            at = jnp.broadcast_to(jnp.arange(visible.shape[-1],
                                             dtype=jnp.float32),
                                  visible.shape)
            return jnp.where(visible, at, -jnp.inf)

        def block_scores(qi, wi, ki, start):
            b, q, t = qi.shape[0], qi.shape[1], ki.shape[1]
            seen = jnp.arange(t)[None] <= (start + jnp.arange(q))[:, None]
            return by_position(jnp.broadcast_to(seen, (b, q, t)))

        def paged_scores(qi, wi, pool, pages, positions):
            n = pages.shape[1] * pool.shape[1]
            return by_position(jnp.arange(n)[None] <= positions[:, None])

        sa.block_index_scores = block_scores
        sa.paged_index_scores = paged_scores
    elif name == "dense":
        sa.kth_largest = lambda scores, k: jnp.full(
            scores.shape[:-1], -jnp.inf, scores.dtype)

        def every_key(scores, k):
            idx = jnp.broadcast_to(jnp.arange(scores.shape[-1],
                                              dtype=jnp.int32), scores.shape)
            return idx, scores > -jnp.inf

        sa.select_topk = every_key
    elif name == "router_bf16":
        def route(self, p, x):
            bf = jnp.bfloat16
            probs = jax.nn.softmax(x.astype(bf) @ p["router"].astype(bf).T,
                                   axis=-1)
            top, ids = jax.lax.top_k(probs, self.experts_per_token)
            gates = top.astype(jnp.float32)
            return ids.astype(jnp.int32), \
                gates / gates.sum(-1, keepdims=True) * self.routed_scale

        hybrid.HybridLM._route = route
    else:
        raise ValueError(f"variant {name!r}: one of {VARIANTS}")


def main(argv=None) -> int:
    import argparse

    from benchmark import run
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--variant", required=True, choices=VARIANTS)
    args, rest = ap.parse_known_args(argv)
    apply_variant(args.variant)
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main())
