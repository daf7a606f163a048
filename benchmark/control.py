"""The control of a serving cell's ``correct``: ``python
benchmark/control.py --workload gpt2_xl.chat_closed --seeds 11,12,13``.

The plain reference put in the program's place and computed one step of
precision below what the configuration states (bf16 weights -> int8
weights, one scale per output row, the step a later PR would be tempted
by).  It need not decode: over sequences drawn from the seed at the
configuration's ``max_len``, at each of the last ``tolerance.rows``
positions the token the int8 reference puts first is read under the
float32 reference by the harness's own statistic (its logit below the
maximum, in standard deviations of that position's logits).  The widest
such gap is the control's reading; it has to lie above the
configuration's ``logit_gap_std`` for the limit to hold.  Not part of a
benchmark run: the builder of a benchmark PR runs it on the chip, and
``tests/benchmark_harness`` holds it at a toy size."""

from __future__ import annotations

import importlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

def int8_weights(params):
    """Every matrix rounded to 127 levels either side of zero, one scale
    per row (output channel; a token's row of the embedding), and handed
    back in its own dtype; vectors stay as they are."""
    import jax
    import jax.numpy as jnp

    def q(a):
        if a.ndim < 2:
            return a
        w = a.astype(jnp.float32)
        scale = jnp.max(jnp.abs(w), axis=-1, keepdims=True) / 127.0
        scale = jnp.where(scale > 0, scale, 1.0)
        return (jnp.round(w / scale) * scale).astype(a.dtype)

    return jax.tree_util.tree_map(q, params)


def readings(cfg, params, seed: int):
    """The control's gaps at every compared position of as many sequences
    of the seed as a run compares requests."""
    import numpy as np

    from benchmark.serve_cell import CHECKED, logit_gaps
    reference = importlib.import_module(cfg["reference"])
    heads = int(cfg["model"]["kwargs"]["num_heads"])
    max_len = int(cfg["server"]["max_len"])
    vocab = int(cfg["model"]["args"][0])
    n = int(cfg["tolerance"]["rows"])
    low = int8_weights(params)
    rs = np.random.default_rng([int(seed), 17])
    rows = np.arange(max_len - n, max_len, dtype=np.int32)
    gaps = []
    for _ in range(CHECKED):
        seq = rs.integers(1, vocab + 1, max_len, dtype=np.int32)
        want = np.asarray(reference.logits_at(params, seq, rows, heads=heads))
        got = np.asarray(reference.logits_at(low, seq, rows, heads=heads))
        gaps.extend(logit_gaps(want, got.argmax(axis=-1) + 1))
    return np.asarray(gaps)


def reading(cfg, params, seed: int) -> float:
    """The control's widest gap, the number a run compares."""
    return float(readings(cfg, params, seed).max())


def main(argv=None) -> int:
    import argparse

    from benchmark import cells, harness, serve_cell
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = cells.load_cell(ROOT, args.workload)
    harness.start_jax(cell.chips)
    cfg = cell.config
    limit = float(cfg["tolerance"]["logit_gap_std"])
    for seed in (int(s) for s in args.seeds.split(",")):
        _model, params, _state = serve_cell.weights(cfg, seed)
        gaps = readings(cfg, params, seed)
        worst = float(gaps.max())
        print(f"control {args.workload} seed {seed}: int8 weights read "
              f"{worst:.4f} against the limit {limit}: "
              f"{'not correct' if worst > limit else 'PASSES the limit'}; "
              f"{serve_cell.gaps_line(gaps)}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
