"""Controls of the looped cell's ``correct``: ``python
benchmark/looped_control.py --variant fewer_passes --workload
ouro_2_6b.short_reason_closed --seed 11 --seconds 10``.

The cell as ``run.py`` runs it, with the SERVED model changed in one way
that the plain reference does not share, so that the run's own comparison
has to read ``correct: false``:

* ``fewer_passes``: the stack applied one time fewer than the
  configuration's ``loops``;
* ``shared_pool``: every pass's layer ``l`` writes and reads pass 1's pool
  of layer ``l`` (one cache for all passes, which holds a quarter of the
  bytes and is a different model);
* ``kv_f8``: every key and value rounded to float8 (e4m3) on its way into
  the pool, the step of precision below the configuration's bf16 cache.

Not part of a benchmark run: the builder of the configuration runs it on
the chip (``PERF.md``), and ``tests/benchmark_harness`` holds each variant
at a toy size."""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

VARIANTS = ("fewer_passes", "shared_pool", "kv_f8")


def apply_variant(name: str, config: dict) -> None:
    """Change the served model as ``name`` says (``config`` is the cell's,
    changed in place where the variant is a setting)."""
    import jax.numpy as jnp

    from bigdl_tpu.models import looped
    from bigdl_tpu.nn import attention

    if name == "fewer_passes":
        config["model"]["kwargs"]["loops"] -= 1
    elif name == "shared_pool":
        looped.LoopedLM._pool = lambda self, t, l: l
    elif name == "kv_f8":
        real = attention._write_rows

        def rounded(pool, new, *a, **kw):
            return real(pool, new.astype(jnp.float8_e4m3fn), *a, **kw)

        attention._write_rows = rounded
    else:
        raise ValueError(f"variant {name!r}: one of {VARIANTS}")


def main(argv=None) -> int:
    import argparse

    from benchmark import cells, run
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--variant", required=True, choices=VARIANTS)
    args, rest = ap.parse_known_args(argv)
    real = cells.load_cell

    def load_cell(root, workload):
        cell = real(root, workload)
        apply_variant(args.variant, cell.config)
        return cell

    cells.load_cell = load_cell
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main())
