"""Finds the knee of an open cell again: ``python benchmark/sweep.py
--workload gpt2_xl.score_open --rates 3,4,5,6,7,8 --seconds 20``.

One process, one generator; each rate runs the cell's open loop for
``--seconds`` and prints a row: requests due, resolved, the median and
95th percentile of the time to the first token, the TTFT of the window's
last fifth over its first fifth (a backlog that grows shows as a ratio
well above 1) and the queue depth when the window ended.  The knee is the
highest rate whose backlog does not grow; the cell's ``rate_per_s`` is
four fifths of it, written into the traffic file as a number."""

from __future__ import annotations

import os
import sys
import time

T0 = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    import argparse
    import statistics

    from benchmark import cells, harness, serve_cell
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    cell = cells.load_cell(ROOT, args.workload)
    if cell.traffic["kind"] != "open":
        harness.die("only an open cell has a knee")

    started = harness.start_jax(cell.chips)
    run = harness.new_run(
        root=ROOT, cell=cell, seed=args.seed, seconds=args.seconds,
        trace_on=False, out_dir=os.path.join(ROOT, ".bench_out"), t0=T0,
        peaks=started["peaks"], device=started["device"])
    _model, _params, gen = serve_cell.build(run)
    vocab = int(cell.config["model"]["args"][0])
    poller = serve_cell.Poller(gen)
    poller.start()
    print("rate due resolved p50_ms p95_ms last_fifth_over_first queue_end",
          flush=True)
    try:
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            cell.traffic["rate_per_s"] = rate
            run.seed = args.seed + i
            serve_cell.run_open(run, gen, poller, vocab)
            ttft = run.samples["ttft_ms"]
            fifth = max(1, len(ttft) // 5)
            growth = statistics.median(ttft[-fifth:]) \
                / statistics.median(ttft[:fifth]) if ttft else float("nan")
            print(f"sweep {rate} {run.attempted} "
                  f"{run.attempted - run.failed} {run.e2e['ttft_p50_ms']} "
                  f"{run.e2e['ttft_p95_ms']} {growth:.2f} "
                  f"{run.counters['queue_depth_end']}", flush=True)
            # let the queue empty before the next rate
            poller.wait_for(lambda st: st["queue_depth"] == 0
                            and st["active"] == 0, 120)
    finally:
        poller.stop()
        gen.drain(timeout=60)
    return 0


if __name__ == "__main__":
    sys.exit(main())
