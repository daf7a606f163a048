"""``python benchmark/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>``, from the root of a checkout.

One run of one cell of ``BENCHMARK.json`` on the machine it is started
on.  Everything worth reading goes on earlier lines; the last line of the
standard output is the contract's one JSON object.  Without a ``tpu``
backend, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result."""

from __future__ import annotations

import time

T0 = time.monotonic()       # process start, as near as Python can stamp it

import argparse             # noqa: E402
import os                   # noqa: E402
import sys                  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

RUNNERS = {"train": "benchmark.train_cell", "closed": "benchmark.serve_cell",
           "open": "benchmark.serve_cell"}


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=os.path.join(ROOT, ".bench_out"),
                    help="where a traced run keeps its profile, ledger "
                         "and extracted event list")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    import importlib

    from benchmark import cells, harness
    args = parse(argv)
    if args.trace:
        # a traced run switches the program's run ledger on to read its
        # spans back; the XLA cost analysis the ledger would add (one
        # extra compile per program) stays off.  Set before jax starts a
        # thread that could read the environment.
        os.environ["BIGDL_TPU_COSTS"] = "0"
    try:
        cell = cells.load_cell(ROOT, args.workload)
    except (KeyError, OSError, ValueError) as e:
        harness.die(str(e))
    try:
        import bigdl_tpu  # noqa: F401
    except ImportError as e:
        harness.die(f"the program is not in this checkout: {e}")
    seconds = args.seconds if args.seconds is not None \
        else float(cells.load_benchmark(ROOT)["run_seconds"])

    harness.say(f"benchmark: cell {cell.name} seed {args.seed} seconds "
                f"{seconds} trace {args.trace}")
    started = harness.start_jax(cell.chips)
    device, peak = started["device"], started["peaks"]
    meter = harness.CompileMeter().install()
    run = harness.new_run(
        root=ROOT, cell=cell, seed=args.seed, seconds=seconds,
        trace_on=bool(args.trace), out_dir=args.out, t0=T0, peaks=peak,
        meter=meter, device=device)
    os.makedirs(args.out, exist_ok=True)

    importlib.import_module(RUNNERS[cell.traffic["kind"]]).run(run)

    run.compile = {
        "setup_s": meter.seconds_before(run.window[0]),
        "window": meter.count_between(run.window[0], run.window[1])}
    # a serving cell reads the peak when its window closes, before the
    # reference runs on the same chip: a process's peak never falls again
    device["memory_peak_bytes"] = run.memory_peak_bytes \
        or harness.memory_peak_bytes()
    harness.say(f"device: {device}; set-up {run.setup_s:.1f} s of which "
                     f"compile or cache loads {run.compile['setup_s']:.1f} s "
                     f"(cache hits {meter.hits} misses {meter.misses}); "
                     f"compiles inside the window {run.compile['window']}")
    harness.say(harness.memory_line())
    run.e2e["setup_s"] = run.setup_s
    if run.trace_on:
        from benchmark import report
        metrics = report.traced(run, device)
    else:
        metrics = {m["name"]: {"value": float(run.e2e[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end
                   if run.e2e.get(m["name"]) is not None}
    if run.compile["window"]:
        harness.say("NOT steady: something compiled inside the window")
    # what decided `correct`, as the last lines of the standard error too
    for name, (value, limit) in run.compared.items():
        print(f"compared: {name} {value} limit {limit}", file=sys.stderr,
              flush=True)
    print(harness.result_line(run, metrics, device), flush=True)
    if getattr(run, "hard_exit", False):
        sys.stderr.flush()
        os._exit(0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
