"""What a ``--trace 1`` run adds: the per-layer metrics by their own
readers, the device's busy time, and the breakdown."""

from __future__ import annotations

import gzip
import json
import os

from benchmark import cells, harness
from benchmark.trace_reduce import top


def traced(run, device: dict) -> dict:
    metrics = cells.read_per_layer(run.root, run.cell, run)
    tr = run.trace
    if tr is None or not tr.devices:
        harness.die("the traced run holds no device operation: the "
                    "profiler's plane or line names changed, or nothing "
                    "ran on the device inside the slice")
    harness.say(f"traced run's own end-to-end readings, for the tracing "
                f"overhead against an untraced run (they are not the "
                f"cell's): {run.e2e}")
    device["busy_s"] = tr.busy_s()
    device["window_s"] = tr.window_s()
    program_spans = [r for r in run.records if r.get("type") == "span"]
    gaps = tr.gaps_by_host_span(tr.host_spans(program_spans))
    run.breakdown = {"device_ops": top(tr.group_seconds()),
                     "idle_gaps": top(gaps)}
    harness.say(f"trace: window {device['window_s']:.3f} s, busy "
                     f"{device['busy_s']:.3f} s, programs "
                     f"{ {k: round(v, 4) for k, v in tr.module_names().items()} }")
    harness.say(f"trace: device seconds by group "
                     f"{run.breakdown['device_ops']}")
    harness.say(f"trace: idle seconds by host span "
                     f"{run.breakdown['idle_gaps']}")
    # the extracted event list, for the reader and for cutting fixtures
    with gzip.open(os.path.join(run.out_dir,
                                f"events.{run.cell.name}.json.gz"),
                   "wt", encoding="utf-8") as f:
        json.dump(tr.events, f)
    return metrics
