"""The trainer loop's idle gap, split at the moment the host began to
dispatch the next step (the ``train.dispatch`` span of the program's
tracer, laid on the trace's clock through ``bench.sync``)."""

from __future__ import annotations

import bisect
from typing import List, Optional, Tuple

from benchmark.spans import spans_named


def split_gaps(run) -> Optional[List[Tuple[float, float]]]:
    """For each pair of consecutive runs of the step program, the device's
    idle seconds ``(from the first one's last operation to the start of
    the next step's train.dispatch span, from there to the next one's
    first operation)``.  The span's start is clamped into the gap, so a
    loop that dispatches ahead reads 0 in the first part, never a
    negative.  None without a trace, its clock pair, or the spans (a
    program that does not emit them)."""
    tr = run.trace
    if tr is None or not tr.sync:
        return None
    dispatches = spans_named(run.records, "train.dispatch")
    if not dispatches:
        return None
    off = tr.sync["trace_ns"] - tr.sync["mono_ns"]
    starts = sorted(r["mono"] * 1e9 + off for r in dispatches)
    runs = tr.runs(run.cell.config["programs"]["step"])

    def idle(lo, hi):
        return max(0.0, (hi - lo) / 1e9 - tr.busy_in((lo, hi)))

    out = []
    for a, b in zip(runs, runs[1:]):
        # the dispatch of step b: the last one begun before b ran
        k = bisect.bisect_right(starts, b[0])
        if not k or starts[k - 1] <= a[0]:
            continue
        cut = min(max(starts[k - 1], a[1]), b[0])
        out.append((idle(a[1], cut), idle(cut, b[0])))
    return out or None
