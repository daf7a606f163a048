"""Arithmetic from stamps to numbers: percentiles, whole-step and
whole-chunk rates, lateness.  Pure Python, tested on synthetic stamps."""

from __future__ import annotations

import math
import statistics
from typing import List, Optional, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (``ceil(q/100 * n)``), the arithmetic of
    ``bigdl_tpu.observability.report._percentile``; None for no samples."""
    if not values:
        return None
    vals = sorted(values)
    rank = math.ceil(q / 100.0 * len(vals))
    return vals[min(len(vals) - 1, max(0, rank - 1))]


def median(values: Sequence[float]) -> Optional[float]:
    return statistics.median(values) if values else None


def whole_step_rate(stamps: Sequence[float], t_start: float, t_end: float,
                    per_step: float) -> Tuple[Optional[float], int]:
    """Work per second over whole steps.  ``stamps`` are the instants at
    which a step had finished (ascending).  Counted are the steps between
    the first stamp at or after ``t_start`` and the last stamp at or
    before ``t_end``, over the time between those two stamps, so a window
    edge that cuts a step changes nothing.  Returns (rate, steps)."""
    inside = [s for s in stamps if t_start <= s <= t_end]
    if len(inside) < 2 or inside[-1] <= inside[0]:
        return None, 0
    steps = len(inside) - 1
    return steps * per_step / (inside[-1] - inside[0]), steps


def whole_chunk_rate(changes: Sequence[Tuple[float, float]], t_start: float,
                     t_end: float) -> Tuple[Optional[float], float]:
    """Counter units per second between changes of a counter.
    ``changes`` is ``[(instant, counter value), ...]``, one entry per
    observed change (ascending).  The rate is (value at the last change
    in the window - value at the first) over the time between those two
    changes.  Returns (rate, units counted)."""
    inside = [(t, v) for t, v in changes if t_start <= t <= t_end]
    if len(inside) < 2 or inside[-1][0] <= inside[0][0]:
        return None, 0
    units = inside[-1][1] - inside[0][1]
    return units / (inside[-1][0] - inside[0][0]), units


def lateness_ms(sent: Sequence[float], due: Sequence[float]) -> List[float]:
    """How late the generator sent each request, in ms (never negative:
    a request is not sent before it is due)."""
    return [max(0.0, (s - d) * 1e3) for s, d in zip(sent, due)]
