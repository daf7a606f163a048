"""From the extracted event list (``trace_capture.extract``) to numbers:
busy and idle time, gaps between runs of a program, device time by
operation group, idle gaps by what the host was doing.  Pure Python over
lists, so that a recorded event list is its test."""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "collective")

CONTAINERS = ("while", "conditional", "call")


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Union of intervals as a sorted list of disjoint intervals."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def covered(merged: Sequence[Interval], a: float, b: float) -> float:
    """Length of ``[a, b]`` that the disjoint sorted intervals cover."""
    return sum(max(0.0, min(e, b) - max(s, a)) for s, e in merged
               if e > a and s < b)


_COPIES = {"copy", "slice", "transpose", "bitcast", "dynamic-update-slice",
           "dynamic-slice", "concatenate", "pad", "reshape"}


def op_group(name: str, category: str) -> str:
    """The breakdown's group of one device operation, from its
    instruction name and its category (``trace_capture.parse_op``: the
    profiler's HLO category, a custom call's target or a fusion's kind).
    On a TPU a convolution or matmul runs as an output fusion
    (``kind=kOutput``) with its bias, activation or gradient scaling
    fused in: that kind is the MXU group."""
    n, c = name.lower().lstrip("%"), category.lower()
    if any(n.startswith(k) or k in c for k in COLLECTIVES):
        return "collectives"
    if n.startswith("custom-call") or c.startswith("custom-call"):
        target = category[12:] or "unnamed"
        return "Pallas custom call" if target == "tpu_custom_call" \
            else "custom call " + target
    if n.startswith("select-and-scatter") or "select-and-scatter" in c:
        return "max-pool backward"
    if n.startswith("reduce-window") or "reduce-window" in c:
        return "pool forward and LRN"
    if c == "koutput" or "convolution" in c or c == "dot" \
            or n.startswith(("convolution", "dot")):
        return "MXU convolutions and matmuls"
    base = re.sub(r"(-start|-done)?[.\d]*$", "", n)   # copy-done.287 -> copy
    if c.startswith("copy") or "data formatting" in c or base in _COPIES:
        return "copies and layout"
    if c in ("kloop", "kinput") or "fusion" in c or "fusion" in n \
            or "reduc" in c or "elementwise" in c:
        return "VPU fusions"
    return "other"


class Reduced:
    """One traced slice.  Times are seconds; per-device quantities are
    averaged over the devices that ran anything."""

    def __init__(self, events: dict):
        self.events = events
        # control flow is left out: a ``while`` spans the operations of
        # its body, which are events of their own, and would count the
        # gaps between them as busy
        self.devices = {
            k: {"modules": v["modules"],
                "ops": [o for o in v["ops"]
                        if not o[0].lstrip("%").startswith(CONTAINERS)]}
            for k, v in sorted(events["devices"].items()) if v["ops"]}
        self.host = events.get("host", [])
        self.sync = events.get("sync")

    # -- windows -------------------------------------------------------

    def _ops(self, dev: str) -> List[Interval]:
        return [(s, s + d) for _n, _c, s, d in self.devices[dev]["ops"]]

    def window(self) -> Optional[Interval]:
        """First op start to last op end over all devices, in ns."""
        spans = [iv for d in self.devices for iv in self._ops(d)]
        if not spans:
            return None
        return min(a for a, _ in spans), max(b for _, b in spans)

    def window_s(self) -> float:
        w = self.window()
        return (w[1] - w[0]) / 1e9 if w else 0.0

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over devices."""
        if not self.devices:
            return 0.0
        total = sum(sum(b - a for a, b in merge(self._ops(d)))
                    for d in self.devices)
        return total / len(self.devices) / 1e9

    def idle_pct(self) -> Optional[float]:
        w = self.window_s()
        return 100.0 * (1.0 - self.busy_s() / w) if w > 0 else None

    # -- runs of one program --------------------------------------------

    def module_names(self) -> Dict[str, float]:
        """Program name (run id stripped) -> total seconds, first device."""
        out: Dict[str, float] = {}
        for dev in self.devices.values():
            for name, _s, d in dev["modules"]:
                key = re.sub(r"\(\d+\)$", "", name)
                out[key] = out.get(key, 0.0) + d / 1e9
            break
        return out

    def dominant_module(self) -> Optional[str]:
        names = self.module_names()
        return max(names, key=names.get) if names else None

    def runs(self, program: Optional[str], dev: Optional[str] = None
             ) -> List[Interval]:
        """Intervals (ns) of the runs of the program whose name contains
        ``program`` (None: the program that took most time) on one
        device (default: the first)."""
        if not self.devices:
            return []
        program = program or self.dominant_module()
        if program is None:
            return []
        dev = dev or next(iter(self.devices))
        return sorted((s, s + d) for name, s, d in self.devices[dev]["modules"]
                      if program in name)

    def busy_in(self, iv: Interval, dev: Optional[str] = None) -> float:
        """Busy seconds of one device inside an interval (ns)."""
        dev = dev or next(iter(self.devices))
        return covered(merge(self._ops(dev)), iv[0], iv[1]) / 1e9

    def idle_between_runs(self, program: Optional[str]) -> List[float]:
        """Idle seconds between each run of a program and the next one:
        from one run's end to the next one's start, less whatever other
        operation ran on the device in between."""
        runs = self.runs(program)
        dev = next(iter(self.devices)) if self.devices else None
        busy = merge(self._ops(dev)) if dev else []
        return [max(0.0, (b[0] - a[1]) - covered(busy, a[1], b[0])) / 1e9
                for a, b in zip(runs, runs[1:])]

    def busy_per_run(self, program: Optional[str]) -> List[float]:
        return [self.busy_in(iv) for iv in self.runs(program)]

    # -- operations ------------------------------------------------------

    def group_seconds(self) -> Dict[str, float]:
        """Device seconds by operation group, averaged over devices."""
        out: Dict[str, float] = {}
        for dev in self.devices.values():
            for name, cat, _s, d in dev["ops"]:
                g = op_group(name, cat)
                out[g] = out.get(g, 0.0) + d / 1e9
        n = max(1, len(self.devices))
        return {g: v / n for g, v in out.items()}

    def op_seconds_in(self, needle: str, intervals: Sequence[Interval]
                      ) -> float:
        """Seconds of the operations (first device) whose name or group
        contains ``needle`` and that start inside one of the intervals
        (ns), e.g. the runs of one program."""
        ivs = merge(intervals)
        for dev in self.devices.values():
            return sum(d for name, cat, s, d in dev["ops"]
                       if (needle in name or needle in op_group(name, cat))
                       and any(a <= s < b for a, b in ivs)) / 1e9
        return 0.0

    def exposed_collective_s(self) -> float:
        """Seconds in collective operations while no other operation ran
        on that device, averaged over devices."""
        if not self.devices:
            return 0.0
        total = 0.0
        for dev in self.devices.values():
            coll = [(s, s + d) for n, c, s, d in dev["ops"]
                    if op_group(n, c) == "collectives"]
            rest = merge([(s, s + d) for n, c, s, d in dev["ops"]
                          if op_group(n, c) != "collectives"])
            for a, b in merge(coll):
                total += (b - a) - covered(rest, a, b)
        return total / len(self.devices) / 1e9

    # -- idle gaps by host span --------------------------------------------

    def idle_gaps(self) -> List[Interval]:
        """Idle intervals (ns) of the first device inside the window."""
        if not self.devices:
            return []
        busy = merge(self._ops(next(iter(self.devices))))
        return [(a[1], b[0]) for a, b in zip(busy, busy[1:]) if b[0] > a[1]]

    def gaps_by_host_span(self, spans: Sequence[dict]) -> Dict[str, float]:
        """Idle seconds by the host span open at the time.  ``spans`` are
        ``{"name", "start_ns", "end_ns"}`` on the trace's clock; where
        several are open the one that started last (the innermost) takes
        the time, and time under no span goes to ``none``."""
        out: Dict[str, float] = {}
        marks = sorted(spans, key=lambda s: s["start_ns"])
        for a, b in self.idle_gaps():
            cuts = sorted({a, b} | {t for s in marks
                                    for t in (s["start_ns"], s["end_ns"])
                                    if a < t < b})
            for lo, hi in zip(cuts, cuts[1:]):
                mid = (lo + hi) / 2
                open_ = [s for s in marks
                         if s["start_ns"] <= mid < s["end_ns"]]
                name = open_[-1]["name"] if open_ else "none"
                out[name] = out.get(name, 0.0) + (hi - lo) / 1e9
        return out

    def host_spans(self, program_spans: Sequence[dict] = ()) -> List[dict]:
        """The benchmark's own annotations plus the program's tracer
        spans (``mono`` seconds, ``dur_s``), both on the trace's clock."""
        out = [{"name": n, "start_ns": s, "end_ns": s + d}
               for n, s, d in self.host if not n.endswith(".sync")]
        if self.sync:
            off = self.sync["trace_ns"] - self.sync["mono_ns"]
            for r in program_spans:
                start = r["mono"] * 1e9 + off
                out.append({"name": r["name"], "start_ns": start,
                            "end_ns": start + r.get("dur_s", 0.0) * 1e9})
        return out


def top(d: Dict[str, float], n: int = 10) -> List[List]:
    """The contract's ``[[name, seconds], ...]``, largest first."""
    return [[re.sub(r"[^A-Za-z0-9_.-]+", "_", k), v]
            for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
