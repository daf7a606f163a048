"""Profiles a short slice of a run and extracts the event list the
reduction (``trace_reduce.py``) works on.

The slice is opened and closed by the runner inside its window.  A
``bench.sync`` annotation carries ``time.monotonic_ns()`` into the trace,
which puts the program's tracer spans (stamped on the monotonic clock) on
the trace's clock."""

from __future__ import annotations

import glob
import os
import re
import shutil
import time
from typing import Optional

DEVICE_PLANE = "/device:"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
ANNOTATION_PREFIX = "bench."


class Slice:
    """``start()`` ... ``stop()`` around a few steps; ``events()`` after."""

    def __init__(self, logdir: str):
        self.logdir = logdir
        self.started = False
        self.stopped = False

    def start(self) -> None:
        import jax
        shutil.rmtree(self.logdir, ignore_errors=True)
        os.makedirs(self.logdir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # no per-call Python events
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.logdir, profiler_options=opts)
        self.started = True
        with jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + "sync",
                                          mono_ns=time.monotonic_ns()):
            pass

    def stop(self) -> None:
        import jax
        if self.started and not self.stopped:
            jax.profiler.stop_trace()
            self.stopped = True

    def events(self) -> Optional[dict]:
        if not self.stopped:
            return None
        paths = glob.glob(os.path.join(self.logdir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            return None
        return extract(max(paths, key=os.path.getmtime))


_INAME = re.compile(r"^%?([^\s=]+)")
_KIND = re.compile(r"kind=(k\w+)")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def parse_op(text: str, hlo_category: str = "") -> tuple:
    """The name and category of one device operation.  On a TPU the
    profiler names an operation by its whole HLO instruction (``%fusion.7
    = bf16[...] fusion(...), kind=kOutput, calls=...``); kept are the
    instruction's own name and, as its category, the profiler's
    ``hlo_category`` where it gives one, else the custom call's target,
    else the fusion's kind."""
    m = _INAME.match(text)
    name = m.group(1) if m else text[:64]
    if hlo_category:
        return name, hlo_category
    target = _TARGET.search(text)
    if target:
        return name, "custom-call " + target.group(1)
    kind = _KIND.search(text)
    return name, kind.group(1) if kind else ""


def extract(xplane_path: str) -> dict:
    """``.xplane.pb`` -> ``{"devices": {plane: {"ops": [[name, category,
    start_ns, dur_ns], ...], "modules": [[name, start_ns, dur_ns], ...]}},
    "host": [[name, start_ns, dur_ns], ...], "sync": {"trace_ns",
    "mono_ns"}}``.  Device planes are those named ``/device:...``; of the
    host plane only the benchmark's own annotations are kept."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    out = {"devices": {}, "host": [], "sync": None}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OP_LINE:
                    for e in line.events:
                        cat = ""
                        for k, v in e.stats:
                            if k == "hlo_category":
                                cat = str(v)
                                break
                        name, cat = parse_op(e.name, cat)
                        dev["ops"].append([name, cat, float(e.start_ns),
                                           float(e.duration_ns)])
                elif line.name == MODULE_LINE:
                    for e in line.events:
                        dev["modules"].append([e.name, float(e.start_ns),
                                               float(e.duration_ns)])
            if dev["ops"] or dev["modules"]:
                out["devices"][plane.name] = dev
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if not e.name.startswith(ANNOTATION_PREFIX):
                        continue
                    out["host"].append([e.name, float(e.start_ns),
                                        float(e.duration_ns)])
                    if e.name == ANNOTATION_PREFIX + "sync":
                        mono = dict(e.stats).get("mono_ns")
                        if mono is not None:
                            out["sync"] = {"trace_ns": float(e.start_ns),
                                           "mono_ns": float(mono)}
    return out
