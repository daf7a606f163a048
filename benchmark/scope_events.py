"""Device operations with the scope path the program gave them
(``jax.named_scope``: the step's ``forward`` / ``update`` / ``guard``, the
containers' module names, the server's ``embed`` / ``attn`` / ``kv.write``
/ ``attn.paged`` / ``mlp`` / ``logits`` / ``sample``).

``trace_capture.extract`` keeps an operation's instruction name and drops
the rest, so this opens the newest ``.xplane.pb`` of the traced slice
itself.  The path is the instruction's ``op_name``.  On a TPU v5e it is
NOT in the event's name (the whole HLO instruction, but without its
``metadata={...}``) nor among the event's own stats, which is all that
``jax.profiler.ProfileData`` shows; the profiler keeps it as the ``tf_op``
stat of the event's METADATA (``jit(prefill)/block_20/attn/kv.write/
scatter:``), so the file's wire format is read here directly (first chip
call of PR 24).  A program without scopes (the parent of the PR that added
them) still gives paths, ``jit(step)/...`` and primitive names; the
readers then find none of their scopes and report nothing.  The compiler's
own operations carry the path of the argument they relayout
(``cache[3]['k']``), a bare primitive's name, or nothing.

``ops(run)`` -> ``[[name, category, path, start_ns, dur_ns], ...]`` of the
first device, control flow left out as in ``trace_reduce.Reduced``; kept
on ``run.scope_ops`` (a test or a fixture sets that and no profile is
opened).  The first call of a run also prints the device seconds by
top-level scope on an earlier line and keeps the list beside the event
list, for cutting fixtures."""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

from benchmark import harness, trace_capture
from benchmark.trace_reduce import CONTAINERS, op_group, top

# ``jit(step)``, ``jvp(forward)``, ``transpose(jvp(forward))``: how a
# component was traced, around what it names
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
_FLOW = {"while", "body", "cond", "body_fun", "cond_fun", "scan", "branch",
         "closed_call", "checkpoint", "remat", "core_call",
         "custom_jvp_call", "custom_vjp_call", "pallas_call"}
_BLOCK = re.compile(r"^block_\d+$")
# the attention layer's cache write and its paged read (``nn/attention.py``)
KV_SCOPES = ("attn.paged", "kv.write")


# -- the profile's file, read directly -------------------------------------------
#
# ``jax.profiler.ProfileData`` gives an event's own stats and not those of
# its metadata, where the profiler keeps ``tf_op`` (the instruction's
# ``op_name``); so the few messages of ``xplane.proto``
# that matter are read from the wire format here (field numbers of XSpace,
# XPlane, XLine, XEvent, XEventMetadata, XStatMetadata, XStat).

def _varint(buf, i):
    out = shift = 0
    while True:
        c = buf[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if not c & 0x80:
            return out, i


def _fields(buf):
    """(field number, wire type, value) of one message: a varint's value,
    or the bytes of a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            val, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire}")
        yield key >> 3, wire, val


def _map_entry(buf) -> bytes:
    """The value (field 2) of one ``map<int64, Message>`` entry."""
    return next((v for f, _w, v in _fields(buf) if f == 2), b"")


def read_profile(xplane_path: str) -> Tuple[List[list], dict]:
    """Every operation of the first device plane that ran any, with its
    path, and what the probe saw (``PERF.md`` quotes it)."""
    opener = gzip.open if xplane_path.endswith(".gz") else open
    with opener(xplane_path, "rb") as f:
        space = memoryview(f.read())
    planes = []
    for f, _w, plane in _fields(space):
        if f == 1:
            name = next((bytes(v).decode() for pf, _pw, v in _fields(plane)
                         if pf == 2), "")
            if name.startswith(trace_capture.DEVICE_PLANE):
                planes.append((name, plane))
    for _name, plane in sorted(planes, key=lambda p: p[0]):
        listed, probe = _read_plane(plane)
        if listed:
            return listed, probe
    return [], {"routes": {}, "stat_keys": [], "sample": None}


def _read_plane(plane) -> Tuple[List[list], dict]:
    stat_names: Dict[int, str] = {}
    metas: Dict[int, bytes] = {}
    lines = []
    for f, _w, v in _fields(plane):
        if f == 5:
            d = {mf: mv for mf, _mw, mv in _fields(_map_entry(v))}
            stat_names[d.get(1, 0)] = bytes(d.get(2, b"")).decode()
        elif f == 4:
            entry = _map_entry(v)
            mid = next((mv for mf, _mw, mv in _fields(entry) if mf == 1), 0)
            metas[mid] = entry
        elif f == 3:
            lines.append(v)
    tf_op = next((i for i, n in stat_names.items() if n == "tf_op"), None)

    def describe(entry) -> Tuple[str, str]:
        """An event metadata's name (the HLO instruction) and ``tf_op``."""
        name = path = ""
        for mf, _mw, mv in _fields(entry):
            if mf == 2:
                name = bytes(mv).decode()
            elif mf == 5:
                d = {sf: sv for sf, _sw, sv in _fields(mv)}
                if d.get(1) == tf_op:       # a string, or a reference to one
                    path = bytes(d[5]).decode() if 5 in d \
                        else stat_names.get(d.get(7), "")
        return name, path.rstrip(":")

    out: List[list] = []
    probe = {"routes": {}, "sample": None,
             "stat_keys": sorted(stat_names.values())}
    described: Dict[int, tuple] = {}
    for line in lines:
        d = {}
        events = []
        for lf, _lw, lv in _fields(line):
            if lf == 4:
                events.append(lv)
            elif lf in (2, 3):
                d[lf] = lv
        if bytes(d.get(2, b"")).decode() != trace_capture.OP_LINE:
            continue
        t0_ps = int(d.get(3, 0)) * 1000
        for ev in events:
            e = {ef: evv for ef, ew, evv in _fields(ev) if ew == 0}
            mid = e.get(1, 0)
            if mid not in described:
                text, path = describe(metas.get(mid, b""))
                # the category as ``trace_capture.extract`` finds it (a
                # fusion's kind, a custom call's target), so that
                # ``op_group`` groups as the accepted breakdown does
                name, cat = trace_capture.parse_op(text)
                described[mid] = (name, cat, path)
                if probe["sample"] is None and path and "fusion" in name:
                    probe["sample"] = {"name": text[:300],
                                       "stats": {"tf_op": path + ":"}}
            name, cat, path = described[mid]
            route = "metadata stat tf_op" if path else "none"
            probe["routes"][route] = probe["routes"].get(route, 0) + 1
            out.append([name, cat, path,
                        (t0_ps + e.get(2, 0)) / 1e3, e.get(3, 0) / 1e3])
    return out, probe


def newest_profile(out_dir: str) -> Optional[str]:
    paths = glob.glob(os.path.join(out_dir, "profile", "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def ops(run) -> List[list]:
    """The run's scoped operations (see the module's docstring)."""
    cached = getattr(run, "scope_ops", None)
    if cached is not None:
        return cached
    run.scope_ops = []
    path = newest_profile(getattr(run, "out_dir", "") or "")
    if path is None:
        return run.scope_ops
    try:
        listed, probe = read_profile(path)
    except Exception as e:              # a reader never takes the run down
        harness.say(f"scopes: the profile could not be read: {e!r}")
        return run.scope_ops
    run.scope_ops = [o for o in listed
                     if not o[0].lstrip("%").startswith(CONTAINERS)]
    tr = getattr(run, "trace", None)
    if tr is not None and tr.devices:
        label_edges(run.scope_ops,
                    next(iter(tr.devices.values()))["modules"])
    run.scope_probe = probe
    _report(run, probe)
    return run.scope_ops


EXIT, ENTRY = "(exit)", "(entry)"


def label_edges(listed: List[list], modules: Sequence[Sequence]) -> None:
    """Inside each run of a program (``[name, start_ns, dur_ns]``), the
    operations the compiler left without any path AFTER the last named
    one are the relayout of the program's results (a donated page pool
    copied back into the layout it came in); those before the first named
    one prepare its arguments.  They are marked ``(exit)`` / ``(entry)``
    in place: by position, since the compiler gave them no name."""
    by_start = sorted(range(len(listed)), key=lambda i: listed[i][3])
    starts = [listed[i][3] for i in by_start]
    import bisect
    for _name, start, dur in modules:
        lo = bisect.bisect_left(starts, start)
        hi = bisect.bisect_left(starts, start + dur)
        inside = by_start[lo:hi]
        named = [k for k, i in enumerate(inside) if listed[i][2]]
        if not named:
            continue
        for k, i in enumerate(inside):
            if not listed[i][2]:
                if k > named[-1]:
                    listed[i][2] = EXIT
                elif k < named[0]:
                    listed[i][2] = ENTRY


# -- paths -> scopes -----------------------------------------------------------

def scopes(path: str) -> List[str]:
    """The named scopes of a path, outermost first.  Left out: the
    functions jax traced (``jit(step)``, ``jit(log_softmax)``), control
    flow (``while/body/closed_call``) and the trailing primitive's own
    name.  A scope seen through autodiff keeps its name (``jvp(forward)``
    -> ``forward``), and its gradient (``transpose(jvp(forward))``) reads
    ``backward``."""
    parts = [p for p in path.split("/") if p]
    out: List[str] = []
    for i, p in enumerate(parts):
        inner: Optional[str] = p
        backward = False
        while inner is not None:
            m = _WRAPPED.match(inner)
            if not m:
                break
            backward |= m.group(1) == "transpose"
            inner = None if m.group(1) in ("jit", "pjit") else m.group(2)
        if inner is None or inner in _FLOW:
            continue
        if inner == p and i == len(parts) - 1:
            continue                    # the primitive's own name
        out.append("backward" if backward else inner)
    return out


def has_scope(path: str, names: Sequence[str]) -> bool:
    return any(n in names for n in scopes(path))


def top_level(path: str) -> str:
    """The breakdown's key of one operation: its first named scope; a
    transformer block's number is left out and its half kept
    (``block_7/attn/kv.write`` -> ``attn/kv.write``)."""
    if path in (EXIT, ENTRY):
        return path.strip("()")
    sc = [s for s in scopes(path) if not _BLOCK.match(s)]
    if not sc:
        # the compiler's own operations: a relayout of one of the jitted
        # function's arguments carries the argument's path
        # (``cache[3]['k']``), others carry a bare primitive or nothing
        return "arg:" + path.split("[")[0] if "[" in path \
            and "/" not in path else "unscoped"
    if sc[0] in ("attn", "mlp") and len(sc) > 1:
        return "/".join(sc[:2])
    return sc[0]


def module_path(path: str) -> Optional[str]:
    """For a training step: the module an operation of the forward or the
    backward pass belongs to, two components deep
    (``inception_3a/output``, ``conv1/7x7_s2``)."""
    sc = scopes(path)
    if len(sc) < 2 or sc[0] not in ("forward", "backward"):
        return None
    return "/".join(sc[1:3])


def seconds_by(listed: Sequence[list], key) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for _n, _c, path, _s, d in listed:
        k = key(path)
        if k is not None:
            out[k] = out.get(k, 0.0) + d / 1e9
    return out


def named_share(listed: Sequence[list], compiler: bool = False
                ) -> Optional[float]:
    """Share of the device time that falls under a named scope (with
    ``compiler``: or under what this module can name of the compiler's own
    operations, the relayouts of named arguments and of the results)."""
    total = sum(o[4] for o in listed)
    if not total:
        return None
    keys = [(top_level(o[2]), o[4]) for o in listed]
    named = sum(d for k, d in keys if k != "unscoped" and (
        compiler or not k.startswith(("arg:", "exit", "entry"))))
    return named / total


def copy_share_pct(run, program: str, names: Sequence[str]
                   ) -> Optional[float]:
    """Share (%) of the device time inside the runs of ``program`` spent
    in copy and layout operations whose path holds one of ``names``; None
    where no operation of the slice carries any of them (a program
    without the scopes)."""
    listed = ops(run)
    if run.trace is None or not listed:
        return None
    if not any(has_scope(o[2], names) for o in listed):
        return None
    runs = run.trace.runs(program)
    inside = [o for o in listed if any(a <= o[3] < b for a, b in runs)]
    total = sum(o[4] for o in inside)
    if not total:
        return None
    copies = sum(o[4] for o in inside
                 if op_group(o[0], o[1]) == "copies and layout"
                 and has_scope(o[2], names))
    return 100.0 * copies / total


def _report(run, probe: dict) -> None:
    listed = run.scope_ops
    harness.say(f"scopes: {len(listed)} device operations, path found by "
                f"{probe['routes']}; {len(probe['stat_keys'])} stats keys, "
                f"sample {probe['sample']}")
    if not listed:
        return
    harness.say(f"scopes: device seconds by top-level scope "
                f"{top(seconds_by(listed, top_level), 16)}; under a named "
                f"scope {100 * named_share(listed):.1f}% of device time, "
                f"with the relayouts of named arguments and of results "
                f"{100 * named_share(listed, compiler=True):.1f}%")
    modules = seconds_by(listed, module_path)
    if modules:
        harness.say(f"scopes: ten heaviest module paths (forward and "
                    f"backward together) {top(modules, 10)}")
    copies = [o for o in listed
              if op_group(o[0], o[1]) == "copies and layout"]
    if copies:
        harness.say(f"scopes: copies and layout by scope "
                    f"{top(seconds_by(copies, top_level), 8)}")
    out_dir = getattr(run, "out_dir", None)
    if out_dir and os.path.isdir(out_dir):
        with gzip.open(os.path.join(
                out_dir, f"scope_ops.{run.cell.name}.json.gz"),
                "wt", encoding="utf-8") as f:
            json.dump({"ops": listed, "probe": probe}, f)
