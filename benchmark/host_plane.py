"""The program's own spans where the profile put them, joined to the
ledger's records one to one, and the program runs they dispatched and
synced.

While the ledger is on, every span of the program's tracer opens a
``jax.profiler.TraceAnnotation`` that carries the span's id as its
``span`` stat (``bigdl_tpu/observability/tracer.py``, PR 37).  Those
events sit on the traced slice's host plane, on the profile's own clock,
beside the device planes: no clock pair is needed to lay them on the
device's rows.  A program without the stat (the parent of PR 37) gives no
event here, and every reader of this module reports nothing.

``runs(run)`` pairs each span that holds a dispatch and a sync child
(``train.step``, ``serve.decode``, ``serve.prefill``) with the run of its
program on the device, and checks causality on the cell's devices: a
``*.sync`` span cannot end before the run's last operation ends, and a
``*.dispatch`` span cannot start after the run's first operation.  A run
that breaks either rule by more than ``BREAK_NS`` is printed with the size
of the break and marked, and ``fetch_tails_ms`` reads nothing for the whole
slice: the profile misplaces a slice's host plane against its device planes
as a whole (PR 37 saw one slice of four whose every launch read under
0.1 ms), so the runs that keep the rules read a clock that cannot be
trusted either.

No per-layer metric reads the fetch tails: a listed metric has to read on
every traced run, and on a TPU v5 lite from one in four to every traced
slice of a cell broke causality.  The traced run prints them (``tails``)
beside its breakdown."""

from __future__ import annotations

import gzip
import json
import os
import statistics
from typing import Dict, List, Optional

from benchmark import harness, scope_events, spans, trace_capture

SPAN_STAT = "span"
BREAK_NS = 0.05e6           # 0.05 ms
# a span with a dispatch and a sync child: the configuration's program key
# and the two children's names
KINDS = {"train.step": ("step", "train.dispatch", "train.sync"),
         "serve.decode": ("decode", "serve.decode.dispatch",
                          "serve.decode.sync"),
         "serve.prefill": ("prefill", "serve.prefill.dispatch",
                           "serve.prefill.sync")}


def read_host(xplane_path: str) -> List[list]:
    """``[[name, span id, start_ns, dur_ns], ...]`` of every host-plane
    event that carries a ``span`` stat."""
    from jax.profiler import ProfileData
    out: List[list] = []
    data = ProfileData.from_file(xplane_path)
    for plane in data.planes:
        if plane.name != trace_capture.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                sid = dict(e.stats).get(SPAN_STAT)
                if sid is not None:
                    out.append([e.name, int(sid), float(e.start_ns),
                                float(e.duration_ns)])
    return out


def events(run) -> List[list]:
    """The run's span events (``read_host``), kept on ``run.host_events``
    (a test or a fixture sets that and no profile is opened) and beside
    the event list as ``host_spans.<cell>.json.gz``, for cutting
    fixtures."""
    cached = getattr(run, "host_events", None)
    if cached is not None:
        return cached
    run.host_events = []
    out_dir = getattr(run, "out_dir", "") or ""
    path = scope_events.newest_profile(out_dir)
    if path is None:
        return run.host_events
    try:
        run.host_events = read_host(path)
    except Exception as e:              # a reader never takes the run down
        harness.say(f"host plane: the profile could not be read: {e!r}")
        return run.host_events
    if os.path.isdir(out_dir):
        with gzip.open(os.path.join(
                out_dir, f"host_spans.{run.cell.name}.json.gz"),
                "wt", encoding="utf-8") as f:
            json.dump(run.host_events, f)
    return run.host_events


def joined(run) -> Dict[int, dict]:
    """Span id -> ``{"rec", "start_ns", "end_ns"}``: the ledger's record
    and where its own profile event lies, for every event whose id and
    name match a record's."""
    cached = getattr(run, "host_joined", None)
    if cached is not None:
        return cached
    by_id = {r["span"]: r for r in run.records
             if r.get("type") == "span" and "span" in r}
    out = {}
    for name, sid, start, dur in events(run):
        rec = by_id.get(sid)
        if rec is not None and rec["name"] == name:
            out[sid] = {"rec": rec, "start_ns": start, "end_ns": start + dur}
    run.host_joined = out
    return out


def _edges(tr, program: str, iv) -> Optional[tuple]:
    """First operation start and last operation end of the run of
    ``program`` that overlaps ``iv`` most, over every device of the
    slice (each device's own run of it)."""
    first = last = None
    for dev in tr.devices:
        best, overlap = None, 0.0
        for a, b in tr.runs(program, dev):
            o = min(b, iv[1]) - max(a, iv[0])
            if o > overlap:
                best, overlap = (a, b), o
        if best is None:
            continue
        ops = [(s, s + d) for _n, _c, s, d in tr.devices[dev]["ops"]
               if best[0] <= s < best[1]]
        if not ops:
            continue
        f, l = min(s for s, _ in ops), max(e for _, e in ops)
        first = f if first is None else min(first, f)
        last = l if last is None else max(last, l)
    return (first, last) if first is not None else None


def runs(run) -> List[dict]:
    """Every program run inside the slice whose parent span, dispatch and
    sync all have their profile event: ``{"kind", "sid", "first_ns",
    "last_ns", "dispatch": (start, end), "sync": (start, end), "breaks":
    {"dispatch": ns, "sync": ns}, "ok"}`` and ``"recs"``, the dispatch's
    and the sync's ledger records, by the run's start.  A break is
    how far the span lies on the wrong side of the operation (0 where it
    does not)."""
    cached = getattr(run, "host_runs", None)
    if cached is not None:
        return cached
    run.host_runs = []
    tr = run.trace
    ev = joined(run)
    if tr is None or not tr.devices or not ev:
        return run.host_runs
    kids: Dict[int, Dict[str, dict]] = {}
    for sid, e in ev.items():
        parent = e["rec"].get("parent")
        if parent is not None:
            kids.setdefault(parent, {})[e["rec"]["name"]] = e
    out = []
    for sid, e in ev.items():
        kind = KINDS.get(e["rec"]["name"])
        if kind is None:
            continue
        programs = run.cell.config.get("programs", {})
        d, s = kids.get(sid, {}).get(kind[1]), kids.get(sid, {}).get(kind[2])
        if kind[0] not in programs or d is None or s is None:
            continue
        program = programs[kind[0]]         # None: the slice's dominant one
        edges = _edges(tr, program, (d["start_ns"], s["end_ns"]))
        if edges is None:
            continue
        first, last = edges
        breaks = {"dispatch": max(0.0, d["start_ns"] - first),
                  "sync": max(0.0, last - s["end_ns"])}
        out.append({"kind": e["rec"]["name"], "sid": sid,
                    "first_ns": first, "last_ns": last,
                    "dispatch": (d["start_ns"], d["end_ns"]),
                    "sync": (s["start_ns"], s["end_ns"]),
                    "recs": (d["rec"], s["rec"]),
                    "breaks": breaks,
                    "ok": max(breaks.values()) <= BREAK_NS})
    out.sort(key=lambda r: r["first_ns"])
    run.host_runs = out
    _report(run, ev, out)
    return out


def fetch_tails_ms(run, kind: str) -> Optional[float]:
    """Median over the traced runs of ``kind``: the program's last
    operation (latest over the devices) to the end of the sync span, by
    the span's own profile event.  None where any run of the slice breaks
    causality."""
    slice_runs = runs(run)
    if not all(r["ok"] for r in slice_runs):
        return None
    tails = [(r["sync"][1] - r["last_ns"]) / 1e6 for r in slice_runs
             if r["kind"] == kind]
    return statistics.median(tails) if tails else None


def tails(run) -> Dict[str, Optional[float]]:
    """``fetch_tails_ms`` of every kind of run the slice holds."""
    return {k: fetch_tails_ms(run, k)
            for k in sorted({r["kind"] for r in runs(run)})}


def in_window(run, mono: float) -> bool:
    w = getattr(run, "window", None)
    return w is None or w[0] <= mono <= w[1]


def end_of(rec: dict) -> float:
    return rec["mono"] + rec.get("dur_s", 0.0)


def children(records, parent_name: str) -> Dict[int, Dict[str, dict]]:
    """Parent span id -> {child name: record} for the spans named
    ``parent_name``."""
    parents = {r["span"] for r in spans.spans_named(records, parent_name)}
    out: Dict[int, Dict[str, dict]] = {}
    for r in records:
        if r.get("type") == "span" and r.get("parent") in parents:
            out.setdefault(r["parent"], {})[r["name"]] = r
    return out


def host_turns_ms(run, parent_name: str, dispatch: str, sync: str,
                  between: str = "") -> Optional[float]:
    """Median over every consecutive pair of ``parent_name`` spans of one
    thread inside the window, on the host's clock alone: the end of the
    first one's ``sync`` child to the start of the next one's ``dispatch``
    child, less the seconds under ``between`` spans of the same thread
    that lie in that interval."""
    recs = run.records
    kids = children(recs, parent_name)
    parents = sorted((r for r in spans.spans_named(recs, parent_name)
                      if dispatch in kids.get(r["span"], {})
                      and sync in kids.get(r["span"], {})),
                     key=lambda r: r["mono"])
    others = spans.spans_named(recs, between) if between else []
    turns = []
    for a, b in zip(parents, parents[1:]):
        if a.get("thread") != b.get("thread"):
            continue
        lo = end_of(kids[a["span"]][sync])
        hi = kids[b["span"]][dispatch]["mono"]
        if not (in_window(run, lo) and in_window(run, hi)) or hi < lo:
            continue
        held = sum(o.get("dur_s", 0.0) for o in others
                   if o.get("thread") == a.get("thread")
                   and lo <= o["mono"] and end_of(o) <= hi)
        turns.append(1e3 * (hi - lo - held))
    return statistics.median(turns) if turns else None


def _report(run, ev: Dict[int, dict], out: List[dict]) -> None:
    names: Dict[str, int] = {}
    for e in ev.values():
        names[e["rec"]["name"]] = names.get(e["rec"]["name"], 0) + 1
    harness.say(f"host plane: {len(events(run))} span events, {len(ev)} "
                f"joined to the ledger by id {dict(sorted(names.items()))}; "
                f"{len(out)} program runs with their dispatch and sync")
    broken = [r for r in out if not r["ok"]]
    for r in broken:
        harness.say(f"host plane: causality broken at {r['kind']} span "
                    f"{r['sid']}: dispatch starts "
                    f"{r['breaks']['dispatch'] / 1e6:.4f} ms after the run's "
                    f"first operation, sync ends "
                    f"{r['breaks']['sync'] / 1e6:.4f} ms before its last; "
                    f"the cross-clock readers read nothing for this slice")
    tr = run.trace
    if tr is not None and tr.sync and ev:
        # where the ledger + bench.sync pair puts a span against its own
        # profile event (PERF.md section 7: which clock was off)
        off = tr.sync["trace_ns"] - tr.sync["mono_ns"]
        shift = sorted((e["rec"]["mono"] * 1e9 + off - e["start_ns"]) / 1e6
                       for e in ev.values())
        harness.say(f"host plane: the ledger's spans laid on the trace "
                    f"through bench.sync sit {statistics.median(shift):.4f} "
                    f"ms from their own profile events (median; "
                    f"{shift[0]:.4f} to {shift[-1]:.4f})")
