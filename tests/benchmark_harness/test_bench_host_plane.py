"""The program's spans on the profile's own clock (``benchmark/host_plane.py``),
its fetch tails and the three host-clock readers of PR 37 on recorded
fixtures.

The fixtures ``benchmark/fixtures/*.pr37.*.json.gz`` were cut from this
PR's traced chip runs (TPU v5 lite): a few runs of the cell's program, of
each run its first and last operation on every device and every operation
between runs, the ``bench.sync`` clock pair, the span events of the host
plane (``[name, span id, start_ns, dur_ns]``) and the ledger's records of
the same seconds, with the window those records span.  ``SHIFTED`` is a
slice whose device rows the profile placed about a millisecond early
against the host's (seed 3700000011 of ``local_b256``)."""

import gzip
import json
import os
import types

import pytest

from benchmark import cells, host_plane
from benchmark.trace_reduce import Reduced

ROOT = cells.repo_root()
FIXTURES = {
    "inception_v1.local_b256": "inception_v1_local_b256.pr37.4steps.json.gz",
    "gpt2_xl.chat_closed": "gpt2_xl_chat_closed.pr37.4chunks.json.gz",
    "gpt2_xl.score_open": "gpt2_xl_score_open.pr37.4prefills.json.gz",
}
SHIFTED = "inception_v1_local_b256.pr37.shifted.json.gz"
NEW = {
    "inception_v1.local_b256": ["train_host_turn_ms"],
    "gpt2_xl.chat_closed": ["decode_host_turn_ms"],
    "gpt2_xl.score_open": ["prefill_host_prep_ms"],
}
KIND = {"inception_v1.local_b256": "train.step",
        "gpt2_xl.chat_closed": "serve.decode",
        "gpt2_xl.score_open": "serve.prefill"}
# what the parent of PR 37 does not emit
NEW_SPANS = {"loop.next", "serve.place", "serve.wait", "serve.deliver",
             "serve.decode.put", "serve.decode.dispatch", "serve.decode.sync",
             "serve.prefill.dispatch", "serve.prefill.sync"}


def load(cell_name, fixture=None):
    """A run's context as the readers see it, from one fixture."""
    path = os.path.join(ROOT, "benchmark", "fixtures",
                        fixture or FIXTURES[cell_name])
    with gzip.open(path, "rt", encoding="utf-8") as f:
        fx = json.load(f)
    events = {"devices": fx["devices"], "host": fx["host"],
              "sync": fx["sync"]}
    return types.SimpleNamespace(
        cell=cells.load_cell(ROOT, cell_name), trace=Reduced(events),
        records=fx["records"], host_events=fx["host_spans"],
        window=tuple(fx["window"]), out_dir=None, samples={})


def read(run, metric):
    return cells.load_metric(ROOT, metric).read(run)


def tail(run, cell):
    return host_plane.fetch_tails_ms(run, KIND[cell])


CASES = [(c, m) for c in sorted(NEW) for m in NEW[c]]


@pytest.mark.parametrize("cell,metric", CASES)
def test_each_reader_reads_a_number_on_its_fixture(cell, metric):
    value = read(load(cell), metric)
    assert value is not None and value >= 0.0
    entry = {m["name"]: m for m in cells.load_benchmark(ROOT)["per_layer"]}
    mod = cells.load_metric(ROOT, metric)
    assert cell in entry[metric]["workloads"]
    assert (mod.UNIT, mod.LAYER, mod.MOVES) == (
        entry[metric]["unit"], entry[metric]["layer"],
        entry[metric]["moves"])


@pytest.mark.parametrize("cell,metric", CASES)
def test_each_reader_is_silent_on_the_parents_program(cell, metric):
    """The parent's ledger has no child spans and its profile no ``span``
    stat: every reader reports nothing, as PR 24's did."""
    run = load(cell)
    run.host_events = []
    run.records = [r for r in run.records if r.get("name") not in NEW_SPANS]
    assert read(run, metric) is None


@pytest.mark.parametrize("cell", sorted(FIXTURES))
def test_the_fetch_tail_reads_a_number_on_its_fixture(cell):
    """A clean slice: the tail of its own kind, in the traced run's line
    of tails, and no per-layer metric that crosses the two clocks."""
    run = load(cell)
    value = tail(run, cell)
    assert value is not None and value > 0.0
    assert host_plane.tails(run)[KIND[cell]] == value
    assert not {"train_sync_tail_ms", "decode_fetch_tail_ms",
                "prefill_fetch_tail_ms"} & {
        m["name"] for m in cells.load_benchmark(ROOT)["per_layer"]}


@pytest.mark.parametrize("cell", sorted(FIXTURES))
def test_the_fetch_tail_is_silent_on_the_parents_program(cell):
    run = load(cell)
    run.host_events = []
    run.records = [r for r in run.records if r.get("name") not in NEW_SPANS]
    assert tail(run, cell) is None and host_plane.tails(run) == {}


@pytest.mark.parametrize("cell", sorted(FIXTURES))
def test_every_span_event_joins_its_record_by_id(cell):
    run = load(cell)
    ev = host_plane.joined(run)
    assert len(ev) == len(run.host_events)
    assert {e["rec"]["name"] for e in ev.values()} >= {KIND[cell]}
    runs = [r for r in host_plane.runs(run) if r["kind"] == KIND[cell]]
    assert len(runs) >= 3 and all(r["ok"] for r in runs)
    for r in runs:                  # the span holds its program's run
        assert r["dispatch"][0] <= r["first_ns"] <= r["last_ns"] \
            <= r["sync"][1]


@pytest.mark.parametrize("cell", sorted(FIXTURES))
def test_a_shifted_clock_breaks_causality_and_the_slice_reads_none(
        cell, capsys):
    """The profile's host events moved earlier than any sync's tail: every
    sync now ends before its run's last operation; the break is printed
    and the fetch tail reads nothing."""
    run = load(cell)
    tail_ns = max(r["sync"][1] - r["last_ns"] for r in host_plane.runs(run))
    shifted = load(cell)
    shift = tail_ns + 0.2e6
    shifted.host_events = [[n, sid, s - shift, d]
                           for n, sid, s, d in shifted.host_events]
    runs = [r for r in host_plane.runs(shifted) if r["kind"] == KIND[cell]]
    assert runs and not any(r["ok"] for r in runs)
    assert all(r["breaks"]["sync"] > host_plane.BREAK_NS for r in runs)
    assert "causality broken" in capsys.readouterr().out
    assert tail(shifted, cell) is None
    assert host_plane.tails(shifted)[KIND[cell]] is None
    # the reader on the host's clock alone does not cross the two
    assert read(shifted, NEW[cell][0]) == read(run, NEW[cell][0])


@pytest.mark.parametrize("cell", sorted(FIXTURES))
def test_tail_turn_and_launch_make_up_the_devices_gap(cell):
    """For each pair of consecutive runs of the program with nothing else
    on the device between them: the fetch tail (the sync span's end less
    the first run's last operation) + the host's turn (ledger: the sync's
    end to the next dispatch's start) + the launch (the next run's first
    operation less its dispatch span's start) is the device's idle gap,
    within 0.05 ms.  "Nothing else" within the same 0.05 ms: a trainer's
    gap holds the key split's and the rate's tiny programs (5 us)."""
    run = load(cell)
    tr = run.trace
    runs = [r for r in host_plane.runs(run) if r["kind"] == KIND[cell]]
    pairs = 0
    for a, b in zip(runs, runs[1:]):
        other = 1e3 * tr.busy_in((a["last_ns"], b["first_ns"]))
        if other > 0.05:
            continue                    # another program ran between them
        tail = (a["sync"][1] - a["last_ns"]) / 1e6
        turn = 1e3 * (b["recs"][0]["mono"] - host_plane.end_of(a["recs"][1]))
        launch = (b["first_ns"] - b["dispatch"][0]) / 1e6
        idle = (b["first_ns"] - a["last_ns"]) / 1e6 - other
        assert tail + turn + launch == pytest.approx(idle, abs=0.05)
        pairs += 1
    if cell != "gpt2_xl.score_open":    # an open loop's prefills idle apart
        assert pairs >= 2


def test_a_slice_the_profile_misplaced_is_caught_by_causality(capsys):
    """PR 36's open question (a) on the chip: the ledger's spans laid on
    the trace through ``bench.sync`` sit where their own profile events
    are, to a few microseconds, yet five of eight steps' dispatch spans
    begin AFTER their program's first operation: the profile's
    host-to-device alignment moved, not the monotonic clock pair.  The
    offset is the whole slice's: the three steps that keep causality read
    a tail of 2.71 ms against 1.70-1.78 on clean slices.  The breaks are
    printed and the fetch tail reads nothing for the slice."""
    run = load("inception_v1.local_b256", SHIFTED)
    off = run.trace.sync["trace_ns"] - run.trace.sync["mono_ns"]
    shift = [abs(e["rec"]["mono"] * 1e9 + off - e["start_ns"]) / 1e6
             for e in host_plane.joined(run).values()]
    assert len(shift) == 55 and sorted(shift)[len(shift) // 2] < 0.02
    runs = host_plane.runs(run)
    broken = [r for r in runs if not r["ok"]]
    assert len(runs) == 8 and len(broken) == 5
    assert all(r["breaks"]["sync"] == 0 < r["breaks"]["dispatch"]
               for r in broken)
    # every launch in the slice reads under 0.1 ms, where the clean
    # slice's read 1.0-1.8: one offset for the whole slice
    assert all(r["first_ns"] - r["dispatch"][0] < 0.1e6 for r in runs)
    assert capsys.readouterr().out.count("causality broken") == 5
    tails = [(r["sync"][1] - r["last_ns"]) / 1e6 for r in runs if r["ok"]]
    assert sorted(tails)[1] > 2.5           # biased high by the offset
    assert host_plane.fetch_tails_ms(run, "train.step") is None
    # the reader on the host's clock alone still reads
    assert read(run, "train_host_turn_ms") is not None


def test_the_tail_is_the_latest_device_and_the_launch_the_earliest():
    """Four devices (the SPMD cell): a run's last operation is the latest
    over the devices, its first the earliest; a device that ran nothing
    of the program is passed over."""
    ms = 1e6
    ops, mods = {}, {}
    for k, skew in enumerate((0.0, 0.3, -0.2, 0.1)):
        dev = f"/device:TPU:{k}"
        mods[dev] = [["jit_step(1)", (1 + skew) * ms, 50 * ms]]
        ops[dev] = [["fusion.1", "kOutput", (1 + skew) * ms, 25 * ms],
                    ["fusion.2", "kOutput", (26 + skew) * ms, 25 * ms]]
    devices = {d: {"ops": ops[d], "modules": mods[d]} for d in ops}
    devices["/device:TPU:4"] = {"ops": [["copy.1", "", 70 * ms, 1 * ms]],
                                "modules": [["jit_other(2)", 70 * ms,
                                             1 * ms]]}
    tr = Reduced({"devices": devices, "host": [], "sync": None})
    assert host_plane._edges(tr, "jit_step", (0.5 * ms, 53 * ms)) \
        == (0.8 * ms, 51.3 * ms)


def test_read_host_finds_the_span_stat_of_a_real_profile(tmp_path):
    """On the CPU too: a profile taken while the ledger is on holds each
    span's event with its id."""
    import glob

    import jax

    from bigdl_tpu.observability import ledger as run_ledger
    from bigdl_tpu.observability import tracer
    from benchmark import spans
    run_ledger.set_run_dir(str(tmp_path / "ledger"))
    try:
        jax.profiler.start_trace(str(tmp_path / "profile"))
        with tracer.span("serve.decode") as outer:
            with tracer.span("serve.decode.sync") as inner:
                jax.numpy.ones(4).block_until_ready()
        jax.profiler.stop_trace()
        run_ledger.flush()
        outer, inner = outer.sid, inner.sid
    finally:
        run_ledger.set_run_dir(None)
    path = glob.glob(str(tmp_path / "profile" / "**" / "*.xplane.pb"),
                     recursive=True)[0]
    got = {sid: name for name, sid, _s, _d in host_plane.read_host(path)}
    assert got == {outer: "serve.decode", inner: "serve.decode.sync"}
    run = types.SimpleNamespace(
        records=spans.read_ledger(str(tmp_path / "ledger")),
        host_events=host_plane.read_host(path))
    ev = host_plane.joined(run)
    assert set(ev) == {outer, inner}
    assert ev[outer]["start_ns"] <= ev[inner]["start_ns"] \
        <= ev[inner]["end_ns"] <= ev[outer]["end_ns"]
