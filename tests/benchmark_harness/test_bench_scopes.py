"""The per-layer metrics of PR 24 on recorded fixtures, and the helper that
reads an operation's scope path out of a profile.

The fixtures ``benchmark/fixtures/*.pr24.*.json.gz`` were cut from this PR's
traced chip runs (TPU v5 lite): a few runs of the cell's program with the
``[name, category, path index, start_ns, dur_ns]`` of every operation, the
``paths`` table, the programs' runs, the ``bench.sync`` clock pair and the
ledger records of the same seconds."""

import gzip
import json
import os
import types

import pytest

from benchmark import cells, loop_gaps, scope_events as se
from benchmark.trace_reduce import Reduced, op_group

ROOT = cells.repo_root()
FIXTURES = {
    "inception_v1.local_b256": "inception_v1_local_b256.pr24.4steps.json.gz",
    "gpt2_xl.chat_closed": "gpt2_xl_chat_closed.pr24.1chunk.json.gz",
    "gpt2_xl.score_open": "gpt2_xl_score_open.pr24.3prefills.json.gz",
}
NEW = {
    "inception_v1.local_b256": ["train_launch_wait_ms",
                                "train_turnaround_ms"],
    "gpt2_xl.chat_closed": ["chunk_itl_p95_ms", "decode_kv_copy_pct"],
    "gpt2_xl.score_open": ["admit_wait_p95_ms", "prefill_host_overhead_ms",
                           "prefill_kv_copy_pct"],
}


def load(cell_name):
    """A run's context as the readers see it, from one fixture."""
    path = os.path.join(ROOT, "benchmark", "fixtures", FIXTURES[cell_name])
    with gzip.open(path, "rt", encoding="utf-8") as f:
        fx = json.load(f)
    ops = [[n, c, fx["paths"][p], s, d] for n, c, p, s, d in fx["ops"]]
    events = {"devices": {fx["device"]: {
        "ops": [[n, c, s, d] for n, c, _p, s, d in ops],
        "modules": fx["modules"]}}, "host": fx["host"], "sync": fx["sync"]}
    monos = [r["mono"] for r in fx["records"]]
    return types.SimpleNamespace(
        cell=cells.load_cell(ROOT, cell_name), trace=Reduced(events),
        records=fx["records"], scope_ops=ops, out_dir=None,
        window=(min(monos) - 1.0, max(monos) + 1.0), samples={})


@pytest.fixture(scope="module", params=sorted(FIXTURES))
def run(request):
    return load(request.param)


def read(run, metric):
    return cells.load_metric(ROOT, metric).read(run)


CASES = [(c, m) for c in sorted(NEW) for m in NEW[c]]


@pytest.mark.parametrize("cell,metric", CASES)
def test_each_new_reader_reads_a_number_on_its_fixture(cell, metric):
    value = read(load(cell), metric)
    assert value is not None and value >= 0.0
    entry = {m["name"]: m for m in cells.load_benchmark(ROOT)["per_layer"]}
    mod = cells.load_metric(ROOT, metric)
    assert cell in entry[metric]["workloads"]
    assert (mod.UNIT, mod.LAYER, mod.MOVES) == (
        entry[metric]["unit"], entry[metric]["layer"],
        entry[metric]["moves"])


@pytest.mark.parametrize("cell,metric", CASES)
def test_each_new_reader_is_silent_on_a_program_without_the_instrumentation(
        cell, metric):
    """What the parent commit gives: spans without ``train.dispatch``,
    ``serve.request`` without a timeline, paths without the scopes."""
    run = load(cell)
    run.records = [
        {k: v for k, v in r.items()
         if k not in ("t_submit", "queue_s", "ttft_s", "gaps_s", "n_chunks",
                      "max_gap_s")}
        for r in run.records
        if r.get("name") not in ("train.dispatch", "train.sync")]
    for o in run.scope_ops:
        o[2] = "/".join(p for p in o[2].split("/")
                        if p not in ("kv.write", "attn.paged"))
    assert read(run, metric) is None


def test_launch_wait_and_turnaround_sum_to_the_step_gap():
    run = load("inception_v1.local_b256")
    launch, turn = (read(run, "train_launch_wait_ms"),
                    read(run, "train_turnaround_ms"))
    gap = read(run, "train_step_gap_ms")
    assert launch > 0 and turn > 0
    assert launch + turn == pytest.approx(gap, rel=1e-6)
    names = {r["name"] for r in run.records if r.get("type") == "span"}
    assert {"train.step", "train.dispatch", "train.sync", "h2d"} <= names


def test_idle_gaps_read_the_child_spans_not_train_step():
    run = load("inception_v1.local_b256")
    spans = [r for r in run.records if r.get("type") == "span"]
    by = run.trace.gaps_by_host_span(run.trace.host_spans(spans))
    assert by["train.dispatch"] + by["train.sync"] > 10 * by["train.step"]
    assert max(by, key=by.get) in ("train.dispatch", "train.sync")


def test_a_loop_that_dispatches_ahead_reads_no_turnaround():
    run = load("inception_v1.local_b256")
    for r in run.records:
        if r.get("name") == "train.dispatch":
            r["mono"] -= 0.030            # begun while the last step ran
    gaps = loop_gaps.split_gaps(run)
    assert gaps and all(t == 0.0 and w >= 0.0 for t, w in gaps)
    assert read(run, "train_launch_wait_ms") == pytest.approx(
        read(run, "train_step_gap_ms"), rel=1e-6)


@pytest.mark.parametrize("cell,metric,program", [
    ("gpt2_xl.chat_closed", "decode_kv_copy_pct", "step_chunk"),
    ("gpt2_xl.score_open", "prefill_kv_copy_pct", "prefill")])
def test_kv_copy_share_counts_scoped_copies_inside_the_programs_runs(
        cell, metric, program):
    run = load(cell)
    runs = run.trace.runs(program)
    inside = [o for o in run.scope_ops
              if any(a <= o[3] < b for a, b in runs)]
    copies = [o for o in inside
              if op_group(o[0], o[1]) == "copies and layout"
              and ("kv.write" in o[2].split("/")
                   or "attn.paged" in o[2].split("/"))]
    want = 100.0 * sum(o[4] for o in copies) / sum(o[4] for o in inside)
    assert 0.0 < want < 100.0
    assert read(run, metric) == pytest.approx(want)
    # the accepted breakdown's copies are the same operations' group
    assert sum(o[4] for o in copies) / 1e9 <= \
        run.trace.group_seconds()["copies and layout"]


def test_serving_records_of_the_fixtures_carry_the_timeline():
    chat, score = load("gpt2_xl.chat_closed"), load("gpt2_xl.score_open")
    done = [r for r in chat.records + score.records
            if r.get("type") == "serve.request" and r["status"] == "ok"]
    assert done
    for r in done:
        assert len(r["gaps_s"]) == r["n_chunks"] - 1
        assert r["ttft_s"] >= r["queue_s"] >= 0.0
    decodes = [r for r in chat.records if r.get("name") == "serve.decode"]
    assert decodes and all(r["attrs"]["ctx_tokens"] > 0
                           and r["attrs"]["pages_mapped"] > 0
                           for r in decodes)


def test_admit_wait_counts_the_clean_part_of_the_window_only():
    run = load("gpt2_xl.score_open")
    reqs = sorted((r for r in run.records
                   if r.get("type") == "serve.request"),
                  key=lambda r: r["t_submit"])
    assert len(reqs) >= 3
    run.samples["clean_until"] = reqs[1]["t_submit"]    # the first only
    assert read(run, "admit_wait_p95_ms") == pytest.approx(
        1e3 * reqs[0]["queue_s"])


def test_prefill_host_overhead_is_first_token_less_admit_less_device():
    run = load("gpt2_xl.score_open")
    over = read(run, "prefill_host_overhead_ms")
    device = read(run, "prefill_device_ms")
    ttfts = [1e3 * (r["ttft_s"] - r["queue_s"]) for r in run.records
             if r.get("type") == "serve.request"]
    assert 0.0 < over < min(ttfts)
    assert over + device == pytest.approx(sorted(ttfts)[len(ttfts) // 2],
                                          rel=0.25)


def test_at_least_nine_tenths_of_device_time_can_be_named(run):
    assert se.named_share(run.scope_ops, compiler=True) >= 0.9
    by = se.seconds_by(run.scope_ops, se.top_level)
    if run.cell.name.startswith("inception"):
        assert {"forward", "backward", "guard"} <= set(by)
        assert by["backward"] > by["forward"] > by["guard"]
        modules = se.seconds_by(run.scope_ops, se.module_path)
        assert any(m.startswith("inception_3a/") for m in modules)
    else:
        assert {"attn/kv.write", "attn/attn.paged", "mlp"} <= set(by)


# -- paths -> scopes -------------------------------------------------------------------

@pytest.mark.parametrize("path,scopes,top,module", [
    ("jit(step)/transpose(jvp(forward))/inception_3a/output/Sequential_0/"
     "inception_3a/1x1/conv_general_dilated",
     ["backward", "inception_3a", "output", "Sequential_0", "inception_3a",
      "1x1"], "backward", "inception_3a/output"),
    ("jit(step)/jvp(forward)/LogSoftMax_3/jit(log_softmax)",
     ["forward", "LogSoftMax_3"], "forward", "LogSoftMax_3"),
    ("jit(step)/jvp(loss)/jit(take_along_axis)/lt", ["loss"], "loss", None),
    ("jit(step)/update/mul", ["update"], "update", None),
    ("jit(step_chunk_kernel)/while/body/closed_call/block_12/attn/kv.write/"
     "concatenate", ["block_12", "attn", "kv.write"], "attn/kv.write", None),
    ("jit(prefill)/block_1/attn/attn.paged/paged_attention/pallas_call",
     ["block_1", "attn", "attn.paged", "paged_attention"],
     "attn/attn.paged", None),
    ("jit(prefill)/block_0/mlp/dot_general", ["block_0", "mlp"], "mlp", None),
    ("jit(step_chunk_kernel)/while/body/closed_call", [], "unscoped", None),
    ("reduce_max", [], "unscoped", None),
    ("", [], "unscoped", None),
    ("cache[47]['k']", [], "arg:cache", None),
    (se.EXIT, [], "exit", None),
])
def test_scopes_of_a_path(path, scopes, top, module):
    if path != se.EXIT:
        assert se.scopes(path) == scopes
    assert se.top_level(path) == top
    assert se.module_path(path) == module


def test_unnamed_operations_at_a_programs_edges_are_labelled_by_position():
    ops = [["copy.1", "", "", 5.0, 1.0],                  # before the run
           ["copy.2", "", "", 11.0, 1.0],                 # entry
           ["fusion.1", "kLoop", "jit(f)/embed/add", 13.0, 1.0],
           ["copy.3", "", "", 15.0, 1.0],                 # between: stays
           ["fusion.2", "kLoop", "jit(f)/logits/add", 17.0, 1.0],
           ["copy.4", "", "", 19.0, 1.0]]                 # exit
    se.label_edges(ops, [["jit_f(1)", 10.0, 15.0]])
    assert [o[2] for o in ops] == ["", se.ENTRY, "jit(f)/embed/add", "",
                                   "jit(f)/logits/add", se.EXIT]
    assert se.named_share(ops) == pytest.approx(2 / 6)
    assert se.named_share(ops, compiler=True) == pytest.approx(4 / 6)


# -- the profile's wire format ----------------------------------------------------------

def _vi(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _f(field, payload):                 # a length-delimited field
    return _vi(field << 3 | 2) + _vi(len(payload)) + payload


def _n(field, value):                   # a varint field
    return _vi(field << 3) + _vi(value)


def _entry(key, message):               # one map<int64, Message> entry
    return _n(1, key) + _f(2, message)


def _tiny_xplane(tmp_path, gz):
    stat_meta = _f(5, _entry(1, _n(1, 1) + _f(2, b"tf_op"))) \
        + _f(5, _entry(2, _n(1, 2) + _f(2, b"flops")))
    fusion = _n(1, 7) + _f(2, b"%fusion.1 = bf16[8]{0} fusion(%p), "
                              b"kind=kLoop, calls=%fc") \
        + _f(5, _n(1, 2) + _n(4, 512)) \
        + _f(5, _n(1, 1) + _f(5, b"jit(prefill)/block_3/attn/kv.write/"
                                 b"scatter:"))
    copy_ = _n(1, 8) + _f(2, b"%copy.2 = bf16[8]{0} copy(%fusion.1)")
    loop = _n(1, 9) + _f(2, b"%while.3 = (s32[]) while(%t), body=%b")
    events = _f(4, _n(1, 7) + _n(2, 5000) + _n(3, 2000)) \
        + _f(4, _n(1, 8) + _n(2, 8000) + _n(3, 1500)) \
        + _f(4, _n(1, 9) + _n(2, 4000) + _n(3, 9000))
    line = _f(3, _n(1, 1) + _f(2, b"XLA Ops") + _n(3, 1000) + events)
    other = _f(3, _n(1, 2) + _f(2, b"XLA Modules") + _n(3, 1000)
               + _f(4, _n(1, 7) + _n(2, 0) + _n(3, 99000)))
    plane = _n(1, 1) + _f(2, b"/device:TPU:0") + line + other + stat_meta \
        + _f(4, _entry(7, fusion)) + _f(4, _entry(8, copy_)) \
        + _f(4, _entry(9, loop))
    host = _n(1, 2) + _f(2, b"/host:CPU") + _f(3, _f(2, b"python"))
    data = _f(1, host) + _f(1, plane)
    path = tmp_path / ("t.xplane.pb" + (".gz" if gz else ""))
    with (gzip.open if gz else open)(path, "wb") as f:
        f.write(data)
    return str(path)


@pytest.mark.parametrize("gz", [False, True])
def test_the_path_is_read_from_the_event_metadatas_tf_op_stat(tmp_path, gz):
    ops, probe = se.read_profile(_tiny_xplane(tmp_path, gz))
    assert ops == [
        ["fusion.1", "kLoop", "jit(prefill)/block_3/attn/kv.write/scatter",
         1005.0, 2.0],
        ["copy.2", "", "", 1008.0, 1.5],
        ["while.3", "", "", 1004.0, 9.0]]
    assert probe["routes"] == {"metadata stat tf_op": 1, "none": 2}
    assert probe["sample"]["stats"]["tf_op"].endswith("scatter:")
    assert "tf_op" in probe["stat_keys"]


def test_ops_of_a_run_leave_control_flow_out_and_print_once(tmp_path, capsys):
    prof = tmp_path / "profile" / "plugins" / "profile" / "t"
    prof.mkdir(parents=True)
    os.replace(_tiny_xplane(tmp_path, False), prof / "h.xplane.pb")
    run = types.SimpleNamespace(
        out_dir=str(tmp_path), trace=None,
        cell=types.SimpleNamespace(name="toy.cell"))
    assert [o[0] for o in se.ops(run)] == ["fusion.1", "copy.2"]
    assert se.ops(run) is run.scope_ops
    out = capsys.readouterr().out
    assert out.count("device seconds by top-level scope") == 1
    assert "attn_kv.write" in out
    assert os.path.exists(tmp_path / "scope_ops.toy.cell.json.gz")
    assert se.ops(types.SimpleNamespace(out_dir=str(tmp_path / "none"))) == []
