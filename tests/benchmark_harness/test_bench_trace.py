"""The reduction from an event list to busy, idle, gap and group numbers,
on synthetic events, on the recorded fixture, and the extraction itself on
a trace made on the CPU."""

import gzip
import json
import os
import time

import pytest

from benchmark import cells, trace_capture
from benchmark.trace_reduce import Reduced, covered, merge, op_group, top

ROOT = cells.repo_root()
FIXTURES = os.path.join(ROOT, "benchmark", "fixtures")


def _events():
    """Two steps of a program on one device, 100 us apart, in ns."""
    ops = [["convolution.1", "convolution", 1000.0, 400.0],
           ["fusion.2", "loop fusion", 1400.0, 100.0],
           ["select-and-scatter.3", "", 1500.0, 200.0],
           ["copy.4", "copy", 1800.0, 100.0],            # after a 100 ns gap
           ["all-reduce.5", "all-reduce", 1900.0, 100.0],
           ["convolution.1", "convolution", 3000.0, 400.0],
           ["fusion.2", "loop fusion", 3400.0, 100.0],
           ["all-reduce.5", "all-reduce", 3450.0, 150.0]]  # half hidden
    ops.append(["while.9", "", 1000.0, 1000.0])     # spans its body's ops
    modules = [["jit_step(1)", 1000.0, 1000.0], ["jit_step(1)", 3000.0, 600.0],
               ["jit_split(2)", 2500.0, 0.0]]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": [["bench.sync", 500.0, 10.0]],
            "sync": {"trace_ns": 500.0, "mono_ns": 5_000_000_500.0}}


def test_merge_and_covered():
    assert merge([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]
    assert covered([(1, 4), (5, 8)], 3, 6) == 2
    assert covered([(1, 4)], 4, 9) == 0


def test_busy_idle_and_window_of_synthetic_events():
    r = Reduced(_events())
    assert r.window() == (1000.0, 3600.0)
    assert r.window_s() == pytest.approx(2600e-9)
    # 1000-1700 and 1800-2000, then 3000-3600
    assert r.busy_s() == pytest.approx((700 + 200 + 600) * 1e-9)
    assert r.idle_pct() == pytest.approx(100 * (1 - 1500 / 2600))
    assert r.idle_gaps() == [(1700.0, 1800.0), (2000.0, 3000.0)]


def test_runs_of_a_program_and_the_gaps_between_them():
    r = Reduced(_events())
    assert r.dominant_module() == "jit_step"
    assert r.runs(None) == [(1000.0, 2000.0), (3000.0, 3600.0)]
    assert r.runs("step") == r.runs(None)
    assert r.idle_between_runs(None) == [pytest.approx(1000e-9)]
    assert r.busy_per_run("step") == [pytest.approx(900e-9),
                                      pytest.approx(600e-9)]


def test_groups_and_exposed_collectives():
    r = Reduced(_events())
    g = r.group_seconds()
    assert g["MXU convolutions and matmuls"] == pytest.approx(800e-9)
    assert g["VPU fusions"] == pytest.approx(200e-9)
    assert g["max-pool backward"] == pytest.approx(200e-9)
    assert g["copies and layout"] == pytest.approx(100e-9)
    assert g["collectives"] == pytest.approx(250e-9)
    assert "other" not in g              # the while is control flow
    # the first all-reduce runs alone; of the second, 50 ns hide
    # under the fusion
    assert r.exposed_collective_s() == pytest.approx((100 + 100) * 1e-9)
    assert r.op_seconds_in("convolution", [r.window()]) == \
        pytest.approx(800e-9)
    assert r.op_seconds_in("convolution", [(2500.0, 4000.0)]) == \
        pytest.approx(400e-9)
    assert top(g, 2)[0] == ["MXU_convolutions_and_matmuls",
                            pytest.approx(800e-9)]


@pytest.mark.parametrize("name,cat,group", [
    ("convolution.12", "convolution", "MXU convolutions and matmuls"),
    ("fusion.7", "convolution fusion", "MXU convolutions and matmuls"),
    ("fusion.9", "loop fusion", "VPU fusions"),
    ("select-and-scatter.2", "", "max-pool backward"),
    ("reduce-window.3", "", "pool forward and LRN"),
    ("copy.1", "copy", "copies and layout"),
    ("fusion.44", "data formatting", "copies and layout"),
    ("all-gather.1", "all-gather", "collectives"),
    ("reduce-scatter.4", "", "collectives"),
    ("custom-call.3", "custom-call tpu_custom_call", "Pallas custom call"),
    ("custom-call.9", "custom-call ConcatBitcast",
     "custom call ConcatBitcast"),
    ("fusion.738", "kOutput", "MXU convolutions and matmuls"),
    ("broadcast_maximum_fusion.33", "kOutput",
     "MXU convolutions and matmuls"),
    ("pad_maximum_fusion.7", "kLoop", "VPU fusions"),
    ("copy-done.287", "", "copies and layout"),
    ("slice-done.279", "", "copies and layout"),
    ("weird.1", "", "other"),
])
def test_operation_groups(name, cat, group):
    assert op_group(name, cat) == group


def test_parse_op_reads_the_instruction_not_its_operands():
    text = ('%fusion.738 = (u8[64,3,7]{0,1,2:T(4,128)(4,1)S(1)}, bf16[64,3,7,7]'
            '{0,1,3,2}) fusion(bf16[256,64,112,112]{0,1,3,2:T(8,128)(2,1)} '
            '%select-and-scatter.12, bf16[256,3,224,224] %custom-call.2), '
            'kind=kOutput, calls=%fused_computation.3')
    assert trace_capture.parse_op(text) == ("fusion.738", "kOutput")
    assert op_group(*trace_capture.parse_op(text)) == \
        "MXU convolutions and matmuls"
    call = ('%custom-call.7 = bf16[10,25,1,64]{3,2,1,0} custom-call(s32[10,64] '
            '%p), custom_call_target="tpu_custom_call", operand_layout=...')
    assert trace_capture.parse_op(call) == \
        ("custom-call.7", "custom-call tpu_custom_call")
    assert trace_capture.parse_op("%copy.3 = f32[8] copy(f32[8] %x)") == \
        ("copy.3", "")
    assert trace_capture.parse_op("%x = f32[] add()", "loop fusion") == \
        ("x", "loop fusion")


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(FIXTURES, "inception_v1_local_b256.3steps.json.gz")
    with gzip.open(path, "rt", encoding="utf-8") as f:
        return Reduced(json.load(f))


def test_recorded_fixture_gives_known_busy_idle_and_gap_numbers(recorded):
    r = recorded
    # three of the eight steps traced on a TPU v5 lite (PR 23)
    assert list(r.devices) == ["/device:TPU:0"]
    assert r.dominant_module() == "jit_step" and len(r.runs(None)) == 3
    assert r.window_s() == pytest.approx(0.198154685, rel=1e-9)
    assert r.busy_s() == pytest.approx(0.153005652, rel=1e-9)
    assert r.idle_pct() == pytest.approx(22.784741627, rel=1e-9)
    assert r.idle_between_runs(None) == [pytest.approx(0.021486355),
                                         pytest.approx(0.023504324)]
    assert r.busy_per_run(None) == [pytest.approx(0.051004594),
                                    pytest.approx(0.05099909),
                                    pytest.approx(0.050995766)]


def test_recorded_fixture_gives_known_group_numbers(recorded):
    g = recorded.group_seconds()
    assert g["MXU convolutions and matmuls"] == pytest.approx(0.077703256)
    assert g["max-pool backward"] == pytest.approx(0.029599408)
    assert g["pool forward and LRN"] == pytest.approx(0.011124955)
    assert g["VPU fusions"] == pytest.approx(0.029978257)
    assert g["copies and layout"] == pytest.approx(0.003710311)
    assert "collectives" not in g and recorded.exposed_collective_s() == 0
    # every operation's time lands in exactly one group
    dev = recorded.devices["/device:TPU:0"]
    assert sum(g.values()) == pytest.approx(
        sum(d for _n, _c, _s, d in dev["ops"]) / 1e9)


def test_recorded_fixture_attributes_its_gaps_to_the_trainers_spans(recorded):
    with open(os.path.join(
            FIXTURES, "inception_v1_local_b256.3steps.host_spans.json"),
            encoding="utf-8") as f:
        spans = json.load(f)
    gaps = recorded.gaps_by_host_span(spans)
    assert max(gaps, key=gaps.get) == "train.step"
    assert gaps["train.step"] == pytest.approx(0.038349726, rel=1e-6)
    assert gaps["h2d"] == pytest.approx(0.001847333, rel=1e-6)
    assert sum(gaps.values()) == pytest.approx(
        recorded.window_s() - recorded.busy_s())


def test_idle_gaps_go_to_the_innermost_open_host_span():
    r = Reduced(_events())
    # program spans arrive on the monotonic clock: mono 5.0000015 s is
    # trace ns 1500
    spans = r.host_spans([
        {"name": "train.step", "mono": 5.0000010, "dur_s": 1200e-9},
        {"name": "h2d", "mono": 5.0000023, "dur_s": 300e-9}])
    by = {s["name"]: (round(s["start_ns"]), round(s["end_ns"]))
          for s in spans}
    assert by == {"train.step": (1000, 2200), "h2d": (2300, 2600)}
    gaps = r.gaps_by_host_span(spans)
    assert gaps["train.step"] == pytest.approx((100 + 200) * 1e-9)
    assert gaps["h2d"] == pytest.approx(300e-9)
    assert gaps["none"] == pytest.approx((100 + 400) * 1e-9)
    assert sum(gaps.values()) == pytest.approx(1100e-9)


def test_several_devices_are_averaged():
    ev = _events()
    ev["devices"]["/device:TPU:1"] = {
        "ops": [["convolution.1", "convolution", 1000.0, 1000.0]],
        "modules": [["jit_step(1)", 1000.0, 1000.0]]}
    r = Reduced(ev)
    assert r.busy_s() == pytest.approx((1500 + 1000) / 2 * 1e-9)


def test_an_empty_trace_reduces_to_nothing():
    r = Reduced({"devices": {}, "host": [], "sync": None})
    assert r.window() is None and r.busy_s() == 0.0
    assert r.idle_pct() is None and r.runs(None) == []
    assert r.idle_between_runs("x") == [] and r.group_seconds() == {}


def test_extraction_on_a_trace_made_on_the_cpu(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    s = trace_capture.Slice(str(tmp_path / "profile"))
    before = time.monotonic_ns()
    s.start()
    with jax.profiler.TraceAnnotation("bench.window", k=1):
        f(x).block_until_ready()
    s.stop()
    after = time.monotonic_ns()
    ev = s.events()
    names = [n for n, _s, _d in ev["host"]]
    assert "bench.sync" in names and "bench.window" in names
    assert before <= ev["sync"]["mono_ns"] <= after
    # the CPU backend has no /device: plane: nothing to reduce, and the
    # harness then refuses the traced run rather than report zeros
    assert ev["devices"] == {}
    r = Reduced(ev)
    assert r.devices == {} and r.busy_s() == 0.0
    spans = r.host_spans([{"name": "train.step",
                           "mono": ev["sync"]["mono_ns"] / 1e9,
                           "dur_s": 0.001}])
    step = [s_ for s_ in spans if s_["name"] == "train.step"][0]
    assert step["start_ns"] == pytest.approx(ev["sync"]["trace_ns"], abs=1)
