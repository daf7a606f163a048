"""The cell ``ouro_2_6b.short_reason_closed``: the configuration against the
catalog row and the sizing arithmetic, the closed runner end to end on a
toy configuration of the looped model, the reference check passing and
failing for each of ``benchmark/looped_control.py``'s variants, the four
new readers on synthetic slices, and the cost functions against hand
counts."""

import copy
import json
import os
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells, costs_looped, harness, looped_trace, peaks

ROOT = cells.repo_root()
CELL = "ouro_2_6b.short_reason_closed"
PEAKS = peaks.lookup("TPU v5 lite")
TOY = dict(max_len=64, embed_dim=64, num_heads=2, num_kv_heads=2,
           head_dim=32, num_layers=2, loops=2, ffn_dim=96)
READERS = {"looped_paged_decode_roofline": ("attn", "attn.paged"),
           "looped_step_hbm_pct": None, "looped_step_mfu_pct": None,
           "loop_pass_device_ms": None}


# -- the files ---------------------------------------------------------------------

def test_load_cell_finds_the_new_cell_and_its_readers():
    cell = cells.load_cell(ROOT, CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "closed"
    assert cell.config["model"]["args"] == [49152]
    assert callable(cells.resolve(cell.config["model"]["factory"]))
    assert {m["name"] for m in cell.end_to_end} \
        >= {"serve_tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert names >= set(READERS) | {
        "compile_s", "window_compiles", "slot_occupancy_pct",
        "decode_gap_ms", "decode_step_device_ms", "chat_device_idle_pct",
        "decode_host_turn_ms", "chunk_itl_p95_ms", "decode_kv_copy_pct"}
    # the two whose cost function takes embed_dim for the page width, which
    # is one pool's and not this stack's of 192 pools
    assert not {"paged_attn_decode_roofline",
                "paged_attn_prefill_roofline"} & names
    for name in names:
        assert hasattr(cells.load_metric(ROOT, name), "read")
    from benchmark.reference import ouro
    assert callable(ouro.logits_at)


def test_traffic_is_the_issues_multiset():
    from benchmark import traffic
    cell = cells.load_cell(ROOT, CELL)
    spec, srv = cell.traffic, cell.config["server"]
    prompts = traffic.expand(spec["prompt_lengths"])
    outs = traffic.expand(spec["output_lengths"])
    assert sorted(prompts) == [64, 64, 128, 128, 192, 256]
    assert sorted(outs) == [128, 128, 128, 192, 192, 192]
    assert sum(outs) / len(outs) == 160
    assert "shared_head" not in spec and spec["discard_chunks"] == 5
    # every request fits a slot, and every prompt is a bucket of its own
    assert max(prompts) + max(outs) <= srv["max_len"] == 448
    assert set(prompts) == set(srv["seq_buckets"])
    assert (srv["num_slots"], srv["page_size"]) == (12, 16)


def test_the_configuration_keeps_the_row_whole():
    cfg = cells.load_cell(ROOT, CELL).config
    pub, kw = cfg["published"], cfg["model"]["kwargs"]
    assert cfg["reduced"] == []
    # every key of the row is in the file at the top level, unchanged
    for key, value in pub.items():
        assert cfg[key] == value, key
    assert kw["embed_dim"] == pub["hidden_size"] == 2048
    assert (kw["num_heads"], kw["num_kv_heads"], kw["head_dim"]) \
        == (pub["num_attention_heads"], pub["num_key_value_heads"],
            pub["head_dim"]) == (16, 16, 128)
    assert kw["ffn_dim"] == pub["intermediate_size"] == 5632
    assert kw["num_layers"] == pub["num_hidden_layers"] == 48
    assert kw["loops"] == pub["total_ut_steps"] == 4
    assert pub["early_exit_threshold"] == 1
    assert kw["rope_theta"] == pub["rope_theta"]
    assert kw["norm_eps"] == pub["rms_norm_eps"]
    assert cfg["model"]["args"] == [pub["vocab_size"]]
    assert not pub["tie_word_embeddings"] and pub["rope_scaling"] is None
    assert set(pub["layer_types"]) == {"full_attention"}
    for key in ("sandwich_norms", "end_of_pass_norm", "cache_by_pass",
                "rope", "no_bias", "exit_gate", "init", "slots"):
        assert cfg["assumed"][key], key
    assert "ONE chip" in cfg["deployment"]
    assert cfg["tolerance"]["why"] and cfg["tolerance"]["logit_gap_std"] > 0


def test_the_contract_reads_weights_and_pool_as_reckoned():
    """5.34 GB of weights, 336 pages of 16 tokens across 192 pools (25.2 MB
    a page, 8.48 GB with the trash page): 13.82 GB, 86% of the chip, by
    the shapes the program's own factory gives."""
    with open(os.path.join(ROOT, "benchmark/configs/ouro_2_6b.json"),
              encoding="utf-8") as f:
        cfg = json.load(f)
    model = cells.resolve(cfg["model"]["factory"])(
        *cfg["model"]["args"], **cfg["model"]["kwargs"])
    params, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_paged_cache(
        12 * 448 // 16, 16, jnp.bfloat16, num_slots=12))
    leaves = jax.tree_util.tree_leaves
    weights = sum(int(np.prod(a.shape)) * 2 for a in leaves(params))
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in leaves(cache))
    assert weights == pytest.approx(5.336e9, rel=1e-3)
    assert cache["pages"]["k"].shape == (337, 192, 16, 2048)
    assert held == 337 * 192 * 16 * 2048 * 2 * 2
    assert (weights + held) / 1e9 == pytest.approx(13.82, abs=0.01)
    assert 0.5 * 16e9 <= weights + held <= 0.9 * 16e9


# -- the runner, end to end, toy widths ---------------------------------------------

def _toy_run(tmp_path, seconds=1.5):
    cell = copy.deepcopy(cells.load_cell(ROOT, CELL))
    cell.config["model"]["args"] = [97]
    cell.config["model"]["kwargs"].update(TOY)
    cell.config["server"].update(
        num_slots=3, max_len=64, dtype="float32", seq_buckets=[8, 16])
    cell.config["tolerance"].update(rows=64, logit_gap_std=1e-4)
    scale = lambda ms: [[max(2, v // 16), c] for v, c in ms]
    cell.traffic["prompt_lengths"] = scale(cell.traffic["prompt_lengths"])
    cell.traffic["output_lengths"] = scale(cell.traffic["output_lengths"])
    return harness.new_run(
        root=ROOT, cell=cell, seed=2 ** 31 + 42, seconds=seconds,
        trace_on=False, out_dir=str(tmp_path), t0=time.monotonic(),
        peaks=PEAKS, meter=harness.CompileMeter().install(),
        device={"platform": "cpu", "kind": "cpu", "count": 1})


@pytest.fixture
def toy_published(monkeypatch):
    from benchmark.reference import ouro
    monkeypatch.setitem(ouro.PUBLISHED, "total_ut_steps", TOY["loops"])
    monkeypatch.setattr(ouro, "SEQ_STEP", 16)


def test_closed_runner_end_to_end_on_a_toy_configuration(tmp_path,
                                                         toy_published):
    from benchmark import serve_cell
    run = _toy_run(tmp_path)
    serve_cell.run(run)
    assert run.correct and run.failed == 0 and run.attempted > 0
    assert run.e2e["serve_tokens_per_s"] > 0
    assert run.counters["shed"] == 0 and run.counters["chunks"] > 5
    assert run.counters["prefix_hit_rate"] is None      # declined


@pytest.mark.parametrize("variant", ["fewer_passes", "shared_pool", "kv_f8"])
def test_each_control_variant_reads_not_correct(tmp_path, toy_published,
                                                monkeypatch, variant):
    """``benchmark/looped_control.py``'s changes to the served model, each
    through the same runner and the same comparison."""
    from benchmark import looped_control, serve_cell
    from bigdl_tpu.models import looped
    from bigdl_tpu.nn import attention
    monkeypatch.setattr(looped.LoopedLM, "_pool", looped.LoopedLM._pool)
    monkeypatch.setattr(attention, "_write_rows", attention._write_rows)
    monkeypatch.setattr(serve_cell, "CHECKED", 16)
    run = _toy_run(tmp_path)
    looped_control.apply_variant(variant, run.cell.config)
    serve_cell.run(run)
    assert run.failed == 0 and run.attempted > 0
    assert not run.correct
    assert run.compared["logit_gap_std"][0] > 1e-3


# -- the readers, on synthetic slices ----------------------------------------------

class _Trace:
    """Two decode chunks of 10 ms, 100 ms apart, on a clock 5 s ahead."""
    sync = {"mono_ns": 105_000_000_000, "trace_ns": 100_000_000_000}

    def __init__(self, busy_s=0.010):
        self._runs = [(100.0e9, 100.0e9 + 10e6), (100.1e9, 100.1e9 + 10e6)]
        self._busy = busy_s

    def runs(self, program):
        return list(self._runs) if program == "step_chunk" else []

    def busy_in(self, iv):
        return self._busy


PATH = ("jit(step_chunk_kernel)/while/body/closed_call/while/body/"
        "closed_call/pass/while/body/closed_call/block/{}/dot_general")


def _synthetic(counters, scope_s, busy_s=0.010, with_trace=True):
    """A run whose two traced chunks each carry ``counters`` and hold one
    operation of ``scope_s[key]`` seconds under each scope path ``key``
    below ``pass/block``."""
    cell = cells.load_cell(ROOT, CELL)
    trace = _Trace(busy_s) if with_trace else None
    ops = []
    for a, _b in (_Trace()._runs if with_trace else []):
        for scope, sec in scope_s.items():
            ops.append(["fusion.1", "fusion", PATH.format(scope), a + 1e3,
                        sec * 1e9])
    records = [{"type": "span", "name": "serve.decode", "mono": m,
                "dur_s": 0.05, "attrs": dict(counters, steps=4)}
               for m in (104.99, 105.09)]
    return SimpleNamespace(cell=cell, trace=trace, records=records,
                           scope_ops=ops, peaks=PEAKS, out_dir="")


# a chunk of four steps of 12 rows at a mean context of 217 tokens
COUNTERS = {"loop_passes": 4 * 4 * 12, "state_rows": 4 * 12,
            "latent_tokens": 4 * 12 * 217}


def _floor_s(name, d):
    c = COUNTERS
    return {
        "looped_paged_decode_roofline": costs_looped.kv_read_bytes(
            c["latent_tokens"], 4, d) / PEAKS["hbm_bytes_per_s"],
        "looped_step_hbm_pct": costs_looped.step_min_bytes(
            4, c["latent_tokens"], 4, d) / PEAKS["hbm_bytes_per_s"],
        "looped_step_mfu_pct": costs_looped.step_matmul_flops(
            c["state_rows"], c["latent_tokens"], 4, d) / PEAKS["bf16_flops"],
    }[name]


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_gives_nothing_without_a_trace_counters_or_scopes(name):
    reader = cells.load_metric(ROOT, name)
    assert reader.MOVES == "serve_tokens_per_s"
    assert reader.read(_synthetic(COUNTERS, {"mlp": 1.0},
                                  with_trace=False)) is None
    # a program whose spans lack the counter (the parent's, or another
    # model's), or that has none of the scopes
    other = {k: v for k, v in COUNTERS.items() if k != "loop_passes"}
    assert reader.read(_synthetic(other, {"attn/attn.paged": 1.0})) is None
    if name != "looped_step_hbm_pct" and name != "looped_step_mfu_pct":
        bare = _synthetic(COUNTERS, {})
        bare.scope_ops = [[o, "fusion", "jit(step_chunk_kernel)/x/y/dot",
                           100.0e9, 1e6] for o in ("a", "b")]
        assert reader.read(bare) is None


@pytest.mark.parametrize("name",
                         sorted(set(READERS) - {"loop_pass_device_ms"}))
@pytest.mark.parametrize("slack", [1.0, 2.0])
def test_reader_reads_a_known_share_and_never_over_100_at_the_floor(
        name, slack):
    pair = READERS[name]
    d = looped_trace.dims(_synthetic(COUNTERS, {}))
    floor = _floor_s(name, d)           # of ONE chunk
    run = _synthetic(COUNTERS, {"/".join(pair): slack * floor} if pair
                     else {"mlp": 0.001}, busy_s=slack * floor)
    got = cells.load_metric(ROOT, name).read(run)
    assert got == pytest.approx(100.0 / slack, rel=1e-6)
    assert got <= 100.0 + 1e-6


def test_the_paged_share_counts_only_its_own_scope():
    d = looped_trace.dims(_synthetic(COUNTERS, {}))
    floor = _floor_s("looped_paged_decode_roofline", d)
    run = _synthetic(COUNTERS, {"attn/attn.paged": floor,
                                "attn/kv.write": 9.0, "mlp": 9.0})
    got = cells.load_metric(ROOT, "looped_paged_decode_roofline").read(run)
    assert got == pytest.approx(100.0, rel=1e-6)


def test_a_pass_is_the_time_under_pass_over_steps_times_passes():
    """Two chunks of 4 steps of 4 passes; 3 ms under ``pass`` a chunk (and
    time under ``logits`` outside it that does not count): 3 ms x 2 / 32."""
    run = _synthetic(COUNTERS, {"mlp": 0.002, "attn/attn.paged": 0.001})
    for a, _b in run.trace._runs:
        run.scope_ops.append(["fusion.9", "fusion",
                              "jit(step_chunk_kernel)/while/body/logits/dot",
                              a + 2e3, 5e6])
    got = cells.load_metric(ROOT, "loop_pass_device_ms").read(run)
    assert got == pytest.approx(1e3 * 2 * 0.003 / (2 * 4 * 4))


def test_cost_functions_against_the_issues_arithmetic():
    d = looped_trace.dims(_synthetic(COUNTERS, {}))
    assert (d["layers"], d["loops"], d["hkv"], d["vocab"]) == \
        (48, 4, 16, 49152)
    # one layer: 4 x 2,048^2 of attention, 3 x 2,048 x 5,632 of MLP, 4 norms
    assert costs_looped.layer_matmul_params(d) == 4 * 2048 ** 2 \
        + 3 * 2048 * 5632 == 51_380_224
    assert costs_looped.layer_params(d) == 51_388_416
    # 2.668 B parameters, 5.34 GB in bf16
    assert costs_looped.model_params(d) == 48 * 51_388_416 + 2048 \
        + 2 * 49152 * 2048
    assert 2 * costs_looped.model_params(d) / 1e9 == pytest.approx(5.336,
                                                                   abs=1e-3)
    # a token's KV across the 192 pools: 1.57 MB; a 16-token page 25.2 MB
    assert costs_looped.kv_bytes_per_context_token(d) == 1_572_864
    assert 16 * costs_looped.kv_bytes_per_context_token(d) / 1e6 \
        == pytest.approx(25.17, abs=0.01)
    # a step reads the stack four times and the head once: 19.9 GB,
    # 24.3 ms at 819 GB/s; with 12 rows at a mean position of 217 the KV
    # adds 4.1 GB (12 x 218 x 4 x 48 x 8 KB) and 5.0 ms
    assert costs_looped.stack_bytes(d) / 1e9 == pytest.approx(4.933, abs=1e-3)
    weights = costs_looped.step_min_bytes(1, 0, 4, d)
    assert weights / 1e9 == pytest.approx(19.93, abs=0.01)
    assert weights / PEAKS["hbm_bytes_per_s"] == pytest.approx(24.3e-3,
                                                               abs=0.05e-3)
    kv = costs_looped.kv_read_bytes(12 * 218, 4, d)
    assert kv == 12 * 218 * 4 * 48 * 8192
    assert kv / 1e9 == pytest.approx(4.11, abs=0.01)
    assert kv / PEAKS["hbm_bytes_per_s"] == pytest.approx(5.0e-3, abs=0.05e-3)
    flops = costs_looped.step_matmul_flops(12, 12 * 218, 4, d)
    assert flops == 2.0 * 12 * (4 * 48 * 51_380_224 + 49152 * 2048) \
        + 12 * 218 * 4 * 48 * 4 * 16 * 128
    # compute is about 1.2 ms of a step: memory-bound many times over
    assert flops / PEAKS["bf16_flops"] == pytest.approx(1.2e-3, rel=0.05)


def test_the_lowered_decode_program_carries_the_scopes_the_readers_read():
    """The toy decode chunk lowered for a TPU (no chip needed): every scope
    pair ``looped_trace`` names appears in the operations' locations, the
    paged kernel among them.  A scan's body starts its own name stack in
    the lowered text (``block/attn/...`` inside a location of ``pass``);
    the compiled program's ``op_name`` joins them
    (``pass/while/body/closed_call/block/attn/attn.paged``), and
    ``scope_events.scopes`` reads that as ``pass, block, attn,
    attn.paged``."""
    from unittest import mock

    import bigdl_tpu.ops
    from bigdl_tpu.models.looped import LoopedLM
    from bigdl_tpu.serving.scheduler import ContinuousGenerator
    model = LoopedLM(97, **dict(TOY, embed_dim=256, num_heads=2,
                                num_kv_heads=2, head_dim=128))
    params, state = model.init(jax.random.PRNGKey(0))
    with mock.patch.object(bigdl_tpu.ops, "pallas_enabled",
                           return_value=True):
        gen = ContinuousGenerator(model, params, state, num_slots=2,
                                  max_len=64, seq_buckets=[16],
                                  warmup=False, donate_cache=True)
        try:
            text = gen._step_fn.trace(
                params, state, np.ones(2, np.int32), gen._cache,
                np.zeros((2, 4), np.int32), np.zeros(2, np.int32),
                np.ones(2, bool), np.full(2, 9, np.int32),
                jax.random.split(jax.random.PRNGKey(0), 4)).lower(
                lowering_platforms=("tpu",)).as_text(debug_info=True)
        finally:
            gen.drain(timeout=30)
    for parent, child in looped_trace.SCOPES:
        if child == "attn.prefill" or (parent, child) == ("pass", "block"):
            continue            # a prefill's; the scan's body (see above)
        assert f"{parent}/{child}" in text, (parent, child)
    assert 'loc("pass/' in text and 'loc("block/' in text
    assert "tpu_custom_call" in text and "paged_attention" in text
