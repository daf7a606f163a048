"""The cell ``keye_vl2_30b_a3b.longdoc_closed``: the configuration against
the catalog row's widths and the contract's memory rule, the traffic, the
closed runner end to end on a toy configuration of the sparse-attention
pattern model, the reference check passing and each control of
``benchmark/dsa_control.py`` failing it, the new readers on synthetic
events, and the cost functions against hand counts."""

import copy
import json
import os
import time
from types import SimpleNamespace

import jax
import pytest

from benchmark import cells, costs_dsa, dsa_trace, harness, peaks

ROOT = cells.repo_root()
CELL = "keye_vl2_30b_a3b.longdoc_closed"
PEAKS = peaks.lookup("TPU v5 lite")
TOY = dict(max_len=1024, embed_dim=64, num_heads=4, num_kv_heads=2,
           num_layers=2, layers=[["dsa", "experts"], ["dsa", "experts"]],
           head_dim=16, expert_dim=24, num_experts=16, experts_per_token=4,
           experts_held=8, index_heads=16, index_dim=16, index_rope_dim=8,
           topk=16, index_block=32)
TOY_PUBLISHED = dict(num_experts_per_tok=4, topk=16, index_rope_dim=8)
NEW = ("dsa_indexer_decode_roofline", "dsa_sparse_decode_roofline",
       "dsa_select_share_pct", "dsa_moe_step_mfu_pct",
       "dsa_moe_step_hbm_pct", "dsa_prefill_mfu_pct")


# -- the files ---------------------------------------------------------------------

def test_load_cell_finds_the_new_cell_and_its_readers():
    cell = cells.load_cell(ROOT, CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "closed"
    assert cell.config["model"]["args"] == [18992]
    assert {m["name"] for m in cell.end_to_end} \
        == {"serve_tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert names == set(NEW) | {
        "compile_s", "window_compiles", "slot_occupancy_pct",
        "decode_gap_ms", "decode_step_device_ms", "chat_device_idle_pct",
        "chunk_itl_p95_ms", "decode_kv_copy_pct", "decode_host_turn_ms",
        "prefill_window_share_pct"}
    for name in names:
        assert hasattr(cells.load_metric(ROOT, name), "read")


def test_traffic_is_the_issues_multiset():
    """The issue's ten prompts and seven outputs, each multiset repeated
    (x7, x10) so that a block of seventy pairs them whole: the same mix."""
    from benchmark import traffic
    cell = cells.load_cell(ROOT, CELL)
    spec, srv = cell.traffic, cell.config["server"]
    prompts = traffic.expand(spec["prompt_lengths"])
    outs = traffic.expand(spec["output_lengths"])
    assert [[v, c // 7] for v, c in spec["prompt_lengths"]] \
        == [[4096, 3], [8192, 3], [16384, 2], [24576, 1], [30720, 1]]
    assert [[v, c // 10] for v, c in spec["output_lengths"]] \
        == [[256, 2], [512, 3], [1024, 2]]
    assert len(prompts) == len(outs) == 70
    assert sum(prompts) / 70 == pytest.approx(12492.8)
    assert sum(outs) / 70 == pytest.approx(585.14, abs=0.01)
    assert "shared_head" not in spec and spec["discard_chunks"] == 5
    assert max(prompts) + max(outs) <= srv["max_len"] == 32768
    assert sorted(set(prompts)) == srv["seq_buckets"]
    assert srv["num_slots"] == 32 and srv["page_size"] == 16


def _config():
    with open(os.path.join(ROOT, "benchmark/configs/keye_vl2_30b_a3b.json"),
              encoding="utf-8") as f:
        return json.load(f)


def test_the_configuration_keeps_every_published_width():
    cfg = _config()
    pub, kw = cfg["published"], cfg["model"]["kwargs"]
    sa = pub["sa_config"]
    assert kw["embed_dim"] == pub["hidden_size"] == cfg["hidden_size"] == 2048
    assert (kw["num_heads"], kw["num_kv_heads"], kw["head_dim"]) \
        == (pub["num_attention_heads"], pub["num_key_value_heads"],
            pub["head_dim"]) == (32, 4, 128)
    assert (kw["index_heads"], kw["index_dim"], kw["topk"],
            kw["index_block"]) == (sa["indexer_num_heads"],
                                   sa["indexer_head_dim"], sa["topk"],
                                   sa["q_chunk_size"]) == (16, 64, 2048, 512)
    assert sa["indexer_num_kv_heads"] == 1 and sa["kv_chunk_size"] == 512
    assert kw["expert_dim"] == pub["moe_intermediate_size"] == 768
    assert kw["num_experts"] == pub["num_experts"] == 128
    assert kw["experts_per_token"] == pub["num_experts_per_tok"] == 8
    assert (kw["router"], kw["shared_expert"], kw["routed_scale"]) \
        == ("softmax", False, 1.0) and pub["norm_topk_prob"]
    assert kw["rope_theta"] == pub["rope_theta"]
    assert sum(pub["rope_scaling"]["mrope_section"]) * 2 == kw["head_dim"]
    assert kw["norm_eps"] == pub["rms_norm_eps"]
    assert set(cfg["reduced"]) == set(cfg["reduced_why"]) == {
        "num_hidden_layers", "num_experts", "vocab_size", "vision_tower"}
    # every key of the row is in the file, and only the reduced ones differ
    for key, value in pub.items():
        assert (cfg[key] != value) == (key in cfg["reduced"]), key
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (4, 16, 18992)
    assert cfg["vocab_size"] * 8 == pub["vocab_size"]
    assert kw["experts_held"] * 8 == pub["num_experts"]
    assert kw["layers"] == [["dsa", "experts"]] * 4 == \
        [["dsa", "experts"] for _ in cfg["layers_held"]]
    assert pub["decoder_sparse_step"] == 1 and pub["mlp_only_layers"] == []
    from benchmark.reference import keye_vl2
    assert (keye_vl2.PUBLISHED["topk"], keye_vl2.PUBLISHED["rope_theta"],
            keye_vl2.PUBLISHED["rms_norm_eps"]) \
        == (sa["topk"], pub["rope_theta"], pub["rms_norm_eps"])
    for key in ("norm_placement", "qk_norm", "rope", "indexer", "experts",
                "sparse_layers", "token_ids", "init", "page_size", "slots",
                "buckets"):
        assert cfg["assumed"][key]
    assert "8 chips" in cfg["deployment"]


@pytest.mark.parametrize("slots,gb", [(32, 10.59), (40, 13.01)])
def test_the_resident_bytes_are_the_issues_arithmetic(slots, gb):
    """Weights 0.93 GB; a token 2,304 bytes a layer in three pools (K and
    V of 512 lanes, the indexer's key of 64 padded to 128), 9,216 over the
    four; at 32 slots inside the contract's half to nine tenths, at 40
    too close to its top to leave the prefill and the reference room."""
    import jax.numpy as jnp
    import numpy as np
    cfg = _config()
    model = cells.resolve(cfg["model"]["factory"])(
        *cfg["model"]["args"], **cfg["model"]["kwargs"])
    params, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_paged_cache(
        slots * 2048, 16, jnp.bfloat16, num_slots=slots))
    leaves = jax.tree_util.tree_leaves
    weights = sum(int(np.prod(a.shape)) * 2 for a in leaves(params))
    assert weights == pytest.approx(0.93e9, rel=0.01)
    assert model.state_bytes(cache) == {"page": {"dsa": 4 * 16 * 2304},
                                        "slot": {}}
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in leaves(cache))
    assert (weights + held) / 1e9 == pytest.approx(gb, abs=0.02)
    if slots == 32:
        assert 8.0e9 <= weights + held <= 14.4e9


# -- the runner, end to end, toy widths ---------------------------------------------

def _toy_run(tmp_path, seconds=1.5):
    cell = copy.deepcopy(cells.load_cell(ROOT, CELL))
    cell.config["model"]["args"] = [97]
    cell.config["model"]["kwargs"].update(TOY)
    cell.config["server"].update(
        num_slots=4, max_len=1024, dtype="float32",
        seq_buckets=[64, 128, 256, 384, 480])
    cell.config["tolerance"].update(rows=64, logit_gap_std=1e-4)
    scale = lambda ms, n: [[max(2, v // n), c] for v, c in ms]
    cell.traffic["prompt_lengths"] = scale(cell.traffic["prompt_lengths"], 64)
    cell.traffic["output_lengths"] = scale(cell.traffic["output_lengths"], 16)
    return harness.new_run(
        root=ROOT, cell=cell, seed=2 ** 31 + 44, seconds=seconds,
        trace_on=False, out_dir=str(tmp_path), t0=time.monotonic(),
        peaks=PEAKS, meter=harness.CompileMeter().install(),
        device={"platform": "cpu", "kind": "cpu", "count": 1})


@pytest.fixture
def toy_published(monkeypatch):
    from benchmark.reference import keye_vl2
    for k, v in TOY_PUBLISHED.items():
        monkeypatch.setitem(keye_vl2.PUBLISHED, k, v)
    monkeypatch.setattr(keye_vl2, "SEQ_STEP", 64)


def test_closed_runner_end_to_end_on_a_toy_configuration(tmp_path,
                                                         toy_published):
    from benchmark import serve_cell
    run = _toy_run(tmp_path)
    serve_cell.run(run)
    assert run.correct and run.failed == 0 and run.attempted > 0
    assert run.e2e["serve_tokens_per_s"] > 0
    assert run.counters["shed"] == 0 and run.counters["chunks"] > 5
    assert run.counters["occupancy_pct"] == pytest.approx(100.0, abs=5.0)


@pytest.mark.parametrize("variant", ["last_keys", "dense", "router_bf16"])
def test_each_control_fails_the_reference_check(tmp_path, toy_published,
                                                monkeypatch, variant):
    """The run with the served model changed as ``dsa_control.py`` changes
    it is a different result, and the check says so."""
    from benchmark import dsa_control, serve_cell
    from bigdl_tpu.models import hybrid
    from bigdl_tpu.ops import sparse_attention as sa
    for name in ("block_index_scores", "paged_index_scores", "kth_largest",
                 "select_topk"):
        monkeypatch.setattr(sa, name, getattr(sa, name))
    monkeypatch.setattr(hybrid.HybridLM, "_route", hybrid.HybridLM._route)
    dsa_control.apply_variant(variant)
    monkeypatch.setattr(serve_cell, "CHECKED", 64)
    run = _toy_run(tmp_path)
    serve_cell.run(run)
    assert run.failed == 0 and run.attempted > 0
    assert not run.correct


# -- the readers, on synthetic events --------------------------------------------

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


def _synthetic(counters, scope_s, busy_s=0.010, with_trace=True):
    """A run whose two traced chunks each carry ``counters`` and hold one
    operation of ``scope_s[pair]`` seconds under each scope pair."""
    cell = cells.load_cell(ROOT, CELL)
    trace = _Trace(busy_s) if with_trace else None
    ops = []
    for a, _b in (_Trace()._runs if with_trace else []):
        for (parent, child), sec in scope_s.items():
            ops.append(["fusion.1", "fusion",
                        f"jit(step_chunk_kernel)/while/body/closed_call/"
                        f"block_2/{parent}/{child}/dot_general",
                        a + 1e3, sec * 1e9])
    records = [{"type": "span", "name": "serve.decode", "mono": m,
                "dur_s": 0.05, "attrs": dict(counters, steps=4)}
               for m in (104.99, 105.09)]
    return SimpleNamespace(cell=cell, trace=trace, records=records,
                           scope_ops=ops, peaks=PEAKS, out_dir="")


READERS = {
    "dsa_indexer_decode_roofline": ("dsa", "indexer"),
    "dsa_sparse_decode_roofline": ("dsa", "attn.sparse"),
    "dsa_moe_step_mfu_pct": None, "dsa_moe_step_hbm_pct": None}
# a chunk of four steps of 32 rows at 12,800 tokens, 2 pairs a held expert
COUNTERS = {"expert_pairs": 4 * 4 * 32, "experts_hit": 4 * 4 * 12,
            "state_rows": 4 * 32, "keys_indexed": 4 * 4 * 32 * 12800,
            "keys_selected": 4 * 4 * 32 * 2048,
            "latent_tokens": 4 * 32 * 12800, "expert_pairs_max": 5}


def _floor_s(name, d):
    c = dict(COUNTERS, steps=4)
    return {
        "dsa_indexer_decode_roofline": costs_dsa.indexer_read_floor_s(
            c, d, PEAKS),
        "dsa_sparse_decode_roofline": costs_dsa.sparse_read_floor_s(
            c, d, PEAKS),
        "dsa_moe_step_mfu_pct": costs_dsa.step_matmul_flops(c, d)
        / PEAKS["bf16_flops"],
        "dsa_moe_step_hbm_pct": costs_dsa.step_min_bytes(c, d)
        / PEAKS["hbm_bytes_per_s"]}[name]


@pytest.mark.parametrize("name", sorted(READERS) + ["dsa_select_share_pct"])
def test_reader_gives_nothing_without_a_trace_or_without_counters(name):
    reader = cells.load_metric(ROOT, name)
    assert reader.UNIT == "%" and reader.MOVES == "serve_tokens_per_s"
    assert reader.read(_synthetic(COUNTERS, {}, with_trace=False)) is None
    # a program whose spans lack the counters (the parent's models), or
    # that has no such scopes
    other = {k: v for k, v in COUNTERS.items()
             if k not in ("keys_indexed", "keys_selected")}
    pair = READERS.get(name, ("dsa", "select")) or ("a", "b")
    assert reader.read(_synthetic(other, {pair: 1.0})) is None
    if READERS.get(name, ("dsa", "select")):
        assert reader.read(_synthetic(COUNTERS, {("a", "b"): 1.0})) is None


@pytest.mark.parametrize("name", sorted(READERS))
@pytest.mark.parametrize("slack", [1.0, 2.0])
def test_reader_reads_a_known_share_and_never_over_100_at_the_floor(
        name, slack):
    pair = READERS[name]
    d = dsa_trace.dims(_synthetic(COUNTERS, {}))
    floor = _floor_s(name, d)           # of ONE chunk
    run = _synthetic(COUNTERS, {pair: slack * floor} if pair else {},
                     busy_s=slack * floor)
    got = cells.load_metric(ROOT, name).read(run)
    assert got == pytest.approx(100.0 / slack, rel=1e-6)
    assert got <= 100.0 + 1e-6


def test_the_select_share_counts_its_own_scope_over_the_busy_time():
    run = _synthetic(COUNTERS, {("dsa", "select"): 0.002,
                                ("dsa", "indexer"): 0.005}, busy_s=0.010)
    got = cells.load_metric(ROOT, "dsa_select_share_pct").read(run)
    assert got == pytest.approx(20.0)


def test_cost_functions_against_hand_counts():
    d = dsa_trace.dims(_synthetic(COUNTERS, {}))
    assert (d["dsa"], d["experts"], d["hi"], d["di"], d["topk"]) \
        == (4, 4, 16, 64, 2048)
    attention = 2 * 2048 * 4096 + 2 * 2048 * 512            # 18.87 M
    indexer = 2048 * (1024 + 64 + 16)                        # 2.26 M
    assert (attention, indexer) == (18_874_368, 2_260_992)
    resident = 4 * (attention + indexer + 128 * 2048) + 18992 * 2048
    assert costs_dsa.resident_matmul_params(d) == resident == 124_485_632
    assert costs_dsa.expert_bytes(d) == 9_437_184
    assert costs_dsa.expert_pair_flops(d) == 9_437_184
    assert costs_dsa.index_key_bytes(d) == 256
    assert costs_dsa.kv_bytes_per_token(d) == 2048
    assert costs_dsa.index_flops_per_key(d) == 2048
    assert costs_dsa.attention_flops_per_key(d) == 16384
    # 0.47 B parameters: resident + embedding + 4 x 16 held experts
    total = resident + 18992 * 2048 + 64 * 3 * 2048 * 768
    assert total == pytest.approx(0.465e9, rel=1e-2)
    # the issue's decode step: 32 rows at 12,800 tokens, 2 pairs on each
    # of the 16 held experts of every layer: 249 MB resident, 604 MB of
    # experts, 419 MB of indexer keys, 537 MB of selected keys and values
    c = {"steps": 1, "state_rows": 32, "expert_pairs": 128,
         "experts_hit": 64, "keys_indexed": 4 * 32 * 12800,
         "keys_selected": 4 * 32 * 2048}
    step = costs_dsa.step_min_bytes(c, d)
    assert step == 2 * resident + 64 * 9_437_184 + 4 * 32 * 12800 * 256 \
        + 4 * 32 * 2048 * 2048
    assert step / 1e9 == pytest.approx(1.81, abs=0.01)
    assert costs_dsa.indexer_read_floor_s(c, d, PEAKS) == pytest.approx(
        (4 * 32 * 12800 * 256 + 32 * 4 * 1024 * 2) / 819e9)
    assert costs_dsa.sparse_read_floor_s(c, d, PEAKS) == pytest.approx(
        (4 * 32 * 2048 * 2048 + 32 * 4 * 2 * 4096 * 2) / 819e9)
    flops = costs_dsa.step_matmul_flops(c, d)
    assert flops == 2.0 * 32 * resident + 128 * 9_437_184 \
        + 4 * 32 * 12800 * 2048 + 4 * 32 * 2048 * 16384


def test_prefill_sparse_flops_against_hand_counts():
    d = dsa_trace.dims(_synthetic(COUNTERS, {}))
    body = 124_485_632 - 18992 * 2048
    # 100 tokens, under topk: every query attends over its whole prefix
    keys = 100 * 101 // 2
    assert costs_dsa.selected_keys(100, 2048) == keys
    assert costs_dsa.prefill_sparse_flops(100, 0, d) \
        == 2.0 * 100 * body + 2.0 * 18992 * 2048 + 4 * keys * (2048 + 16384)
    # 12,500 tokens: 2,048 x 2,049 / 2 + 10,452 x 2,048 selected pairs a
    # layer against 78 M causal pairs scored
    sel = 2048 * 2049 // 2 + (12500 - 2048) * 2048
    assert costs_dsa.selected_keys(12500, 2048) == sel
    got = costs_dsa.prefill_sparse_flops(12500, 8 * 12500 // 8, d)
    assert got == 2.0 * 12500 * body + 2.0 * 18992 * 2048 \
        + 12500 * 9_437_184 + 4 * (12500 * 12501 // 2) * 2048 \
        + 4 * sel * 16384
    # the issue's count: about 1.5 TFLOP of selected attention over the
    # four layers, 0.6 of indexer
    assert 4 * sel * 16384 / 1e12 == pytest.approx(1.6, abs=0.1)
    assert 4 * (12500 * 12501 // 2) * 2048 / 1e12 == pytest.approx(
        0.64, abs=0.05)


def test_the_prefill_reader_counts_the_sparse_flops_of_the_window():
    mfu = cells.load_metric(ROOT, "dsa_prefill_mfu_pct")
    d = dsa_trace.dims(_synthetic(COUNTERS, {}))
    attrs = {"tp": 8192, "expert_pairs": 8192, "keys_selected": 1,
             "keys_indexed": 1}
    floor = costs_dsa.prefill_sparse_flops(8192, 8192, d) \
        / PEAKS["bf16_flops"]
    records = [{"type": "span", "name": "serve.prefill", "mono": m,
                "dur_s": 4 * floor, "attrs": dict(attrs, slot=0)}
               for m in (101.0, 110.0)]
    run = SimpleNamespace(cell=cells.load_cell(ROOT, CELL), trace=None,
                          records=records, window=(100.0, 130.0),
                          peaks=PEAKS)
    assert mfu.read(run) == pytest.approx(25.0)
    for r in records:
        del r["attrs"]["keys_selected"]
    assert mfu.read(run) is None
