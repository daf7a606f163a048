"""The cell ``nemotron3_super_120b.mixed_closed``: the configuration against
the catalog row's widths and the issue's arithmetic, the closed runner end
to end on a toy configuration of the state-space / latent-expert pattern,
the reference check passing and failing, the new readers on synthetic
events, the cost functions against hand counts, and the shared kernel's
plan pinned at the new cell's shapes."""

import copy
import time
from types import SimpleNamespace

import jax
import pytest

from benchmark import cells, costs_ssm_moe, harness, peaks, ssm_trace

ROOT = cells.repo_root()
CELL = "nemotron3_super_120b.mixed_closed"
PEAKS = peaks.lookup("TPU v5 lite")
M, E, A = ["mamba2", None], [None, "latent_experts"], ["full", None]
TOY = dict(max_len=640, embed_dim=64, num_heads=4, num_kv_heads=2,
           head_dim=16, num_layers=5, layers=[E, M, E, M, A], expert_dim=24,
           num_experts=16, experts_per_token=4, experts_held=4,
           latent_size=32, shared_dim=48, ssm_heads=8, ssm_head_dim=8,
           ssm_state=16, ssm_groups=2, ssm_chunk=16)
TOY_PUBLISHED = dict(n_groups=2, num_experts_per_tok=4)


# -- the files ---------------------------------------------------------------------

def test_load_cell_finds_the_new_cell_and_its_readers():
    cell = cells.load_cell(ROOT, CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "closed"
    assert cell.traffic_name == "mixed_closed"
    assert cell.config["model"]["args"] == [32768]
    assert {m["name"] for m in cell.end_to_end} \
        == {"serve_tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert names >= {
        "compile_s", "window_compiles", "slot_occupancy_pct",
        "decode_gap_ms", "decode_step_device_ms", "chat_device_idle_pct",
        "prefill_window_share_pct", "ssm_state_decode_roofline",
        "latent_experts_decode_roofline", "gqa2_paged_decode_roofline",
        "ssm_moe_step_mfu_pct", "ssm_moe_step_hbm_pct",
        "ssm_moe_prefill_mfu_pct"}
    for name in names:
        assert hasattr(cells.load_metric(ROOT, name), "read")
    # the benchmark: five configurations, seven cells, one on four chips
    bench = cells.load_benchmark(ROOT)
    assert len(bench["configs"]) == 5 and len(bench["workloads"]) == 7
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert bench["workloads"][-1]["name"] == CELL
    assert all(len(w["why"]) <= 200 for w in bench["workloads"])
    assert all(len(c["why"]) <= 200 for c in bench["configs"])


def test_the_traffic_file_serves_both_pattern_models_as_it_is():
    k_exaone = cells.load_cell(ROOT, "k_exaone_236b.mixed_closed")
    cell = cells.load_cell(ROOT, CELL)
    assert cell.traffic == k_exaone.traffic
    srv = cell.config["server"]
    from benchmark import traffic
    prompts = traffic.expand(cell.traffic["prompt_lengths"])
    outs = traffic.expand(cell.traffic["output_lengths"])
    assert max(prompts) + max(outs) <= srv["max_len"] == 10240
    assert sorted(set(prompts)) == srv["seq_buckets"]
    assert srv["num_slots"] in (128, 112, 96) and srv["page_size"] == 16


def test_the_configuration_keeps_every_published_width():
    cfg = cells.load_cell(ROOT, CELL).config
    pub, kw = cfg["published"], cfg["model"]["kwargs"]
    assert kw["embed_dim"] == pub["hidden_size"] == cfg["hidden_size"] == 4096
    assert (kw["num_heads"], kw["num_kv_heads"], kw["head_dim"]) \
        == (pub["num_attention_heads"], pub["num_key_value_heads"],
            pub["head_dim"]) == (32, 2, 128)
    assert (kw["ssm_heads"], kw["ssm_head_dim"], kw["ssm_state"],
            kw["ssm_groups"], kw["ssm_chunk"], kw["conv_taps"]) \
        == (pub["mamba_num_heads"], pub["mamba_head_dim"],
            pub["ssm_state_size"], pub["n_groups"], pub["chunk_size"],
            pub["conv_kernel"]) == (128, 64, 128, 8, 128, 4)
    assert kw["ssm_heads"] * kw["ssm_head_dim"] \
        == pub["expand"] * pub["hidden_size"]
    assert kw["expert_dim"] == pub["moe_intermediate_size"] == 2688
    assert kw["latent_size"] == pub["moe_latent_size"] == 1024
    assert kw["shared_dim"] == pub["moe_shared_expert_intermediate_size"] \
        == 5376
    assert kw["expert_act"] == pub["mlp_hidden_act"] == "relu2"
    assert kw["num_experts"] == pub["n_routed_experts"] == 512
    assert kw["experts_per_token"] == pub["num_experts_per_tok"] == 22
    assert (kw["n_group"], kw["topk_group"]) \
        == (pub["n_group"], pub["topk_group"]) == (1, 1)
    assert kw["routed_scale"] == pub["routed_scaling_factor"] == 5
    assert kw["norm_eps"] == pub["norm_eps"] == pub["layer_norm_epsilon"]
    assert kw["qk_norm"] is False
    assert set(cfg["reduced"]) == set(cfg["reduced_why"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "num_nextn_predict_layers"}
    # every key of the row is in the file, and only the reduced ones differ
    for key, value in pub.items():
        assert (cfg[key] != value) == (key in cfg["reduced"]), key
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"], cfg["num_nextn_predict_layers"]) \
        == (11, 128, 32768, 0)
    assert cfg["vocab_size"] * 4 == pub["vocab_size"]
    assert kw["experts_held"] * 4 == pub["n_routed_experts"]
    # the kept blocks are the row's own pattern at blocks 26-36
    kind = {"M": M, "E": E, "*": A}
    assert cfg["layers_held"] == list(range(26, 37))
    assert [list(l) for l in kw["layers"]] == [
        kind[pub["hybrid_override_pattern"][i]] for i in cfg["layers_held"]]
    assert len(pub["hybrid_override_pattern"]) == pub["num_hidden_layers"]
    assert "".join(pub["hybrid_override_pattern"][i]
                   for i in cfg["layers_held"]) == "EMEMEMEMEM*"
    from benchmark.reference import nemotron_h
    assert nemotron_h.PUBLISHED == {
        "norm_eps": pub["norm_eps"], "n_groups": pub["n_groups"],
        "num_experts_per_tok": pub["num_experts_per_tok"],
        "routed_scaling_factor": pub["routed_scaling_factor"],
        "expert_offset": kw["expert_offset"]}
    for key in ("blocks", "no_rope", "mamba2", "state_dtype",
                "gate_before_norm", "experts", "token_ids", "init",
                "page_size", "steps_per_sync", "slots", "buckets"):
        assert cfg["assumed"][key] and "PLACEHOLDER" not in cfg["assumed"][key]
    assert "PLACEHOLDER" not in cfg["tolerance"]["why"]
    assert "4 chips" in cfg["deployment"]


@pytest.mark.parametrize("slots,gb", [(128, 13.36), (112, 12.85),
                                      (96, 12.35)])
def test_the_contracts_band_holds_at_the_three_slot_counts(slots, gb):
    """Weights 9.30 GB; a slot is 10,240 tokens of the one attention
    block's pages (10.5 MB) and five states and tails (21.3 MB): the
    issue's arithmetic, by the contract test's own count."""
    import json
    import os

    import jax.numpy as jnp
    import numpy as np
    with open(os.path.join(ROOT, "benchmark/configs/"
                           "nemotron3_super_120b.json"),
              encoding="utf-8") as f:
        cfg = json.load(f)
    model = cells.resolve(cfg["model"]["factory"])(
        *cfg["model"]["args"], **cfg["model"]["kwargs"])
    params, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_paged_cache(
        slots * 640, 16, jnp.bfloat16, num_slots=slots))
    leaves = jax.tree_util.tree_leaves
    count = sum(int(np.prod(a.shape)) for a in leaves(params))
    assert count == 4_648_163_712              # 4.65 B, 9.30 GB in bf16
    kinds = model.state_bytes(cache)
    assert kinds == {
        "page": {"full": 16 * 2 * 256 * 2},
        "slot": {"mamba2": 5 * (128 * 64 * 128 * 4 + 3 * 10240 * 2)}}
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in leaves(cache))
    assert (2 * count + held) / 1e9 == pytest.approx(gb, abs=0.02)
    assert 12.3e9 <= 2 * count + held <= 14.4e9


# -- the runner, end to end, toy widths ---------------------------------------------

def _toy_run(tmp_path, seconds=1.5):
    cell = copy.deepcopy(cells.load_cell(ROOT, CELL))
    cell.config["model"]["args"] = [97]
    cell.config["model"]["kwargs"].update(TOY)
    cell.config["server"].update(
        num_slots=4, max_len=640, dtype="float32",
        seq_buckets=[32, 64, 128, 256, 384, 512])
    cell.config["tolerance"].update(rows=64, logit_gap_std=1e-4)
    scale = lambda ms: [[max(2, v // 16), c] for v, c in ms]
    cell.traffic["prompt_lengths"] = scale(cell.traffic["prompt_lengths"])
    cell.traffic["output_lengths"] = scale(cell.traffic["output_lengths"])
    return harness.new_run(
        root=ROOT, cell=cell, seed=2 ** 31 + 35, seconds=seconds,
        trace_on=False, out_dir=str(tmp_path), t0=time.monotonic(),
        peaks=PEAKS, meter=harness.CompileMeter().install(),
        device={"platform": "cpu", "kind": "cpu", "count": 1})


@pytest.fixture
def toy_published(monkeypatch):
    from benchmark.reference import nemotron_h
    for k, v in TOY_PUBLISHED.items():
        monkeypatch.setitem(nemotron_h.PUBLISHED, k, v)
    monkeypatch.setattr(nemotron_h, "SEQ_STEP", 64)


def test_closed_runner_end_to_end_on_a_toy_configuration(tmp_path,
                                                         toy_published):
    from benchmark import serve_cell
    run = _toy_run(tmp_path)
    serve_cell.run(run)
    assert run.correct and run.failed == 0 and run.attempted > 0
    assert run.e2e["serve_tokens_per_s"] > 0
    assert run.counters["shed"] == 0 and run.counters["chunks"] > 5
    assert run.counters["prefix_hit_rate"] is None      # declined
    assert run.counters["occupancy_pct"] == pytest.approx(100.0, abs=5.0)


def test_reference_check_fails_a_bf16_router(tmp_path, toy_published,
                                             monkeypatch):
    """The same run with the router's scores rounded to bf16 before
    selection and gating is a different result, and the check says so."""
    from benchmark import serve_cell
    from bigdl_tpu.parallel import expert
    real = expert.sigmoid_group_route

    def rounded(scores, *a, **kw):
        # reduce_precision, not a cast there and back, which XLA is
        # allowed to drop (xla_allow_excess_precision)
        return real(jax.lax.reduce_precision(scores, 8, 7), *a, **kw)

    monkeypatch.setattr("bigdl_tpu.models.hybrid.sigmoid_group_route",
                        rounded)
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


def _synthetic(counters, scope_s, busy_s=0.010, with_trace=True, cell=CELL):
    """A run whose two traced chunks each carry ``counters`` and hold one
    operation of ``scope_s[pair]`` seconds under each scope pair."""
    cell = cells.load_cell(ROOT, cell)
    trace = _Trace(busy_s) if with_trace else None
    ops = []
    for a, _b in (_Trace()._runs if with_trace else []):
        for (parent, child), sec in scope_s.items():
            ops.append(["fusion.1", "fusion",
                        f"jit(step_chunk_kernel)/while/body/closed_call/"
                        f"block_3/{parent}/{child}/dot_general",
                        a + 1e3, sec * 1e9])
    records = [{"type": "span", "name": "serve.decode", "mono": m,
                "dur_s": 0.05, "attrs": dict(counters, steps=4)}
               for m in (104.99, 105.09)]
    return SimpleNamespace(cell=cell, trace=trace, records=records,
                           scope_ops=ops, peaks=PEAKS, out_dir="")


READERS = {
    "ssm_state_decode_roofline": ("mamba2", "state"),
    "latent_experts_decode_roofline": ("moe", "experts"),
    "gqa2_paged_decode_roofline": ("full", "attn.paged"),
    "ssm_moe_step_mfu_pct": None, "ssm_moe_step_hbm_pct": None}
# a chunk of four steps of 128 rows at 3,400 tokens, every held expert hit
COUNTERS = {"expert_pairs": 4 * 5 * 704, "experts_hit": 4 * 5 * 128,
            "state_rows": 4 * 128, "latent_tokens": 4 * 128 * 3400,
            "expert_pairs_max": 14}


def _floor_s(name, d):
    c = COUNTERS
    return {
        "ssm_state_decode_roofline": costs_ssm_moe.ssm_state_floor_s(
            c["state_rows"], d, PEAKS),
        "latent_experts_decode_roofline":
            costs_ssm_moe.latent_experts_floor_s(
                c["expert_pairs"], c["experts_hit"], d, PEAKS),
        "gqa2_paged_decode_roofline": costs_ssm_moe.gqa2_read_floor_s(
            c["latent_tokens"], d, PEAKS),
        "ssm_moe_step_mfu_pct": costs_ssm_moe.step_matmul_flops(
            c["state_rows"], c["expert_pairs"], c["latent_tokens"], d)
        / PEAKS["bf16_flops"],
        "ssm_moe_step_hbm_pct": costs_ssm_moe.step_min_bytes(
            4, c["state_rows"], c["experts_hit"], c["latent_tokens"], d)
        / PEAKS["hbm_bytes_per_s"]}[name]


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_gives_nothing_without_a_trace_counters_or_this_model(name):
    reader = cells.load_metric(ROOT, name)
    assert reader.UNIT == "%" and reader.MOVES == "serve_tokens_per_s"
    pair = READERS[name] or ("a", "b")
    assert reader.read(_synthetic(COUNTERS, {}, with_trace=False)) is None
    # a program whose spans lack the counters, or that has no such scopes
    assert reader.read(_synthetic({"ctx_tokens": 7}, {pair: 1.0})) is None
    if READERS[name]:
        assert reader.read(_synthetic(COUNTERS, {("a", "b"): 1.0})) is None
    # another model's cell, whose spans do carry the counters
    assert reader.read(_synthetic(COUNTERS, {pair: 1.0},
                                  cell="k_exaone_236b.mixed_closed")) is None


@pytest.mark.parametrize("name", sorted(READERS))
@pytest.mark.parametrize("slack", [1.0, 2.0])
def test_reader_reads_a_known_share_and_never_over_100_at_the_floor(
        name, slack):
    pair = READERS[name]
    d = ssm_trace.dims(_synthetic(COUNTERS, {}))
    floor = _floor_s(name, d)           # of ONE chunk
    run = _synthetic(COUNTERS, {pair: slack * floor} if pair else {},
                     busy_s=slack * floor)
    got = cells.load_metric(ROOT, name).read(run)
    assert got == pytest.approx(100.0 / slack, rel=1e-6)
    assert got <= 100.0 + 1e-6


def test_a_reader_counts_only_its_own_scope():
    d = ssm_trace.dims(_synthetic(COUNTERS, {}))
    floor = _floor_s("ssm_state_decode_roofline", d)
    run = _synthetic(COUNTERS, {("mamba2", "state"): floor,
                                ("mamba2", "conv"): 9.0,
                                ("full", "attn.paged"): 9.0})
    got = cells.load_metric(ROOT, "ssm_state_decode_roofline").read(run)
    assert got == pytest.approx(100.0, rel=1e-6)


def test_cost_functions_against_hand_counts():
    d = ssm_trace.dims(_synthetic(COUNTERS, {}))
    assert (d["mamba2"], d["full"], d["experts"]) == (5, 1, 5)
    # the issue's parameter counts, to the digit
    mamba = 4096 * 18560 + 8192 * 4096 + 10240 * 5 + 3 * 128 + 8192 + 4096
    attention = 2 * 4096 * 4096 + 2 * 4096 * 256 + 4096
    outside = 512 * 4096 + 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376 + 4096
    expert = 2 * 1024 * 2688
    assert (mamba, attention, outside, expert) \
        == (109_640_064, 35_655_680, 54_530_560, 5_505_024)
    assert costs_ssm_moe.block_params(d) == {
        "mamba2": mamba, "full": attention,
        "experts": outside + 128 * expert, "ends": 2 * 32768 * 4096 + 4096}
    assert outside + 128 * expert == 759_173_632
    assert costs_ssm_moe.total_params(d) == 5 * mamba + 5 * 759_173_632 \
        + attention + 268_439_552 == 4_648_163_712
    # the whole model: 40 M, 8 *, 40 E with all 512 experts, the whole
    # vocabulary: the row's 120B
    whole = 40 * mamba + 8 * attention + 40 * (outside + 512 * expert) \
        + 2 * 131072 * 4096 + 4096
    assert whole == pytest.approx(120.67e9, rel=2e-4)
    resident = 5 * (4096 * 18560 + 8192 * 4096) \
        + 2 * 4096 * 4096 + 2 * 4096 * 256 \
        + 5 * (512 * 4096 + 2 * 4096 * 1024 + 2 * 4096 * 5376) \
        + 32768 * 4096
    assert costs_ssm_moe.resident_matmul_params(d) == resident \
        == 990_380_032
    assert costs_ssm_moe.expert_bytes(d) == 11_010_048
    assert costs_ssm_moe.expert_pair_flops(d) == 11_010_048
    assert costs_ssm_moe.state_bytes_per_row_layer(d) == 4_194_304
    assert costs_ssm_moe.kv_bytes_per_token(d) == 1024
    assert costs_ssm_moe.attention_flops_per_key(d) == 16384
    # one step of 128 rows at 3,400 tokens, all 640 held experts hit: 1.98
    # GB resident + 7.05 GB of experts + 5.37 GB of state read and written
    # + 0.45 GB of pages = 14.84 GB, 18.1 ms at 819 GB/s
    step = costs_ssm_moe.step_min_bytes(1, 128, 640, 128 * 3400, d)
    assert step == 2 * resident + 640 * 11_010_048 \
        + 128 * 5 * 2 * 4_194_304 + 128 * 3400 * 1024
    assert step / PEAKS["hbm_bytes_per_s"] == pytest.approx(18.1e-3,
                                                            rel=0.01)
    assert costs_ssm_moe.ssm_state_floor_s(128, d, PEAKS) \
        == pytest.approx(6.55e-3, rel=0.01)
    assert costs_ssm_moe.gqa2_read_floor_s(128 * 3400, d, PEAKS) \
        == pytest.approx(0.544e-3, rel=0.01)
    flops = costs_ssm_moe.step_matmul_flops(128, 5 * 704, 128 * 3400, d)
    assert flops == 2.0 * 128 * resident + 5 * 704 * 11_010_048 \
        + 128 * 5 * 4 * 128 * 64 * 128 + 128 * 3400 * 16384
    # a floor counts from below: the experts' floor is the read until about
    # 240 pairs an expert
    assert costs_ssm_moe.latent_experts_floor_s(704, 128, d, PEAKS) \
        == pytest.approx(128 * 11_010_048 / 819e9)
    assert costs_ssm_moe.latent_experts_floor_s(10 ** 6, 128, d, PEAKS) \
        == pytest.approx(10 ** 6 * 11_010_048 / 197e12)


def test_prefill_and_chunked_scan_counts_against_hand_counts():
    d = ssm_trace.dims(_synthetic(COUNTERS, {}))
    body = 990_380_032 - 32768 * 4096
    got = costs_ssm_moe.prefill_matmul_flops(8192, 5 * 8192 * 22 // 4, d)
    assert got == 2.0 * 8192 * body + 2.0 * 32768 * 4096 \
        + 5 * 8192 * 22 // 4 * 11_010_048 \
        + 8192 * 5 * 4 * 128 * 64 * 128 + 8192 * 8193 // 2 * 16384
    assert got / 8192 == pytest.approx(2.10e9, rel=0.01)    # a token
    # one block's chunked scan over 2,048 tokens: 16 chunks, each 8 groups'
    # C B^T (128 x 128 x 128), 128 heads' masked product (128 x 128 x 64)
    # and two products with the state (128 x 64 x 128)
    per = 2 * 128 * 128 * 128 * 8 + 2 * 128 * 128 * 64 * 128 \
        + 4 * 128 * 64 * 128 * 128
    assert costs_ssm_moe.ssd_chunked_flops(2048, 128, d) == 16 * per
    assert costs_ssm_moe.ssd_chunked_bytes(2048, 128, d) \
        == 2048 * (2 * 8192 + 2 * 1024 + 128) * 4 + 16 * 2 * 4_194_304


# -- the prefills of the window, from the program's spans ----------------------------

def _prefill_run(spans_, window=(100.0, 130.0), cell=CELL):
    records = [{"type": "span", "name": "serve.prefill", "mono": m,
                "dur_s": dur, "attrs": dict(attrs, slot=0, bucket=8192)}
               for m, dur, attrs in spans_]
    return SimpleNamespace(cell=cells.load_cell(ROOT, cell), trace=None,
                           records=records, window=window, peaks=PEAKS)


def test_prefill_reader_counts_the_spans_whole_inside_the_window():
    mfu = cells.load_metric(ROOT, "ssm_moe_prefill_mfu_pct")
    d = ssm_trace.dims(_synthetic(COUNTERS, {}))
    attrs = {"tp": 8192, "expert_pairs": 5 * 8192 * 22 // 4}
    floor = costs_ssm_moe.prefill_matmul_flops(
        8192, attrs["expert_pairs"], d) / PEAKS["bf16_flops"]
    inside = [(101.0, 2 * floor, attrs), (110.0, 2 * floor, attrs)]
    outside = [(99.9, 1.0, attrs), (129.9, 1.0, attrs)]   # straddle an edge
    assert mfu.read(_prefill_run(inside + outside)) == pytest.approx(50.0)
    assert mfu.read(_prefill_run([(101.0, 0.3, {"tp": 512})])) is None
    assert mfu.read(_prefill_run(outside)) is None
    assert mfu.read(_prefill_run(
        inside, cell="k_exaone_236b.mixed_closed")) is None
    assert mfu.UNIT == "%" and mfu.MOVES == "serve_tokens_per_s"


# -- the shared kernel's tiling at the new cell's shapes -----------------------------

def test_paged_tiling_at_two_kv_heads_of_sixteen_query_heads():
    """2 KV heads of 128 on a pool 256 lanes wide, 16 query heads a group,
    640 table slots of 16 tokens, bf16: a decode step has few queries (the
    rows form: both heads' 32 query rows one product against the row's 256
    lanes), ONE lane group, 21 MB of scratch for two rows' K and V inside
    the first plan's 40 MiB, 48 MiB declared; blocks of 8 pages.  The
    accepted cells' plans beside it, where they were."""
    from bigdl_tpu.ops.attention import (_paged_few, _paged_tiling,
                                         paged_block_pages, paged_pool_width)
    assert paged_pool_width(2, 128) == 256
    assert _paged_few(2, 16, 1, 640 * 16)
    assert _paged_tiling(2, 16, 1, 640 * 16, 128, 16, 2) \
        == (1, 256, True, 48 * 1024 * 1024)
    assert paged_block_pages(16, 640) == 8
    # k_exaone_236b's (8 x 8 over 640 slots), gpt2_xl's (25 heads of 64
    # over 64 slots) and ling3_flash_vl's latent pool (one head of 576)
    assert _paged_tiling(8, 8, 1, 640 * 16, 128, 16, 2) \
        == (1, 1024, True, 112 * 1024 * 1024)
    assert _paged_tiling(25, 1, 1, 64 * 16, 64, 16, 2)[0] == 1
    assert _paged_tiling(1, 32, 1, 208 * 16, 576, 16, 2)[0] == 1
