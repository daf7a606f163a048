"""The cell ``k_exaone_236b.mixed_closed``: the configuration against the
catalog row's widths and the issue's arithmetic, the closed runner end to
end on a toy configuration of the window/full pattern model, the reference
check passing and failing, the new readers on synthetic events, the cost
functions against hand counts, and the shared kernel's plan pinned at the
new cell's shapes."""

import copy
import time
from types import SimpleNamespace

import jax
import pytest

from benchmark import cells, costs_gqa_moe, gqa_trace, harness, peaks

ROOT = cells.repo_root()
CELL = "k_exaone_236b.mixed_closed"
PEAKS = peaks.lookup("TPU v5 lite")
TOY = dict(max_len=640, embed_dim=64, num_heads=4, num_kv_heads=2,
           num_layers=3,
           layers=[["swa", "dense"], ["swa", "experts"], ["full", "experts"]],
           head_dim=16, ffn_dim=96, expert_dim=24, num_experts=16,
           experts_per_token=4, experts_held=8, window=8)
TOY_PUBLISHED = dict(sliding_window=8, num_experts_per_tok=4,
                     layer_types=("sliding_attention", "sliding_attention",
                                  "full_attention"))


# -- the files ---------------------------------------------------------------------

def test_load_cell_finds_the_new_cell_and_its_readers():
    cell = cells.load_cell(ROOT, CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "closed"
    assert cell.config["model"]["args"] == [19200]
    assert {m["name"] for m in cell.end_to_end} \
        >= {"serve_tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert names >= {
        "compile_s", "window_compiles", "slot_occupancy_pct",
        "decode_gap_ms", "decode_step_device_ms", "chat_device_idle_pct",
        "chunk_itl_p95_ms", "decode_kv_copy_pct", "prefill_window_share_pct",
        "gqa_moe_prefill_mfu_pct", "gqa_paged_decode_roofline",
        "swa_ring_decode_roofline", "held_experts_decode_roofline",
        "gqa_moe_step_mfu_pct", "gqa_moe_step_hbm_pct",
        "decode_host_turn_ms"}
    # the two whose cost function takes embed_dim for the page width, which
    # is not this pool's (8 KV heads of 128 under a hidden size of 6,144)
    assert not {"paged_attn_decode_roofline",
                "paged_attn_prefill_roofline"} & names
    for name in names:
        assert hasattr(cells.load_metric(ROOT, name), "read")


def test_traffic_is_the_issues_multiset():
    from benchmark import traffic
    cell = cells.load_cell(ROOT, CELL)
    spec, srv = cell.traffic, cell.config["server"]
    prompts = traffic.expand(spec["prompt_lengths"])
    outs = traffic.expand(spec["output_lengths"])
    assert len(prompts) == 16 and sum(prompts) / 16 == 2560
    assert len(outs) == 8 and sum(outs) / 8 == 1152
    assert "shared_head" not in spec and spec["order_seed"] == 33
    assert spec["discard_chunks"] == 5
    assert max(prompts) + max(outs) <= srv["max_len"] == 10240
    # no prompt of the traffic is padded by more than a third
    for p in prompts:
        bucket = min(b for b in srv["seq_buckets"] if b >= p)
        assert bucket <= p * 4 / 3
    assert srv["num_slots"] == 96 and srv["page_size"] == 16


def test_the_configuration_keeps_every_published_width():
    cfg = cells.load_cell(ROOT, CELL).config
    pub, kw = cfg["published"], cfg["model"]["kwargs"]
    assert kw["embed_dim"] == pub["hidden_size"] == cfg["hidden_size"] == 6144
    assert (kw["num_heads"], kw["num_kv_heads"], kw["head_dim"]) \
        == (pub["num_attention_heads"], pub["num_key_value_heads"],
            pub["head_dim"]) == (64, 8, 128)
    assert kw["ffn_dim"] == pub["intermediate_size"] == 18432
    assert kw["expert_dim"] == pub["moe_intermediate_size"] == 2048
    assert kw["num_experts"] == pub["num_experts"] == 128
    assert kw["experts_per_token"] == pub["num_experts_per_tok"] == 8
    assert (kw["n_group"], kw["topk_group"]) \
        == (pub["n_group"], pub["topk_group"]) == (1, 1)
    assert kw["routed_scale"] == pub["routed_scaling_factor"]
    assert kw["window"] == pub["sliding_window"] == 128
    assert kw["rope_theta"] == pub["rope_parameters"]["rope_theta"]
    assert kw["norm_eps"] == pub["rms_norm_eps"]
    assert set(cfg["reduced"]) == set(cfg["reduced_why"]) == {
        "num_hidden_layers", "num_experts", "vocab_size",
        "num_nextn_predict_layers"}
    # every key of the row is in the file, and only the reduced ones differ
    for key, value in pub.items():
        assert (cfg[key] != value) == (key in cfg["reduced"]), key
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"],
            cfg["num_nextn_predict_layers"]) == (5, 16, 19200, 0)
    assert cfg["vocab_size"] * 8 == pub["vocab_size"]
    assert kw["experts_held"] * 8 == pub["num_experts"]
    # the kept layers are the row's own kinds at layers 0 and 4-7
    kind = {"sliding_attention": "swa", "full_attention": "full"}
    ffn = {"dense": "dense", "sparse": "experts"}
    assert cfg["layers_held"] == [0, 4, 5, 6, 7]
    assert [list(l) for l in kw["layers"]] == [
        [kind[pub["layer_types"][i]], ffn[pub["mlp_layer_types"][i]]]
        for i in cfg["layers_held"]]
    from benchmark.reference import k_exaone
    assert list(k_exaone.PUBLISHED["layer_types"]) \
        == [pub["layer_types"][i] for i in cfg["layers_held"]]
    for key in ("norm_placement", "qk_norm", "rope_by_kind", "window",
                "token_ids", "init", "page_size", "slots"):
        assert cfg["assumed"][key]
    assert "8 chips" in cfg["deployment"]


@pytest.mark.parametrize("slots,gb", [(96, 11.65), (80, 10.95), (64, 10.24)])
def test_the_contracts_band_holds_at_the_three_slot_counts(slots, gb):
    """Weights 7.42 GB; a slot is 10,240 tokens of the one full layer's
    pages (41.9 MB) and four rings (2.1 MB): the issue's arithmetic, by
    the contract test's own count."""
    import json
    import os

    import jax.numpy as jnp
    import numpy as np
    with open(os.path.join(ROOT, "benchmark/configs/k_exaone_236b.json"),
              encoding="utf-8") as f:
        cfg = json.load(f)
    model = cells.resolve(cfg["model"]["factory"])(
        *cfg["model"]["args"], **cfg["model"]["kwargs"])
    params, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_paged_cache(
        slots * 640, 16, jnp.bfloat16, num_slots=slots))
    leaves = jax.tree_util.tree_leaves
    weights = sum(int(np.prod(a.shape)) * 2 for a in leaves(params))
    assert weights == pytest.approx(7.424e9, rel=1e-3)
    kinds = model.state_bytes(cache)
    assert kinds == {"page": {"full": 16 * 2 * 1024 * 2},
                     "slot": {"swa": 4 * 2 * 128 * 1024 * 2}}
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in leaves(cache))
    assert (weights + held) / 1e9 == pytest.approx(gb, abs=0.02)
    assert 8.0e9 <= weights + held <= 14.4e9


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
        root=ROOT, cell=cell, seed=2 ** 31 + 33, seconds=seconds,
        trace_on=False, out_dir=str(tmp_path), t0=time.monotonic(),
        peaks=PEAKS, meter=harness.CompileMeter().install(),
        device={"platform": "cpu", "kind": "cpu", "count": 1})


@pytest.fixture
def toy_published(monkeypatch):
    from benchmark.reference import k_exaone
    for k, v in TOY_PUBLISHED.items():
        monkeypatch.setitem(k_exaone.PUBLISHED, k, v)
    monkeypatch.setattr(k_exaone, "SEQ_STEP", 64)


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
                        f"block_4/{parent}/{child}/dot_general",
                        a + 1e3, sec * 1e9])
    records = [{"type": "span", "name": "serve.decode", "mono": m,
                "dur_s": 0.05, "attrs": dict(counters, steps=4)}
               for m in (104.99, 105.09)]
    return SimpleNamespace(cell=cell, trace=trace, records=records,
                           scope_ops=ops, peaks=PEAKS, out_dir="")


READERS = {
    "gqa_paged_decode_roofline": ("full", "attn.paged"),
    "swa_ring_decode_roofline": ("swa", "attn.ring"),
    "held_experts_decode_roofline": ("moe", "experts"),
    "gqa_moe_step_mfu_pct": None, "gqa_moe_step_hbm_pct": None}
# a chunk of four steps of 96 rows at 3,250 tokens, every held expert hit
COUNTERS = {"expert_pairs": 4 * 4 * 96, "experts_hit": 4 * 4 * 16,
            "state_rows": 4 * 96, "full_tokens": 4 * 96 * 3250,
            "window_tokens": 4 * 96 * 128, "latent_tokens": 4 * 96 * 3250,
            "expert_pairs_max": 11}


def _floor_s(name, d):
    c = COUNTERS
    return {
        "gqa_paged_decode_roofline": costs_gqa_moe.gqa_read_floor_s(
            c["full_tokens"], d, PEAKS),
        "swa_ring_decode_roofline": costs_gqa_moe.ring_read_floor_s(
            c["window_tokens"], d, PEAKS),
        "held_experts_decode_roofline": costs_gqa_moe.held_experts_floor_s(
            c["expert_pairs"], c["experts_hit"], d, PEAKS),
        "gqa_moe_step_mfu_pct": costs_gqa_moe.step_matmul_flops(
            c["state_rows"], c["expert_pairs"], c["full_tokens"],
            c["window_tokens"], d) / PEAKS["bf16_flops"],
        "gqa_moe_step_hbm_pct": costs_gqa_moe.step_min_bytes(
            4, c["experts_hit"], c["full_tokens"], c["window_tokens"], d)
        / PEAKS["hbm_bytes_per_s"]}[name]


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_gives_nothing_without_a_trace_or_without_counters(name):
    reader = cells.load_metric(ROOT, name)
    assert reader.UNIT == "%" and reader.MOVES == "serve_tokens_per_s"
    assert reader.read(_synthetic(COUNTERS, {}, with_trace=False)) is None
    # a program whose spans lack the counters (the recurrent pattern's
    # say latent_tokens alone), or that has no such scopes
    assert reader.read(_synthetic({"ctx_tokens": 7}, {})) is None
    other = {k: v for k, v in COUNTERS.items()
             if k not in ("full_tokens", "window_tokens")}
    assert reader.read(_synthetic(other, {READERS[name] or ("a", "b"): 1.0})
                       ) is None


@pytest.mark.parametrize("name", sorted(READERS))
@pytest.mark.parametrize("slack", [1.0, 2.0])
def test_reader_reads_a_known_share_and_never_over_100_at_the_floor(
        name, slack):
    pair = READERS[name]
    d = gqa_trace.dims(_synthetic(COUNTERS, {}))
    floor = _floor_s(name, d)           # of ONE chunk
    run = _synthetic(COUNTERS, {pair: slack * floor} if pair else {},
                     busy_s=slack * floor)
    got = cells.load_metric(ROOT, name).read(run)
    assert got == pytest.approx(100.0 / slack, rel=1e-6)
    assert got <= 100.0 + 1e-6


def test_a_reader_counts_only_its_own_scope():
    d = gqa_trace.dims(_synthetic(COUNTERS, {}))
    floor = _floor_s("swa_ring_decode_roofline", d)
    run = _synthetic(COUNTERS, {("swa", "attn.ring"): floor,
                                ("swa", "kv.write"): 9.0,
                                ("full", "attn.paged"): 9.0})
    got = cells.load_metric(ROOT, "swa_ring_decode_roofline").read(run)
    assert got == pytest.approx(100.0, rel=1e-6)


def test_cost_functions_against_hand_counts():
    d = gqa_trace.dims(_synthetic(COUNTERS, {}))
    assert (d["swa"], d["full"], d["dense"], d["experts"]) == (4, 1, 1, 4)
    attention = 2 * 6144 * 8192 + 2 * 6144 * 1024           # 113.2 M
    assert attention == 113_246_208
    resident = 5 * attention + 3 * 6144 * 18432 \
        + 4 * (128 * 6144 + 3 * 6144 * 2048) + 19200 * 6144
    assert costs_gqa_moe.resident_matmul_params(d) == resident \
        == 1_178_075_136
    assert costs_gqa_moe.expert_bytes(d) == 75_497_472
    assert costs_gqa_moe.expert_pair_flops(d) == 75_497_472
    assert costs_gqa_moe.kv_bytes_per_token(d) == 4096
    assert costs_gqa_moe.attention_flops_per_key(d) == 32768
    # 3.71 B parameters: resident + embedding + 4 x 16 held experts
    total = resident + 19200 * 6144 + 64 * 3 * 6144 * 2048
    assert total == pytest.approx(3.712e9, rel=1e-3)
    # one step of 96 rows at 3,250 tokens, all 16 experts hit a layer:
    # 2.36 GB resident + 4.83 GB of experts + 1.28 GB of pages + 0.20 GB
    # of rings = 8.67 GB, 10.6 ms at 819 GB/s
    step = costs_gqa_moe.step_min_bytes(1, 64, 96 * 3250, 96 * 128, d)
    assert step == 2 * resident + 64 * 75_497_472 \
        + (96 * 3250 + 4 * 96 * 128) * 4096
    assert step / PEAKS["hbm_bytes_per_s"] == pytest.approx(10.6e-3,
                                                            rel=0.01)
    assert costs_gqa_moe.gqa_read_floor_s(96 * 3250, d, PEAKS) \
        == pytest.approx(1.56e-3, rel=0.01)
    assert costs_gqa_moe.ring_read_floor_s(96 * 128, d, PEAKS) \
        == pytest.approx(0.246e-3, rel=0.01)
    flops = costs_gqa_moe.step_matmul_flops(96, 4 * 96, 96 * 3250,
                                            96 * 128, d)
    assert flops == 2.0 * 96 * resident + 4 * 96 * 75_497_472 \
        + (96 * 3250 + 4 * 96 * 128) * 32768
    # a floor counts from below: more pairs or hits never lower it, and
    # the experts' floor is the read until about 240 pairs an expert
    assert costs_gqa_moe.held_experts_floor_s(96, 16, d, PEAKS) \
        == pytest.approx(16 * 75_497_472 / 819e9)
    assert costs_gqa_moe.held_experts_floor_s(10 ** 6, 16, d, PEAKS) \
        == pytest.approx(10 ** 6 * 75_497_472 / 197e12)


# -- the prefills of the window, from the program's spans ----------------------------

def _prefill_run(spans_, window=(100.0, 130.0), slice_end_ns=None):
    """A run whose ledger holds ``serve.prefill`` spans ``(mono, dur_s,
    attrs)``; with ``slice_end_ns`` a traced one whose profiled slice closed
    there (trace clock 5 s behind, as ``_Trace``'s)."""
    trace = None
    if slice_end_ns is not None:
        trace = SimpleNamespace(sync=_Trace.sync,
                                window=lambda: (0.0, slice_end_ns))
    records = [{"type": "span", "name": "serve.prefill", "mono": m,
                "dur_s": dur, "attrs": dict(attrs, slot=0, bucket=8192)}
               for m, dur, attrs in spans_]
    return SimpleNamespace(cell=cells.load_cell(ROOT, CELL), trace=trace,
                           records=records, window=window, peaks=PEAKS)


def test_prefill_readers_count_the_spans_whole_inside_the_window():
    share = cells.load_metric(ROOT, "prefill_window_share_pct")
    mfu = cells.load_metric(ROOT, "gqa_moe_prefill_mfu_pct")
    d = gqa_trace.dims(_synthetic(COUNTERS, {}))
    attrs = {"tp": 8192, "expert_pairs": 4 * 8192}
    flops = costs_gqa_moe.prefill_matmul_flops(8192, 4 * 8192, d)
    floor = flops / PEAKS["bf16_flops"]
    inside = [(101.0, 2 * floor, attrs), (110.0, 2 * floor, attrs)]
    outside = [(99.9, 1.0, attrs), (129.9, 1.0, attrs)]   # straddle an edge
    run = _prefill_run(inside + outside)
    assert share.read(run) == pytest.approx(100 * 4 * floor / 30.0)
    assert mfu.read(run) == pytest.approx(50.0)
    # traced: only what began 2 s after the slice closed (106 s + 2 s here)
    run = _prefill_run(inside + outside, slice_end_ns=101.0e9)
    assert share.read(run) == pytest.approx(100 * 2 * floor / 22.0)
    assert mfu.read(run) == pytest.approx(50.0)
    # a program whose prefill spans carry no counters has a share and no
    # FLOPs to count; one with no prefill in the window has neither
    bare = _prefill_run([(101.0, 0.3, {"tp": 512})])
    assert share.read(bare) == pytest.approx(1.0) and mfu.read(bare) is None
    empty = _prefill_run(outside)
    assert share.read(empty) is None and mfu.read(empty) is None
    for reader in (share, mfu):
        assert reader.UNIT == "%" and reader.MOVES == "serve_tokens_per_s"


def test_prefill_flops_against_hand_counts():
    d = gqa_trace.dims(_synthetic(COUNTERS, {}))
    body = 1_178_075_136 - 19200 * 6144
    # 100 tokens, inside the window of 128: both kinds see the whole prefix
    keys = 100 * 101 // 2
    assert costs_gqa_moe.prefill_matmul_flops(100, 0, d) \
        == 2.0 * 100 * body + 2.0 * 19200 * 6144 + 5 * keys * 32768
    # 8,192 tokens: a window layer's band is 128 x 129 / 2 + 8,064 x 128
    band = 128 * 129 // 2 + (8192 - 128) * 128
    got = costs_gqa_moe.prefill_matmul_flops(8192, 4 * 8192, d)
    assert got == 2.0 * 8192 * body + 2.0 * 19200 * 6144 \
        + 4 * 8192 * 75_497_472 + (8192 * 8193 // 2 + 4 * band) * 32768
    assert got / 8192 == pytest.approx(2.56e9, rel=0.01)    # a token


# -- the shared kernel's tiling at the new cell's shapes -----------------------------

def test_paged_tiling_at_eight_kv_heads_of_eight_query_heads():
    """8 KV heads of 128 on a pool 1,024 lanes wide, 8 query heads a
    group, 640 table slots of 16 tokens, bf16: a decode step has few
    queries (the rows form).  The first plan (40 MiB) would split the
    width into four lane groups of two KV heads and copy every page in
    four slices of 512-byte rows (9.6 ms a call on the chip against 3.5,
    PR 33); the wide plan takes the row whole: ONE lane group, 86 MB of
    scratch for two rows' K and V, 112 MiB declared; blocks of 8 pages.
    Shorter tables of the same heads fit the first plan as they are."""
    from bigdl_tpu.ops.attention import (_paged_few, _paged_tiling,
                                         paged_block_pages, paged_pool_width)
    assert paged_pool_width(8, 128) == 1024
    assert _paged_few(8, 8, 1, 640 * 16)
    assert _paged_tiling(8, 8, 1, 640 * 16, 128, 16, 2) \
        == (1, 1024, True, 112 * 1024 * 1024)
    assert _paged_tiling(8, 8, 1, 256 * 16, 128, 16, 2) \
        == (1, 1024, True, 48 * 1024 * 1024)
    assert paged_block_pages(16, 640) == 8
