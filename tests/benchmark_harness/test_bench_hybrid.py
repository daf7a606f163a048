"""The cell ``ling3_flash_vl.reason_closed``: the closed runner end to end
on a toy configuration of the layer-pattern model, the reference check
passing and failing, the five new readers on synthetic events, the cost
functions against the issue's arithmetic, and the shared kernel's plan
pinned at the accepted cells' shapes."""

import copy
import time
from types import SimpleNamespace

import jax
import pytest

from benchmark import cells, costs_hybrid, harness, hybrid_trace, peaks

ROOT = cells.repo_root()
CELL = "ling3_flash_vl.reason_closed"
PEAKS = peaks.lookup("TPU v5 lite")
TOY = dict(max_len=256, embed_dim=64, num_heads=4, num_layers=3,
           layers=[["kda", "dense"], ["kda", "experts"], ["mla", "experts"]],
           head_dim=16, ffn_dim=96, expert_dim=24, num_experts=16,
           experts_per_token=4, n_group=4, topk_group=2, experts_held=8,
           latent_dim=32, rope_dim=8, nope_dim=16, v_dim=16)
TOY_PUBLISHED = dict(num_experts_per_tok=4, n_group=4, topk_group=2,
                     kv_lora_rank=32, qk_rope_head_dim=8)


def test_load_cell_finds_both_new_cells():
    cell = cells.load_cell(ROOT, CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "closed"
    assert cell.config["model"]["args"] == [39296]
    kw = cell.config["model"]["kwargs"]
    assert (kw["num_heads"], kw["num_layers"], kw["embed_dim"]) \
        == (32, 7, 2560)
    names = {m["name"] for m in cell.per_layer}
    assert {"moe_experts_roofline", "kda_state_roofline",
            "mla_paged_decode_roofline", "serve_step_mfu_pct",
            "serve_step_hbm_pct", "decode_step_device_ms", "decode_gap_ms",
            "slot_occupancy_pct", "chat_device_idle_pct"} <= names
    # the two whose cost function takes embed_dim for the page width, and
    # the one whose list an accepted test pins to its first cell
    assert not {"paged_attn_decode_roofline", "decode_kv_copy_pct",
                "chunk_itl_p95_ms"} & names
    for name in names:
        assert hasattr(cells.load_metric(ROOT, name), "read")
    four = cells.load_cell(ROOT, "inception_v1.distri4_b1024")
    assert four.chips == 4 and four.traffic["optimizer"] == "distri"
    assert "collective_exposed_pct" in {m["name"] for m in four.per_layer}


def test_traffic_is_the_issues_multiset():
    from benchmark import traffic
    spec = cells.load_cell(ROOT, CELL).traffic
    prompts = traffic.expand(spec["prompt_lengths"])
    outs = traffic.expand(spec["output_lengths"])
    assert len(prompts) == 16 and sum(prompts) / 16 == 784
    assert len(outs) == 8 and sum(outs) / 8 == 576
    assert "shared_head" not in spec and spec["order_seed"] == 27
    cfg = cells.load_cell(ROOT, CELL).config["server"]
    assert max(prompts) <= max(cfg["seq_buckets"])
    assert max(prompts) + max(outs) <= cfg["max_len"]


def test_the_configuration_keeps_every_published_width():
    cfg = cells.load_cell(ROOT, CELL).config
    pub, kw = cfg["published"], cfg["model"]["kwargs"]
    assert kw["embed_dim"] == pub["hidden_size"] == cfg["hidden_size"]
    assert kw["head_dim"] == pub["head_dim"]
    assert kw["ffn_dim"] == pub["intermediate_size"]
    assert kw["expert_dim"] == pub["moe_intermediate_size"]
    assert kw["num_experts"] == pub["num_experts"] == 512
    assert kw["experts_per_token"] == pub["num_experts_per_tok"]
    assert (kw["latent_dim"], kw["rope_dim"], kw["nope_dim"], kw["v_dim"]) \
        == (pub["kv_lora_rank"], pub["qk_rope_head_dim"],
            pub["qk_nope_head_dim"], pub["v_head_dim"])
    assert set(cfg["reduced"]) == {"num_hidden_layers", "num_experts",
                                   "vocab_size", "vision_tower"}
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (7, 128, 39296)
    # the kept layers: 1, 6-10 delta-rule, 11 latent attention
    assert [m for m, _ in kw["layers"]] == ["kda"] * 6 + ["mla"]
    assert [f for _, f in kw["layers"]] == ["dense"] + ["experts"] * 6
    for i in (1, 6, 7, 8, 9, 10, 11):
        assert (i + 1) % pub["layer_group_size"] == 0 or i != 11
        assert cfg["expert_swiglu_limit_list"][i] == 0
        assert cfg["share_expert_swiglu_limit_list"][i] == 0


# -- the runner, end to end, toy widths ---------------------------------------------

def _toy_run(tmp_path, seconds=1.5):
    cell = copy.deepcopy(cells.load_cell(ROOT, CELL))
    cell.config["model"]["args"] = [97]
    cell.config["model"]["kwargs"].update(TOY)
    cell.config["server"].update(num_slots=4, max_len=256,
                                 seq_buckets=[32, 64, 128], dtype="float32")
    cell.config["tolerance"].update(rows=64, logit_gap_std=1e-4)
    scale = lambda ms: [[max(2, v // 16), c] for v, c in ms]
    cell.traffic["prompt_lengths"] = scale(cell.traffic["prompt_lengths"])
    cell.traffic["output_lengths"] = scale(cell.traffic["output_lengths"])
    return harness.new_run(
        root=ROOT, cell=cell, seed=2 ** 31 + 27, seconds=seconds,
        trace_on=False, out_dir=str(tmp_path), t0=time.monotonic(),
        peaks=PEAKS, meter=harness.CompileMeter().install(),
        device={"platform": "cpu", "kind": "cpu", "count": 1})


@pytest.fixture
def toy_published(monkeypatch):
    from benchmark.reference import ling3_flash
    for k, v in TOY_PUBLISHED.items():
        monkeypatch.setitem(ling3_flash.PUBLISHED, k, v)


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


def test_reference_check_fails_a_bf16_recurrent_state(tmp_path,
                                                      toy_published,
                                                      monkeypatch):
    """The same run with the delta-rule state rounded to bf16 after every
    update is a different result, and the check says so."""
    from benchmark import serve_cell
    from bigdl_tpu.nn import linear_attention

    def rounded(fn):
        def wrapped(*a, **kw):
            o, s = fn(*a, **kw)
            # reduce_precision, not a cast there and back, which XLA is
            # allowed to drop (xla_allow_excess_precision)
            return o, jax.lax.reduce_precision(s, 8, 7)
        return wrapped

    monkeypatch.setattr(linear_attention, "kda_step",
                        rounded(linear_attention.kda_step))
    monkeypatch.setattr(linear_attention, "kda_chunked",
                        rounded(linear_attention.kda_chunked))
    # every finished request is compared, not four: a rounded state
    # moves the argmax only where two logits nearly tie
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
                        f"block_3/{parent}/{child}/dot_general",
                        a + 1e3, sec * 1e9])
    records = [{"type": "span", "name": "serve.decode", "mono": m,
                "dur_s": 0.05, "attrs": dict(counters, steps=4)}
               for m in (104.99, 105.09)]
    return SimpleNamespace(cell=cell, trace=trace, records=records,
                           scope_ops=ops, peaks=PEAKS, out_dir="")


READERS = {
    "moe_experts_roofline": ("moe", "experts"),
    "kda_state_roofline": ("kda", "state"),
    "mla_paged_decode_roofline": ("mla", "attn.paged"),
    "serve_step_mfu_pct": None, "serve_step_hbm_pct": None}
COUNTERS = {"expert_pairs": 4 * 6 * 128, "experts_hit": 4 * 6 * 81,
            "state_rows": 4 * 64, "latent_tokens": 4 * 64 * 1100,
            "expert_pairs_max": 5}


def _floor_s(name, d):
    c = COUNTERS
    return {
        "moe_experts_roofline": costs_hybrid.moe_experts_floor_s(
            c["expert_pairs"], c["experts_hit"], d, PEAKS),
        "kda_state_roofline": costs_hybrid.kda_state_floor_s(
            c["state_rows"], d, PEAKS),
        "mla_paged_decode_roofline": costs_hybrid.mla_read_floor_s(
            c["latent_tokens"], d, PEAKS),
        "serve_step_mfu_pct": costs_hybrid.step_matmul_flops(
            c["state_rows"], c["expert_pairs"], c["latent_tokens"], d)
        / PEAKS["bf16_flops"],
        "serve_step_hbm_pct": costs_hybrid.step_min_bytes(
            4, c["state_rows"], c["experts_hit"], c["latent_tokens"], d)
        / PEAKS["hbm_bytes_per_s"]}[name]


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_gives_nothing_without_a_trace_or_without_counters(name):
    reader = cells.load_metric(ROOT, name)
    assert reader.UNIT == "%" and reader.MOVES == "serve_tokens_per_s"
    assert reader.read(_synthetic(COUNTERS, {}, with_trace=False)) is None
    # the parent's program: spans without the counters, no such scopes
    assert reader.read(_synthetic({"ctx_tokens": 7}, {})) is None


@pytest.mark.parametrize("name", sorted(READERS))
@pytest.mark.parametrize("slack", [1.0, 2.0])
def test_reader_reads_a_known_share_and_never_over_100_at_the_floor(
        name, slack):
    pair = READERS[name]
    d = hybrid_trace.dims(_synthetic(COUNTERS, {}))
    floor = _floor_s(name, d)           # of ONE chunk
    run = _synthetic(COUNTERS, {pair: slack * floor} if pair else {},
                     busy_s=slack * floor)
    got = cells.load_metric(ROOT, name).read(run)
    assert got == pytest.approx(100.0 / slack, rel=1e-6)
    assert got <= 100.0 + 1e-6


def test_the_grouped_products_custom_calls_count_under_moe_experts():
    """``lax.ragged_dot``'s custom calls carry no scope, only their own
    name as path: the configuration's ``kernels.moe_experts`` files them."""
    d = hybrid_trace.dims(_synthetic(COUNTERS, {}))
    floor = _floor_s("moe_experts_roofline", d)
    run = _synthetic(COUNTERS, {("moe", "experts"): 0.25 * floor})
    for a, _b in _Trace()._runs:
        run.scope_ops.append(["ragged-dot-none.2",
                              "custom-call tpu_custom_call",
                              "ragged-dot-none", a + 2e3,
                              0.75 * floor * 1e9])
    got = cells.load_metric(ROOT, "moe_experts_roofline").read(run)
    assert got == pytest.approx(100.0, rel=1e-6)


def test_cost_functions_reproduce_the_issues_arithmetic():
    d = hybrid_trace.dims(_synthetic(COUNTERS, {}))
    # 5.17 B parameters: resident matrices + 6 x 128 experts + embedding
    total = costs_hybrid.resident_matmul_params(d) + d["vocab"] * d["e"] \
        + 6 * 128 * 3 * d["e"] * d["f"]
    assert total == pytest.approx(5.169e9, rel=2e-3)
    assert costs_hybrid.state_bytes_per_row_layer(d) * 6 \
        == pytest.approx(12.58e6, rel=1e-3)
    assert costs_hybrid.latent_bytes_per_token(d) == 1152
    # one step of 64 rows at 1,100 tokens, 81 of 128 experts hit a layer
    step = costs_hybrid.step_min_bytes(1, 64, 6 * 81, 64 * 1100, d)
    assert step / PEAKS["hbm_bytes_per_s"] == pytest.approx(10.4e-3,
                                                            rel=0.01)
    # a floor counts from below: more rows, pairs or hits never lower it
    assert costs_hybrid.moe_experts_floor_s(10, 5, d, PEAKS) \
        <= costs_hybrid.moe_experts_floor_s(10, 6, d, PEAKS)
    assert costs_hybrid.moe_experts_floor_s(10 ** 6, 5, d, PEAKS) \
        == pytest.approx(10 ** 6 * 6 * 2560 * 768 / 197e12)


# -- the shared kernel's tiling at the accepted cells' shapes ------------------------

VMEM = 48 * 1024 * 1024


@pytest.mark.parametrize("queries,tiling", [
    (1, (1, 1664, True, VMEM)),     # decode: 26 head rows x the whole width
    (256, (1, 128, False, VMEM)),   # prefill buckets: one lane group, by
    (512, (1, 128, False, VMEM)),   # 128-lane chunks, head by head
    (768, (1, 128, False, VMEM))])
def test_paged_tiling_is_pinned_at_gpt2_xl_shapes(queries, tiling):
    """25 heads of 64 on a pool 1,664 lanes wide, 64 table slots of 16
    tokens, bf16: what the accepted cells' programs are compiled with
    (PR 28; the slot count is no argument).  A new shape rule for another
    model must not move them."""
    from bigdl_tpu.ops.attention import _paged_tiling
    assert _paged_tiling(25, 1, queries, 64 * 16, 64, 16, 2) == tiling


def test_paged_tiling_takes_the_latent_pool_from_its_shapes():
    """One KV head of width 576 (a pool 640 lanes wide) read by 32 query
    rows over 256 table slots: one lane group, one chunk of the whole
    width, a lone head is its own row (no rows form), the one VMEM
    declaration."""
    from bigdl_tpu.ops.attention import _paged_tiling
    assert _paged_tiling(1, 1, 32, 256 * 16, 576, 16, 2) \
        == (1, 640, False, VMEM)
