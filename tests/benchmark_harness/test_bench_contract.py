"""``BENCHMARK.json`` against the contract and against the files it names."""

import glob
import json
import os
import re

import pytest

from benchmark import cells, peaks, traffic

ROOT = cells.repo_root()
BENCH = cells.load_benchmark(ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
CELLS = [w["name"] for w in BENCH["workloads"]]
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _config(name):
    entry = {c["name"]: c for c in BENCH["configs"]}[name]
    with open(os.path.join(ROOT, entry["file"]), encoding="utf-8") as f:
        return json.load(f)


def _cells_of(metric):
    return metric.get("workloads", CELLS)


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][:2] == ["python", "benchmark/run.py"]
    assert set(BENCH["paths"]) == {"benchmark", "tests/benchmark_harness"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["configs"]) <= 24
    assert 1 <= len(BENCH["end_to_end"]) <= 16
    assert 1 <= len(BENCH["per_layer"]) <= 128
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    # a full check with the full 24 cells fits the driver's allowance
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_its_files(cell):
    c = cells.load_cell(ROOT, cell)
    assert c.chips in (1, 4)
    assert c.traffic["kind"] in traffic.KINDS
    assert callable(cells.resolve(c.config["model"]["factory"]))
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and len(c.per_layer) >= 1
    for m in c.per_layer:
        mod = cells.load_metric(ROOT, m["name"])
        assert callable(mod.read)
        assert (mod.UNIT, mod.LAYER, mod.MOVES) == \
            (m["unit"], m["layer"], m["moves"])


def test_names_units_and_lines_use_only_allowed_characters():
    names = [w["name"] for w in BENCH["workloads"]] \
        + [c["name"] for c in BENCH["configs"]] \
        + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]] \
        + [w["config"] for w in BENCH["workloads"]] \
        + [w["traffic"] for w in BENCH["workloads"]] \
        + [k for c in BENCH["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for text in [w["why"] for w in BENCH["workloads"]] \
            + [c["why"] for c in BENCH["configs"]] \
            + [c["source"] for c in BENCH["configs"]] \
            + [m["layer"] for m in BENCH["per_layer"]] + BENCH["command"]:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_no_two_entries_share_a_name_and_pairs_are_unique():
    for group in (BENCH["workloads"], BENCH["configs"],
                  BENCH["end_to_end"] + BENCH["per_layer"]):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


def test_entries_have_just_the_contracts_keys():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == \
            {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == \
            {"name", "unit", "better", "source", "layer", "moves"}


def test_every_moves_is_an_end_to_end_metric_of_each_cell_that_reports_it():
    for m in BENCH["per_layer"]:
        assert m["moves"] in E2E, m
        for cell in _cells_of(m):
            assert cell in CELLS
            assert cell in _cells_of(E2E[m["moves"]]), (m["name"], cell)


def test_setup_s_everywhere_and_four_chip_cells_within_the_quarter():
    assert "workloads" not in E2E["setup_s"]
    assert E2E["setup_s"]["bound"] <= 0.1
    assert 1 <= len(BENCH["workloads"]) <= 24
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)


def test_roofline_and_mfu_names_carry_the_percent_unit():
    for m in BENCH["per_layer"]:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%" and m["better"] == "higher"


def test_config_files_state_source_reduced_and_assumed():
    for c in BENCH["configs"]:
        cfg = _config(c["name"])
        assert cfg["reduced"] == c["reduced"]
        assert cfg["source"] and isinstance(cfg["assumed"], dict)
        assert cfg["tolerance"]["why"]
        assert c["file"].startswith("benchmark/configs/")


# -- a serving configuration is the size of a deployment -----------------------------

SERVING = [c["name"] for c in BENCH["configs"]
           if _config(c["name"])["kind"] == "serve"]
HBM = peaks.PEAKS["TPU v5 lite"]["hbm_bytes"]


def _resident_bytes(cfg, slots=None):
    """Weights in the served dtype plus the page pool (and the per-slot
    state of a model that has one) with pages for every slot at
    ``max_len``, from the shapes the program's own factory and
    ``init_paged_cache`` give for the file's settings; nothing is
    allocated."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    model = cells.resolve(cfg["model"]["factory"])(
        *cfg["model"].get("args", []), **cfg["model"].get("kwargs", {}))
    srv = cfg["server"]
    dtype = jnp.dtype(srv["dtype"])
    slots = int(srv["num_slots"]) if slots is None else slots
    page = int(srv["page_size"])
    extra = {"num_slots": slots} \
        if getattr(model, "recurrent_state", False) else {}
    params, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_paged_cache(
        slots * int(srv["max_len"]) // page, page, dtype, **extra))
    leaves = jax.tree_util.tree_leaves
    return sum(int(np.prod(a.shape)) * dtype.itemsize
               for a in leaves(params)) \
        + sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in leaves(cache))


@pytest.mark.parametrize("config", SERVING)
def test_a_serving_configuration_holds_what_a_deployment_on_the_chip_would(
        config):
    """Between a half and nine tenths of the chip's memory in weights,
    pool and state: no deployment leaves half the chip empty (10 slots of
    ``gpt2_xl`` did, PR 23-28), and the programs' temporaries, the
    runtime's reservation and the reference need the last tenth."""
    assert 0.5 * HBM <= _resident_bytes(_config(config)) <= 0.9 * HBM


@pytest.mark.parametrize("slots,gb,fits", [
    (10, 6.4, False), (16, 8.4, True), (24, 11.0, True), (32, 13.6, True),
    (40, 16.2, False)])
def test_the_rule_reads_gpt2_xl_as_the_issue_reckoned_it(slots, gb, fits):
    nbytes = _resident_bytes(_config("gpt2_xl"), slots)
    assert nbytes / 1e9 == pytest.approx(gb, abs=0.06)
    assert (0.5 * HBM <= nbytes <= 0.9 * HBM) == fits


# -- an open mix is paced under a knee it names -------------------------------------

OPEN = sorted(
    p for p in glob.glob(os.path.join(ROOT, "benchmark", "traffic", "*.json"))
    if traffic.load(p)["kind"] == "open")


@pytest.mark.parametrize("path", OPEN, ids=os.path.basename)
def test_an_open_mix_is_paced_under_the_knee_it_names(path):
    """``knee_per_s`` is the swept knee, ``rate_per_s`` at most four
    fifths of it (beyond that the tails swing with the smallest change)
    and at least half (below that the cell measures a wake-up from idle,
    as 8/s did once PR 28 had moved the knee); ``rate_why`` names both."""
    spec = traffic.load(path)
    knee, rate = float(spec["knee_per_s"]), float(spec["rate_per_s"])
    assert 0.5 * knee <= rate <= 0.8 * knee + 0.05
    for number in (knee, rate):
        assert f"{number:g}" in spec["rate_why"]
    assert "four fifths" in spec["rate_why"]
    # the discarded head of the run is half a second of arrivals or more
    assert spec["discard_requests"] >= 0.5 * rate
