"""``BENCHMARK.json`` against the contract and against the files it names."""

import os
import re

import pytest

from benchmark import cells, traffic

ROOT = cells.repo_root()
BENCH = cells.load_benchmark(ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
CELLS = [w["name"] for w in BENCH["workloads"]]
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _cells_of(metric):
    return metric.get("workloads", CELLS)


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][:2] == ["python", "benchmark/run.py"]
    assert set(BENCH["paths"]) == {"benchmark", "tests/benchmark_harness"}
    assert 1 <= BENCH["run_seconds"] <= 51
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
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)


def test_roofline_and_mfu_names_carry_the_percent_unit():
    for m in BENCH["per_layer"]:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%" and m["better"] == "higher"


def test_config_files_state_source_reduced_and_assumed():
    for c in BENCH["configs"]:
        with open(os.path.join(ROOT, c["file"]), encoding="utf-8") as f:
            import json
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"]
        assert cfg["source"] and isinstance(cfg["assumed"], dict)
        assert cfg["tolerance"]["why"]
        assert c["file"].startswith("benchmark/configs/")
