"""Finds a cell's files by the names in ``BENCHMARK.json``.  The harness
keeps no table of cells, configurations, mixes or metrics: a new one is a
new file plus one entry (README.md says how)."""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
from types import SimpleNamespace

from benchmark import traffic as traffic_mod


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_benchmark(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(root: str, workload: str) -> SimpleNamespace:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(has: {sorted(cells)})")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, cfg_entry["file"]), encoding="utf-8") as f:
        config = json.load(f)
    spec = traffic_mod.load(traffic_mod.path_for(root, w["traffic"]))
    return SimpleNamespace(
        name=workload, chips=int(w["chips"]), config_name=w["config"],
        traffic_name=w["traffic"], config=config, traffic=spec,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)])


def metric_path(root: str, name: str) -> str:
    return os.path.join(root, "benchmark", "metrics", name + ".py")


def load_metric(root: str, name: str):
    """The reader module of one per-layer metric: ``UNIT``, ``LAYER``,
    ``MOVES`` and ``read(run) -> number or None``."""
    spec = importlib.util.spec_from_file_location(
        "benchmark.metrics._" + name.replace(".", "_").replace("-", "_"),
        metric_path(root, name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(dotted: str):
    """``package.module.attr`` -> the attribute."""
    mod, _, attr = dotted.rpartition(".")
    return getattr(importlib.import_module(mod), attr)


def read_per_layer(root: str, cell, run) -> dict:
    """Every per-layer metric of the cell, by its own reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell.per_layer:
        value = load_metric(root, m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
