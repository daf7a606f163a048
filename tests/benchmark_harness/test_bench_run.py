"""The entry point fails closed, and the serving runners run end to end
at toy widths on the CPU (the chip is never called from a test)."""

import copy
import json
import os
import subprocess
import sys
import time

import pytest

from benchmark import cells, harness, peaks

ROOT = cells.repo_root()


def test_run_py_exits_non_zero_without_a_tpu_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "inception_v1.local_b256", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=240)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    for line in p.stdout.splitlines():
        assert not line.startswith("{"), line        # no result line


def test_run_py_refuses_an_unknown_workload():
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "nope.none"],
        cwd=ROOT, capture_output=True, text=True, timeout=240,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and "nope.none" in p.stderr


def _toy_serve_run(tmp_path, workload, seconds, trace_on=False):
    cell = copy.deepcopy(cells.load_cell(ROOT, workload))
    cell.config["model"]["args"] = [97]
    cell.config["model"]["kwargs"].update(
        max_len=64, embed_dim=32, num_heads=4, num_layers=2, ffn_dim=64)
    cell.config["server"].update(num_slots=4, max_len=64,
                                 seq_buckets=[16, 32, 48], dtype="float32")
    cell.config["tolerance"]["rows"] = 16

    def scale(ms):
        return [[max(2, v // 16), c] for v, c in ms]

    cell.traffic["prompt_lengths"] = scale(cell.traffic["prompt_lengths"])
    if cell.traffic["kind"] == "closed":
        cell.traffic["output_lengths"] = scale(cell.traffic["output_lengths"])
        cell.traffic["shared_head"]["tokens"] = 4
    else:
        cell.traffic["rate_per_s"] = 25.0
    return harness.new_run(
        root=ROOT, cell=cell, seed=2 ** 31 + 5, seconds=seconds,
        trace_on=trace_on, out_dir=str(tmp_path), t0=time.monotonic(),
        peaks=peaks.lookup("TPU v5 lite"),
        meter=harness.CompileMeter().install(),
        device={"platform": "cpu", "kind": "cpu", "count": 1})


def test_closed_loop_runs_counts_whole_chunks_and_parks(tmp_path):
    from benchmark import serve_cell
    run = _toy_serve_run(tmp_path, "gpt2_xl.chat_closed", 1.5)
    serve_cell.run(run)
    assert run.correct and run.failed == 0 and run.attempted > 0
    assert run.e2e["serve_tokens_per_s"] > 0
    assert run.counters["chunks"] > 5
    assert run.counters["occupancy_pct"] == pytest.approx(100.0, abs=5.0)
    assert run.setup_s > 0 and not run.hard_exit
    line = json.loads(harness.result_line(run, {}, run.device))
    # each number that decided `correct` beside its limit, last in the line
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    assert line["compared"]["logit_gap_std"][1] == 0.1
    assert line["compared"]["shed"] == [0, 0]
    assert run.memory_peak_bytes is None        # no TPU: nothing to read


def test_open_loop_times_each_request_from_when_it_was_due(tmp_path):
    from benchmark import serve_cell
    run = _toy_serve_run(tmp_path, "gpt2_xl.score_open", 1.5)
    serve_cell.run(run)
    assert run.correct and run.failed == 0
    assert run.attempted == len(run.samples["ttft_ms"]) >= 30
    assert all(t > 0 for t in run.samples["ttft_ms"])
    assert all(l >= 0 for l in run.samples["late_ms"])
    assert run.e2e["ttft_p95_ms"] >= run.e2e["ttft_p50_ms"] > 0
    start, end = run.window
    assert end - start == pytest.approx(1.5)
    # the warm requests are sent and not counted
    assert len(run.samples["sent"]) == run.attempted \
        + run.cell.traffic["discard_requests"]


@pytest.mark.parametrize("workload", ["gpt2_xl.chat_closed",
                                      "gpt2_xl.score_open"])
def test_a_token_altered_where_it_is_produced_reads_not_correct(
        tmp_path, monkeypatch, workload):
    """The rest of a run with the timed path broken underneath: the
    program's head hands every program (prefill and decode chunk) the
    logits moved by one place, so each served token is the neighbour of
    the one the model puts first; the reference, which shares nothing
    with the program, has to say so."""
    import jax.numpy as jnp

    from benchmark import serve_cell
    from bigdl_tpu.models.transformer import TransformerLM
    head = TransformerLM._head
    monkeypatch.setattr(
        TransformerLM, "_head",
        lambda self, p, s, x: jnp.roll(head(self, p, s, x), 1, axis=-1))
    run = _toy_serve_run(tmp_path, workload, 1.0)
    serve_cell.run(run)
    gap, limit = run.compared["logit_gap_std"]
    assert not run.correct and gap > 3 * limit
    assert run.failed == 0 and run.compared["shed"] == [0, 0]
