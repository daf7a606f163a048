"""CPU rehearsal of ``chip_smoke.py``: the same phase functions at toy
sizes on the virtual CPU mesh (kernels under the Pallas interpreter),
``main()`` refusing a ``cpu`` backend, and the compile-cache helper's
placement contract."""

import os
import subprocess
import sys

import jax
import pytest

import chip_smoke
from bigdl_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# tier-1 runs under a wall-clock limit: every size here is the smallest
# that still walks the phase's code
TOY = dict(input_shape=(8,), classes=4)


@pytest.fixture
def interpret_mode(monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_PALLAS_INTERPRET", "1")


def _toy_model():
    import bigdl_tpu.nn as nn
    return nn.Sequential().add(nn.Linear(8, 4)).add(nn.LogSoftMax())


def test_phase_device_reports_cache_store_and_native():
    line = chip_smoke.phase_device("/some/cache")
    assert "backend=cpu" in line and "pallas=off" in line
    assert "compile_cache=/some/cache" in line
    assert "tune-cpu.json entries=" in line
    assert "native=" in line


def test_phase_train_toy():
    line = chip_smoke.phase_train(_toy_model, batch=8, steps=3, **TOY)
    assert "steps=3" in line and "params_on=['cpu']" in line


def test_phase_train_fails_on_nonfinite_loss():
    from bigdl_tpu.resilience.fault_injector import FaultInjector
    FaultInjector.install(FaultInjector().add("grad.nan", step=1))
    try:
        with pytest.raises(chip_smoke.SmokeFailure, match="finite losses"):
            chip_smoke.phase_train(_toy_model, batch=8, steps=3, **TOY)
    finally:
        FaultInjector.clear()


def test_phase_serve_toy(interpret_mode):
    line = chip_smoke.phase_serve(
        vocab=64, embed=32, heads=4, layers=1, max_len=48, buckets=(16, 32),
        slots=2, prompt_lens=(20, 20, 20), shared_prefix=16, max_new=4,
        compiled=False)
    assert "requests=3" in line and "failed=0 shed=0" in line
    assert "paged_kernel=interpreted" in line
    assert "prefix_hit_rate=0.00" not in line       # the shared page hit
    # f32-exact toy model: the scheduler's greedy path IS generate's
    assert "token_match=3/3" in line


def test_interpreted_kernel_does_not_pass_for_compiled(interpret_mode):
    assert not chip_smoke._paged_kernel_compiled()


def test_phase_kernels_toy(interpret_mode):
    line = chip_smoke.phase_kernels(
        t_fused=16, t_stream=16, heads=1, wide_heads=3, head_dim=64,
        slots=2, max_len=64, page_size=16, prefill=16,
        matmuls=((2, 128, 128),), conv=(1, 8, 6, 16, 3), fp16_n=1000,
        compiled=False)
    # the paged kernel's four rows: a decode step, a prefill bucket,
    # nearly empty slots and full ones
    assert line.startswith("kernels=13 interpreted")


def test_phase_kernels_refuses_the_reference_path():
    """Without Pallas dispatch the phase would compare the references
    with themselves: it must fail instead."""
    with pytest.raises(chip_smoke.SmokeFailure, match="Pallas dispatch"):
        chip_smoke.phase_kernels()


def test_phase_multichip_toy_and_skip():
    from bigdl_tpu.engine import Engine
    assert chip_smoke.phase_multichip(min_devices=99) == \
        f"skipped ({len(jax.devices())} device)"
    Engine.reset()
    try:
        line = chip_smoke.phase_multichip(_toy_model, per_chip_batch=2,
                                          steps=2, **TOY)
    finally:
        Engine.reset()
    n = len(jax.devices())
    assert f"devices={n} global_batch={2 * n}" in line
    assert f"wshard/batch over {n} devices" in line


def test_main_exits_nonzero_on_cpu(capsys):
    assert jax.default_backend() == "cpu"
    assert chip_smoke.main() != 0
    out, err = capsys.readouterr()
    assert '"ok"' not in out
    assert "no TPU" in err


# -- the compile-cache helper -------------------------------------------------

def test_cache_helper_sets_nothing_when_placed_from_outside(monkeypatch):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/placed/from/outside")
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: updates.append(a))
    assert compile_cache.enable_compile_cache() == "/placed/from/outside"
    assert updates == []


def test_cache_helper_default_is_fixed_under_the_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    # a second process, another working directory, no package import:
    # the path comes from the file's location and nothing else
    out = subprocess.run(
        [sys.executable, "-c",
         "import runpy, sys; print(runpy.run_path(sys.argv[1])"
         "['default_cache_dir']())", compile_cache.__file__],
        cwd="/", capture_output=True, text=True, timeout=60, check=True)
    assert out.stdout.strip() == want


def test_phase_hybrid_toy(interpret_mode):
    """The logits phase at toy widths in float32: the served path agrees
    with the reference to rounding, the router chooses the reference's
    experts, and both controls (state and router rounded to bf16) fail
    their tolerances; a tolerance the state control passes fails the
    phase."""
    toy = dict(max_len=128, embed_dim=64, num_heads=4, num_layers=3,
               layers=[["kda", "dense"], ["kda", "experts"],
                       ["mla", "experts"]],
               head_dim=16, ffn_dim=96, expert_dim=24, num_experts=16,
               experts_per_token=4, n_group=4, topk_group=2, experts_held=8,
               latent_dim=32, rope_dim=8, nope_dim=16, v_dim=16)
    kw = dict(vocab=97, overrides=toy,
              reference_kw=dict(num_experts_per_tok=4, n_group=4,
                                topk_group=2, kv_lora_rank=32,
                                qk_rope_head_dim=8),
              prompt_len=40, slots=3, max_len=128, buckets=(64,),
              compiled=False, dtype="float32")
    line = chip_smoke.phase_hybrid(steps=12, tolerance=(2e-4, 2e-3), **kw)
    assert "bf16_state=" in line and "router_agree=1.0000" in line
    with pytest.raises(chip_smoke.SmokeFailure, match="bf16-state control"):
        chip_smoke.phase_hybrid(steps=4, tolerance=(9.0, 9.0), **kw)


def test_phase_window_toy(interpret_mode):
    """The window phase at toy widths in float32: a ring of 8 wrapped once
    by the prompt and five times more by the decode steps agrees with the
    reference to rounding, and a window off by one fails; a tolerance that
    control passes fails the phase."""
    toy = dict(max_len=128, embed_dim=64, num_heads=4, num_kv_heads=2,
               head_dim=16, ffn_dim=96, expert_dim=24, num_experts=16,
               experts_per_token=4, experts_held=8, window=8)
    kw = dict(vocab=97, overrides=toy,
              reference_kw=dict(num_experts_per_tok=4, sliding_window=8),
              prompt_len=13, slots=3, max_len=128, buckets=(16,),
              compiled=False, dtype="float32")
    line = chip_smoke.phase_window(steps=40, tolerance=(2e-4, 2e-3), **kw)
    assert "window_off_by_one=" in line and "router_agree=1.0000" in line
    with pytest.raises(chip_smoke.SmokeFailure,
                       match="window-off-by-one control"):
        chip_smoke.phase_window(steps=4, tolerance=(9.0, 9.0), **kw)


def test_phase_ssm_toy(interpret_mode):
    """The ssm phase at toy widths in float32: the recurrence agrees with
    its definition and a state kept in bf16 does not; a state prefilled by
    the chunked scan (chunks of 8, a prompt of 13) and updated 40 times
    agrees with the reference to rounding, and the gate after the norm
    fails; a tolerance that control passes fails the phase."""
    toy = dict(max_len=128, embed_dim=64, num_heads=4, num_kv_heads=2,
               head_dim=16, expert_dim=24, num_experts=16,
               experts_per_token=4, experts_held=4, latent_size=32,
               shared_dim=48, ssm_heads=8, ssm_head_dim=8, ssm_state=16,
               ssm_groups=2, ssm_chunk=8)
    kw = dict(vocab=97, overrides=toy,
              reference_kw=dict(num_experts_per_tok=4, n_groups=2),
              prompt_len=13, slots=3, max_len=128, buckets=(16,),
              compiled=False, dtype="float32",
              recurrence=dict(heads=8, head_dim=8, state=16, groups=2,
                              chunk=8, prefill=40, steps=24))
    line = chip_smoke.phase_ssm(steps=40, tolerance=(1e-4, 5e-4), **kw)
    assert "gate_after_norm=" in line and "bf16_state=" in line \
        and "router_agree=1.0000" in line
    with pytest.raises(chip_smoke.SmokeFailure,
                       match="gate-after-norm control"):
        chip_smoke.phase_ssm(steps=4, tolerance=(9.0, 9.0), **kw)
