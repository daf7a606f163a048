"""Perf-harness CLI tests (``models/utils/{Local,Distri}OptimizerPerf``
flag parity).  The double/x64 path runs in a subprocess because
``jax_enable_x64`` is a process-global switch that must not leak into
the rest of the suite.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from bigdl_tpu.models.perf import _cast_floats, _parser


def test_parser_accepts_reference_flags():
    args = _parser("t").parse_args(
        ["-b", "8", "-i", "2", "-m", "vgg16", "-d", "constant",
         "--dataType", "double", "-c", "28"])
    assert args.batchSize == 8
    assert args.dataType == "double"
    assert args.corePerNode == 28


@pytest.mark.filterwarnings(
    "ignore:Explicitly requested dtype")
def test_cast_floats_targets_only_floating_leaves():
    """Int leaves must never be cast; the true f64 result needs x64
    enabled, which only the subprocess test below can do safely."""
    import jax.numpy as jnp
    tree = {"w": jnp.ones((2, 2), jnp.float32),
            "step": jnp.asarray(3, jnp.int32)}
    out = _cast_floats(tree, np.float64)
    assert jnp.issubdtype(out["w"].dtype, jnp.floating)
    assert out["step"].dtype == jnp.int32
    # float32 request is the identity
    assert _cast_floats(tree, np.float32) is tree


@pytest.mark.slow
def test_local_perf_double_runs_in_subprocess():
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    pythonpath = repo_root + os.pathsep + os.environ.get("PYTHONPATH", "")
    env = dict(os.environ,
               JAX_PLATFORMS="cpu", PYTHONPATH=pythonpath)
    out = subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.models.perf", "local",
         "-m", "alexnetowt", "-b", "4", "-i", "1", "--dataType", "double",
         "-c", "4"],
        env=env, capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "Average throughput" in out.stderr + out.stdout


@pytest.mark.slow
def test_longcontext_perf_tiny():
    from bigdl_tpu.models.perf import longcontext_perf_main
    toks = longcontext_perf_main(["-t", "32", "-l", "1", "-e", "16",
                                  "--heads", "2", "--vocab", "50",
                                  "-i", "1"])
    assert toks > 0


def test_infer_perf_main_runs():
    """The infer subcommand (bigdl-tpu-perf infer) measures the jitted
    eval forward end to end."""
    from bigdl_tpu.models.perf import infer_perf_main
    ips = infer_perf_main(["-m", "alexnet", "-b", "8", "-i", "2"])
    assert ips > 0
