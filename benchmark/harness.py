"""What every kind of cell shares: the compile meter, the device's
description, the run's context and the result line."""

from __future__ import annotations

import json
import os
import sys
import time
from types import SimpleNamespace
from typing import List, Optional, Tuple

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileMeter:
    """Compile seconds and persistent-cache traffic from the
    ``jax.monitoring`` events jax records itself (the idea of
    ``chip_smoke.CompileMeter``), each with the instant it ended, so that
    compiles before and inside the window can be told apart."""

    def __init__(self):
        self.events: List[Tuple[float, float]] = []    # (monotonic, seconds)
        self.hits = 0
        self.misses = 0

    def install(self) -> "CompileMeter":
        from jax import monitoring
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)
        return self

    def _on_event(self, key: str, **kw) -> None:
        if key == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif key == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_duration(self, key: str, dur: float, **kw) -> None:
        if key == COMPILE_EVENT:
            self.events.append((time.monotonic(), float(dur)))

    def seconds_before(self, t: float) -> float:
        return sum(d for at, d in self.events if at <= t)

    def count_between(self, t0: float, t1: float) -> int:
        return sum(1 for at, _d in self.events if t0 < at <= t1)


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> Optional[int]:
    """Peak bytes on the fullest chip, where the backend says: the
    allocator's ``peak_bytes_in_use`` (live arrays) plus
    ``peak_bytes_reserved``, the space the runtime reserves for the
    programs' temporaries, which the first does not hold (on a v5e GPT-2
    XL's decode chunk reserves 8.4 GB beside 7.3 GB of weights and
    cache)."""
    import jax
    peaks = []
    for d in jax.local_devices():
        st = d.memory_stats()
        if st and "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"])
                         + int(st.get("peak_bytes_reserved", 0)))
    return max(peaks) if peaks else None


def memory_line() -> str:
    import jax
    st = jax.local_devices()[0].memory_stats() or {}
    return (f"memory of device 0: peak in use "
            f"{st.get('peak_bytes_in_use')} reserved for programs "
            f"{st.get('peak_bytes_reserved')} limit {st.get('bytes_limit')}")


def seed_key(seed: int):
    """A PRNG key from any ``--seed`` (the driver's are past 2**31).  The
    key is an ARGUMENT of the jitted initialisers, so every seed runs the
    same cached program."""
    import jax
    return jax.random.PRNGKey(int(seed) % (2 ** 31 - 1))


def new_run(**kw) -> SimpleNamespace:
    """The context a runner fills and the metric readers read."""
    run = SimpleNamespace(
        e2e={}, attempted=0, failed=0, correct=False, setup_s=None,
        window=None, trace=None, records=[], counters={}, samples={},
        train=None, compared={}, memory_peak_bytes=None)
    run.__dict__.update(kw)
    return run


def say(text: str) -> None:
    """An earlier line of the run's output: everything worth reading
    that is not the result."""
    print(text, flush=True)


def start_jax(chips: int = 1) -> dict:
    """Compile cache on (where ``JAX_COMPILATION_CACHE_DIR`` says, else
    ``<checkout>/.jax_cache``), sub-second programs cached too (every run
    is a new process and pays them otherwise), and the device's
    description; dies without a ``tpu`` backend, with too few chips or
    with a device ``peaks.py`` does not know."""
    import jax

    from benchmark import peaks
    from bigdl_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.default_backend() != "tpu":
        die(f"JAX found no TPU (backend {jax.default_backend()!r}); the "
            f"benchmark never runs on another backend")
    device = device_info()
    if device["count"] < chips:
        die(f"the cell needs {chips} chips, JAX sees {device['count']}")
    try:
        device_peaks = peaks.lookup(device["kind"])
    except KeyError as e:
        die(str(e))
    say(f"benchmark: jax {jax.__version__} platform {device['platform']} "
        f"device_kind {device['kind']} devices {device['count']}; compile "
        f"cache {cache_dir}")
    return {"device": device, "peaks": device_peaks}


def start_ledger(run) -> Optional[str]:
    """Traced runs switch the program's run ledger on, in a directory of
    the run's own, so that its spans can be read back."""
    if not run.trace_on:
        return None
    import shutil

    from bigdl_tpu.observability import ledger
    path = os.path.join(run.out_dir, "ledger")
    shutil.rmtree(path, ignore_errors=True)
    ledger.set_run_dir(path)
    return path


def stop_ledger(run, path: Optional[str]) -> None:
    if path is None:
        return
    from benchmark import spans
    from bigdl_tpu.observability import ledger
    ledger.flush()
    run.records = spans.read_ledger(path)
    ledger.set_run_dir(None)


def result_line(run, metrics: dict, device: dict) -> str:
    """The contract's one JSON object; ``compared``, each number that
    decided ``correct`` as ``[value, limit]``, comes last."""
    out = {"correct": bool(run.correct), "attempted": int(run.attempted),
           "failed": int(run.failed), "metrics": metrics, "device": device}
    if getattr(run, "breakdown", None):
        out["breakdown"] = run.breakdown
    out["compared"] = run.compared
    return json.dumps(out)


def die(msg: str, code: int = 1) -> "NoReturn":  # noqa: F821
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)
