"""Kernel autotuner — swept Pallas tiling configs per (op, shape, dtype).

Every Pallas kernel in ``ops/`` ran on hand-picked tile shapes until r14
(``quant._BLOCK_M/N/K``, ``fp16._BLOCK_ROWS``, the attention
``_SCORE_TILE_BYTES`` heuristic, ``lrn._pick_tile``, ``pooling._pick_bc``)
— numbers measured once on one chip and frozen.  This module makes the
choice empirical and cached (the compiled-kernel-selection direction of
TensorFlow's 1605.08695, applied BigDL-style as a library concern,
1804.05839):

* **candidates** are generated from hardware-aligned divisors — lane
  (128) and sublane multiples, bounded by a VMEM budget — never free-form
  integers, so every candidate is a config Mosaic can actually lay out;
* **measurement** is compile-and-time (steady-state median, compile
  excluded) with ``observability/costs.py`` ``cost_analysis`` as the
  cross-check objective: the winner's and fallback's FLOPs/bytes ride
  into the store, so a "win" that merely moved more HBM is visible;
* **winners** are cached in an on-disk per-platform JSON store —
  ``set_tune_dir()`` API > ``BIGDL_TPU_TUNE_DIR`` env > a user-cache
  default — written by atomic rename, schema-versioned, and entries for
  another platform (or schema) are IGNORED, never misapplied;
* **lookup** is the only thing the kernels do at trace time: the
  caller's current constant is the always-present fallback rung, so an
  EMPTY cache is bit-identical to the pre-r14 behavior (no silent
  numeric drift from this refactor), and a cached winner that fails the
  caller's validity contract (divisibility, VMEM cap) is discarded in
  favor of the fallback rather than trusted.

``cli tune`` (``bigdl_tpu/bench_tune.py``) pre-warms the store for a zoo
model and emits the ``tune.run`` ledger record run-report renders.

graftlint pairs this subsystem with the ``tuned-tile-bypass`` rule: a
module that imports this registry must not hand a literal block shape
straight to ``pallas_call``/``BlockSpec`` — that is the exact hazard
this module exists to remove.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from bigdl_tpu.utils.durable_io import atomic_write_json

SCHEMA_VERSION = 1

# hardware alignment floors shared by every candidate generator: the
# minor (lane) dim tiles at 128, the second-minor (sublane) at 8 f32
# rows — 32 covers every operand dtype in the tree (int8's floor is the
# largest, the same constant ops/quant.py pads with)
LANES = 128
SUBLANES = 8
SUBLANES_ANY_DTYPE = 32

# hard per-operand VMEM cap candidates must fit (v5e VMEM is 128 MB but
# Mosaic's scoped-vmem default is 16 MB; half of it keeps double
# buffering honest) — a CAP, not a heuristic: the measured sweep picks
# inside it
VMEM_CAP_BYTES = 8 * 1024 * 1024

# the pooling kernel's per-block input budget (the unrolled kernel keeps
# ~10 live block temporaries; ops/pooling.py's fallback derives from the
# same constant) — owned here so the candidate generator and the
# kernel-side recheck can never disagree
POOL_BC_BUDGET_BYTES = 256 << 10

_lock = threading.Lock()
_api_dir: Optional[str] = None          # set_tune_dir() override
_store_cache: Dict[str, Optional[dict]] = {}   # path -> entries|None


# -- store resolution --------------------------------------------------------

def set_tune_dir(path: Optional[str]) -> None:
    """API-level store location (wins over ``BIGDL_TPU_TUNE_DIR``);
    ``None`` restores env/default resolution.  Clears the read cache so
    tests and the CLI see their own store immediately."""
    global _api_dir
    with _lock:
        _api_dir = path
        _store_cache.clear()


def tune_dir() -> str:
    """Resolved store directory: API > env > user-cache default.  The
    default is OUTSIDE the package tree (packaging: the cache must
    never ride in a wheel/sdist — MANIFEST.in prunes the in-repo name
    too, belt and braces)."""
    if _api_dir is not None:
        return _api_dir
    env = os.environ.get("BIGDL_TPU_TUNE_DIR")
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "bigdl_tpu",
                        "tune")


def platform() -> str:
    """Store partition key: winners measured on one platform must never
    be served to another (a v5e tile layout means nothing on CPU
    interpret timings and vice versa).  A backend that cannot
    initialise raises — there is no store to serve without one."""
    import jax
    backend = jax.default_backend()
    if backend == "tpu":
        kind = jax.devices()[0].device_kind
        return "tpu-" + str(kind).strip().lower().replace(" ", "-")
    return str(backend)


def _store_path(directory: Optional[str] = None) -> str:
    return os.path.join(directory or tune_dir(),
                        f"tune-{platform()}.json")


def store_summary() -> Tuple[str, int]:
    """(path, live entry count) of the store :func:`lookup` reads on
    this platform — what a bring-up check prints so that a warm store
    cannot change tiles unseen."""
    path = _store_path()
    return path, len(_load_entries(path) or {})


def _load_entries(path: str) -> Optional[dict]:
    """Entries dict from one store file, or ``None`` when absent,
    unreadable, schema-mismatched or written for another platform —
    every one of those means "no cache", never "wrong cache"."""
    with _lock:
        if path in _store_cache:
            return _store_cache[path]
    entries: Optional[dict] = None
    try:
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
        if (isinstance(data, dict)
                and data.get("schema") == SCHEMA_VERSION
                and data.get("platform") == platform()
                and isinstance(data.get("entries"), dict)):
            entries = data["entries"]
    except (OSError, ValueError):
        entries = None
    with _lock:
        _store_cache[path] = entries
    return entries


def invalidate_cache() -> None:
    """Drop the in-process read cache (tests; after external writes)."""
    with _lock:
        _store_cache.clear()


def key(op: str, sig: str, dtype: str) -> str:
    return f"{op}|{sig}|{dtype}"


def lookup(op: str, sig: str, dtype: str,
           fallback: Sequence[int]) -> Tuple[int, ...]:
    """The kernels' trace-time entry: the cached winner for
    ``(op, sig, dtype)`` on this platform, else ``fallback`` —
    callers validate the returned tiles against their own divisibility
    contract and fall back themselves when a stale entry fails it."""
    entries = _load_entries(_store_path())
    if entries is not None:
        e = entries.get(key(op, sig, dtype))
        if isinstance(e, dict):
            tiles = e.get("tiles")
            if (isinstance(tiles, list) and tiles
                    and all(isinstance(t, int) and t > 0 for t in tiles)):
                return tuple(tiles)
    return tuple(fallback)


def lookup_entry(op: str, sig: str, dtype: str) -> Optional[dict]:
    """Full cached record (tiles + measurements) or ``None`` — the CLI's
    cache-hit probe."""
    entries = _load_entries(_store_path())
    if entries is None:
        return None
    e = entries.get(key(op, sig, dtype))
    return dict(e) if isinstance(e, dict) else None


def record(op: str, sig: str, dtype: str, entry: dict,
           directory: Optional[str] = None) -> str:
    """Merge one winner into the per-platform store: atomic rename so a
    concurrent READER sees the old or new complete file (never torn),
    plus an advisory flock around the read-merge-write so a concurrent
    WRITER (two ``cli tune`` runs sharing a store) cannot lose the
    other's entries to a last-writer-wins race.  The lock is fail-soft:
    where flock is unavailable the write still lands atomically, only
    the cross-process merge guarantee degrades.  Returns the store
    path."""
    path = _store_path(directory)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    lock_fd = None
    try:
        try:
            import fcntl
            lock_fd = os.open(path + ".lock",
                              os.O_CREAT | os.O_RDWR, 0o644)
            fcntl.flock(lock_fd, fcntl.LOCK_EX)
        except Exception:
            if lock_fd is not None:
                os.close(lock_fd)
            lock_fd = None
        data = {"schema": SCHEMA_VERSION, "platform": platform(),
                "entries": {}}
        try:
            with open(path, encoding="utf-8") as f:
                old = json.load(f)
            if (isinstance(old, dict)
                    and old.get("schema") == SCHEMA_VERSION
                    and old.get("platform") == platform()
                    and isinstance(old.get("entries"), dict)):
                data["entries"] = old["entries"]
        except (OSError, ValueError):
            pass
        data["entries"][key(op, sig, dtype)] = entry
        atomic_write_json(path, data, indent=1, sort_keys=True)
    finally:
        if lock_fd is not None:
            try:
                import fcntl
                fcntl.flock(lock_fd, fcntl.LOCK_UN)
            except Exception:
                pass
            os.close(lock_fd)
    invalidate_cache()
    return path


# -- shape signatures (shared by kernel lookups and the CLI sweeps) ----------

def matmul_sig(m: int, k: int, n: int) -> str:
    return f"m{m}k{k}n{n}"


def elementwise_sig(n: int) -> str:
    return f"n{n}"


def attention_sig(t_q: int, t_k: int, d: int) -> str:
    return f"tq{t_q}tk{t_k}d{d}"


def lrn_sig(c: int, f: int) -> str:
    return f"c{c}f{f}"


def pool_sig(c: int, h: int, w: int, itemsize: int) -> str:
    return f"c{c}h{h}w{w}i{itemsize}"


# -- candidate generation ----------------------------------------------------

def _round_up(n: int, m: int) -> int:
    return (n + m - 1) // m * m


def _aligned_leq(cap: int, unit: int, ladder: Sequence[int]) -> List[int]:
    """Ladder values that are ``unit``-aligned and no larger than the
    ``unit``-rounded cap — candidates never exceed the (padded) problem
    size, which would only waste VMEM on padding."""
    hi = _round_up(max(cap, 1), unit)
    return [v for v in ladder if v % unit == 0 and v <= hi] or \
        [min(ladder)]


def matmul_candidates(m: int, k: int, n: int, x_itemsize: int = 4,
                      w_itemsize: int = 1,
                      vmem_cap: int = VMEM_CAP_BYTES
                      ) -> List[Tuple[int, int, int]]:
    """(bm, bn, bk) tiles for the fused dequant-matmul family: bm at the
    any-dtype sublane floor, bn/bk lane-aligned, the (x + w + acc)
    block footprint bounded by ``vmem_cap``."""
    bms = _aligned_leq(m, SUBLANES_ANY_DTYPE, (32, 64, 128, 256))
    bns = _aligned_leq(n, LANES, (128, 256))
    bks = _aligned_leq(k, LANES, (128, 256, 512, 1024))
    out = []
    for bm in bms:
        for bn in bns:
            for bk in bks:
                if matmul_footprint(bm, bn, bk, x_itemsize,
                                    w_itemsize) <= vmem_cap:
                    out.append((bm, bn, bk))
    return out


def elementwise_candidates(n: int) -> List[Tuple[int]]:
    """(block_rows,) for the flat (rows, 128) elementwise kernels
    (fp16 codec): sublane-aligned row counts under the VMEM cap."""
    rows_total = _round_up(n, LANES) // LANES
    ladder = (64, 128, 256, 512, 1024)
    return [(r,) for r in _aligned_leq(rows_total, SUBLANES, ladder)]


def _divisors_from(total: int, ladder: Sequence[int]) -> List[int]:
    return [v for v in ladder if total % v == 0]


# -- footprint bounds (shared by candidate generation AND lookup rechecks) ---
#
# Each kernel family's per-step VMEM expression lives here ONCE: the
# candidate generator filters with it and the kernel's trace-time lookup
# re-checks a cached winner with the SAME function, so a change to one
# side can never make sweeps record winners the serve path silently
# rejects (or vice versa) — the same no-drift argument that puts the
# fallback-tile formulas in the kernel modules.

def matmul_footprint(bm: int, bn: int, bk: int, x_itemsize: int = 4,
                     w_itemsize: int = 1) -> int:
    """Per-step VMEM bytes for the fused dequant-matmul family: the
    (bm, bk) x block, (bn, bk) packed weight block, per-channel scale
    row, and the f32 accumulator + output pair."""
    return (bm * bk * x_itemsize + bn * bk * w_itemsize
            + bn * 4 + 2 * bm * bn * 4)


def attention_stream_footprint(bq: int, bk: int, d: int) -> int:
    """Per-step VMEM bytes for the streaming flash kernel: q/k/v blocks
    plus the f32 score tile, the (m, l) carry rows and the o scratch."""
    return (bq * d + 2 * bk * d + bq * bk) * 4 \
        + (2 * bq * LANES + bq * d) * 4


def attention_stream_candidates(t_q: int, t_k: int, d: int,
                                vmem_cap: int = VMEM_CAP_BYTES
                                ) -> List[Tuple[int, int]]:
    """(block_q, block_k) divisor pairs for the streaming flash kernel;
    the per-step block footprint (q/k/v blocks + the f32 score tile +
    carry scratch) stays under the cap."""
    out = []
    for bq in _divisors_from(t_q, (8, 16, 32, 64, 128, 256)):
        for bk in _divisors_from(t_k, (8, 16, 32, 64, 128, 256, 512)):
            if attention_stream_footprint(bq, bk, d) <= vmem_cap:
                out.append((bq, bk))
    return out


def attention_fused_candidates(t_q: int, t_k: int, d: int,
                               vmem_cap: int = VMEM_CAP_BYTES
                               ) -> List[Tuple[int]]:
    """(block_q,) for the whole-K/V-resident forward kernel: the
    (block_q, t_k) f32 score tile plus resident K/V under the cap."""
    out = []
    for bq in _divisors_from(t_q, (8, 16, 32, 64, 128, 256, 512)):
        if (bq * t_k + 2 * t_k * d + bq * d) * 4 <= vmem_cap:
            out.append((bq,))
    return out


def lrn_candidates(c: int, f: int) -> List[Tuple[int]]:
    """(tile,) pixel-tile widths for the LRN kernel grid — lane-aligned,
    never wider than the rounded plane."""
    return [(t,) for t in _aligned_leq(f, LANES, (128, 256, 512, 1024))]


def pool_candidates(c: int, h: int, w: int,
                    itemsize: int) -> List[Tuple[int]]:
    """(bc,) channel-block divisors for the pooling kernel, bounded so
    the unrolled kernel's ~10 live block temporaries stay in scoped
    VMEM (the ops/pooling.py budget argument)."""
    budget = POOL_BC_BUDGET_BYTES
    out = []
    for bc in range(1, c + 1):
        if c % bc == 0 and bc * h * w * itemsize <= budget:
            out.append((bc,))
    return out[-6:] if len(out) > 6 else out


# -- measurement -------------------------------------------------------------

def time_callable(fn: Callable[[], object], iters: int = 5,
                  warmup: int = 1) -> float:
    """Median steady-state seconds per call; ``fn`` must block until
    the result is ready (callers np.asarray / block_until_ready).  The
    warmup calls eat compilation so the median times the KERNEL."""
    for _ in range(max(warmup, 1)):
        fn()
    times = []
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def sweep(op: str, sig: str, dtype: str,
          fallback: Sequence[int],
          candidates: Sequence[Sequence[int]],
          build: Callable[[Tuple[int, ...]], Callable[[], object]],
          iters: int = 5,
          cost_fn: Optional[Callable[[Tuple[int, ...]],
                                     Optional[dict]]] = None,
          directory: Optional[str] = None) -> dict:
    """Measure every candidate (the fallback is ALWAYS candidate 0, so
    the winner can never lose to the hand-picked rung) and record the
    winner in the store.  ``build(tiles)`` returns a nullary callable
    running the kernel at those tiles (blocking); a candidate whose
    build/run raises is skipped — an unlayoutable config is a skipped
    rung, not a sweep failure.  ``cost_fn(tiles)`` (optional) returns
    the ``costs.analyze_jitted`` dict for the cross-check columns.

    Returns the stored entry: ``{"tiles", "speedup", "fallback",
    "fallback_s", "best_s", "swept", "skipped", "cost", "fallback_cost",
    "measured_at"}``.
    """
    fb = tuple(int(v) for v in fallback)
    cands: List[Tuple[int, ...]] = [fb]
    for c in candidates:
        t = tuple(int(v) for v in c)
        if t not in cands:
            cands.append(t)
    timed: List[Tuple[float, Tuple[int, ...]]] = []
    skipped = 0
    fallback_s = None
    for tiles in cands:
        try:
            fn = build(tiles)
            dt = time_callable(fn, iters=iters)
        except Exception:
            if tiles == fb:
                raise        # the fallback rung MUST run — that is the
                # bit-identical contract; a broken fallback is a bug
            skipped += 1
            continue
        timed.append((dt, tiles))
        if tiles == fb:
            fallback_s = dt
    best_s, best = min(timed, key=lambda p: p[0])
    entry = {
        "tiles": list(best),
        "fallback": list(fb),
        "fallback_s": fallback_s,
        "best_s": best_s,
        "speedup": (fallback_s / best_s) if best_s > 0 else 1.0,
        "swept": len(timed),
        "skipped": skipped,
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    if cost_fn is not None:
        try:
            entry["cost"] = cost_fn(best)
            entry["fallback_cost"] = (entry["cost"] if best == fb
                                      else cost_fn(fb))
        except Exception:
            entry["cost"] = entry["fallback_cost"] = None
    record(op, sig, dtype, entry, directory=directory)
    return entry


def emit_tune_run(ops: Sequence[str], swept: int, cache_hits: int,
                  winners: Dict[str, dict], wall_s: float,
                  **extra) -> None:
    """One ``tune.run`` ledger record per tuning session — the source
    of run-report's "kernel tuning" section.  ``winners`` maps store
    keys to ``{"tiles", "speedup"}``."""
    from bigdl_tpu.observability import ledger as run_ledger
    run_ledger.emit(
        "tune.run", platform=platform(), ops=sorted(set(ops)),
        swept=int(swept), cache_hits=int(cache_hits),
        winners={k: {"tiles": list(v.get("tiles", [])),
                     "speedup": float(v.get("speedup", 1.0))}
                 for k, v in winners.items()},
        wall_s=float(wall_s), store=_store_path(), **extra)
