"""A serving cell: the configuration's language model behind
``ContinuousGenerator``, under a closed loop (one client per slot) or a
paced open loop, as the traffic file says."""

from __future__ import annotations

import concurrent.futures
import importlib
import math
import os
import threading
import time

import numpy as np

from benchmark import cells, harness, stats, trace_capture, traffic
from benchmark.trace_reduce import Reduced

POLL_S = 0.015             # how often the token counter is read
SLICE_CHUNKS = 5           # traced closed run: decode chunks in the slice
SLICE_PREFILLS = 20        # traced open run: prefill calls in the slice
DRAIN_S = 10.0             # open loop: wait for stragglers after the window
PARK_S = 30.0              # closed loop: wait for the scheduler to park
CHECKED = 4                # finished requests compared with the reference


def weights(cfg, seed):
    """The configuration's model and its weights in the served dtype,
    made on the device in one jitted call from the seed."""
    import jax
    import jax.numpy as jnp

    model = cells.resolve(cfg["model"]["factory"])(
        *cfg["model"].get("args", []), **cfg["model"].get("kwargs", {}))
    dtype = jnp.dtype(cfg["server"]["dtype"])

    def init(key):
        params, state = model.init(key)
        return jax.tree_util.tree_map(lambda a: a.astype(dtype), params), \
            state

    params, state = jax.jit(init)(harness.seed_key(seed))
    return model, params, state


def build(run):
    """Model, weights and the generator with the configuration's
    settings."""
    import jax.numpy as jnp

    from bigdl_tpu.serving.scheduler import ContinuousGenerator

    cfg = run.cell.config
    model, params, state = weights(cfg, run.seed)
    dtype = jnp.dtype(cfg["server"]["dtype"])
    srv = cfg["server"]
    gen = ContinuousGenerator(
        model, params, state, num_slots=int(srv["num_slots"]),
        max_len=int(srv["max_len"]), seq_buckets=list(srv["seq_buckets"]),
        cache_dtype=dtype)
    st = gen.stats()
    harness.say(f"server: slots {st['slots']} paged {st['paged']} "
                     f"kernel {st['paged_kernel']} pages "
                     f"{st['pages']['total']} x {st['pages']['page_size']} "
                     f"tokens, pool {st['pages']['pool_bytes'] / 1e9:.2f} GB")
    return model, params, gen


class Poller(threading.Thread):
    """Reads ``gen.stats()`` every ``POLL_S`` and stamps each change of
    the token counter: ``changes = [(instant, tokens, chunks), ...]``."""

    def __init__(self, gen):
        super().__init__(name="bench-poller", daemon=True)
        self.gen = gen
        self.changes = []
        self.last = None            # the newest stats() snapshot
        self.poll_s = 0.0           # seconds spent inside stats()
        self.polls = 0
        self._halt = threading.Event()

    def run(self) -> None:
        seen = None
        while not self._halt.is_set():
            t0 = time.monotonic()
            st = self.gen.stats()
            t1 = time.monotonic()
            self.poll_s += t1 - t0
            self.polls += 1
            self.last = st
            if st["tokens"] != seen:
                seen = st["tokens"]
                self.changes.append((t1, st["tokens"], st["chunks"]))
            self._halt.wait(POLL_S)

    def stop(self) -> None:
        self._halt.set()
        self.join(5.0)

    def wait_for(self, cond, timeout: float):
        """The first snapshot for which ``cond(stats)`` holds."""
        end = time.monotonic() + timeout
        while time.monotonic() < end:
            st = self.last
            if st is not None and cond(st):
                return st
            time.sleep(POLL_S)
        raise TimeoutError("the generator did not reach the expected state")


def occupancy_sum(st: dict) -> float:
    return st["mean_occupancy"] * st["chunks"]


# -- closed loop ------------------------------------------------------------

class ClosedLoop:
    """One client per slot; a client's next request is submitted from the
    done-callback of its last one, i.e. on the scheduler's own thread
    before its next admit, so that a freed slot never idles a chunk
    waiting for the client."""

    def __init__(self, gen, source):
        self.gen = gen
        self.source = source
        self.lock = threading.Lock()
        self.records = []
        self.stopping = threading.Event()
        self.parked = threading.Event()
        self._hold = threading.Event()      # never set: parks the scheduler

    def submit(self, budget=None, max_prompt=None, rng=None) -> None:
        """The next request of the sequence.  With a ``budget`` (set-up
        only) the request stands where it would be with ``budget`` tokens
        still to come: the tokens it would have produced so far are
        appended to its prompt (as far as the largest prefill bucket
        reaches), so that its context and the pages mapped for it are
        those of the steady state from the window's first chunk on."""
        with self.lock:
            prompt, out = next(self.source)
            if budget is not None:
                grown = min(len(prompt) + out - budget, max_prompt)
                extra = rng.integers(1, int(prompt.max()) + 1,
                                     max(0, grown - len(prompt)),
                                     dtype=prompt.dtype)
                prompt, out = np.concatenate([prompt, extra]), budget
            rec = {"prompt": prompt, "max_new": out,
                   "t_submit": time.monotonic(), "t_done": None,
                   "out": None, "error": None}
            self.records.append(rec)
        try:
            fut = self.gen.submit(prompt, rec["max_new"])
        except Exception as e:      # a typed shed is a failed request
            rec["t_done"], rec["error"] = time.monotonic(), repr(e)
            return
        fut.add_done_callback(lambda f, rec=rec: self._done(rec, f))

    def _done(self, rec, fut) -> None:
        rec["t_done"] = time.monotonic()
        try:
            rec["out"] = fut.result()
        except Exception as e:
            rec["error"] = repr(e)
        if self.stopping.is_set():
            # the window is over: hold the scheduler's thread here, at a
            # point where no device call is in flight, so that the run
            # can check results and exit without draining 32 generations
            self.parked.set()
            self._hold.wait()
            return
        self.submit()


def run_closed(run, gen, poller, vocab):
    tr = run.cell.traffic
    slots = gen.stats()["slots"]
    loop = ClosedLoop(gen, traffic.requests(tr, run.seed, vocab))
    # fill every slot by real prefills, each first request with a
    # remaining budget spread over 1..its output length
    peek = traffic.requests(tr, run.seed, vocab)
    budgets = traffic.initial_budgets(tr, [next(peek)[1]
                                           for _ in range(slots)])
    rng = np.random.default_rng([int(run.seed), 5])
    max_prompt = max(run.cell.config["server"]["seq_buckets"])
    for b in budgets:
        loop.submit(budget=b, max_prompt=max_prompt, rng=rng)
    filled = poller.wait_for(
        lambda st: st["counters"].get("serve.gen.prefills", 0) >= slots, 600)
    discard = int(tr.get("discard_chunks", 5))
    st0 = poller.wait_for(
        lambda st: st["chunks"] >= filled["chunks"] + discard, 600)
    # the window opens at the change that completed the discarded chunks
    start = next(t for t, _tok, ch in poller.changes if ch >= st0["chunks"])
    end = start + run.seconds
    slice_ = None
    if run.trace_on:
        slice_ = trace_capture.Slice(os.path.join(run.out_dir, "profile"))
        base = poller.wait_for(lambda st: st["chunks"] >= st0["chunks"] + 2,
                               600)
        slice_.start()
        poller.wait_for(
            lambda st: st["chunks"] >= base["chunks"] + SLICE_CHUNKS + 1, 600)
        slice_.stop()
    time.sleep(max(0.0, end - time.monotonic()))
    st1 = poller.last
    loop.stopping.set()
    parked = loop.parked.wait(PARK_S)
    harness.say(f"closed loop: scheduler parked after the window: "
                     f"{parked}")
    rate, tokens = stats.whole_chunk_rate(
        [(t, tok) for t, tok, _ch in poller.changes], start, end)
    done = [r for r in loop.records
            if r["t_done"] is not None and start <= r["t_done"] <= end]
    run.window = (start, end)
    run.attempted = len(done)
    run.failed = sum(1 for r in done if r["error"] is not None)
    run.e2e["serve_tokens_per_s"] = rate
    chunks = st1["chunks"] - st0["chunks"]
    run.counters = {
        "tokens": tokens, "chunks": chunks,
        "occupancy_pct": (100.0 * (occupancy_sum(st1) - occupancy_sum(st0))
                          / chunks) if chunks else None,
        "prefix_hit_rate": (st1.get("prefix") or {}).get("hit_rate"),
        "shed": sum(v for k, v in st1["counters"].items()
                    if k.startswith("serve.shed."))}
    harness.say(f"closed loop: {tokens} tokens in {chunks} decode "
                     f"chunks, {len(done)} requests resolved in the window "
                     f"({run.failed} failed), prefix hit rate "
                     f"{run.counters['prefix_hit_rate']}")
    return [r for r in loop.records if r["out"] is not None], slice_, parked


# -- open loop ---------------------------------------------------------------

def run_open(run, gen, poller, vocab):
    tr = run.cell.traffic
    rate = float(tr["rate_per_s"])
    warm = int(tr.get("discard_requests", 5))
    n = warm + math.ceil(rate * run.seconds)
    source = traffic.requests(tr, run.seed, vocab)
    reqs = [next(source) for _ in range(n)]
    due = traffic.due_times(tr, n)
    records = []
    slice_ = trace_capture.Slice(os.path.join(run.out_dir, "profile")) \
        if run.trace_on else None
    slice_at = warm + (n - warm) // 2          # the middle of the window
    go_start, go_stop = threading.Event(), threading.Event()
    opened_at = []

    def profile() -> None:
        # on a thread of its own: starting and stopping the profiler
        # takes seconds, and the sender must not run late for it
        go_start.wait()
        slice_.start()
        go_stop.wait()
        concurrent.futures.wait(list(futures), timeout=DRAIN_S)
        slice_.stop()

    profiler = threading.Thread(target=profile, name="bench-profile",
                                daemon=True)
    if slice_ is not None:
        profiler.start()
    sched0 = time.monotonic() + 0.25
    start = sched0 + warm / rate
    end = start + run.seconds
    futures = []
    for i, ((prompt, out), d) in enumerate(zip(reqs, due)):
        if i == slice_at and slice_ is not None:
            opened_at.append(time.monotonic())
            go_start.set()
        elif i == slice_at + SLICE_PREFILLS:
            go_stop.set()    # closes once everything sent so far resolved
        t_due = sched0 + float(d)
        time.sleep(max(0.0, t_due - time.monotonic()))
        rec = {"prompt": prompt, "max_new": out, "t_due": t_due,
               "t_send": time.monotonic(), "t_done": None, "out": None,
               "error": None}
        records.append(rec)
        try:
            fut = gen.submit(prompt, out)
        except Exception as e:
            rec["t_done"], rec["error"] = time.monotonic(), repr(e)
            continue

        def done(f, rec=rec):
            rec["t_done"] = time.monotonic()
            try:
                rec["out"] = f.result()
            except Exception as e:
                rec["error"] = repr(e)

        fut.add_done_callback(done)
        futures.append(fut)
    # bounded drain: what was due in the window and is not resolved when
    # it ends counts as failed
    time.sleep(max(0.0, end - time.monotonic()))
    concurrent.futures.wait(futures, timeout=DRAIN_S)
    if slice_ is not None:
        go_start.set()
        go_stop.set()
        profiler.join(60.0)
    st1 = gen.stats()
    counted = [r for r in records if start <= r["t_due"] < end]
    ok = [r for r in counted if r["out"] is not None]
    ttft = [(r["t_done"] - r["t_due"]) * 1e3 for r in ok]
    late = stats.lateness_ms([r["t_send"] for r in counted],
                             [r["t_due"] for r in counted])
    run.window = (start, end)
    run.attempted, run.failed = len(counted), len(counted) - len(ok)
    run.e2e["ttft_p50_ms"] = stats.median(ttft)
    run.e2e["ttft_p95_ms"] = stats.percentile(ttft, 95)
    # what the host clock reads once the profiler starts is the
    # profiler's doing (its start and stop stall the process for
    # seconds): a traced run's lateness and queue wait count the
    # requests due before the slice opened, half the window
    clean = opened_at[0] if opened_at else end
    run.samples = {"ttft_ms": ttft, "sent": records, "clean_until": clean,
                   "late_ms": [l for l, r in zip(late, counted)
                               if r["t_due"] < clean]}
    run.counters = {
        "shed": sum(v for k, v in st1["counters"].items()
                    if k.startswith("serve.shed.")),
        "queue_depth_end": st1["queue_depth"]}
    beyond = len(ttft) - math.ceil(0.95 * len(ttft)) if ttft else 0
    harness.say(f"open loop: {rate} requests/s, {len(counted)} due in "
                     f"the window, {len(ok)} resolved; TTFT samples "
                     f"{len(ttft)} ({beyond} beyond the 95th percentile), "
                     f"p50 {run.e2e['ttft_p50_ms']} ms p95 "
                     f"{run.e2e['ttft_p95_ms']} ms max "
                     f"{max(ttft) if ttft else None} ms; generator late "
                     f"p95 {stats.percentile(late, 95)} ms")
    return [r for r in records if r["out"] is not None], slice_, False


# -- correctness -----------------------------------------------------------------

def logit_gaps(logits, tokens):
    """How far the reference's logit of each (1-based) token lies under
    the reference's maximum at its position, in standard deviations of
    that position's logits: 0 where the token is the reference's first
    choice."""
    import numpy as np
    chosen = logits[np.arange(len(tokens)), np.asarray(tokens) - 1]
    return (logits.max(axis=-1) - chosen) / logits.std(axis=-1)


def gaps_line(gaps) -> str:
    """Readings beside the widest gap that no limit is set on yet: a
    later benchmark issue needs them from a dozen seeds (PERF.md 7)."""
    import numpy as np
    gaps = np.asarray(gaps)
    if not gaps.size:
        return "no position compared"
    return (f"off the reference's first choice at {int((gaps > 0).sum())} "
            f"of {gaps.size} positions, mean gap {float(gaps.mean()):.6f}")


def reference_check(run, params, finished) -> bool:
    """A seeded sample of finished requests: prompt plus served tokens go
    through the plain reference, and at every served position the
    reference's logit of the served token must lie within the stated
    tolerance of the reference's maximum, in units of the standard
    deviation of that position's logits.  Needs only the tokens the
    server returned."""
    import numpy as np

    cfg = run.cell.config
    reference = importlib.import_module(cfg["reference"])
    heads = int(cfg["model"]["kwargs"]["num_heads"])
    max_len = int(cfg["server"]["max_len"])
    rows_n = int(cfg["tolerance"]["rows"])
    rs = np.random.default_rng([int(run.seed), 13])
    picks = rs.permutation(len(finished))[:CHECKED]
    gaps, in_range = [0.0], True
    for i in picks:
        rec = finished[int(i)]
        out = np.asarray(rec["out"], np.int32)
        in_range &= bool(out.size and 1 <= out.min()
                         and out.max() <= cfg["model"]["args"][0])
        tp = len(rec["prompt"])
        seq = np.ones(max_len, np.int32)
        seq[:tp] = rec["prompt"]
        seq[tp:tp + out.size - 1] = out[:-1]
        n = min(out.size, rows_n)
        rows = np.full(rows_n, tp - 1, np.int32)
        rows[:n] = np.arange(tp - 1, tp - 1 + n)
        logits = np.asarray(reference.logits_at(params, seq, rows,
                                                heads=heads))[:n]
        gaps.extend(logit_gaps(logits, out[:n]))
    worst, checked = float(max(gaps)), len(gaps) - 1
    tol = float(cfg["tolerance"]["logit_gap_std"])
    ok = bool(len(picks)) and in_range and worst <= tol
    run.compared.update(logit_gap_std=[worst, tol],
                        nothing_to_check=[int(not len(picks)), 0],
                        tokens_out_of_range=[int(not in_range), 0])
    harness.say(f"reference check: {len(picks)} requests, {checked} "
                     f"served positions, worst (max logit - served logit) "
                     f"/ std = {worst:.4f} (tolerance {tol}), "
                     f"{gaps_line(gaps[1:])}, tokens in range: {in_range}: "
                     f"{'ok' if ok else 'FAIL'}")
    return ok


def run(run) -> None:
    ledger_dir = harness.start_ledger(run)
    model, params, gen = build(run)
    vocab = int(run.cell.config["model"]["args"][0])
    poller = Poller(gen)
    poller.start()
    kind = run.cell.traffic["kind"]
    try:
        finished, slice_, parked = (run_closed if kind == "closed"
                                    else run_open)(run, gen, poller, vocab)
    finally:
        poller.stop()
    run.setup_s = run.window[0] - run.t0
    harness.say(f"poller: {poller.polls} reads of stats(), "
                     f"{1e6 * poller.poll_s / max(1, poller.polls):.0f} us "
                     f"each, {100 * poller.poll_s / run.seconds:.3f}% of "
                     f"the window")
    run.memory_peak_bytes = harness.memory_peak_bytes()
    if kind == "open":
        gen.drain(timeout=60)
    shed = run.counters.get("shed", 0)
    run.correct = reference_check(run, params, finished) and shed == 0
    run.compared["shed"] = [shed, 0]
    harness.stop_ledger(run, ledger_dir)
    if slice_ is not None:
        events = slice_.events()
        if events is not None:
            run.trace = Reduced(events)
    # a closed loop leaves 32 generations running behind a parked (or,
    # if it never parked, live) scheduler thread: do not wait for them
    run.hard_exit = kind == "closed" and not parked
