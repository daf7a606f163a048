"""PR 24: the request's timeline, the trainer loop's dispatch and sync
spans, the scope names the device trace carries, and the program's spans
on the profile's own timeline."""

from functools import partial
import math
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
from bigdl_tpu.dataset.dataset import DataSet
from bigdl_tpu.dataset.transformer import MiniBatch
from bigdl_tpu.observability import ledger as run_ledger
from bigdl_tpu.observability import tracer
from bigdl_tpu.observability.report import load_ledger
from bigdl_tpu.optim import DistriOptimizer, LocalOptimizer, SGD, Trigger
from bigdl_tpu.serving.errors import SlotCapacityError
from bigdl_tpu.serving.scheduler.continuous import (ContinuousGenerator,
                                                    Timeline)
from tests.checkers import record_steps

STEPS = 4                   # steps_per_sync of the toy generator


def _lm():
    from bigdl_tpu.models.transformer import TransformerLM
    m = TransformerLM(64, max_len=64, embed_dim=32, num_heads=2,
                      num_layers=2)
    params, state = m.init(jax.random.PRNGKey(0))
    return m, params, state


def _gen(**kw):
    m, params, state = _lm()
    kw.setdefault("num_slots", 2)
    kw.setdefault("seq_buckets", [16])
    kw.setdefault("steps_per_sync", STEPS)
    kw.setdefault("page_size", 4)
    return ContinuousGenerator(m, params, state, **kw)


@pytest.fixture(scope="module")
def gen():
    g = _gen()
    yield g
    g.drain(timeout=30)


def _monotone(tl: Timeline) -> None:
    stamps = [t for t in (tl.t_submit, tl.t_admit, tl.t_first, tl.t_last)
              if t is not None]
    assert stamps == sorted(stamps), stamps


# -- A. the timeline, with the ledger OFF ----------------------------------------

def _plain(g):
    fut = g.submit([3, 4, 5, 6, 7], 6)
    assert len(fut.result(60)) == 6
    tl = fut.timeline
    # the prefill's token, then five more in two chunks of four steps
    assert tl.n_chunks == 1 + math.ceil(5 / STEPS)
    assert tl.t_last > tl.t_first
    return tl


def _one_token(g):
    fut = g.submit([3, 4, 5], 1)
    assert len(fut.result(60)) == 1
    tl = fut.timeline
    assert tl.n_chunks == 1 and tl.gaps_s == [] and tl.t_last == tl.t_first
    return tl


def _continuation(g):
    g.submit([3, 4, 5, 6], 3, session="tl").result(60)
    fut = g.submit([7, 8], 3, session="tl")     # prefills the suffix only
    assert len(fut.result(60)) == 3
    g.close_session("tl").result(60)
    assert fut.timeline.t_first is not None
    return fut.timeline


def _shed(g):
    # the pool gives no page while the request is placed: held back at
    # its first placement, shed typed at the forced one
    real = g._alloc.alloc
    g._alloc.alloc = lambda n: None
    try:
        fut = g.submit([3, 4, 5], 2)
        with pytest.raises(SlotCapacityError):
            fut.result(60)
    finally:
        g._alloc.alloc = real
    tl = fut.timeline
    assert tl.t_admit is not None and tl.t_first is None
    assert tl.n_chunks == 0 and tl.gaps_s == []
    return tl


def _cancelled(g):
    # hold the scheduler's thread in a done-callback, cancel a request
    # that is still queued, let go: the scheduler meets it cancelled
    held, go = threading.Event(), threading.Event()

    def hold(_f):
        held.set()
        go.wait(30)

    first = g.submit([3, 4], 1)
    first.add_done_callback(hold)
    assert held.wait(60)
    fut = g.submit([5, 6, 7], 2)
    assert fut.cancel()
    last = g.submit([8, 9], 1)
    go.set()
    last.result(60)                     # FIFO: the cancelled one was met
    tl = fut.timeline
    assert fut.cancelled() and tl.t_admit is not None
    assert tl.t_first is None and tl.n_chunks == 0
    return tl


CASES = {"plain": _plain, "one_token": _one_token,
         "session_continuation": _continuation, "shed": _shed,
         "cancelled": _cancelled}


@pytest.mark.parametrize("case", sorted(CASES))
def test_timeline_is_on_the_future_with_the_ledger_off(gen, case):
    assert not run_ledger.enabled()
    tl = CASES[case](gen)
    assert isinstance(tl, Timeline)
    _monotone(tl)
    assert len(tl.gaps_s) == max(0, tl.n_chunks - 1)
    assert tl.max_gap_s == (max(tl.gaps_s) if tl.gaps_s else 0.0)
    assert all(g >= 0 for g in tl.gaps_s)


def test_timeline_keeps_the_first_admit_of_a_held_back_request():
    tl = Timeline()
    assert tl.delivered(tl.t_submit + 1.0) is None          # the first token
    assert tl.delivered(tl.t_submit + 1.5) == pytest.approx(0.5)
    assert tl.delivered(tl.t_submit + 1.7) == pytest.approx(0.2)
    assert (tl.n_chunks, tl.max_gap_s) == (3, pytest.approx(0.5))
    f = tl.fields()
    assert f["ttft_s"] == pytest.approx(1.0) and "queue_s" not in f
    assert f["gaps_s"] == pytest.approx([0.5, 0.2])


def test_stats_shows_the_three_histograms(gen):
    _plain(gen)
    h = gen.stats()["histograms"]
    for name in ("serve.gen.queue_wait_s", "serve.gen.ttft_s",
                 "serve.gen.chunk_gap_s"):
        assert h[name]["count"] >= 1 and h[name]["sum"] >= 0.0, name
    # every delivery after the first is one gap
    assert h["serve.gen.chunk_gap_s"]["count"] >= math.ceil(5 / STEPS)


# -- the ledger's view -------------------------------------------------------------

@pytest.fixture(scope="module")
def serve_records(tmp_path_factory):
    run_dir = str(tmp_path_factory.mktemp("serve_ledger"))
    run_ledger.set_run_dir(run_dir)
    try:
        g = _gen()
        _plain(g)
        _one_token(g)
        _continuation(g)
        _shed(g)
        _cancelled(g)
        g.drain(timeout=30)
        run_ledger.flush()
    finally:
        run_ledger.set_run_dir(None)
    records, bad = load_ledger(run_dir)
    assert bad == 0
    return records


def _requests(records, status):
    return [r for r in records
            if r.get("type") == "serve.request" and r["status"] == status]


@pytest.mark.parametrize("status,n", [("ok", 6), ("failed", 1),
                                      ("cancelled", 1)])
def test_serve_request_carries_the_timeline(serve_records, status, n):
    recs = _requests(serve_records, status)
    assert len(recs) == n
    for r in recs:
        assert r["t_submit"] <= r["mono"] and r["queue_s"] >= 0
        assert {"n_chunks", "max_gap_s", "dur_s", "rid"} <= set(r)
        if status == "ok":
            assert r["ttft_s"] >= r["queue_s"]
            assert len(r["gaps_s"]) == r["n_chunks"] - 1
            assert r["dur_s"] >= r["ttft_s"] + sum(r["gaps_s"]) - 1e-6
        else:
            assert "ttft_s" not in r and "gaps_s" not in r
            assert r["n_chunks"] == 0


def test_decode_spans_carry_context_and_pages(serve_records):
    spans = [r for r in serve_records if r.get("type") == "span"]
    decodes = [r for r in spans if r["name"] == "serve.decode"]
    assert decodes
    for r in decodes:
        a = r["attrs"]
        assert a["ctx_tokens"] >= a["active"] >= 1
        assert 1 <= a["pages_mapped"] <= 2 * 16
    prefills = [r for r in spans if r["name"] == "serve.prefill"]
    assert prefills and all("rid" in r["attrs"] for r in prefills)


@pytest.mark.parametrize("block", [8, 128])
def test_walk_counters_equal_a_replay_of_the_positions(tmp_path,
                                                       monkeypatch, block):
    """``pages_walked`` / ``pages_table`` on the spans of the calls that
    hold the paged kernel equal numpy's count over the positions the
    slots had when the call was made, and ``stats()`` shows their
    running ratio; ``blocks_walked`` / ``blocks_table`` count the same
    walk in the kernel's blocks (``block`` key slots each: 2 pages of 4
    and 32 of them, the kernel's own)."""
    from bigdl_tpu.ops import attention
    monkeypatch.setenv("BIGDL_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(attention, "_PAGED_BLOCK_SLOTS", block)
    ps, lp = 4, 64 // 4
    n = min(block // ps, lp)            # pages a block
    assert attention.paged_block_pages(ps, lp) == n
    run_ledger.set_run_dir(str(tmp_path))
    try:
        g = _gen()
        seen = []                   # positions of the active rows, a chunk
        chunk = g._plain_chunk
        monkeypatch.setattr(g, "_plain_chunk", lambda: (
            seen.append(g._pos[g._active].copy()), chunk())[1])
        futs = [g.submit(list(range(3, 3 + n)), new)
                for n, new in ((5, 9), (14, 4), (2, 7))]
        for f in futs:
            f.result(120)
        share = g.stats()["counters"]["serve.paged walk share"]
        g.drain(timeout=30)
        run_ledger.flush()
    finally:
        run_ledger.set_run_dir(None)
    records, bad = load_ledger(str(tmp_path))
    assert bad == 0
    spans = [r for r in records if r.get("type") == "span"]
    decodes = [r["attrs"] for r in spans if r["name"] == "serve.decode"]
    assert len(decodes) == len(seen) >= 3
    for a, pos in zip(decodes, seen):
        assert a["pages_walked"] == int((pos // ps + 1).sum())
        assert a["pages_table"] == pos.size * lp == a["active"] * lp
        assert a["blocks_walked"] == int((pos // ps // n + 1).sum())
        assert a["blocks_table"] == pos.size * -(-lp // n)
    prefills = [r["attrs"] for r in spans if r["name"] == "serve.prefill"]
    assert len(prefills) == 3
    for a in prefills:
        # a prefill's queries run to the end of its bucket
        last = a["shared_tokens"] + a["bucket"] - 1
        assert a["pages_walked"] == last // ps + 1 and a["pages_table"] == lp
        assert a["blocks_walked"] == last // ps // n + 1
        assert a["blocks_table"] == -(-lp // n)
    walked = sum(a["pages_walked"] for a in decodes + prefills)
    table = sum(a["pages_table"] for a in decodes + prefills)
    assert share == pytest.approx(walked / table) and 0 < share < 0.5


def test_run_start_comes_with_a_clock_record(serve_records):
    clocks = [r for r in serve_records if r.get("type") == "clock"]
    assert len(clocks) == 1
    c = clocks[0]
    # both clocks read together, and the record's own stamps agree
    assert abs(c["mono_ns"] / 1e9 - c["mono"]) < 0.05
    assert abs(c["wall_ns"] / 1e9 - c["ts"]) < 0.05


# -- B. the trainer loop ---------------------------------------------------------

def _toy_trainer(kind):
    model = nn.Sequential()
    model.add(nn.Linear(4, 8).set_name("fc1")).add(nn.ReLU())
    model.add(nn.Linear(8, 2)).add(nn.LogSoftMax())
    model.build(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    batches = [MiniBatch(rng.rand(8, 4).astype(np.float32),
                         (np.arange(8) % 2 + 1).astype(np.float32))
               for _ in range(2)]
    args = (model, nn.ClassNLLCriterion(), DataSet.array(batches),
            Trigger.max_iteration(3))
    if kind == "local":
        return LocalOptimizer(*args)
    from bigdl_tpu.parallel.mesh import build_mesh
    devices = jax.devices()[:4]             # four virtual CPU devices
    shape = "4,1,1" if kind == "distri_flat" else "2,1,2"
    return DistriOptimizer(*args, mesh=build_mesh(shape, devices=devices))


@pytest.mark.parametrize("kind", ["local", "distri_flat", "distri_spec"])
def test_dispatch_and_sync_are_children_of_train_step(tmp_path, kind):
    from bigdl_tpu.engine import Engine
    run_ledger.set_run_dir(str(tmp_path))
    try:
        opt = _toy_trainer(kind).set_optim_method(SGD(learning_rate=0.1))
        opt.optimize()
        run_ledger.flush()
    finally:
        run_ledger.set_run_dir(None)
        Engine.reset()
    records, bad = load_ledger(str(tmp_path))
    assert bad == 0
    spans = [r for r in records if r.get("type") == "span"]
    by_start = lambda name: sorted(
        (r for r in spans if r["name"] == name), key=lambda r: r["mono"])
    end = lambda r: r["mono"] + r["dur_s"]
    steps = by_start("train.step")
    assert [r["attrs"]["step"] for r in steps] == [0, 1, 2]
    # the loss is read one step late: step N's span holds its dispatch and
    # then the sync of step N-1, already queued behind step N (``ahead``);
    # the last step is drained after the loop's end, nothing behind it
    dispatches, syncs = by_start("train.dispatch"), by_start("train.sync")
    assert [r["parent"] for r in dispatches] == [r["span"] for r in steps]
    assert [(r["attrs"]["step"], r["attrs"]["ahead"]) for r in syncs] == \
        [(0, True), (1, True), (2, False)]
    assert [r.get("parent") for r in syncs[:2]] == \
        [r["span"] for r in steps[1:]]
    drain = syncs[2]
    assert drain.get("parent") not in {r["span"] for r in steps}
    assert drain["mono"] >= end(steps[2])
    for r in dispatches + syncs[:2]:      # inside its step, in time too
        p = next(s for s in steps if s["span"] == r["parent"])
        assert p["mono"] <= r["mono"]
        assert end(r) <= end(p) + 1e-3
    # the input is one batch ahead (``optim/batch_ahead.py``): step 0's
    # batch is put in the open, then one more under every step, after its
    # dispatch and before the sync it holds; the put under the last step
    # feeds none, so three steps make FOUR puts
    h2d, fetches = by_start("h2d"), by_start("data.next")
    assert len(h2d) == len(fetches) == 4
    assert all(r["attrs"]["bytes"] == 8 * 4 * 4 + 8 * 4 for r in h2d)
    assert [r["attrs"]["ahead"] for r in h2d] == [False, True, True, True]
    # the first batch is fetched and put under the loop's head
    heads = {r["span"] for r in spans if r["name"] == "loop.next"}
    assert h2d[0]["parent"] == fetches[0]["parent"] and \
        h2d[0]["parent"] in heads
    for n, dispatch in enumerate(dispatches):
        fetch, put = fetches[n + 1], h2d[n + 1]
        assert fetch["parent"] == put["parent"] == dispatch["parent"]
        assert end(dispatch) <= fetch["mono"] <= end(fetch) <= put["mono"]
        if n:                   # the sync step n holds comes after the put
            assert end(put) <= syncs[n - 1]["mono"] + 1e-3
    assert [r["type"] for r in records].count("clock") == 1


@pytest.mark.parametrize("kind", ["local", "distri_flat", "distri_spec"])
def test_a_batch_already_on_the_device_is_not_copied_again(tmp_path, kind):
    """A staged pipeline (``ShardedDataSet(staging=True)``,
    ``PrefetchToDevice``) yields ``jax.Array``s: the look-ahead passes
    them through, and its ``h2d`` spans say so."""
    from bigdl_tpu.engine import Engine
    from bigdl_tpu.parallel.mesh import batch_sharding
    run_ledger.set_run_dir(str(tmp_path))
    try:
        opt = _toy_trainer(kind).set_optim_method(SGD(learning_rate=0.1))
        place = jnp.asarray if kind == "local" else partial(
            jax.device_put, device=batch_sharding(opt.mesh))
        staged = [MiniBatch(place(b.data), place(b.labels))
                  for b in opt.dataset.buffer]
        opt.dataset = DataSet.array(staged)
        taken = record_steps(opt)
        opt.optimize()
        run_ledger.flush()
    finally:
        run_ledger.set_run_dir(None)
        Engine.reset()
    records, _ = load_ledger(str(tmp_path))
    h2d = [r["attrs"] for r in records
           if r.get("type") == "span" and r["name"] == "h2d"]
    assert len(h2d) == 4
    assert all(a["staged"] and a["bytes"] == 0 for a in h2d)
    # the step was handed the dataset's own device buffers
    buffers = lambda a: tuple(s.data.unsafe_buffer_pointer()
                              for s in a.addressable_shards)
    assert len(taken) == 3
    assert {buffers(a) for _, a in taken} <= \
        {buffers(b.data) for b in staged}


# -- C. the names the device trace carries ------------------------------------------

def test_a_lowered_step_holds_the_scope_names():
    opt = _toy_trainer("local").set_optim_method(SGD(learning_rate=0.1))
    model = opt.model
    step = opt._build_step()
    lowered = step.lower(
        model.params, opt.optim_method.init_state(model.params),
        model.state, jnp.zeros((8, 4)), jnp.ones((8,)),
        jax.random.PRNGKey(0), jnp.asarray(0, jnp.int32),
        jnp.asarray(0.1, jnp.float32))
    text = lowered.as_text(debug_info=True)
    for name in ("jvp(forward)/fc1", "transpose(jvp(forward))/fc1",
                 "jvp(forward)/ReLU_1", "jvp(forward)/Linear_2",
                 "jvp(loss)", "update/", "guard/"):
        assert name in text, name
    assert "jit(step)" in text          # the accepted readers' program name


def test_a_lowered_step_chunk_and_prefill_hold_the_scope_names(gen):
    n, lp = gen.slots.num_slots, gen._lp
    keys = jax.random.split(jax.random.PRNGKey(0), STEPS)
    chunk = gen._step_fn.lower(
        gen.params, gen.state, jnp.ones(n, jnp.int32), gen._cache,
        jnp.zeros((n, lp), jnp.int32), jnp.zeros(n, jnp.int32),
        jnp.ones(n, bool), jnp.full(n, 8, jnp.int32),
        keys).as_text(debug_info=True)
    prefill = gen._prefill_fn.lower(
        gen.params, gen.state, jnp.ones((1, 16), jnp.int32), 3, gen._cache,
        jnp.zeros((1, lp), jnp.int32), 0, keys[0]).as_text(debug_info=True)
    # the names the trace readers find the programs by: `step_chunk`
    # is a substring of the one decode program's
    assert "jit(step_chunk_kernel)" in chunk and "jit(prefill)" in prefill
    for text in (chunk, prefill):
        for name in ("embed/", "block_0/attn/", "block_1/mlp/", "logits/",
                     "sample/"):
            assert name in text, name
    # both write the cache and read it through the page table
    for text in (chunk, prefill):
        assert "attn/kv.write/" in text and "attn/attn.paged/" in text


@pytest.mark.parametrize("given,index,want", [
    ("inception_3a/1x1", 0, "inception_3a/1x1"), (None, 3, "ReLU_3")])
def test_a_container_scopes_a_child_by_its_given_name_else_class_and_index(
        given, index, want):
    seq = nn.Sequential()
    for _ in range(index):
        seq.add(nn.Identity())
    child = nn.ReLU()
    if given:
        child.set_name(given)
    seq.add(child)
    text = jax.jit(lambda x: seq.apply([()] * (index + 1),
                                       [()] * (index + 1), x)[0]) \
        .lower(jnp.ones(3)).as_text(debug_info=True)
    assert want + "/" in text


# -- D. the program's spans on the profile's timeline -------------------------------

def test_a_span_opens_a_trace_annotation_only_while_the_ledger_is_on(
        tmp_path, monkeypatch):
    seen, stats_seen = [], []

    class Recorder:
        def __init__(self, name, **stats):
            self.name = name
            stats_seen.append(stats)

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setattr(tracer, "_annotation", Recorder)
    with tracer.span("off.outer"):
        pass
    assert seen == [] and tracer.begin_span("x") is tracer._NULL
    run_ledger.set_run_dir(str(tmp_path))
    try:
        with tracer.span("train.step") as step:
            with tracer.span("train.sync") as sync:
                time.sleep(0.001)
    finally:
        run_ledger.set_run_dir(None)
    assert seen == [("enter", "train.step"), ("enter", "train.sync"),
                    ("exit", "train.sync"), ("exit", "train.step")]
    # each annotation carries its span's id: the profile's event joins
    # the ledger's record one to one
    assert stats_seen == [{"span": step.sid}, {"span": sync.sid}]


# -- the compile cache's key across checkouts ---------------------------------------

def test_source_files_are_recorded_relative_to_the_checkout():
    """A Pallas kernel's payload keeps its debug locations, and with
    absolute file names the same program lowered from another copy of the
    repository got another cache key (``utils/compile_cache.py``)."""
    import os
    import re

    from bigdl_tpu.utils import compile_cache
    root = compile_cache.checkout_root()
    assert os.path.isdir(os.path.join(root, "bigdl_tpu"))
    compile_cache.enable_compile_cache()        # conftest did; idempotent
    assert jax.config.jax_hlo_source_file_canonicalization_regex \
        == re.escape(root + os.sep)
    opt = _toy_trainer("local").set_optim_method(SGD(learning_rate=0.1))
    model = opt.model
    text = opt._build_step().lower(
        model.params, opt.optim_method.init_state(model.params),
        model.state, jnp.zeros((8, 4)), jnp.ones((8,)),
        jax.random.PRNGKey(0), jnp.asarray(0, jnp.int32),
        jnp.asarray(0.1, jnp.float32)).as_text(debug_info=True)
    assert '"bigdl_tpu/optim/local_optimizer.py"' in text
    assert root + os.sep not in text
