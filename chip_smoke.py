"""The quickest proof that the system still starts on the chip.

``python chip_smoke.py`` drives both halves of the main path once, in ONE
process, through the entry points a user calls, at the full width of the
models the repo's chip history is about (depth as published for
Inception-v1; eight transformer layers), with random weights made from a
seed:

1. **device**    the backend must be ``tpu`` — anything else is an error,
                 never a CPU run; prints cache, tune-store and native state
2. **train**     Inception-v1, batch 256, bf16 mixed precision, through
                 ``LocalOptimizer.optimize()`` over an in-memory ``DataSet``
3. **serve**     an 8-layer, 512-wide, vocab-32000 ``TransformerLM`` (bf16
                 params and cache) behind ``ContinuousGenerator`` defaults
                 (paged, kernel by the platform gate, prefix cache, warm-up)
3b. **hybrid**   the layer-pattern model with recurrent state at the
                 published widths of ``ling3_flash_vl``: a 512-token prefill
                 and 1,024 decode steps, LOG-PROBS against the plain float32
                 reference, the router against the reference's, and two
                 controls (bf16 state, bf16 router) that have to fail
3c. **window**   the same drive for a window layer's ring beside a full
                 layer's pages, at the published widths of
                 ``k_exaone_236b``: a 200-token prefill (the ring wrapped
                 once) and 300 decode steps (twice more), and the control
                 of a window off by one
3d. **ssm**      the same drive for a state-space layer's float32 state
                 beside an attention layer's pages, at the published widths
                 of ``nemotron3_super_120b`` (a Mamba-2, a latent-expert and
                 an attention block): a 300-token prefill and 2,048 decode
                 steps, and the control of the gate after the norm; before
                 it the recurrence alone against its definition, and the
                 control of a state kept in bf16
4. **kernels**   every Pallas kernel a TPU backend switches on without an
                 opt-in variable, compiled and compared with its jnp
                 reference inside the tolerances below
5. **multichip** the trainer through ``DistriOptimizer`` over
                 ``Engine.init()`` when four or more devices are visible

Every phase prints one result line; a failed phase makes the exit status
non-zero and withholds the final JSON line.  The script spawns nothing
that touches JAX: a chip belongs to one process.  Sizes are function
arguments so ``tests/test_chip_smoke.py`` drives the same phases at toy
sizes on the CPU mesh; there is no environment switch that lets ``main``
run off the chip.
"""

from __future__ import annotations

import contextlib
import json
import math
import os
import sys
import time
import traceback
from functools import partial
from unittest import mock

# -- tolerances (the numerics gate is phase 4) --------------------------------
# max|kernel - reference| / max|reference|, reference in f32 at HIGHEST
# matmul precision from the same bf16-rounded inputs.  bf16 carries 8
# significand bits (eps 2^-8 = 3.9e-3); a kernel and XLA round at
# different points of the same sum, so a handful of ulps is agreement.
TOL_BF16 = 2e-2
# flash backward recomputes p in bf16 and feeds three chained bf16
# matmuls: its error is a few forward-errors wide
TOL_BF16_GRAD = 4e-2
# the n-way step against the one-chip step on the same global batch —
# the bound ``__graft_entry__._dryrun_flagship`` uses on the CPU mesh
TOL_LOSS = 2e-4


class SmokeFailure(Exception):
    """A phase observed a wrong result."""


def check(cond, msg: str) -> None:
    """``assert`` that survives ``python -O``."""
    if not cond:
        raise SmokeFailure(msg)


def _rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(got.shape == want.shape, f"shape {got.shape} != {want.shape}")
    check(bool(np.isfinite(got).all()), "non-finite kernel output")
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-30))


class CompileMeter:
    """Compile seconds and persistent-cache traffic, from the
    ``jax.monitoring`` events jax itself records."""

    def __init__(self):
        self.compile_s = 0.0
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
        if key == "/jax/core/compile/backend_compile_duration":
            self.compile_s += dur

    def snapshot(self):
        return self.compile_s, self.hits, self.misses


# -- phase 1: device ----------------------------------------------------------

def phase_device(cache_dir: str) -> str:
    import jax

    from bigdl_tpu import native
    from bigdl_tpu.ops import pallas_enabled, tuning

    store, entries = tuning.store_summary()
    built = "loaded" if native.lib() is not None else "numpy fallback"
    return (f"backend={jax.default_backend()} "
            f"pallas={'on' if pallas_enabled() else 'off'} "
            f"compile_cache={cache_dir} "
            f"tune_store={store} entries={entries} native={built}")


# -- phase 2: train -----------------------------------------------------------

def _synthetic_batches(n, batch, input_shape, classes, seed=0):
    import numpy as np

    from bigdl_tpu.dataset.transformer import MiniBatch
    rs = np.random.RandomState(seed)
    return [MiniBatch(rs.rand(batch, *input_shape).astype(np.float32),
                      (rs.randint(0, classes, batch) + 1).astype(np.float32))
            for _ in range(n)]


def _inception(classes, **kw):
    from bigdl_tpu.models.inception import Inception_v1
    return Inception_v1(classes, **kw)


def phase_train(model_fn=None, input_shape=(3, 224, 224), classes=1000,
                batch=256, steps=4) -> str:
    import jax
    import numpy as np

    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset.dataset import DataSet
    from bigdl_tpu.observability.summary import TrainSummary
    from bigdl_tpu.optim import LocalOptimizer, SGD, Trigger

    model = model_fn() if model_fn else _inception(classes)
    model.build(seed=0)
    before = [np.asarray(l) for l in jax.tree_util.tree_leaves(model.params)]
    summary = TrainSummary("", "chip_smoke", tensorboard=False)
    opt = (LocalOptimizer(model, nn.ClassNLLCriterion(),
                          DataSet.array(_synthetic_batches(
                              2, batch, input_shape, classes)),
                          Trigger.max_iteration(steps))
           .set_optim_method(SGD(learning_rate=0.01))
           .set_mixed_precision(True)
           .set_train_summary(summary))
    opt.optimize()

    losses = [v for _, v, _ in summary.read_scalar("Loss")]
    check(len(losses) == steps and all(math.isfinite(v) for v in losses),
          f"want {steps} finite losses, got {losses}")
    check(opt.state.get("skippedSteps", 0) == 0,
          f"{opt.state.get('skippedSteps')} non-finite steps skipped")
    leaves = jax.tree_util.tree_leaves(model.params)
    moved = max(float(np.abs(np.asarray(a) - b).max())
                for a, b in zip(leaves, before))
    check(moved > 0, "weights did not move")
    platforms = {d.platform for l in leaves for d in l.devices()}
    check(platforms == {jax.default_backend()},
          f"params live on {platforms}, backend {jax.default_backend()}")
    return (f"steps={steps} batch={batch} losses="
            f"{[round(v, 4) for v in losses]} max|dw|={moved:.3e} "
            f"params_on={sorted(platforms)}")


# -- phase 3: serve -----------------------------------------------------------

def _paged_kernel_compiled() -> bool:
    """The serving read path is the compiled Pallas kernel: platform
    gate on, interpreter off."""
    from bigdl_tpu.ops import attention
    return attention.paged_attention_enabled() and not attention._interpret()


def phase_serve(vocab=32000, embed=512, heads=8, layers=8, max_len=1024,
                buckets=(128, 512), slots=8,
                prompt_lens=(200, 40, 400, 100) * 3, shared_prefix=64,
                max_new=32, compiled=True) -> str:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.core.precision import cast_tree
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.serving.scheduler import ContinuousGenerator

    model = TransformerLM(vocab, max_len=max_len, embed_dim=embed,
                          num_heads=heads, num_layers=layers)
    params, state = jax.jit(model.init)(jax.random.PRNGKey(0))
    params = cast_tree(params, jnp.bfloat16)
    rs = np.random.RandomState(1)
    prefix = rs.randint(1, vocab + 1, shared_prefix)
    prompts = []
    for i, n in enumerate(prompt_lens):
        p = rs.randint(1, vocab + 1, n).astype(np.int32)
        if i % 2 == 0 and n > shared_prefix:
            p[:shared_prefix] = prefix
        prompts.append(p)

    gen = ContinuousGenerator(model, params, state, num_slots=slots,
                              max_len=max_len, seq_buckets=list(buckets),
                              cache_dtype=jnp.bfloat16)
    try:
        futures = [gen.submit(p, max_new) for p in prompts]
        # result() re-raises what the scheduler set on the future: the
        # loop catches every exception by design, so a kernel the
        # compiler refused surfaces HERE or in the failed counter
        outs = [np.asarray(f.result(timeout=900)) for f in futures]
    finally:
        gen.drain(timeout=60)
    stats = gen.stats()
    counters = stats["counters"]
    failed = counters.get("serve.gen.failed", 0)
    shed = sum(v for k, v in counters.items() if k.startswith("serve.shed."))
    check(failed == 0 and shed == 0, f"failed={failed} shed={shed}")
    check(stats["completed"] == len(prompts),
          f"completed {stats['completed']} of {len(prompts)}")
    check(stats["paged"] and stats["paged_kernel"],
          f"generator left the paged kernel: paged={stats['paged']} "
          f"paged_kernel={stats['paged_kernel']}")
    check(_paged_kernel_compiled() == compiled,
          f"paged kernel compiled={_paged_kernel_compiled()}, "
          f"expected {compiled}")
    for o in outs:
        check(o.shape == (max_new,), f"output shape {o.shape}")
        check(1 <= o.min() and o.max() <= vocab,
              f"token out of range [{o.min()}, {o.max()}]")

    # greedy tokens against TransformerLM.generate (reported, not gated:
    # at vocab 32000 with random weights a near-tie can flip between two
    # correct bf16 kernels; phase 4 is the numerics gate)
    generate = jax.jit(partial(model.generate, max_new=max_new,
                               max_len=max_len, cache_dtype=jnp.bfloat16))
    ref = [None] * len(prompts)
    for n in sorted(set(prompt_lens)):
        idx = [i for i, p in enumerate(prompts) if len(p) == n]
        out = np.asarray(generate(params, state, jnp.asarray(
            np.stack([prompts[i] for i in idx]))))
        for row, i in zip(out, idx):
            ref[i] = row
    matched = [bool(np.array_equal(o, r)) for o, r in zip(outs, ref)]
    line = (f"requests={len(prompts)} tokens={stats['tokens']} failed=0 "
            f"shed=0 paged_kernel={'compiled' if compiled else 'interpreted'}"
            f" prefix_hit_rate={stats['prefix']['hit_rate']:.2f} "
            f"token_match={sum(matched)}/{len(prompts)}")
    if not all(matched):
        i = matched.index(False)
        j = int(np.argmax(outs[i] != ref[i]))
        ctx = np.concatenate([prompts[i], ref[i][:j]])[None]
        logp, _ = jax.jit(model.decode)(
            params, state, jnp.asarray(ctx),
            model.init_cache(1, max_len, jnp.bfloat16), 0)
        top2 = np.sort(np.asarray(logp[0, -1], np.float32))[-2:]
        line += (f" first_mismatch=request {i} position {j} "
                 f"(got {outs[i][j]}, reference {ref[i][j]}, reference "
                 f"top-2 logit margin {top2[1] - top2[0]:.4f})")
    return line


# -- phase 3b: a model with recurrent state, on logits ---------------------------

# The hybrid phase's tolerance on max |served log-prob - reference
# log-prob| over the standard deviation of that position's reference
# logits: (the MEDIAN over the positions, the MAXIMUM over them).  The
# served path is bf16 weights and activations around a float32 recurrent
# state and a float32 router; the reference is float32 throughout.  What
# makes precision visible is the model's own initialisation
# (``models/hybrid.py``, ``nn/linear_attention.py``; the configuration's
# ``assumed.init``): decay rates that keep hundreds to thousands of
# tokens in the state, so that a state rounded to bf16 after every update
# drifts, and a routed expert whose exchange for its runner-up (what a
# hidden state rounded to bf16 does to a token now and then, in any
# implementation) moves the logits less than the rounding itself does.
# 1,024 decode steps let the rounding add up (after 256 the control
# still reads as the served path does).  Readings on a v5e (PR 27):
# served (0.0761, 0.1462), the control that rounds the state to bf16
# (0.2317, 0.3510); the tolerance lies between (the geometric means), and
# the control has to FAIL it.
HYBRID_TOLERANCE = (0.135, 0.225)
# The router itself, on one random hidden state at the published width:
# the same experts chosen as the reference chooses, gates within this of
# the reference's.  The control (scores rounded to bf16 before selection
# and gating, 2^-9 relative) has to fail it: a router of this gain puts a
# token's best scores within 1e-4 of 1, where bf16 has no values left
# between them, and on a v5e no token of 512 then chose the reference's
# eight.
ROUTER_TOLERANCE = 2e-5


def _bf16(x):
    """``x`` rounded to bf16's 8 exponent and 7 mantissa bits, in place of
    a cast there and back, which XLA may drop (it is allowed excess
    precision)."""
    import jax
    return jax.lax.reduce_precision(x, 8, 7)


def _rounded(fn, index):
    """``fn`` with output ``index`` rounded to bf16 (a control)."""
    def wrapped(*args, **kw):
        out = list(fn(*args, **kw))
        out[index] = _bf16(out[index])
        return tuple(out)
    return wrapped


def _bf16_state(model):
    """The control of the hybrid and the ssm phase: the recurrent state
    (the delta rule's, the state-space layer's) rounded to bf16 after every
    update."""
    from bigdl_tpu.nn import linear_attention, state_space
    stack = contextlib.ExitStack()
    for module, names in ((linear_attention, ("kda_step", "kda_chunked")),
                          (state_space, ("ssd_step", "ssd_chunked"))):
        for name in names:
            stack.enter_context(mock.patch.object(
                module, name, _rounded(getattr(module, name), 1)))
    return stack


def _gate_after_norm(model):
    """A control of the ssm phase: ``rmsnorm_group(y) * silu(z)`` where the
    configuration states ``rmsnorm_group(y * silu(z))``."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.nn.state_space import Mamba2Mixer

    def swapped(self, params, o, z):
        b, s, inner = o.shape
        o = o.reshape(b, s, self.groups, -1)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + self.eps)
        return o.reshape(b, s, inner) \
            * params["norm"]["weight"].astype(jnp.float32) \
            * jax.nn.silu(z.astype(jnp.float32))

    return mock.patch.object(Mamba2Mixer, "_gate_norm", swapped)


def _window_off_by_one(model):
    """The control of the window phase: every window layer keeps and reads
    one key more than the configuration's window."""
    stack = contextlib.ExitStack()
    for m in model.mixers:
        if getattr(m, "window", None):
            stack.enter_context(mock.patch.object(m, "window", m.window + 1))
    return stack


def phase_hybrid(config="benchmark/configs/ling3_flash_vl.json", vocab=None,
                 overrides=None, reference_kw=None, prompt_len=512, steps=1024,
                 slots=8, max_len=2048, buckets=(512,), compiled=True,
                 tolerance=HYBRID_TOLERANCE,
                 router_tolerance=ROUTER_TOLERANCE, dtype="bfloat16",
                 controls=(("bf16_state", _bf16_state),)) -> str:
    """Prefill of ``prompt_len`` tokens, then ``steps`` decode steps, the
    way ``ContinuousGenerator``'s two programs call the model (slot-
    addressed prefill from position 0 with its real length; whole-batch
    decode steps with the other rows inactive), against the plain
    reference's full forward over the same tokens, on LOG-PROBS; the
    router against the reference's on one hidden state; the same request
    through the generator itself; and the controls (``controls``: the name
    and the patch of each that the per-slot state has to fail, beside the
    router's own)."""
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.models import hybrid
    from bigdl_tpu.serving.scheduler import ContinuousGenerator

    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           config), encoding="utf-8") as f:
        cfg = json.load(f)
    kwargs = dict(cfg["model"]["kwargs"], **(overrides or {}))
    vocab = vocab or cfg["model"]["args"][0]
    reference = importlib.import_module(cfg["reference"])
    ref_kw = reference_kw or {}
    model = hybrid.HybridLM(vocab, **kwargs)
    def init(key):
        params, _ = model.init(key)
        return jax.tree_util.tree_map(lambda a: a.astype(dtype), params)

    params = jax.jit(init)(jax.random.PRNGKey(27))
    rs = np.random.RandomState(27)
    prompt = rs.randint(1, vocab + 1, prompt_len).astype(np.int32)
    slot, ps = slots - 1, 16
    lp_w = -(-max_len // ps)
    need = -(-(prompt_len + steps) // ps)
    table = np.full((slots, lp_w), slots * lp_w, np.int32)   # all trash
    table[slot, :need] = np.arange(need)
    bucket = min(b for b in buckets if b >= prompt_len)
    padded = np.ones((1, bucket), np.int32)
    padded[0, :prompt_len] = prompt

    def served(params):
        """(log-probs (steps + 1, vocab), greedy tokens) of the request."""
        cache = model.init_paged_cache(slots * lp_w, ps, jnp.dtype(dtype),
                                       num_slots=slots)
        pages = jnp.asarray(table)

        @jax.jit
        def prefill(params, tokens, cache):
            return model.decode_pages(
                params, {}, tokens, cache, pages[slot][None],
                jnp.zeros((1,), jnp.int32), jnp.ones((1,), bool),
                slots=jnp.asarray([slot]),
                lengths=jnp.asarray([prompt_len]))[:2]

        @jax.jit
        def step(params, tok, cache, pos):
            active = jnp.arange(slots) == slot
            lp, cache, _ = model.decode_pages(
                params, {}, jnp.where(active, tok, 1)[:, None], cache,
                pages, jnp.where(active, pos, 0), active)
            return lp[slot, 0], cache

        lp, cache = prefill(params, jnp.asarray(padded), cache)
        rows, toks = [lp[0, 0]], []
        for i in range(steps):
            toks.append(jnp.argmax(rows[-1]).astype(jnp.int32) + 1)
            lp, cache = step(params, toks[-1], cache, prompt_len + i)
            rows.append(lp)
        toks.append(jnp.argmax(rows[-1]).astype(jnp.int32) + 1)
        return np.asarray(jnp.stack(rows), np.float32), \
            np.asarray(jnp.stack(toks))

    def gap(params, logp, toks):
        """(median, maximum) over the positions of the largest |served -
        reference| log-prob over the std of the reference's logits, the
        reference fed the served tokens."""
        total = -(-(prompt_len + steps + 1) // 512) * 512
        seq = np.ones(total, np.int32)
        seq[:prompt_len] = prompt
        seq[prompt_len:prompt_len + steps] = toks[:steps]
        rows = np.arange(prompt_len - 1, prompt_len + steps)
        logits = np.asarray(reference.logits_at(
            params, seq, rows, heads=kwargs["num_heads"], **ref_kw),
            np.float32)
        want = np.asarray(jax.nn.log_softmax(logits, axis=-1))
        per = np.abs(logp - want).max(axis=-1) / logits.std(axis=-1)
        return float(np.median(per)), float(per.max())

    def inside(reading, limit):
        return reading[0] <= limit[0] and reading[1] <= limit[1]

    # 1. the served path, and the control that keeps its state in bf16
    logp, toks = served(params)
    check(np.isfinite(logp).all(), "non-finite served log-probs")
    tight = gap(params, logp, toks)
    check(inside(tight, tolerance), f"served log-probs (median, max) "
          f"{tight} std from the reference's (tolerance {tolerance})")
    failed = {}
    for control_name, patched in controls:
        with patched(model):
            failed[control_name] = gap(params, *served(params))
        check(not inside(failed[control_name], tolerance),
              f"the {control_name.replace('_', '-')} control "
              f"passed the tolerance {tolerance}: {failed[control_name]}")

    # 2. the router against the reference's, one hidden state
    layer = next(b["ffn"] for b in params["blocks"]
                 if "router" in b.get("ffn", {}))
    x = jax.random.normal(jax.random.PRNGKey(28),
                          (prompt_len, model.embed_dim)).astype(dtype)
    published = {**reference.PUBLISHED, **ref_kw}

    def routed(route):
        ids, gates = jax.jit(route)(layer, x)
        with jax.default_matmul_precision("highest"):
            scores = jax.nn.sigmoid(
                x.astype(jnp.float32)
                @ layer["router"].astype(jnp.float32).T)
            want_ids, want_gates = reference.route(
                scores, layer["bias"].astype(jnp.float32), published)
        order, want_order = np.argsort(ids, -1), np.argsort(want_ids, -1)
        same = np.take_along_axis(np.asarray(ids), order, -1) \
            == np.take_along_axis(np.asarray(want_ids), want_order, -1)
        off = np.abs(np.take_along_axis(np.asarray(gates), order, -1)
                     - np.take_along_axis(np.asarray(want_gates),
                                          want_order, -1))
        rows = same.all(axis=-1)
        # (no row left to compare gates on: every token chose otherwise)
        return float(rows.mean()), \
            float(off[rows].max()) if rows.any() else float("inf")

    agree, gates_off = routed(model._route)
    check(agree == 1.0 and gates_off <= router_tolerance,
          f"router: {agree:.4f} of the tokens choose the reference's "
          f"experts, gates off by {gates_off:.2e} (tolerance "
          f"{router_tolerance})")
    route = hybrid.sigmoid_group_route
    with mock.patch.object(
            hybrid, "sigmoid_group_route",
            lambda scores, *a, **kw: route(_bf16(scores), *a, **kw)):
        agree_bf16, gates_bf16 = routed(model._route)
    check(agree_bf16 < 1.0 or gates_bf16 > router_tolerance,
          f"the bf16-router control passed: {agree_bf16} {gates_bf16:.2e}")

    # 3. the same request through the generator's own loop
    gen = ContinuousGenerator(model, params, {}, num_slots=slots,
                              max_len=max_len, seq_buckets=list(buckets),
                              cache_dtype=jnp.dtype(dtype))
    try:
        out = np.asarray(gen.submit(prompt, steps + 1).result(timeout=900))
    finally:
        gen.drain(timeout=60)
    st = gen.stats()
    check(st["counters"].get("serve.gen.failed", 0) == 0
          and out.shape == (steps + 1,), f"generator: {st['counters']}")
    check(_paged_kernel_compiled() == compiled,
          f"paged kernel compiled={_paged_kernel_compiled()}")
    fmt = "({:.4f}, {:.4f})".format
    return (f"prompt={prompt_len} steps={steps} gap_median_max="
            f"{fmt(*tight)} (tolerance {tolerance}) "
            + "".join(f"{k}={fmt(*v)} " for k, v in failed.items())
            + f"router_agree={agree:.4f} gates_off={gates_off:.2e} "
            f"(tolerance {router_tolerance}) bf16_router=({agree_bf16:.4f}, "
            f"{gates_bf16:.2e}) state_bytes_per_slot="
            f"{st['state']['bytes_per_slot']}")


# The window phase's tolerance, read as the hybrid phase's: (median,
# maximum) over 301 positions of max |served - reference| log-prob over
# the std of the reference's logits.  Two layers at the published widths
# of ``k_exaone_236b`` (a window layer and a full one, each with its 16
# held experts), bf16 around a float32 router.  Readings on a v5e (PR 33):
# served (0.0205, 0.1801), the control in which every window layer keeps
# and reads 129 keys (0.0625, 0.4032); the tolerance lies between (the
# geometric means), and the control has to FAIL it.
WINDOW_TOLERANCE = (0.036, 0.27)


def phase_window(config="benchmark/configs/k_exaone_236b.json", vocab=None,
                 overrides=None, reference_kw=None, prompt_len=200, steps=300,
                 slots=8, max_len=1024, buckets=(256,), compiled=True,
                 tolerance=WINDOW_TOLERANCE, dtype="bfloat16") -> str:
    """``phase_hybrid``'s drive on a two-layer ``swa`` + ``full`` pattern
    at the published widths: the prompt wraps the ring once, the decode
    steps twice more, so every ring row has been overwritten in place
    before the last position is compared."""
    two = {"num_layers": 2, "layers": [["swa", "experts"],
                                       ["full", "experts"]]}
    return phase_hybrid(
        config, vocab, dict(two, **(overrides or {})),
        dict({"layer_types": ("sliding_attention", "full_attention")},
             **(reference_kw or {})),
        prompt_len, steps, slots, max_len, buckets, compiled, tolerance,
        dtype=dtype, controls=(("window_off_by_one", _window_off_by_one),))


# The ssm phase's tolerance, read as the hybrid phase's: (median, maximum)
# over 2,049 positions of max |served - reference| log-prob over the std of
# the reference's logits.  Three blocks at the published widths of
# ``nemotron3_super_120b`` (a Mamba-2 block, a latent-expert block with its
# 128 held experts, the attention block), bf16 around a float32 state and a
# float32 router.  Readings on a v5e (PR 35): served (0.0199, 0.1171), the
# control with the gate after the norm (1.2202, 1.8514); the tolerance is
# three times the served reading, and that control has to FAIL it.  The
# state rounded to bf16 after every update reads (0.0201, 0.1066) there, as
# the served path does: at the row's own decay ranges a head remembers about
# a dozen tokens (1 / (dt A), geometric mean), so 2,048 roundings of 2^-9
# never add up past the bf16 activations' own.  What holds the state to
# float32 on the chip is the check on the recurrence itself, below.
SSM_TOLERANCE = (0.06, 0.35)
# The recurrence alone, at the published head sizes with SLOW heads (A = -1,
# dt in [0.001, 0.01]: memories of 100 to 1,000 tokens): the state after a
# chunked prefill and one-token steps against the float32 definition token
# by token, as the largest difference over the largest entry.  Readings on
# a v5e (PR 35): 1.13e-4 (the chunked form's products at the highest
# precision against element-wise float32), the state rounded to bf16 after
# every update 4.18e-2; the tolerance is their geometric mean, and the
# control has to FAIL it, as a matmul that rounds its operands to bf16 (a
# TPU's default float32 product) would.
SSM_STATE_TOLERANCE = 2e-3


def _ssm_recurrence(heads=128, head_dim=64, state=128, groups=8, chunk=128,
                    prefill=2048, steps=256, tolerance=SSM_STATE_TOLERANCE):
    """(sound, bf16-state control): ``ssd_chunked`` then ``ssd_step``s
    against ``ssd_naive`` over the same tokens, relative error of the final
    state and of the outputs (the larger)."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops import ssd
    k = jax.random.split(jax.random.PRNGKey(35), 5)
    t = prefill + steps
    x = jax.random.normal(k[0], (1, t, heads, head_dim))
    dt = jnp.exp(jax.random.uniform(k[1], (1, t, heads), minval=math.log(1e-3),
                                    maxval=math.log(1e-2)))
    b, c = (jax.random.normal(kk, (1, t, groups, state)) for kk in k[2:4])
    a, d = -jnp.ones((heads,)), jnp.ones((heads,))
    zero = jnp.zeros((1, heads, head_dim, state))
    want_y, want_s = jax.jit(ssd.ssd_naive)(x, dt, a, b, c, d, zero)

    def served(keep):
        @jax.jit
        def run(x, dt, b, c):
            y0, s = ssd.ssd_chunked(x[:, :prefill], dt[:, :prefill], a,
                                    b[:, :prefill], c[:, :prefill], d, zero,
                                    chunk)

            def one(s, v):
                y, s = ssd.ssd_step(v[0], v[1], a, v[2], v[3], d, s)
                return keep(s), y

            s, y1 = jax.lax.scan(one, keep(s), tuple(
                jnp.moveaxis(v[:, prefill:], 1, 0) for v in (x, dt, b, c)))
            return jnp.concatenate([y0, jnp.moveaxis(y1, 0, 1)], 1), s

        y, s = run(x, dt, b, c)
        return max(_rel_err(s, want_s), _rel_err(y, want_y))

    sound, control = served(lambda s: s), served(_bf16)
    check(sound <= tolerance, f"the recurrence on this backend is {sound:.2e} "
          f"off its definition (tolerance {tolerance})")
    check(control > tolerance, f"the bf16-state control of the recurrence "
          f"passed the tolerance {tolerance}: {control:.2e}")
    return sound, control


def phase_ssm(config="benchmark/configs/nemotron3_super_120b.json",
              vocab=None, overrides=None, reference_kw=None, prompt_len=300,
              steps=2048, slots=8, max_len=4096, buckets=(512,),
              compiled=True, tolerance=SSM_TOLERANCE, dtype="bfloat16",
              recurrence=None) -> str:
    """The recurrence alone against its definition (``_ssm_recurrence``;
    ``recurrence`` its sizes), then ``phase_hybrid``'s drive on a ``mamba2``
    + ``latent_experts`` + ``full`` pattern of one-part blocks at the
    published widths: the state is prefilled by the chunked scan (two chunks
    and a part of a third), then updated in place 2,048 times."""
    sound, control = _ssm_recurrence(**(recurrence or {}))
    three = {"num_layers": 3, "layers": [["mamba2", None],
                                         [None, "latent_experts"],
                                         ["full", None]]}
    return phase_hybrid(
        config, vocab, dict(three, **(overrides or {})), reference_kw,
        prompt_len, steps, slots, max_len, buckets, compiled, tolerance,
        dtype=dtype, controls=(("gate_after_norm", _gate_after_norm),)) \
        + f" recurrence_off={sound:.2e} bf16_state={control:.2e} " \
        f"(tolerance {SSM_STATE_TOLERANCE})"


# -- phase 4: kernels ---------------------------------------------------------

def _f32_highest(fn, *args):
    """``fn`` on f32 copies at HIGHEST matmul precision — the reference
    a bf16 kernel is judged against (the TPU's default f32 matmul is a
    bf16 pass itself)."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        return fn(*[a.astype(jnp.float32)
                    if jnp.issubdtype(a.dtype, jnp.floating) else a
                    for a in args])


def _attention_rows(t_fused, t_stream, heads, head_dim):
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops import attention as A

    scale = 1.0 / math.sqrt(head_dim)
    rows = []
    for name, t, kernel in (
            ("attention.fused", t_fused,
             lambda q, k, v: A._fused_attention(q, k, v, True, scale)),
            ("attention.stream", t_stream,
             lambda q, k, v: A._streaming_attention(q, k, v, None, True,
                                                    scale))):
        ks = jax.random.split(jax.random.PRNGKey(t), 4)
        q, k, v, do = [jax.random.normal(kk, (1, heads, t, head_dim),
                                         jnp.bfloat16) for kk in ks]

        def run(fn, q, k, v, do):
            out, vjp = jax.vjp(fn, q, k, v)
            return (out,) + vjp(do.astype(out.dtype))

        got = jax.jit(partial(run, kernel))(q, k, v, do)
        want = _f32_highest(jax.jit(partial(
            run, lambda q, k, v: A.attention_reference(q, k, v, True, scale))),
            q, k, v, do)
        rows.append((f"{name}.fwd T={t}", _rel_err(got[0], want[0]),
                     TOL_BF16))
        rows.append((f"{name}.bwd T={t}",
                     max(_rel_err(g, w) for g, w in zip(got[1:], want[1:])),
                     TOL_BF16_GRAD))
    return rows


def _paged_rows(slots, heads, wide_heads, head_dim, max_len, page_size,
                prefill):
    """``paged_attention`` against the gather path of
    ``apply_decode_pages``, bf16 cache on the token-major pool, tables
    part trash: a decode step (S=1, every slot) and a prefill bucket
    (one row) at the serve model's heads; and at ``wide_heads`` (GPT-2
    XL's 25: an odd count, the pool's width padded to whole lane tiles)
    a decode step whose slots hold one to three pages, so that nearly
    all of the walk is skipped, and one whose slots are full to the last
    position, so that none is.  Beside each row's error, what the step
    costs: the layer as the generator's programs hold it (the cache
    donated, written and read), its device time by group from a trace of
    ``OBSERVED`` runs and the program's ``temp_size_in_bytes`` — a
    relayout of the pool would show as copies of about the pool's time
    and as temporaries of its size."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.core.precision import cast_tree
    from bigdl_tpu.nn.attention import MultiHeadAttention
    from bigdl_tpu.ops import attention as ops_attention

    lp = max_len // page_size
    rs = np.random.RandomState(2)
    few = min(3 * page_size, max_len) - 1
    layers = {}
    for h in (heads, wide_heads):
        attn = MultiHeadAttention(h * head_dim, h)
        layers[h] = attn, jax.jit(lambda k: cast_tree(
            attn.init_params(k), jnp.bfloat16))(jax.random.PRNGKey(0))
    rows = []
    # row i holds pos[i] cached tokens before its s queries
    for name, b, s, h, lo, hi in (
            ("decode", slots, 1, heads, page_size, max_len - 1),
            ("prefill", 1, prefill, heads, page_size, max_len - prefill),
            ("decode.short", slots, 1, wide_heads, 0, few),
            ("decode.full", slots, 1, wide_heads, max_len - 1, max_len)):
        attn, params = layers[h]
        num_pages = b * lp
        width = ops_attention.paged_pool_width(h, head_dim)

        def pool(key):
            # K (or V) of every head side by side, the padding lanes zero
            return jnp.pad(jax.random.normal(
                key, (num_pages + 1, page_size, h * head_dim),
                jnp.bfloat16), ((0, 0), (0, 0), (0, width - h * head_dim)))

        ck, cv = [pool(k) for k in jax.random.split(jax.random.PRNGKey(s), 2)]
        pos = rs.randint(lo, hi, b).astype(np.int32)
        # the rest of a row's table is trash
        pages = np.full((b, lp), num_pages, np.int32)
        perm = rs.permutation(num_pages)
        for i in range(b):
            used = -(-(int(pos[i]) + s) // page_size)
            pages[i, :used] = perm[i * lp:i * lp + used]
        x = jax.random.normal(jax.random.PRNGKey(7),
                              (b, s, h * head_dim), jnp.bfloat16)

        def layer(p, x, k, v):
            return attn.apply_decode_pages(
                p, x, {"k": k, "v": v}, jnp.asarray(pages),
                jnp.asarray(pos), jnp.ones((b,), bool))

        def run():
            return jax.jit(lambda *a: layer(*a)[0])(params, x, ck, cv)

        check(ops_attention.paged_attention_enabled(),
              "paged-attention gate is off")
        kernel = run()
        # the gate is read at trace time; closing it in this process
        # (not in the environment: setenv races every thread that reads
        # it) sends the same call down the gather path
        with mock.patch.object(ops_attention, "paged_attention_enabled",
                               return_value=False):
            gather = run()
        label = f"paged_attention.{name} B={b} H={h} S={s} L={max_len}"
        rows.append((label, _rel_err(kernel, gather), TOL_BF16))
        print(f"    {label}: {_paged_step_cost(layer, params, x, ck, cv)}",
              flush=True)
    return rows


OBSERVED = 5        # runs of a paged step inside its traced slice


def _paged_step_cost(layer, params, x, ck, cv) -> str:
    """One line on what a paged step costs beside its kernel: see
    ``_paged_rows``."""
    import jax

    from benchmark import trace_capture, trace_reduce

    step = jax.jit(layer, donate_argnums=(2, 3))
    compiled = step.lower(params, x, ck, cv).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    pool_mb = 2 * ck.size * ck.dtype.itemsize / 1e6
    ck, cv = ck + 0, cv + 0             # the caller keeps its own
    _, cache = step(params, x, ck, cv)
    sl = trace_capture.Slice(os.path.join(
        os.environ.get("TMPDIR", "/tmp"), f"chip-smoke-paged-{os.getpid()}"))
    sl.start()
    try:
        for _ in range(OBSERVED):
            y, cache = step(params, x, cache["k"], cache["v"])
        jax.block_until_ready(y)
    finally:
        sl.stop()
    by_group = {}
    for dev in ((sl.events() or {}).get("devices") or {}).values():
        for op_name, category, _start, dur in dev["ops"]:
            if not op_name.lstrip("%").startswith(trace_reduce.CONTAINERS):
                g = trace_reduce.op_group(op_name, category)
                by_group[g] = by_group.get(g, 0.0) + dur / 1e6 / OBSERVED
        break
    timed = "device time not traced on this backend"
    if by_group:
        timed = (f"kernel {by_group.get('Pallas custom call', 0.0):.3f} ms, "
                 f"copies and layout "
                 f"{by_group.get('copies and layout', 0.0):.3f} ms of "
                 f"{sum(by_group.values()):.3f} ms a step")
    return f"{timed}; temp {temp / 1e6:.2f} MB beside pools of {pool_mb:.1f} MB"


def _quant_rows(matmuls, conv):
    """Each row is one jitted program: pack, fused kernel, reference."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops import quant

    def w8(x, w):
        qt = quant.pack(w)
        return (quant.int8_matmul(x, qt),
                quant.int8_matmul_reference(x, qt["q8"], qt["scale"]))

    def w8a8(x, w):
        sx = jnp.max(jnp.abs(x.astype(jnp.float32))) / 127.0
        qt = quant.pack(w, sx=sx)
        return (quant.int8_matmul(x, qt),
                quant.int8_matmul_reference(x, qt["q8"], qt["scale"], sx))

    def w4(x, w):
        qt = quant.pack(w, mode="w4")
        return (quant.int8_matmul(x, qt),
                quant.int4_matmul_reference(x, qt["q4"], qt["scale"],
                                            quant.packed_k(qt)))

    rows = []
    for m, k, n in matmuls:
        kx, kw = jax.random.split(jax.random.PRNGKey(m + n))
        x = jax.random.normal(kx, (m, k), jnp.bfloat16)
        w = jax.random.normal(kw, (n, k), jnp.float32) / math.sqrt(k)
        for rung in (w8, w8a8, w4):
            rows.append((f"quant.{rung.__name__} {m}x{k}x{n}",
                         _rel_err(*jax.jit(rung)(x, w)), TOL_BF16))

    nb, c, hw, o, kk = conv
    check(quant.int8_conv_enabled(), "fused int8 conv gate is off")
    pad = kk // 2

    def int8_conv(x, w):
        qt = quant.pack(w)
        with jax.default_matmul_precision("highest"):
            want = jax.lax.conv_general_dilated(
                x.astype(jnp.float32), quant.unpack(qt, jnp.float32),
                (1, 1), ((pad, pad), (pad, pad)),
                dimension_numbers=("NCHW", "OIHW", "NCHW"))
        return quant.int8_conv2d(x, qt, padding=(pad, pad)), want

    kx, kw = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.normal(kx, (nb, c, hw, hw), jnp.bfloat16)
    w = jax.random.normal(kw, (o, c, kk, kk)) / math.sqrt(c * kk * kk)
    rows.append((f"quant.int8_conv {nb}x{c}x{hw}x{hw} -> {o} k{kk}",
                 _rel_err(*jax.jit(int8_conv)(x, w)), TOL_BF16))
    return rows


def _fp16_rows(n):
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops import fp16

    def codec(a, b):
        ca, cb = fp16.fp16_compress(a), fp16.fp16_compress(b)
        ra, rb = (fp16.fp16_compress_reference(a),
                  fp16.fp16_compress_reference(b))
        return (jnp.array_equal(ca, ra)
                & jnp.array_equal(fp16.fp16_decompress(ca),
                                  fp16.fp16_decompress_reference(ra))
                & jnp.array_equal(
                    fp16.fp16_add(ca, cb),
                    fp16.fp16_compress_reference(
                        fp16.fp16_decompress_reference(ra)
                        + fp16.fp16_decompress_reference(rb))))

    a, b = jax.random.normal(jax.random.PRNGKey(5), (2, n), jnp.float32)
    # the codec is bit-twiddling: anything but bit-equal is wrong
    return [(f"fp16.codec n={n}",
             0.0 if bool(jax.jit(codec)(a, b)) else 1.0, 0.0)]


def phase_kernels(t_fused=1024, t_stream=4096, heads=8, wide_heads=25,
                  head_dim=64, slots=8, max_len=1024, page_size=16,
                  prefill=128,
                  matmuls=((8, 512, 2048), (128, 2048, 512),
                           (8, 512, 32000)),
                  conv=(8, 192, 28, 128, 3), fp16_n=7_000_000,
                  compiled=True) -> str:
    """Defaults are the shapes phases 2-3 and the quantised rungs run:
    attention at the serve model's head dim over its cache length (fused)
    and past the fused kernel's VMEM cut (streaming); the paged kernel
    for one decode step of every slot and one prefill bucket, and at
    GPT-2 XL's 25 heads for nearly empty and for full slots; the packed
    matmuls of a decode step (ffn up, logits) and a prefill bucket (ffn
    down) at (M, K, N); an Inception 3x3 conv as (N, C, HW, O, k)."""
    from bigdl_tpu.ops import attention

    check(attention._use_pallas(), "Pallas dispatch is off on this backend")
    check(attention._interpret() != compiled,
          f"kernels compiled={not attention._interpret()}, "
          f"expected {compiled}")
    rows = (_attention_rows(t_fused, t_stream, heads, head_dim)
            + _paged_rows(slots, heads, wide_heads, head_dim, max_len,
                          page_size, prefill)
            + _quant_rows(matmuls, conv)
            + _fp16_rows(fp16_n))
    for name, err, tol in rows:
        print(f"    {name:<48} err={err:.2e} tol={tol:.0e} "
              f"{'ok' if err <= tol else 'FAIL'}", flush=True)
    bad = [name for name, err, tol in rows if not err <= tol]
    check(not bad, f"outside tolerance: {bad}")
    return (f"kernels={len(rows)} "
            f"{'compiled' if compiled else 'interpreted'} worst="
            f"{max(rows, key=lambda r: r[1] / (r[2] or 1.0))[0]!r}")


# -- phase 5: multichip -------------------------------------------------------

def phase_multichip(model_fn=None, input_shape=(3, 224, 224), classes=1000,
                    per_chip_batch=256, steps=3, min_devices=4) -> str:
    import jax
    import jax.numpy as jnp
    import numpy as np

    n = len(jax.devices())
    if n < min_devices:
        return f"skipped ({n} device)"

    import bigdl_tpu.nn as nn
    from bigdl_tpu.core.precision import mixed_forward
    from bigdl_tpu.dataset.dataset import DataSet
    from bigdl_tpu.engine import Engine
    from bigdl_tpu.observability.summary import TrainSummary
    from bigdl_tpu.optim import DistriOptimizer, SGD, Trigger
    from bigdl_tpu.parallel.comm_audit import audit_distri_step

    mesh = Engine.init()
    n = mesh.devices.size
    # dropout off: replicas fold their own RNG stream, so only a
    # deterministic forward is comparable with the one-chip step
    # (``__graft_entry__._dryrun_flagship`` does the same)
    model = model_fn() if model_fn else _inception(classes, dropout=0.0)
    model.build(seed=0)
    params0 = jax.tree_util.tree_map(np.asarray, model.params)
    state0 = model.state
    global_batch = per_chip_batch * n
    (batch,) = _synthetic_batches(1, global_batch, input_shape, classes)
    criterion = nn.ClassNLLCriterion()
    optim = SGD(learning_rate=0.01)
    summary = TrainSummary("", "chip_smoke", tensorboard=False)
    stop = Trigger.max_iteration(steps)
    seen = {}

    def end_when(state):
        # after the first step the run's own arrays are live: look at
        # where they actually are, not at what was asked for
        if state["neval"] == 1 and not seen:
            seen.update(_live_shardings(
                mesh, (n, opt._layout.shard_size),
                (global_batch,) + tuple(input_shape)))
        return stop(state)

    opt = (DistriOptimizer(model, criterion, DataSet.array([batch]),
                           end_when)
           .set_optim_method(optim)
           .set_mixed_precision(True)
           .set_train_summary(summary))
    opt.optimize()

    losses = [v for _, v, _ in summary.read_scalar("Loss")]
    check(len(losses) == steps and all(math.isfinite(v) for v in losses),
          f"want {steps} finite losses, got {losses}")
    for what in ("wshard", "batch"):
        check(seen.get(what) == n,
              f"{what} spans {seen.get(what)} devices, want {n}")
    in_use = seen["bytes_in_use"]
    check(in_use is None or all(b > 0 for b in in_use),
          f"a device holds nothing: bytes_in_use={in_use}")

    audit = audit_distri_step(
        model, criterion, optim, mesh, opt.config,
        (global_batch,) + tuple(input_shape), compress=opt.compress,
        compute_dtype=jnp.bfloat16)
    checks = audit["checks"]
    for key in ("compute_and_comm_in_one_program",
                "both_param_phases_present", "groups_span_data_axis"):
        check(checks[key], f"HLO audit: {key} is false ({checks})")
    wire = audit["phase_wire_bytes"]
    check(wire.get("get_weights", 0) > 0
          and wire.get("aggregate_gradient", 0) > 0,
          f"HLO audit: a parameter phase moved no bytes: {wire}")
    ops = sorted({c["base_op"] for c in audit["collectives"]
                  if c["phase"] in ("get_weights", "aggregate_gradient")})

    # the one-chip step on the same global batch, in per-chip-batch
    # pieces (the whole batch does not fit one chip's HBM): the n-way
    # loss is the pmean of exactly these shard means
    @jax.jit
    def shard_loss(p, s, x, y):
        out, _ = mixed_forward(model, p, s, x, training=True,
                               rng=jax.random.PRNGKey(0))
        return criterion.apply(out, y)

    one_chip = float(np.mean([
        float(shard_loss(params0, state0,
                         batch.data[i:i + per_chip_batch],
                         batch.labels[i:i + per_chip_batch]))
        for i in range(0, global_batch, per_chip_batch)]))
    check(abs(losses[0] - one_chip) < TOL_LOSS,
          f"first-step loss {losses[0]} vs one-chip {one_chip}")
    probes, _, _ = opt.metrics.snapshot()
    probe_ms = {k: round(probes[k][0] / 1e6, 3)
                for k in ("get weights average", "aggregate gradient time")
                if k in probes}
    return (f"devices={n} global_batch={global_batch} losses="
            f"{[round(v, 4) for v in losses]} one_chip_loss={one_chip:.4f} "
            f"|d|={abs(losses[0] - one_chip):.1e} wshard/batch over {n} "
            f"devices bytes_in_use={in_use} collectives={ops} "
            f"wire_economy={checks['wire_economy_ratio']} "
            f"probe_ms={probe_ms}")


def _live_shardings(mesh, wshard_shape, batch_shape) -> dict:
    """Device-set sizes of the live weight shard and batch arrays, and
    every mesh device's bytes in use (None where the backend reports no
    memory stats)."""
    import jax
    out = {}
    for a in jax.live_arrays():
        for what, shape in (("wshard", wshard_shape), ("batch", batch_shape)):
            if a.shape != shape:
                continue
            ndev = len(a.sharding.device_set)
            # split over its devices on dim 0, not replicated on them
            if all(s.data.shape[0] * ndev == shape[0]
                   for s in a.addressable_shards):
                out[what] = max(out.get(what, 0), ndev)
    stats = [d.memory_stats() for d in mesh.devices.flat]
    out["bytes_in_use"] = (None if any(s is None for s in stats)
                           else [int(s["bytes_in_use"]) for s in stats])
    return out


# -- runner -------------------------------------------------------------------

def main() -> int:
    import jax

    from bigdl_tpu.utils.compile_cache import enable_compile_cache
    from bigdl_tpu.utils.log import init_logging

    cache_dir = enable_compile_cache()
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: JAX found no TPU (backend "
              f"{jax.default_backend()!r}); this script never runs on "
              f"another backend", file=sys.stderr)
        return 1
    init_logging()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"chip_smoke: jax {jax.__version__} platform={device['platform']} "
          f"device_kind={device['kind']} devices={device['count']}",
          flush=True)

    meter = CompileMeter().install()
    failed = []
    t_start = time.time()
    for name, phase in (("device", partial(phase_device, cache_dir)),
                        ("train", phase_train),
                        ("serve", phase_serve),
                        ("hybrid", phase_hybrid),
                        ("window", phase_window),
                        ("ssm", phase_ssm),
                        ("kernels", phase_kernels),
                        ("multichip", phase_multichip)):
        t0, (c0, h0, m0) = time.time(), meter.snapshot()
        try:
            line = "ok " + phase()
        except Exception:               # report, fail the run, go on:
            traceback.print_exc()       # one chip call shows every phase
            failed.append(name)
            line = "FAIL (traceback above)"
        c1, h1, m1 = meter.snapshot()
        print(f"[{name}] {line} [wall {time.time() - t0:.1f}s compile "
              f"{c1 - c0:.1f}s cache hits {h1 - h0} misses {m1 - m0}]",
              flush=True)
    c, h, m = meter.snapshot()
    print(f"chip_smoke: wall {time.time() - t_start:.1f}s compile {c:.1f}s "
          f"cache hits {h} misses {m}", flush=True)
    if failed:
        print(f"chip_smoke: FAILED phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
