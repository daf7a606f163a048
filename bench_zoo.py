"""Model-zoo training-throughput benchmark — writes ``BENCH_zoo_r5.json``.

Breadth companion to ``bench.py`` (which tracks the Inception-v1 north
star): single-chip bf16 mixed-precision training throughput for the
other zoo flagships, via the same fused train step the trainers compile.
Run: ``python bench_zoo.py`` (on the real chip).

``--audit`` re-measures the top two negative-results claims from
docs/performance.md (NHWC layout, Pallas LRN) so they cannot silently go
stale across toolchain bumps: cite those table rows only while the audit
says they still hold.
"""

from __future__ import annotations

import json
import time


def build_train_step(model, mixed=True, lr=0.05):
    """The benchmark train step: jitted fwd+bwd+SGD with the bf16-mixed
    policy (``core/precision.mixed_forward``) the headline numbers run.
    Returns ``(train_step, params, opt_state, state)`` — shared by
    ``bench.py``, this zoo bench and ``bench_e2e.py`` so all throughput
    artifacts compile the identical program."""
    import jax
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.utils.table import T

    params, state = model.init(jax.random.PRNGKey(0))
    criterion = nn.ClassNLLCriterion()
    optim = SGD(learning_rate=lr)
    opt_state = optim.init_state(params)
    cfg = T()

    @jax.jit
    def train_step(p, o, s, x, y, rng, stepno):
        def loss_fn(pp):
            if mixed:
                from bigdl_tpu.core.precision import mixed_forward
                out, new_s = mixed_forward(model, pp, s, x,
                                           training=True, rng=rng)
            else:
                out, new_s = model.apply(pp, s, x, training=True, rng=rng)
            return criterion.apply(out, y), new_s
        (loss, new_s), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(p)
        c = cfg.clone()
        c["clr"] = jnp.asarray(-lr, jnp.float32)
        new_p, new_o = optim.update(grads, p, o, c, stepno)
        return new_p, new_o, new_s, loss

    return train_step, params, opt_state, state


def measure_train_throughput(model, batch, classes=1000, image=224,
                             iters=15, windows=2, mixed=True,
                             lr=0.05, return_details=False):
    """Best-of-``windows`` training throughput (images/sec) of ``model``
    through the fused train step the trainers compile.

    THE shared benchmark harness — ``bench.py`` (north star) and this
    zoo benchmark both call it, so the two non-obvious invariants live
    in one place: the SGD ``clr`` config carries the NEGATIVE learning
    rate, and every timed window ends in a host read of the loss
    (``float(loss)``), which waits for the device.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    train_step, params, opt_state, state = build_train_step(
        model, mixed=mixed, lr=lr)

    rng = jax.random.PRNGKey(1)
    x = jnp.asarray(np.random.RandomState(0).rand(
        batch, 3, image, image).astype(np.float32))
    y = jnp.asarray((np.arange(batch) % classes + 1).astype(np.float32))
    params, opt_state, state, loss = train_step(
        params, opt_state, state, x, y, rng, jnp.asarray(0, jnp.int32))
    float(loss)                                   # compile + sync

    window_ips = []
    stepno = 0
    for _ in range(windows):
        t0 = time.time()
        for _ in range(iters):
            stepno += 1
            params, opt_state, state, loss = train_step(
                params, opt_state, state, x, y, rng,
                jnp.asarray(stepno, jnp.int32))
        float(loss)
        window_ips.append(batch * iters / (time.time() - t0))
    ips = max(window_ips)
    if return_details:
        # program identity anchor: hash of the LOWERED program (jax
        # level, no second backend compile) + toolchain versions — if
        # these match a prior round's, any throughput delta is chip/
        # environment drift, not code (the repo's interleaved-or-
        # HLO-anchored doctrine, commit ec2d28a, applied to the
        # number of record)
        import hashlib
        lowered = train_step.lower(params, opt_state, state, x, y, rng,
                                   jnp.asarray(0, jnp.int32))
        fp = hashlib.sha256(
            lowered.as_text().encode()).hexdigest()[:16]
        return ips, {"window_ips": [round(w, 1) for w in window_ips],
                     "stablehlo_sha256_16": fp}
    return ips


def zoo_configs():
    """name -> (builder, zoo-bench batch): THE registry both this
    benchmark and ``bench_ceiling.py`` consume, so the ceiling audit
    always traces the exact configuration the throughput headlines
    run (builders lazy — importing models initialises jax)."""
    from bigdl_tpu.models.alexnet import AlexNet_OWT
    from bigdl_tpu.models.inception import Inception_v1, Inception_v2
    from bigdl_tpu.models.resnet import ResNet
    from bigdl_tpu.models.vgg import Vgg_16

    return {
        "alexnet_owt": (lambda: AlexNet_OWT(1000), 1024),
        "vgg16": (lambda: Vgg_16(1000), 256),
        "resnet50": (lambda: ResNet(1000, depth=50,
                                    dataset="imagenet"), 256),
        "inception_v2": (lambda: Inception_v2(1000), 256),
        # bench.py's north-star config (not in the zoo sweep itself)
        "inception_v1": (lambda: Inception_v1(1000), 256),
    }


def measure(name, model, batch, classes=1000, image=224, iters=15):
    ips = measure_train_throughput(model, batch, classes, image, iters)
    entry = {"model": name, "batch": batch,
             "images_per_sec_per_chip": round(ips, 1)}
    print(json.dumps(entry))
    return entry


def main():
    cfg = zoo_configs()
    results = [
        measure(name, cfg[name][0](), cfg[name][1])
        for name in ("alexnet_owt", "vgg16", "resnet50", "inception_v2")
    ]
    with open("BENCH_zoo_r5.json", "w") as f:
        json.dump({
            "metric": "zoo_train_images_per_sec_per_chip",
            "dtype": "bf16 mixed (f32 master weights)",
            "note": "single v5e chip, synthetic ImageNet-shaped data, "
                    "full fused train step (fwd+bwd+SGD), best of two "
                    "15-iter windows",
            "results": results,
        }, f, indent=1)


def audit_main():
    """Re-measure the negative-results table's two biggest claims."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import time as _time

    def timed(fn, *args, iters=20):
        @jax.jit
        def step(*a):
            return jax.value_and_grad(
                lambda x: jnp.sum(fn(x, *a[1:]).astype(jnp.float32)))(a[0])
        l, g = step(*args)
        float(l)                      # compile + sync
        t0 = _time.time()
        for _ in range(iters):
            l, g = step(*args)
        float(l)
        return (_time.time() - t0) / iters * 1e3

    rs = np.random.RandomState(0)
    report = {}

    # -- claim 1: Pallas LRN loses to XLA's reduce_window at training scale
    from bigdl_tpu.ops.lrn import _lrn_pallas, _lrn_xla
    x = jnp.asarray(rs.randn(256, 192, 56, 56), jnp.bfloat16)
    xla_ms = timed(lambda t: _lrn_xla(t, 5, 1e-4, 0.75, 1.0), x)
    pal_ms = timed(lambda t: _lrn_pallas(t, 5, 1e-4, 0.75, 1.0), x)
    report["lrn_pallas_vs_xla"] = {
        "xla_fwd_bwd_ms": round(xla_ms, 2),
        "pallas_fwd_bwd_ms": round(pal_ms, 2),
        "claim_holds": bool(pal_ms > xla_ms),
    }

    # -- claim 2: NHWC conv layout buys <~5% on the Inception-ish block
    from jax import lax

    w_oihw = jnp.asarray(rs.randn(192, 192, 3, 3) * 0.05, jnp.bfloat16)

    def conv_nchw(t):
        return lax.conv_general_dilated(
            t, w_oihw, (1, 1), ((1, 1), (1, 1)),
            dimension_numbers=("NCHW", "OIHW", "NCHW"))

    w_hwio = jnp.transpose(w_oihw, (2, 3, 1, 0))

    def conv_nhwc(t):
        return lax.conv_general_dilated(
            t, w_hwio, (1, 1), ((1, 1), (1, 1)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    x_nchw = jnp.asarray(rs.randn(256, 192, 56, 56), jnp.bfloat16)
    x_nhwc = jnp.transpose(x_nchw, (0, 2, 3, 1))
    nchw_ms = timed(conv_nchw, x_nchw)
    nhwc_ms = timed(conv_nhwc, x_nhwc)
    gain = nchw_ms / nhwc_ms - 1.0
    report["nhwc_layout"] = {
        "nchw_fwd_bwd_ms": round(nchw_ms, 2),
        "nhwc_fwd_bwd_ms": round(nhwc_ms, 2),
        "nhwc_gain_pct": round(gain * 100, 1),
        # the r2 measurement found +3.6% best-case on the full model;
        # flag for re-evaluation if a toolchain bump makes NHWC >10%
        # better at even this single-conv proxy
        "claim_holds": bool(gain < 0.10),
    }

    # -- claim 3 (r5): NHWC is at PARITY (not a win) on the ResNet
    # bottleneck block (1x1·64 -> 3x3·64 -> 1x1·256 + residual at
    # 56x56 — the shapes where the r5 ceiling audit located
    # ResNet-50's low-MFU convs), so the NCHW Torch-parity layout
    # stays.  Interleaved A/B per the repo's drift doctrine.
    def block(fmt):
        if fmt == "NCHW":
            dn = ("NCHW", "OIHW", "NCHW")
            ws = [jnp.asarray(rs.randn(64, 256, 1, 1) * 0.05, jnp.bfloat16),
                  jnp.asarray(rs.randn(64, 64, 3, 3) * 0.05, jnp.bfloat16),
                  jnp.asarray(rs.randn(256, 64, 1, 1) * 0.05, jnp.bfloat16)]
            xb = jnp.asarray(rs.randn(256, 256, 56, 56), jnp.bfloat16)
        else:
            dn = ("NHWC", "HWIO", "NHWC")
            ws = [jnp.asarray(rs.randn(1, 1, 256, 64) * 0.05, jnp.bfloat16),
                  jnp.asarray(rs.randn(3, 3, 64, 64) * 0.05, jnp.bfloat16),
                  jnp.asarray(rs.randn(1, 1, 64, 256) * 0.05, jnp.bfloat16)]
            xb = jnp.asarray(rs.randn(256, 56, 56, 256), jnp.bfloat16)

        def fwd(x, w1, w2, w3):
            h = jax.nn.relu(lax.conv_general_dilated(
                x, w1, (1, 1), "SAME", dimension_numbers=dn))
            h = jax.nn.relu(lax.conv_general_dilated(
                h, w2, (1, 1), "SAME", dimension_numbers=dn))
            h = lax.conv_general_dilated(h, w3, (1, 1), "SAME",
                                         dimension_numbers=dn)
            return jax.nn.relu(h + x)
        return fwd, (xb,) + tuple(ws)

    # grads w.r.t. the WEIGHTS (fwd + dgrad + wgrad through the block);
    # INTERLEAVED bursts so host/chip drift hits both layouts equally —
    # the sequential-burst form of this very measurement once read
    # 0.69x on a loaded host (discarded; docs/performance.md)
    steps = {}
    for fmt in ("NCHW", "NHWC"):
        fn, a = block(fmt)

        @jax.jit
        def step(x, w1, w2, w3, fn=fn):
            return jax.value_and_grad(
                lambda w: jnp.sum(fn(x, *w).astype(jnp.float32)))(
                (w1, w2, w3))
        l, _ = step(*a)
        float(l)                      # compile + sync
        steps[fmt] = (step, a)
    best = {fmt: float("inf") for fmt in steps}
    for _ in range(12):
        for fmt, (step, a) in steps.items():
            t0 = _time.time()
            for _ in range(5):
                l, _ = step(*a)
            float(l)
            best[fmt] = min(best[fmt], (_time.time() - t0) / 5 * 1e3)
    ratio = best["NCHW"] / best["NHWC"]
    report["nhwc_bottleneck"] = {
        "nchw_fwd_bwd_ms": round(best["NCHW"], 2),
        "nhwc_fwd_bwd_ms": round(best["NHWC"], 2),
        "nhwc_speedup": round(ratio, 3),
        "protocol": "interleaved best-of-12 x 5-step bursts",
        # r5 measured PARITY (~1.0x; docs/performance.md ResNet-50
        # section).  Two-sided guard: flag if a toolchain bump makes
        # NHWC a >10% win (layout decision needs revisiting) OR a >10%
        # loss (the parity row in the docs is stale)
        "claim_holds": bool(abs(ratio - 1.0) < 0.10),
    }

    for k, v in report.items():
        status = "still holds" if v["claim_holds"] else \
            "RE-EVALUATE docs/performance.md negative-results row"
        print(f"{k}: {v} -> {status}")
    with open("BENCH_audit_r5.json", "w") as f:
        json.dump(report, f, indent=1)
    return report


if __name__ == "__main__":
    import sys
    if "--audit" in sys.argv:
        audit_main()
    else:
        main()
