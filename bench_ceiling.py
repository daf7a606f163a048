"""Per-model ceiling audit — writes ``BENCH_ceiling_r5.json``.

Inception-v1 got a "where every millisecond goes" table, a floor
estimate and two structural wins in r3 (docs/performance.md); VERDICT
r4 weak #5 asks for the same evidence for the other two conv flagships.
This harness produces it mechanically for ANY zoo model:

* jax-profiler trace of N steps of the LITERAL bench train step
  (``bench_zoo.build_train_step`` — the program every throughput
  headline runs), parsed from the perfetto export;
* per-op DEVICE durations aggregated by HLO category + source op
  (``device_duration_ps`` comes from the chip, so host load cannot
  distort the table);
* a roofline floor per bucket: MXU-bound buckets priced at
  flops/peak-bf16, everything else at bytes/HBM-bandwidth; the summed
  floor is the model's practical step floor, and floor/actual says how
  much headroom is real.

Usage: ``python bench_ceiling.py [--models resnet50 vgg16]``
(``--batch 0``, the default, traces each model at its zoo-bench batch;
a nonzero value overrides all models.)
"""

from __future__ import annotations

import argparse
import collections
import glob
import gzip
import json
import os
import tempfile
import time

V5E_PEAK_BF16 = 197e12          # flop/s
V5E_HBM_BPS = 819e9             # bytes/s


def build(name):
    """(model, zoo-bench batch) from bench_zoo's shared registry — the
    audit must trace the exact configuration the headlines run."""
    from bench_zoo import zoo_configs

    cfg = zoo_configs()
    if name not in cfg:
        raise ValueError(f"{name}: not in bench_zoo.zoo_configs() "
                         f"({sorted(cfg)})")
    builder, batch = cfg[name]
    return builder(), batch


def trace_steps(model, batch, steps=4, logdir=None):
    """Run + trace ``steps`` iterations of the bench train step; returns
    the perfetto trace path."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench_zoo import build_train_step

    train_step, params, opt_state, state = build_train_step(model,
                                                            mixed=True)
    rng = jax.random.PRNGKey(1)
    x = jnp.asarray(np.random.RandomState(0)
                    .rand(batch, 3, 224, 224).astype(np.float32))
    y = jnp.asarray((np.arange(batch) % 1000 + 1).astype(np.float32))
    params, opt_state, state, loss = train_step(
        params, opt_state, state, x, y, rng, jnp.asarray(0, jnp.int32))
    float(loss)                                   # compile + sync

    logdir = logdir or tempfile.mkdtemp(prefix="ceiling_")
    jax.profiler.start_trace(logdir)
    for i in range(steps):
        params, opt_state, state, loss = train_step(
            params, opt_state, state, x, y, rng,
            jnp.asarray(i + 1, jnp.int32))
    float(loss)                                   # drain before stop
    jax.profiler.stop_trace()
    traces = glob.glob(os.path.join(logdir, "**", "*.trace.json.gz"),
                       recursive=True)
    assert traces, f"no perfetto trace under {logdir}"
    return max(traces, key=os.path.getmtime), steps


def _bucket(ev_args, name):
    """Human bucket for one device op event (category + source op)."""
    cat = ev_args.get("hlo_category", name)
    op = ev_args.get("tf_op", "")
    if "convolution" in cat:
        # fwd, dgrad and wgrad all share one MXU bucket (XLA also
        # categorises large dots as "convolution fusion", so VGG's FC
        # matmuls land here too — by design: it is the MXU bucket)
        return "conv (fwd+dgrad+wgrad)"
    if "select-and-scatter" in name or "select-and-scatter" in cat:
        return "max-pool backward"
    if "reduce-window" in cat or "reduce_window" in op:
        return "pool forward"
    if "dot_general" in op or cat == "dot":
        return "fc matmul"
    if "rsqrt" in op or "batch_norm" in op or "bn" in op:
        return "batchnorm"
    if "reduce_sum" in op or cat == "reduction":
        return "reductions (bias grads &c)"
    if cat.startswith("copy") or cat in ("data formatting",):
        return "copies / layout"
    return "other elementwise / misc"


def parse_trace(path, steps):
    """Aggregate device 'XLA Ops' events -> per-step bucket table with
    flops / bytes for the roofline floor."""
    d = json.load(gzip.open(path))
    evs = d.get("traceEvents", [])
    dev_pids = {e["pid"] for e in evs
                if e.get("ph") == "M" and e.get("name") == "process_name"
                and "TPU" in str(e.get("args", {}).get("name", ""))}
    op_tids = {(e["pid"], e["tid"]) for e in evs
               if e.get("ph") == "M" and e.get("name") == "thread_name"
               and e.get("args", {}).get("name") == "XLA Ops"
               and e["pid"] in dev_pids}
    buckets = collections.defaultdict(
        lambda: {"ms": 0.0, "flops": 0, "bytes": 0, "ops": 0})
    total_ms = 0.0
    for e in evs:
        if e.get("ph") != "X" or (e.get("pid"), e.get("tid")) not in op_tids:
            continue
        a = e.get("args", {})
        ms = float(a.get("device_duration_ps", 0)) / 1e9
        b = buckets[_bucket(a, e.get("name", ""))]
        b["ms"] += ms
        b["flops"] += int(a.get("model_flops", 0) or 0)
        b["bytes"] += int(a.get("raw_bytes_accessed", 0) or 0)
        b["ops"] += 1
        total_ms += ms
    rows = []
    for name, b in sorted(buckets.items(), key=lambda kv: -kv[1]["ms"]):
        ms = b["ms"] / steps
        flops = b["flops"] / steps
        byts = b["bytes"] / steps
        mxu_floor = flops / V5E_PEAK_BF16 * 1e3
        hbm_floor = byts / V5E_HBM_BPS * 1e3
        # a bucket's floor is whichever resource it genuinely needs
        # more — CAPPED at the measured time: XLA's bytes_accessed is a
        # logical upper bound (it counts operand re-reads that fusion
        # serves from VMEM), so an uncapped bytes floor can exceed
        # reality; a bucket running FASTER than the priced floor is the
        # counter overcounting, not negative headroom
        floor = min(max(mxu_floor, hbm_floor), ms)
        rows.append({
            "bucket": name, "ms_per_step": round(ms, 2),
            "pct": None,                      # filled below
            "gflops_per_step": round(flops / 1e9, 1),
            "gbytes_per_step": round(byts / 1e9, 2),
            "mfu_pct": round(flops / (ms / 1e3) / V5E_PEAK_BF16 * 100, 1)
            if ms > 0 else None,
            "roofline_floor_ms": round(floor, 2),
            "ops_per_step": b["ops"] // steps,
        })
    if total_ms <= 0:
        raise RuntimeError(
            "trace contains no TPU 'XLA Ops' device events — no TPU "
            "attached, or a toolchain bump changed the profiler's "
            "process/thread naming")
    step_ms = total_ms / steps
    for r in rows:
        r["pct"] = round(100 * r["ms_per_step"] / step_ms, 1)
    return {"device_ms_per_step": round(step_ms, 2),
            "roofline_floor_ms": round(sum(r["roofline_floor_ms"]
                                           for r in rows), 2),
            "rows": rows}


def audit(name, batch, steps=4):
    model, default_batch = build(name)
    batch = batch or default_batch
    t0 = time.time()
    path, n = trace_steps(model, batch, steps=steps)
    out = parse_trace(path, n)
    out["model"] = name
    out["batch"] = batch
    out["images_per_sec_at_device_ms"] = round(
        batch / (out["device_ms_per_step"] / 1e3), 1)
    out["pct_of_roofline"] = round(
        100 * out["roofline_floor_ms"] / out["device_ms_per_step"], 1)
    out["trace_seconds"] = round(time.time() - t0, 1)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--models", nargs="*",
                    default=["resnet50", "vgg16", "inception_v1",
                             "inception_v2", "alexnet_owt"])
    ap.add_argument("--batch", type=int, default=0,
                    help="0 = each model's zoo-bench batch")
    ap.add_argument("--out", default="BENCH_ceiling_r5.json")
    args = ap.parse_args(argv)

    out = {"metric": "per_model_ceiling_audit",
           "note": "device_duration_ps from the chip's own counters — "
                   "host load cannot distort per-op rows.  "
                   "Roofline floor: max(flops/197T, bytes/819G) per "
                   "bucket; pct_of_roofline = floor/actual (100% = no "
                   "headroom left at this batch/layout).",
           "models": []}
    for name in args.models:
        print(f"== tracing {name} ...", flush=True)
        a = audit(name, args.batch)
        print(json.dumps({k: a[k] for k in
                          ("model", "device_ms_per_step",
                           "images_per_sec_at_device_ms",
                           "roofline_floor_ms", "pct_of_roofline")}))
        for r in a["rows"][:8]:
            print(f"   {r['ms_per_step']:8.2f} ms {r['pct']:5.1f}%  "
                  f"{r['bucket']}  (floor {r['roofline_floor_ms']} ms, "
                  f"mfu {r['mfu_pct']}%)")
        out["models"].append(a)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
