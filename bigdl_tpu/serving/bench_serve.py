"""Serving-scheduler benchmark — writes ``BENCH_serve_r11.json``.

Mixed-length generation traffic with a SHARED-SYSTEM-PROMPT head (the
consumer mix: ``--prefix-frac`` of requests open with the same
``--prefix-len`` token head), served the same ways r8 measured —
static waves, a bucketed ladder, and continuous batching — plus the
r11 ablation ladder over the continuous scheduler
(``python -m bigdl_tpu.cli bench-serve`` / ``bigdl-tpu-bench-serve``):

* **static** — the fixed-shape baseline: waves of ``--batch`` requests
  in arrival order, ONE compiled ``generate`` executable that decodes
  the GLOBAL maximum ``max_new`` for every wave.
* **bucketed** — waves grouped by a ``max_new`` bucket ladder, one
  pre-compiled executable per rung.
* **continuous** —
  :class:`~bigdl_tpu.serving.scheduler.continuous.ContinuousGenerator`
  with ``prefix_cache=False``: block-paged KV, admit per chunk, evict
  on finish, every prompt prefilled whole.  This is the baseline the
  features below must beat.
* **ablations** — the same traffic with each win toggled on in turn:
  ``paged_prefix`` (+ content-hash prefix cache — the shared head is
  prefilled once), ``paged_prefix_spec`` (+ speculative decoding
  against a truncated int8 draft).  Every ablation's outputs are
  asserted EQUAL to the baseline's — the bench never reports a
  tokens/s number for wrong tokens — and the prefix-hit and
  draft-accept rates land in the artifact.

Useful tokens = sum of *requested* ``max_new`` over all requests; a
mode's tokens/s divides that by ITS wall, so decode steps spent past a
request's budget count against the mode that spent them.  Compiles are
excluded from every timing (warmup pass per executable).  ``--smoke``
is the fast-tier CI mode; the full run on the serving hardware commits
the artifact.
"""

from __future__ import annotations

import argparse
import json
import time
from typing import List, Optional


def _traffic(rng, n: int, prompt_len: int, prefix_len: int,
             prefix_frac: float, vocab: int,
             short: tuple, long: tuple, long_frac: float):
    """Seeded consumer traffic: fixed-length prompts, a fraction
    opening with the SAME shared head (the system-prompt mix where
    re-prefilling the head dominates), bimodal token budgets."""
    import numpy as np
    head = rng.randint(1, vocab + 1, size=prefix_len).astype(np.int32)
    prompts = []
    for _ in range(n):
        p = rng.randint(1, vocab + 1, size=prompt_len).astype(np.int32)
        if rng.rand() < prefix_frac:
            p[:prefix_len] = head
        prompts.append(p)
    budgets = [int(rng.randint(long[0], long[1] + 1))
               if rng.rand() < long_frac
               else int(rng.randint(short[0], short[1] + 1))
               for _ in range(n)]
    return list(zip(prompts, budgets))


def _mode_result(name: str, useful: int, wall: float,
                 lats: List[float], **extra) -> dict:
    # the same nearest-rank helper the run-report renders with, so the
    # artifact's percentiles can never disagree with a report's
    from bigdl_tpu.observability.report import _percentile
    s = sorted(lats)
    return dict(mode=name, useful_tokens=useful, wall_s=wall,
                tokens_per_s=useful / wall if wall > 0 else 0.0,
                latency_p50_s=_percentile(s, 50),
                latency_p95_s=_percentile(s, 95), **extra)


def _run_waves(model, params, state, requests, batch: int,
               bucket_of, compiled) -> tuple:
    """Shared wave runner for static/bucketed: group arrivals into
    full waves per decode bucket, run each wave through that bucket's
    pre-compiled generate, count only requested tokens as useful."""
    import numpy as np

    waves = {}                           # bucket -> list of requests
    order = []                           # (bucket, wave) in formation order
    for prompt, max_new in requests:
        b = bucket_of(max_new)
        waves.setdefault(b, []).append((prompt, max_new))
        if len(waves[b]) == batch:
            order.append((b, waves.pop(b)))
    for b, wave in sorted(waves.items()):
        order.append((b, wave))          # partial tails, padded to batch

    useful = 0
    lats: List[float] = []
    pad_eff: List[float] = []
    t0 = time.monotonic()
    for b, wave in order:
        prompts = [p for p, _ in wave]
        while len(prompts) < batch:      # pad the wave with row 0
            prompts.append(prompts[0])
        x = np.stack(prompts)
        np.asarray(compiled[b](params, state, x))
        t_done = time.monotonic() - t0
        for _, max_new in wave:
            useful += max_new
            lats.append(t_done)          # all submitted at t=0
        pad_eff.append(sum(n for _, n in wave) / (batch * b))
    wall = time.monotonic() - t0
    return useful, wall, lats, pad_eff


def _run_continuous(gen, requests, useful_total: int, name: str,
                    live_url: Optional[List] = None) -> tuple:
    """Drive one ContinuousGenerator over the whole mix; returns
    (mode result extras, outputs in submission order)."""
    t0 = time.monotonic()
    lats: List[float] = []

    def stamp(_f):
        # completion time at RESOLUTION, not at the submission-order
        # result() walk — a short request finishing behind a long one
        # must not inherit the long one's latency
        lats.append(time.monotonic() - t0)

    futs = []
    for p, n in requests:
        f = gen.submit(p, n)
        f.add_done_callback(stamp)
        futs.append(f)
    live_ok = None
    if live_url is not None:
        # scrape mid-traffic: requests are submitted but not resolved
        from bigdl_tpu.observability.live import scrape
        live_ok = "bigdl_tpu_" in (scrape(live_url[0]) or "")
    outs = [f.result() for f in futs]
    wall = time.monotonic() - t0
    st = gen.stats()
    extra = dict(mean_slot_occupancy=st["mean_occupancy"],
                 decode_chunks=st["chunks"],
                 mean_token_occupancy=st["pages"]["mean_token_occupancy"])
    if st.get("prefix"):
        extra["prefix_hit_rate"] = st["prefix"]["hit_rate"]
        extra["prefix_shared_tokens"] = \
            st["prefix"]["hit_pages"] * st["pages"]["page_size"]
    if st.get("spec"):
        extra["draft_accept_rate"] = st["spec"]["accept_rate"]
    res = _mode_result(name, useful_total, wall, lats, **extra)
    return res, outs, live_ok


def _truncated_draft(model, params, state, layers: int):
    """A draft LM = the target's first ``layers`` blocks + its
    embeddings and final norm — the cheap resident proposer the
    speculative ablation verifies against."""
    from bigdl_tpu.models.transformer import TransformerLM

    dm = TransformerLM(model.vocab_size, max_len=model.max_len,
                       embed_dim=model.embed_dim,
                       num_heads=model.blocks[0].attn.num_heads,
                       num_layers=layers)
    dparams = {"tok": params["tok"], "pos": params["pos"],
               "blocks": params["blocks"][:layers],
               "ln_f": params["ln_f"]}
    dstate = {"blocks": state["blocks"][:layers],
              "ln_f": state["ln_f"]}
    return dm, dparams, dstate


def main(argv: Optional[List[str]] = None) -> int:
    if argv is None:
        import sys
        argv = sys.argv[1:]
    argv = list(argv)
    if "--fleet" in argv:
        # the r15 multi-tenant fleet round: two-tenant autoscaling vs
        # static peak + noisy-neighbor isolation -> BENCH_fleet_r15.json
        # (its own arg set: --smoke/--out/--delay-ms/... — see
        # serving/fleet/bench_fleet.py)
        argv.remove("--fleet")
        from bigdl_tpu.serving.fleet.bench_fleet import main as fleet_main
        return fleet_main(argv)
    if "--cluster" in argv:
        # the r16 cross-host round: N-host fleet through a SIGKILL vs
        # the single-process fleet -> BENCH_fleet_r16.json (its own
        # arg set — see serving/fleet/bench_cluster.py)
        argv.remove("--cluster")
        from bigdl_tpu.serving.fleet.bench_cluster import \
            main as cluster_main
        return cluster_main(argv)
    ap = argparse.ArgumentParser(
        "bench-serve",
        description="static vs bucketed vs continuous-batching generate, "
                    "with +prefix / +speculative ablations "
                    "(docs/serving.md); writes BENCH_serve_r11.json")
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--batch", type=int, default=8,
                    help="wave size for static/bucketed AND the "
                         "continuous scheduler's slot count")
    ap.add_argument("--prompt-len", type=int, default=96)
    ap.add_argument("--prefix-len", type=int, default=80,
                    help="length of the shared system-prompt head")
    ap.add_argument("--prefix-frac", type=float, default=0.75,
                    help="fraction of requests opening with the shared "
                         "head")
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--spec-k", type=int, default=3,
                    help="draft proposals per speculative chunk")
    ap.add_argument("--draft-layers", type=int, default=0,
                    help="layers in the truncated draft (0 = half of "
                         "--layers, min 1)")
    ap.add_argument("--short-range", default="8,24",
                    help="lo,hi token budget of the short mode")
    ap.add_argument("--long-range", default="32,48",
                    help="lo,hi token budget of the long tail")
    ap.add_argument("--long-frac", type=float, default=0.25,
                    help="fraction of long requests in the mix")
    ap.add_argument("--new-buckets", default="24,48",
                    help="max_new bucket ladder for the bucketed mode")
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--embed", type=int, default=128)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--steps-per-sync", type=int, default=6)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="fast-tier CI mode: tiny model, few requests")
    ap.add_argument("--out", default="BENCH_serve_r11.json")
    args = ap.parse_args(argv)

    if args.smoke:
        args.requests, args.batch = 12, 4
        args.prompt_len, args.vocab = 12, 64
        args.prefix_len, args.page_size = 8, 4
        args.embed, args.heads, args.layers = 32, 2, 2
        args.short_range, args.long_range = "4,8", "16,24"
        args.new_buckets = "8,24"
        args.steps_per_sync = 4
        args.spec_k = 3

    import jax
    import numpy as np

    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.serving.scheduler.buckets import BucketLadder
    from bigdl_tpu.serving.scheduler.continuous import ContinuousGenerator

    short = tuple(int(v) for v in args.short_range.split(","))
    long = tuple(int(v) for v in args.long_range.split(","))
    new_ladder = BucketLadder([int(v) for v in
                               args.new_buckets.split(",")],
                              name="max_new")
    if new_ladder.max < long[1]:
        raise ValueError(f"largest max_new bucket {new_ladder.max} < "
                         f"long-range hi {long[1]}")
    if not 0 < args.prefix_len < args.prompt_len:
        raise ValueError(f"--prefix-len must be in (0, {args.prompt_len})")
    max_len = args.prompt_len + new_ladder.max
    model = TransformerLM(args.vocab + 1, max_len=max_len,
                          embed_dim=args.embed, num_heads=args.heads,
                          num_layers=args.layers)
    params, state = model.init(jax.random.PRNGKey(args.seed))
    rng = np.random.RandomState(args.seed)
    requests = _traffic(rng, args.requests, args.prompt_len,
                        args.prefix_len, args.prefix_frac, args.vocab,
                        short, long, args.long_frac)
    useful_total = sum(n for _, n in requests)
    print(f"bench-serve: {args.requests} requests, prompt "
          f"{args.prompt_len} ({args.prefix_frac:.0%} share a "
          f"{args.prefix_len}-token head), max_new "
          f"{short[0]}..{short[1]} (+{args.long_frac:.0%} long "
          f"{long[0]}..{long[1]}; {useful_total} useful tokens), "
          f"batch/slots {args.batch}")

    # pre-compile one generate executable per decode bucket (the static
    # mode only ever uses the top rung); warmup excluded from timing
    compiled = {}
    for b in new_ladder:
        def gen(params, state, prompt, _b=b):
            return model.generate(params, state, prompt, max_new=_b,
                                  temperature=0.0)
        compiled[b] = jax.jit(gen)
        warm = np.ones((args.batch, args.prompt_len), np.int32)
        np.asarray(compiled[b](params, state, warm))

    # -- static: every wave decodes the global max ------------------------
    useful, wall, lats, eff = _run_waves(
        model, params, state, requests, args.batch,
        bucket_of=lambda n: new_ladder.max, compiled=compiled)
    static = _mode_result("static", useful, wall, lats,
                          mean_padding_efficiency=sum(eff) / len(eff))
    print(f"  static:       {static['tokens_per_s']:9.1f} tok/s  "
          f"p95 {static['latency_p95_s'] * 1e3:7.1f} ms  "
          f"padding eff {static['mean_padding_efficiency'] * 100:.0f}%")

    # -- bucketed: every wave decodes its rung ----------------------------
    useful, wall, lats, eff = _run_waves(
        model, params, state, requests, args.batch,
        bucket_of=new_ladder.pick, compiled=compiled)
    bucketed = _mode_result("bucketed", useful, wall, lats,
                            mean_padding_efficiency=sum(eff) / len(eff))
    print(f"  bucketed:     {bucketed['tokens_per_s']:9.1f} tok/s  "
          f"p95 {bucketed['latency_p95_s'] * 1e3:7.1f} ms  "
          f"padding eff {bucketed['mean_padding_efficiency'] * 100:.0f}%")

    # continuous rungs: the full prompt AND the post-prefix suffix, so
    # a prefix hit prefills the short rung instead of the whole prompt
    aligned = (args.prefix_len // args.page_size) * args.page_size
    seq_buckets = sorted({args.prompt_len,
                          max(args.prompt_len - aligned, 1)})
    draft_layers = args.draft_layers or max(1, args.layers // 2)
    dm, dparams, dstate = _truncated_draft(model, params, state,
                                           draft_layers)

    variants = [
        ("continuous", dict(prefix_cache=False), True),
        ("paged_prefix", dict(prefix_cache=True), False),
        ("paged_prefix_spec", dict(prefix_cache=True, draft_model=dm,
                                   draft_params=dparams,
                                   draft_state=dstate,
                                   draft_quantize="w8",
                                   spec_k=args.spec_k), False),
    ]
    results = {}
    ref_outs = None
    live_ok = False
    from bigdl_tpu.observability.live import LiveMetricsServer
    from bigdl_tpu.observability.prometheus import metrics_to_prometheus
    for name, kw, scrape_live in variants:
        gen = ContinuousGenerator(
            model, params, state, num_slots=args.batch, max_len=max_len,
            seq_buckets=seq_buckets, temperature=0.0,
            page_size=args.page_size,
            steps_per_sync=args.steps_per_sync, warmup=True,
            queue_capacity=max(args.requests, 256), **kw)
        # live /metrics over the generator's counters — the bench
        # asserts the endpoint answers valid Prometheus text while
        # traffic is actually decoding (fast-tier live-telemetry check)
        live = (LiveMetricsServer(
            lambda g=gen: metrics_to_prometheus(g.metrics))
            if scrape_live else None)
        try:
            res, outs, ok = _run_continuous(
                gen, requests, useful_total, name,
                live_url=[live.url] if live else None)
            gen.drain(timeout=60)
        finally:
            if live is not None:
                live.close()     # a failed phase must not leak the socket
        if ok is not None:
            live_ok = ok
            print(f"  live /metrics mid-traffic: "
                  f"{'OK' if ok else 'FAILED'}")
        # correctness gate: every variant must produce the baseline
        # run's exact tokens — no tokens/s number for wrong tokens
        if ref_outs is None:
            ref_outs = outs
        else:
            for i, (a, b) in enumerate(zip(ref_outs, outs)):
                if not np.array_equal(a, b):
                    raise AssertionError(
                        f"{name}: request {i} output diverged from the "
                        "continuous baseline")
        results[name] = res
        rates = "".join(
            f"  {k.replace('_', ' ')} {res[k] * 100:.0f}%"
            for k in ("prefix_hit_rate", "draft_accept_rate")
            if k in res)
        print(f"  {name + ':':<13} {res['tokens_per_s']:9.1f} tok/s  "
              f"p95 {res['latency_p95_s'] * 1e3:7.1f} ms{rates}")

    continuous = results.pop("continuous")
    best_name = max(results, key=lambda k: results[k]["tokens_per_s"])
    base = continuous["tokens_per_s"]
    ratio = results[best_name]["tokens_per_s"] / base if base > 0 else 0.0
    out = {
        "bench": "serve_r11",
        "meta": {
            "requests": args.requests, "batch": args.batch,
            "prompt_len": args.prompt_len,
            "prefix_len": args.prefix_len,
            "prefix_frac": args.prefix_frac,
            "page_size": args.page_size,
            "steps_per_sync": args.steps_per_sync,
            "spec_k": args.spec_k, "draft_layers": draft_layers,
            "short_range": list(short), "long_range": list(long),
            "long_frac": args.long_frac,
            "new_buckets": list(new_ladder),
            "seq_buckets": seq_buckets,
            "model": {"vocab": args.vocab, "embed": args.embed,
                      "heads": args.heads, "layers": args.layers,
                      "max_len": max_len},
            "platform": jax.devices()[0].platform,
            "smoke": bool(args.smoke), "seed": args.seed,
        },
        "modes": {"static": static, "bucketed": bucketed,
                  "continuous": continuous},
        "ablations": results,
        "acceptance": {
            "best_ablation": best_name,
            "best_vs_continuous_tokens_per_s": ratio,
            "per_feature_vs_continuous": {
                k: (v["tokens_per_s"] / base if base > 0 else 0.0)
                for k, v in results.items()},
            "prefix_hit_rate":
                results["paged_prefix"].get("prefix_hit_rate", 0.0),
            "draft_accept_rate":
                results["paged_prefix_spec"].get("draft_accept_rate",
                                                 0.0),
            "outputs_bit_equal_across_variants": True,
            "holds": ratio > 1.0,
            "live_endpoint_mid_traffic": live_ok,
        },
    }
    with open(args.out, "w", encoding="utf-8") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    print(f"  best ablation ({best_name}) vs continuous: "
          f"{ratio:.2f}x tokens/s "
          f"({'OK' if ratio > 1.0 else 'BELOW 1.0'}) -> {args.out}")
    return 0 if live_ok else 1


if __name__ == "__main__":
    import sys
    sys.exit(main())
