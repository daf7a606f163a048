"""HBM-pressure survival drill — ``python -m bigdl_tpu.cli mem-drill``.

The r20 headline proof, in two phases (exit 0 iff BOTH hold):

**Phase A — token flood past the device page pool.**  A paged
:class:`~.continuous.ContinuousGenerator` with a deliberately tiny
page pool (tokens are genuinely scarce) and a
:class:`~.membudget.MemoryBudgeter` opens far more multi-turn sessions
than the device can hold.  The degradation ladder must absorb the
flood: idle sessions PARK to the host-RAM offload tier instead of
anything OOMing, the open-session token census must reach at least
**3x the device page pool**, and a second turn on EVERY session —
parked ones resume transparently — must be bit-equal to the
single-shot ``TransformerLM.generate`` reference over the same full
history (a resumed session is indistinguishable from one that never
parked).  A request whose worst-case KV bytes exceed the tenant budget
sheds TYPED (``MemoryBudgetError``, attributed to the tenant in the
budgeter census) while every neighbor's in-flight turn lands intact.
After closing every session the budgeter's ``kv_pages`` and
``host_offload`` charges must return to exactly zero — the accounting
is replayed, not estimated.

**Phase B — victim SLO under a greedy flood.**  The same traffic mix
— small "victim" requests interleaved with pool-sized "flood" requests
— runs twice: once budgeted (floods shed typed at submit) and once
unbudgeted (floods are admitted and hog the pool).  The victims'
completion rate under the budget must be no worse than the unbudgeted
baseline, and their mean latency is reported alongside (the budget
exists to protect neighbors, not to slow them).

Results land in ``BENCH_mem_r20.json``.  ``--smoke`` is the fast CI
preset wired into ``make-dist.sh``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from typing import Dict, List, Optional

VOCAB = 64


def _expect(ok: bool, what: str, failures: List[str]) -> bool:
    print(f"  [{'ok' if ok else 'FAIL'}] {what}")
    if not ok:
        failures.append(what)
    return ok


def _lm(max_len=64):
    import jax

    from bigdl_tpu.models.transformer import TransformerLM
    m = TransformerLM(vocab_size=VOCAB, max_len=max_len, embed_dim=32,
                      num_heads=2, num_layers=2)
    params, state = m.init(jax.random.PRNGKey(0))
    return m, params, state


def _prompts(n, lo, hi, seed=0):
    import numpy as np
    rs = np.random.RandomState(seed)
    return [rs.randint(1, VOCAB + 1,
                       size=int(rs.randint(lo, hi))).astype(np.int32)
            for _ in range(n)]


def _ref(m, params, state, prompt, max_new):
    import numpy as np
    return np.asarray(m.generate(params, state, prompt[None],
                                 max_new=max_new, temperature=0.0))[0]


# -- phase A: flood the pool, park, resume, stay bit-equal --------------------

def _phase_a(args, failures: List[str]) -> dict:
    import numpy as np

    from bigdl_tpu.serving.errors import MemoryBudgetError
    from bigdl_tpu.serving.scheduler.continuous import ContinuousGenerator
    from bigdl_tpu.serving.scheduler.membudget import MemoryBudgeter

    m, params, state = _lm(max_len=64)
    budgeter = MemoryBudgeter()
    print(f"phase A: {args.sessions} sessions vs a "
          f"{args.num_pages}-page pool (page_size={args.page_size})")
    with ContinuousGenerator(
            m, params, state, num_slots=2, seq_buckets=[16],
            steps_per_sync=2, page_size=args.page_size,
            num_pages=args.num_pages, budgeter=budgeter,
            budget_tenant="a", ledger_tags={"tenant": "a"}) as g:
        pb = g.stats()["pages"]["page_bytes"]
        pool_pages = args.num_pages
        pool_tokens = pool_pages * args.page_size
        # one page short of the pool: a pool-sized request can NEVER
        # fit the budget and must shed typed at submit
        budgeter.set_budget("a", (pool_pages - 1) * pb)

        # every session opens with the same system prompt (the shared-
        # prefix serving shape): its published pages are pinned ONCE
        # and shared by all, so pinning cannot exhaust the pool the
        # way N unique pinned chains would
        sys_prompt = np.arange(1, 2 * args.page_size + 1,
                               dtype=np.int32)
        futs = [g.submit(sys_prompt, args.max_new, session=f"s{i}")
                for i in range(args.sessions)]
        # the flood lands while turns are in flight: typed, attributed,
        # and harmless to every neighbor
        flood = _prompts(1, 10, 11, seed=2)[0]
        flood_new = pool_tokens - flood.size   # total == the whole pool
        shed_typed = False
        try:
            g.submit(flood, flood_new)
        except MemoryBudgetError as e:
            shed_typed = e.reason == "byte_starved"
        _expect(shed_typed, "pool-sized request shed typed "
                "(MemoryBudgetError, reason=byte_starved)", failures)
        out1 = [f.result(timeout=180.0) for f in futs]

        st = g.stats()
        resident = int(st["sessions"]["total_tokens"])
        _expect(int(st["sessions"]["open"]) == args.sessions,
                f"every session survived the flood "
                f"({st['sessions']['open']}/{args.sessions} open)",
                failures)
        _expect(resident >= 3 * pool_tokens,
                f"resident-token capacity {resident} >= 3x the "
                f"device page pool ({pool_tokens} tokens)", failures)
        parks = int(st["offload"]["parks"])
        _expect(parks >= 1 and int(st["sessions"]["parked"]) >= 1,
                f"pressure parked idle sessions to host RAM "
                f"({parks} park(s), {st['sessions']['parked']} parked "
                f"now)", failures)

        # second turn on EVERY session: parked ones resume (H2D +
        # re-attach) and must be bit-equal to never-parked history
        turn2 = _prompts(args.sessions, 3, 6, seed=3)
        futs2 = [g.submit(p, args.max_new2, session=f"s{i}")
                 for i, p in enumerate(turn2)]
        out2 = [f.result(timeout=180.0) for f in futs2]
        mismatches = 0
        r1 = _ref(m, params, state, sys_prompt, args.max_new)
        for i in range(args.sessions):
            full2 = np.concatenate([sys_prompt, out1[i], turn2[i]])
            r2 = _ref(m, params, state, full2, args.max_new2)
            if not (np.array_equal(r1, out1[i])
                    and np.array_equal(r2, out2[i])):
                mismatches += 1
        resumes = int(g.stats()["offload"]["resumes"])
        _expect(resumes >= 1, f"parked sessions resumed transparently "
                f"({resumes} resume(s))", failures)
        _expect(mismatches == 0,
                f"both turns bit-equal to the never-parked reference "
                f"across {args.sessions} sessions", failures)

        for i in range(args.sessions):
            g.close_session(f"s{i}").result(timeout=30.0)
        g.drain(timeout=60.0)
        snap = budgeter.snapshot()["tenants"]["a"]
        _expect(snap["charged"]["kv_pages"] == 0
                and snap["charged"]["host_offload"] == 0,
                f"budget accounting exact after close-all "
                f"(kv={snap['charged']['kv_pages']}, "
                f"host={snap['charged']['host_offload']})", failures)
        sheds = int(snap["sheds"])
        _expect(sheds >= 1, f"shed attributed to the tenant in the "
                f"budgeter census ({sheds})", failures)
        return {"sessions": args.sessions,
                "pool_tokens": pool_tokens,
                "resident_tokens": resident,
                "capacity_ratio": resident / max(1, pool_tokens),
                "parks": parks, "resumes": resumes,
                "bit_mismatches": mismatches,
                "typed_sheds": sheds,
                "kv_pages_after_close": snap["charged"]["kv_pages"],
                "host_offload_after_close":
                    snap["charged"]["host_offload"]}


# -- phase B: victim SLO, budgeted vs unbudgeted ------------------------------

def _victim_run(args, budgeted: bool) -> dict:
    import numpy as np

    from bigdl_tpu.serving.errors import MemoryBudgetError
    from bigdl_tpu.serving.scheduler.continuous import ContinuousGenerator
    from bigdl_tpu.serving.scheduler.membudget import MemoryBudgeter

    m, params, state = _lm(max_len=64)
    budgeter = MemoryBudgeter() if budgeted else None
    with ContinuousGenerator(
            m, params, state, num_slots=2, seq_buckets=[16],
            steps_per_sync=2, page_size=args.page_size,
            num_pages=args.num_pages, budgeter=budgeter,
            budget_tenant="noisy",
            ledger_tags={"tenant": "noisy"}) as g:
        pb = g.stats()["pages"]["page_bytes"]
        pool_tokens = args.num_pages * args.page_size
        if budgeter is not None:
            budgeter.set_budget("noisy", (args.num_pages - 1) * pb)
        victims = _prompts(args.victims, 5, 8, seed=4)
        floods = _prompts(args.floods, 10, 11, seed=5)
        vfuts, t0s, sheds, untyped = [], [], 0, 0
        for i, v in enumerate(victims):
            if i % 3 == 0 and i // 3 < len(floods):
                f = floods[i // 3]
                try:
                    g.submit(f, pool_tokens - f.size)
                except MemoryBudgetError:
                    sheds += 1
                except Exception:
                    untyped += 1
            t0s.append(time.monotonic())
            vfuts.append(g.submit(v, args.max_new))
        lats, ok = [], 0
        for t0, f in zip(t0s, vfuts):
            try:
                f.result(timeout=300.0)
                ok += 1
                lats.append(time.monotonic() - t0)
            except Exception:
                pass
        g.drain(timeout=120.0)
    return {"victims": len(victims), "ok": ok,
            "ok_rate": ok / max(1, len(victims)),
            "mean_latency_s": (sum(lats) / len(lats)) if lats else None,
            "floods": len(floods), "floods_shed_typed": sheds,
            "untyped_errors": untyped}


def _phase_b(args, failures: List[str]) -> dict:
    print(f"phase B: {args.victims} victims + {args.floods} pool-sized "
          f"floods, budgeted vs unbudgeted")
    base = _victim_run(args, budgeted=False)
    bud = _victim_run(args, budgeted=True)
    _expect(bud["floods_shed_typed"] == args.floods,
            f"every flood shed typed under the budget "
            f"({bud['floods_shed_typed']}/{args.floods})", failures)
    _expect(bud["untyped_errors"] == 0 and base["untyped_errors"] == 0,
            "zero untyped errors in either run", failures)
    _expect(bud["ok_rate"] >= base["ok_rate"],
            f"victim completion no worse than unbudgeted baseline "
            f"({bud['ok_rate']:.2f} vs {base['ok_rate']:.2f})",
            failures)
    if bud["mean_latency_s"] and base["mean_latency_s"]:
        print(f"  victim mean latency: {bud['mean_latency_s'] * 1e3:.0f}ms "
              f"budgeted vs {base['mean_latency_s'] * 1e3:.0f}ms baseline")
    return {"baseline": base, "budgeted": bud}


# -- the driver ---------------------------------------------------------------

def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        "mem-drill",
        description="HBM pressure survival drill "
                    "(docs/serving.md#memory-budgeting--kv-offload-r20)")
    p.add_argument("--sessions", type=int, default=18,
                   help="multi-turn sessions to open against the pool")
    p.add_argument("--page-size", type=int, default=4)
    p.add_argument("--num-pages", type=int, default=16,
                   help="device page pool (kept tiny so tokens are "
                        "genuinely scarce)")
    p.add_argument("--max-new", type=int, default=6)
    p.add_argument("--max-new2", type=int, default=4,
                   help="second-turn decode budget")
    p.add_argument("--victims", type=int, default=9)
    p.add_argument("--floods", type=int, default=3)
    p.add_argument("--run-dir", default=None,
                   help="run-ledger directory (default: a temp dir)")
    p.add_argument("--out", default="BENCH_mem_r20.json")
    p.add_argument("--smoke", action="store_true",
                   help="fast CI preset: fewer sessions and victims")
    args = p.parse_args(argv)
    if args.smoke:
        args.sessions = 16
        args.victims = 6
        args.floods = 2

    import jax
    jax.config.update("jax_platforms", "cpu")
    from bigdl_tpu.observability import ledger as run_ledger
    os.environ.pop("BIGDL_TPU_TRACE_ID", None)
    run_dir = args.run_dir or tempfile.mkdtemp(prefix="bigdl-mem-drill-")
    run_ledger.set_run_dir(run_dir)

    failures: List[str] = []
    a = _phase_a(args, failures)
    b = _phase_b(args, failures)

    # the ledger trail: mem.budget / mem.offload events feed
    # run-report's memory census
    run_ledger.flush()
    from bigdl_tpu.observability.report import build_report, load_ledger
    records, _bad = load_ledger(run_dir)
    census = build_report(records).get("memory") or {}
    print("ledger: run-report memory census")
    _expect(census.get("parks", 0) >= 1
            and census.get("resumes", 0) >= 1
            and census.get("sheds", 0) >= 1,
            f"memory census carries the drill's parks/resumes/sheds "
            f"(parks={census.get('parks')}, "
            f"resumes={census.get('resumes')}, "
            f"sheds={census.get('sheds')})", failures)

    gates = {
        "capacity_3x": a.get("resident_tokens", 0)
        >= 3 * a.get("pool_tokens", 1),
        "zero_oom_zero_lost": a.get("bit_mismatches", -1) >= 0
        and not any("survived" in f or "untyped" in f
                    for f in failures),
        "typed_attributed_sheds": a.get("typed_sheds", 0) >= 1,
        "park_resume_bit_equal": a.get("bit_mismatches", 1) == 0
        and a.get("resumes", 0) >= 1,
        "accounting_exact": a.get("kv_pages_after_close", 1) == 0
        and a.get("host_offload_after_close", 1) == 0,
        "victim_slo_no_worse": (b.get("budgeted", {}).get("ok_rate", 0)
                                >= b.get("baseline", {})
                                .get("ok_rate", 1)),
    }
    bench = {"bench": "mem_r20", "smoke": bool(args.smoke),
             "phase_a": a, "phase_b": b,
             "memory_census": census, "gates": gates,
             "pass": all(gates.values()) and not failures}
    with open(args.out, "w") as f:
        json.dump(bench, f, indent=2, default=str)
    print(f"\n-- gates ({args.out}) --")
    for k, v in gates.items():
        print(f"  [{'ok' if v else 'FAIL'}] {k}")
        if not v and f"gate {k}" not in failures:
            failures.append(f"gate {k}")
    if failures:
        print(f"\nmem-drill: {len(failures)} check(s) FAILED "
              f"(ledger kept under {run_dir})")
        return 1
    print("\nmem-drill: all checks passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
