"""graftlint tier-1 tests — the static-analysis gate.

Four contracts, all fast-tier:

1. the fixture corpus yields EXACTLY the expected finding set per rule
   (one-plus true positives and one suppressed case per hazard class);
2. ``python -m bigdl_tpu.cli lint`` over ``bigdl_tpu/`` with the
   committed baseline is clean (exit 0) and fast (soft-gated <15s,
   per-rule accountable via ``--profile``/``lint.run`` timings);
3. the CLI's distinct-exit-code contract: clean=0, findings=1, internal
   error=2 — CI must tell "the gate failed the code" from "the gate
   broke";
4. the r12 program-model layer (cross-module call graph, thread-entry
   discovery, multi-thread-reachability, entry-lock fixpoint) is
   unit-tested directly, independent of any rule, and the analyzer
   still never imports jax.

Plus regressions: the two seed-era defect classes that motivated the
analyzer (the PR-1 checkpoint use-after-donate, the PR-2
``Metrics.gathered`` divergence) stay detectable on reduced replicas of
the original code shapes, the fixes graftlint's first sweeps produced
(``nn.Echo`` printing per compile instead of per forward; r12's
``RunLedger.close()`` append racing the drain thread) stay fixed, and
the ``--changed``/baseline-hygiene/docs-drift workflows hold.
"""

import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

from bigdl_tpu.analysis import run_lint
from bigdl_tpu.analysis.context import ModuleContext
from bigdl_tpu.analysis.engine import (Finding, default_baseline_path,
                                       package_root, write_baseline)
from bigdl_tpu.analysis.program import ProgramModel
from bigdl_tpu.analysis.rules import ALL_RULES, ProgramRule

pytestmark = pytest.mark.lint

FIXTURES = os.path.join(package_root(), "analysis", "fixtures")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the exact expected (rule, symbol) multiset per fixture file — a rule
# change that adds or loses a detection fails here, loudly
EXPECTED = {
    "use_after_donate.py": sorted([
        ("use-after-donate", "bad_read_after_donate"),
        ("use-after-donate", "bad_loop_no_rebind"),
        ("use-after-donate", "bad_factory_step"),
        ("use-after-donate", "bad_argnames_read"),
        ("use-after-donate", "Trainer.bad_loop_reads_donated_params"),
    ]),
    "host_calls.py": sorted([
        ("host-call-in-jit", "bad_print"),
        ("host-call-in-jit", "bad_numpy_call"),     # np.asarray
        ("host-call-in-jit", "bad_numpy_call"),     # .item()
        ("host-call-in-jit", "bad_wrapped_logging"),
    ]),
    "ledger_emit.py": sorted([
        ("ledger-in-jit", "bad_emit"),
        ("ledger-in-jit", "bad_span"),
    ]),
    "state_mutation.py": sorted([
        ("nonlocal-mutation-in-jit", "bad_append"),
        ("nonlocal-mutation-in-jit", "bad_global_counter"),
        ("nonlocal-mutation-in-jit", "make_counter.bad_nonlocal"),
        ("nonlocal-mutation-in-jit", "bad_dict_store"),
    ]),
    "collectives.py": sorted([
        ("collective-divergence", "bad_rank_guarded_psum"),
        ("collective-divergence", "bad_env_guarded_gather"),
        ("collective-divergence", "bad_early_exit_before_collective"),
    ]),
    "mesh_axes.py": sorted([
        ("mesh-axis-misuse", "bad_unbound_collective.bad_body"),
        ("mesh-axis-misuse", "bad_hardcoded_collective"),
        ("mesh-axis-misuse", "bad_hardcoded_spec"),
    ]),
    "stale_world.py": sorted([
        ("stale-world-capture", "bad_module_world"),
        ("stale-world-capture", "bad_module_devices"),
        ("stale-world-capture", "BadTrainer.bad_step"),
        ("stale-world-capture", "BadInit.bad_forward"),
    ]),
    "shape_buckets.py": sorted([
        ("shape-bucket-mismatch", "bad_cross_bucket_dispatch"),
        ("shape-bucket-mismatch", "bad_stale_lookup"),
    ]),
    "page_aliasing.py": sorted([
        ("page-aliasing", "bad_write_shared_page"),
        ("page-aliasing", "bad_write_after_free"),
        ("page-aliasing", "bad_scatter_looked_up"),
    ]),
    "quant_scales.py": sorted([
        ("quant-scale-mismatch", "bad_cross_pair_dequant"),
        ("quant-scale-mismatch", "bad_wrong_axis"),
        ("quant-scale-mismatch", "bad_bare_upcast_matmul"),
    ]),
    "tuned_tiles.py": sorted([
        ("tuned-tile-bypass", "bad_literal_blockspec"),
        ("tuned-tile-bypass", "bad_literal_block_shape_kwarg"),
        ("tuned-tile-bypass", "bad_literal_tiles_wrapper"),
    ]),
    "span_tracking.py": sorted([
        ("span-unclosed", "bad_straight_line"),
        ("span-unclosed", "bad_never_ended"),
        ("span-unclosed", "bad_except_only"),
    ]),
    "prng.py": sorted([
        ("prng-reuse", "bad_double_draw"),
        ("prng-reuse", "bad_loop_reuse"),
    ]),
    "blocking_io.py": sorted([
        ("blocking-io-in-jit", "bad_open"),
        ("blocking-io-in-jit", "bad_sleep"),
        ("blocking-io-in-jit", "bad_path_check"),
    ]),
    # concurrency tier (r12)
    "shared_state.py": sorted([
        ("unguarded-shared-mutation", "BadPool.bad_unguarded_bump"),
        ("unguarded-shared-mutation", "BadRoster.bad_close_append"),
    ]),
    "lock_order.py": sorted([
        ("lock-order-cycle", "BadLedgerPair.bad_ab"),
        ("lock-order-cycle", "BadLedgerPair.bad_ba"),
        ("lock-order-cycle", "BadCrossCall.bad_submit"),
        ("lock-order-cycle", "BadCrossCall.bad_reverse"),
    ]),
    "lock_wait.py": sorted([
        ("wait-while-holding", "BadDrain.bad_get_under_lock"),
        ("wait-while-holding", "BadDrain.bad_join_under_lock"),
        ("wait-while-holding", "BadDrain.bad_sleep_under_lock"),
        ("wait-while-holding", "BadTransitive.bad_pump"),
        ("wait-while-holding", "BadTransitive.bad_call_blocks"),
    ]),
    "refcounts.py": sorted([
        ("refcount-unbalanced", "bad_leaked_alloc"),
        ("refcount-unbalanced", "bad_never_freed"),
        ("refcount-unbalanced", "bad_acquire_no_release"),
    ]),
    # fleet tier (r15)
    "cross_tenant_state.py": sorted([
        ("cross-tenant-state", "BadLadderCache.bad_compile"),
        ("cross-tenant-state", "BadEvictionQueue.bad_touch"),
        ("cross-tenant-state", "BadPageCapture.bad_map"),
    ]),
    # fleet tier (r16)
    "cross_host_state.py": sorted([
        ("cross-host-state", "BadStaticRouteTable.bad_dispatch"),
        ("cross-host-state", "BadClassHostList.bad_spill_route"),
        ("cross-host-state", "bad_route_fallback"),
        ("cross-host-state", "bad_route_fallback"),
    ]),
    # fleet tier (r17)
    "trace_context_drop.py": sorted([
        ("trace-context-drop", "bad_publish_literal"),
        ("trace-context-drop", "bad_publish_call_form"),
    ]),
    # fleet tier (r18)
    "stale_version.py": sorted([
        ("stale-version-serve", "BadGlobalVersionServe.bad_serve"),
        ("stale-version-serve", "bad_submit_handle"),
        ("stale-version-serve", "BadClassCheckpoint.bad_predict"),
    ]),
    # durability tier (r19)
    "torn_state.py": sorted([
        ("torn-state-write", "bad_publish_lease"),
        ("torn-state-write", "bad_bus_inbox_write"),
    ]),
    "rename_flush.py": sorted([
        ("rename-without-flush", "bad_replace_unflushed"),
        ("rename-without-flush", "bad_mkstemp_unflushed"),
    ]),
    "ledger_order.py": sorted([
        ("ledger-after-mutation", "bad_claim_stamp"),
    ]),
    "rollback_commit.py": sorted([
        ("rollback-past-commit", "bad_promote_window"),
    ]),
    # memory tier (r20)
    "unbudgeted_alloc.py": sorted([
        ("unbudgeted-alloc", "BadKvPool.bad_rebuild"),
        ("unbudgeted-alloc", "BadPinnedParams.bad_pin"),
        ("unbudgeted-alloc", "BadPinnedParams.bad_draft_cache"),
    ]),
}


def _lint_file(name):
    return run_lint([os.path.join(FIXTURES, name)], baseline_path=None)


# -- 1. fixture corpus --------------------------------------------------------

@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_fixture_corpus_exact_findings(name):
    res = _lint_file(name)
    got = sorted((f.rule, f.symbol) for f in res.findings)
    assert got == EXPECTED[name], \
        f"{name}: finding set drifted:\n" + \
        "\n".join(f.render() for f in res.findings)
    # known-good snippets never flag; known-bad symbols all start bad_
    assert all(s.split(".")[-1].startswith("bad_") for _, s in got)
    # exactly one suppressed deliberate case per hazard class
    assert res.suppressed == 1, \
        f"{name}: expected 1 suppressed case, got {res.suppressed}"


def test_fixture_corpus_covers_every_rule():
    """Every registered rule has at least one true positive AND one
    suppressed case in the corpus (the acceptance-criteria shape)."""
    rules_hit = {r for per_file in EXPECTED.values() for r, _ in per_file}
    assert rules_hit == {r.name for r in ALL_RULES}


# -- 2. the package is clean under the committed baseline ---------------------

def test_package_lints_clean_and_fast():
    t0 = time.monotonic()
    res = run_lint(baseline_path=default_baseline_path())
    wall = time.monotonic() - t0
    assert not res.findings, "\n".join(f.render() for f in res.findings)
    assert not res.errors, res.errors
    assert res.files > 90          # the walk really covered the package
    # the deliberate, justified suppressions currently in-tree
    # (MaskedSelect's documented eager-only numpy path; native.py's
    # build-once-under-lock)
    assert res.suppressed >= 1
    # the soft budget gate (r12): the whole-program concurrency passes
    # ride the same sweep and must stay accountable to seconds, not
    # minutes — per-rule accounting is in res.timings / lint --profile
    # (budget raised 10s -> 15s at r15: the package crossed 150 files
    # and the full sweep sits right at 10s on a loaded box; raised
    # 15s -> 20s at r18: 160 files, the idle sweep sits at ~11.5s and
    # crossed 15s under full-suite load — no single rule is over 12%;
    # raised 20s -> 25s at r19: the durability tier adds four program
    # rules over the shared fact layer, idle sweep ~12-16s — the
    # tier's cost stays visible in lint --profile / rule_ms)
    assert wall < 25.0, f"lint took {wall:.1f}s"
    assert res.timings and "<program-model>" in res.timings
    from bigdl_tpu.analysis.rules import ALL_RULES
    assert {r.name for r in ALL_RULES} <= set(res.timings)


# -- 3. CLI exit-code contract ------------------------------------------------

def _cli(*args, env=None):
    e = dict(os.environ)
    e.pop("BIGDL_TPU_RUN_DIR", None)
    if env:
        e.update(env)
    return subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.cli", *args], cwd=REPO,
        env=e, capture_output=True, text=True, timeout=120)


def test_cli_clean_exit_0():
    r = _cli("lint")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 finding(s)" in r.stdout


def test_cli_findings_exit_1():
    r = _cli("lint", os.path.join(FIXTURES, "prng.py"), "--no-baseline")
    assert r.returncode == 1, r.stdout + r.stderr
    assert "prng-reuse" in r.stdout


def test_cli_internal_error_exit_2():
    r = _cli("lint", "/no/such/path/exists")
    assert r.returncode == 2, r.stdout + r.stderr


def test_cli_unknown_subcommand_exit_2():
    r = _cli("frobnicate")
    assert r.returncode == 2


def test_cli_json_format():
    r = _cli("lint", os.path.join(FIXTURES, "collectives.py"),
             "--format=json", "--no-baseline")
    assert r.returncode == 1
    data = json.loads(r.stdout)
    assert data["summary"]["per_rule"] == {"collective-divergence": 3}
    assert all(f["fingerprint"] for f in data["findings"])


# -- suppressions and baseline workflow ---------------------------------------

def _lint_source(tmp_path, source):
    p = tmp_path / "mod.py"
    p.write_text(textwrap.dedent(source))
    return run_lint([str(p)], baseline_path=None)


def test_suppression_same_line_and_next_line(tmp_path):
    res = _lint_source(tmp_path, """
        import jax

        def two(key, shape):
            a = jax.random.normal(key, shape)
            b = jax.random.normal(key, shape)  # graftlint: disable=prng-reuse
            # graftlint: disable-next=prng-reuse
            c = jax.random.normal(key, shape)
            return a + b + c
    """)
    assert not res.findings
    assert res.suppressed == 2


def test_suppression_all_and_wrong_rule(tmp_path):
    res = _lint_source(tmp_path, """
        import jax

        def two(key, shape):
            a = jax.random.normal(key, shape)
            b = jax.random.normal(key, shape)  # graftlint: disable=all
            c = jax.random.normal(key, shape)  # graftlint: disable=use-after-donate
            return a + b + c
    """)
    # 'all' silences; a different rule's suppression does not
    assert [f.rule for f in res.findings] == ["prng-reuse"]
    assert res.suppressed == 1


def test_loop_local_exits_do_not_flag(tmp_path):
    """A continue/break owned by a loop inside the tainted if (or whose
    loop the collective is not in) cannot skip the rendezvous — legal
    shapes must not force spurious suppressions (the gate has an empty
    baseline and runs in make-dist.sh)."""
    res = _lint_source(tmp_path, """
        import os
        from jax import lax

        def agg(items, x, axis):
            if os.environ.get("VERBOSE"):
                for i in items:
                    if i is None:
                        continue
            return lax.psum(x, axis)

        def agg2(items, x, axis):
            for i in items:
                if os.environ.get("FASTPATH"):
                    break
            return lax.psum(x, axis)

        def still_bad(items, x, axis):
            for i in items:
                if os.environ.get("SKIP"):
                    continue            # skips the psum below on SOME
                x = lax.psum(x, axis)   # processes' iterations
            return x
    """)
    assert [(f.rule, f.symbol) for f in res.findings] == \
        [("collective-divergence", "still_bad")], \
        "\n".join(f.render() for f in res.findings)


def test_baseline_masks_old_findings_only(tmp_path):
    src = """
        import jax

        def two(key, shape):
            a = jax.random.normal(key, shape)
            b = jax.random.normal(key, shape)
            return a + b
    """
    p = tmp_path / "mod.py"
    p.write_text(textwrap.dedent(src))
    first = run_lint([str(p)], baseline_path=None)
    assert len(first.findings) == 1
    bl = tmp_path / "baseline.json"
    write_baseline(str(bl), first.findings)
    # same code: baselined, gate passes
    again = run_lint([str(p)], baseline_path=str(bl))
    assert not again.findings and len(again.baselined) == 1
    # NEW hazard: not masked by the stale baseline
    p.write_text(textwrap.dedent(src) + textwrap.dedent("""
        def more(key, n):
            out = []
            for _ in range(n):
                out.append(jax.random.uniform(key, ()))
            return out
    """))
    third = run_lint([str(p)], baseline_path=str(bl))
    assert [f.symbol for f in third.findings] == ["more"]


def test_baseline_is_multiset_for_identical_lines(tmp_path):
    """Two identical flagged lines fingerprint identically, so each
    baseline entry must forgive exactly one occurrence — baselining one
    duplicate must not mask the other (or a future third)."""
    src = """
        import jax

        def draws(key, shape):
            out = []
            out.append(jax.random.normal(key, shape))
            out.append(jax.random.normal(key, shape))
            out.append(jax.random.normal(key, shape))
            return out
    """
    p = tmp_path / "mod.py"
    p.write_text(textwrap.dedent(src))
    first = run_lint([str(p)], baseline_path=None)
    assert len(first.findings) == 2           # draws 2 and 3 reuse the key
    assert len({f.fingerprint for f in first.findings}) == 1
    bl = tmp_path / "baseline.json"
    write_baseline(str(bl), first.findings[:1])   # forgive ONE occurrence
    again = run_lint([str(p)], baseline_path=str(bl))
    assert len(again.findings) == 1 and len(again.baselined) == 1
    # both entries written -> clean; a NEW identical draw still fails
    write_baseline(str(bl), first.findings)
    assert not run_lint([str(p)], baseline_path=str(bl)).findings
    p.write_text(textwrap.dedent(src).replace(
        "    return out",
        "    out.append(jax.random.normal(key, shape))\n    return out"))
    assert len(run_lint([str(p)], baseline_path=str(bl)).findings) == 1


# -- regressions: the seed-era defect classes stay detectable -----------------

def _check_source(source, factories=None):
    mod = ModuleContext("probe.py", textwrap.dedent(source),
                        factories=factories)
    out = []
    program = ProgramModel([mod])
    for r in ALL_RULES:
        if isinstance(r, ProgramRule):
            out.extend(r.check_program(program))
        else:
            out.extend(r.check(mod))
    return out


def test_regression_pr1_checkpoint_use_after_donate():
    """Reduced replica of the PR-1 bug: the File-checkpoint path read
    ``wshard`` after the jitted step donated it.  The factory registry
    must connect make_distri_train_step's donate_argnums (resolved
    through its platform IfExp) to the trainer's ``step`` name."""
    allre_path = os.path.join(package_root(), "parallel", "allreduce.py")
    with open(allre_path) as f:
        factories = ModuleContext(allre_path, f.read()).export_factories()
    assert "make_distri_train_step" in factories
    assert factories["make_distri_train_step"].spec.argnums == {0, 1}
    findings = _check_source("""
        import jax
        from bigdl_tpu.parallel.allreduce import make_distri_train_step

        def optimize(self, data, labels, sub, stepno, clr):
            step, layout, init_fn = make_distri_train_step(
                self.model, self.criterion, self.optim, self.mesh,
                self.config)
            wshard, opt_shard = init_fn(self.model.params)
            new_w, new_o, ms, loss = step(wshard, opt_shard, None, data,
                                          labels, sub, stepno, clr)
            self.save_checkpoint(wshard)
    """, factories=factories)
    assert [(f.rule, "wshard" in f.message) for f in findings] == \
        [("use-after-donate", True)]


def test_regression_pr1_rebind_is_clean():
    """The FIXED shape (today's distri loop: rebind in the same
    statement) must not flag — the rule understands the safe idiom."""
    allre_path = os.path.join(package_root(), "parallel", "allreduce.py")
    with open(allre_path) as f:
        factories = ModuleContext(allre_path, f.read()).export_factories()
    findings = _check_source("""
        import jax
        from bigdl_tpu.parallel.allreduce import make_distri_train_step

        def optimize(self, data, labels, sub, stepno, clr):
            step, layout, init_fn = make_distri_train_step(
                self.model, self.criterion, self.optim, self.mesh,
                self.config)
            wshard, opt_shard = init_fn(self.model.params)
            wshard, opt_shard, ms, loss = step(wshard, opt_shard, None,
                                               data, labels, sub, stepno,
                                               clr)
            self.save_checkpoint(wshard)
    """, factories=factories)
    assert findings == []


def test_regression_pr2_gathered_divergence():
    """Reduced replica of the PR-2 bug class: ``Metrics.gathered()``
    behind a per-process condition desynchronizes the allgather."""
    findings = _check_source("""
        import jax

        def summary(self, metrics):
            if jax.process_index() == 0:
                scalars, arrays = metrics.gathered()
                return scalars
            return None
    """)
    assert [f.rule for f in findings] == ["collective-divergence"]


def test_regression_echo_prints_per_forward_under_jit(capfd):
    """graftlint's first sweep flagged nn.Echo's bare print (fires once
    per compile).  The fix routes through jax.debug.print; the reference
    contract — one line per FORWARD — must hold under jit."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.nn.containers import Echo

    m = Echo()
    fn = jax.jit(lambda x: m.apply(None, {}, x)[0])
    fn(jnp.ones((2, 3))).block_until_ready()
    fn(jnp.ones((2, 3))).block_until_ready()   # cached executable
    jax.effects_barrier()
    out = capfd.readouterr().out
    assert out.count("(2, 3)") == 2, repr(out)


# -- ledger integration -------------------------------------------------------

def test_lint_emits_ledger_event_and_report_shows_gate(tmp_path):
    run_dir = tmp_path / "run"
    r = _cli("lint", env={"BIGDL_TPU_RUN_DIR": str(run_dir)})
    assert r.returncode == 0, r.stdout + r.stderr
    events = []
    for p in run_dir.glob("events-*.jsonl"):
        for line in p.read_text().splitlines():
            events.append(json.loads(line))     # strict JSON per line
    lint_events = [e for e in events if e["type"] == "lint.run"]
    assert len(lint_events) == 1
    ev = lint_events[0]
    assert ev["clean"] is True and ev["files"] > 90
    assert ev["findings"] == 0 and ev["suppressed"] >= 1
    rep = _cli("run-report", str(run_dir))
    assert rep.returncode == 0, rep.stdout + rep.stderr
    assert "lint gate (graftlint): clean" in rep.stdout


def test_broken_gate_is_not_recorded_clean(tmp_path):
    """A lint run that itself breaks (exit 2) must not leave a
    clean=true lint.run event — run-report has to distinguish 'the gate
    passed' from 'the gate broke and linted nothing'."""
    run_dir = tmp_path / "run"
    r = _cli("lint", "/no/such/path/exists",
             env={"BIGDL_TPU_RUN_DIR": str(run_dir)})
    assert r.returncode == 2
    events = []
    for p in run_dir.glob("events-*.jsonl"):
        for line in p.read_text().splitlines():
            events.append(json.loads(line))
    lint_events = [e for e in events if e["type"] == "lint.run"]
    assert len(lint_events) == 1
    assert lint_events[0]["clean"] is False
    assert lint_events[0]["errors"] == 1
    rep = _cli("run-report", str(run_dir))
    assert "lint gate (graftlint): BROKEN" in rep.stdout


# -- r12: program-model layer (call graph / thread model), rule-free ----------

def _program(**sources):
    """ProgramModel over inline pseudo-modules keyed by bare name."""
    mods = [ModuleContext(f"{name}.py", textwrap.dedent(src))
            for name, src in sources.items()]
    return ProgramModel(mods)


def test_program_thread_entry_discovery():
    """Every documented entry-point form is discovered: Thread target,
    Timer function, ThreadPoolExecutor.submit, threaded HTTP handler."""
    p = _program(m="""
        import threading
        from concurrent.futures import ThreadPoolExecutor
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        def loop():
            pass

        def tick():
            pass

        def job(n):
            pass

        def helper():
            pass

        class Handler(BaseHTTPRequestHandler):
            def do_GET(self):
                helper()

        def main():
            threading.Thread(target=loop, daemon=True).start()
            threading.Timer(1.0, tick).start()
            ex = ThreadPoolExecutor(2)
            ex.submit(job, 1)
            srv = ThreadingHTTPServer(("127.0.0.1", 0), Handler)

        def untouched():
            pass
    """)
    entries = {k.split("::")[1] for k in p.thread_entries}
    assert entries == {"loop", "tick", "job", "Handler.do_GET"}
    # reachability closes over call edges; main itself runs on the
    # spawning thread and untouched is never called
    assert p.is_mt("m::helper")
    assert not p.is_mt("m::main")
    assert not p.is_mt("m::untouched")


def test_program_process_pool_is_not_a_thread_entry():
    """ProcessPoolExecutor workers share no memory — submit targets
    must NOT become multi-thread-reachable."""
    p = _program(m="""
        from concurrent.futures import ProcessPoolExecutor

        def job(n):
            pass

        def main():
            ex = ProcessPoolExecutor(2)
            ex.submit(job, 1)
    """)
    assert not p.thread_entries
    assert not p.is_mt("m::job")


def test_program_self_method_entry_and_reachability():
    p = _program(m="""
        import threading

        class W:
            def __init__(self):
                self.t = threading.Thread(target=self._loop)

            def _loop(self):
                self._step()

            def _step(self):
                pass

            def idle(self):
                pass
    """)
    assert "m::W._loop" in p.thread_entries
    assert p.is_mt("m::W._step")
    assert not p.is_mt("m::W.idle")


def test_program_cross_module_call_edges():
    """Edges resolve through `from mod import name` and through a
    locally-constructed class instance; a module-level Thread spawn is
    an entry like any other."""
    p = _program(
        worklib="""
            def work():
                pass

            class Engine:
                def run(self):
                    pass
        """,
        app="""
            import threading
            from worklib import work, Engine

            def spin():
                work()
                eng = Engine()
                eng.run()

            threading.Thread(target=spin, daemon=True).start()
        """)
    assert "app::spin" in p.thread_entries
    callees = {e.callee for e in p.calls_from["app::spin"]}
    assert {"worklib::work", "worklib::Engine.run"} <= callees
    assert p.is_mt("worklib::work")
    assert p.is_mt("worklib::Engine.run")


def test_program_entry_lock_fixpoint():
    """A helper whose every known call site holds the lock inherits it
    (entry locks); one lock-free call site voids the credit."""
    p = _program(m="""
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()

            def a(self):
                with self._lock:
                    self.always_locked()
                    self.sometimes_locked()

            def b(self):
                with self._lock:
                    self.always_locked()

            def c(self):
                self.sometimes_locked()

            def always_locked(self):
                pass

            def sometimes_locked(self):
                pass
    """)
    assert p.entry_locks["m::C.always_locked"] == frozenset({"_lock"})
    assert p.entry_locks["m::C.sometimes_locked"] == frozenset()


def test_program_unique_method_fallback():
    """x.m() resolves when exactly one class program-wide defines m —
    the recall boost for untypable receivers."""
    p = _program(m="""
        import threading

        class Only:
            def distinctive_step(self):
                pass

        def drive(worker):
            worker.distinctive_step()

        threading.Thread(target=drive, daemon=True).start()
    """)
    assert p.is_mt("m::Only.distinctive_step")


# -- r12: lint --changed (the fast pre-commit path) ---------------------------

def _cli_in(cwd, *args):
    e = dict(os.environ)
    e.pop("BIGDL_TPU_RUN_DIR", None)
    # the repo is imported from its checkout, not site-packages — a
    # foreign cwd needs it on the path
    e["PYTHONPATH"] = REPO + os.pathsep + e.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.cli", *args], cwd=str(cwd),
        env=e, capture_output=True, text=True, timeout=120)


def _git(repo, *args):
    env = dict(os.environ,
               GIT_AUTHOR_NAME="t", GIT_AUTHOR_EMAIL="t@t",
               GIT_COMMITTER_NAME="t", GIT_COMMITTER_EMAIL="t@t")
    subprocess.run(["git", *args], cwd=str(repo), env=env,
                   capture_output=True, check=True)


def test_cli_changed_lints_only_dirty_files(tmp_path):
    repo = tmp_path / "r"
    repo.mkdir()
    _git(repo, "init", "-q")
    (repo / "clean.py").write_text(
        "import jax\n\ndef one(key, s):\n"
        "    return jax.random.normal(key, s)\n")
    (repo / "other.py").write_text("y = 2\n")
    _git(repo, "add", "-A")
    _git(repo, "commit", "-qm", "base")

    # nothing changed: quiet success, no sweep
    r = _cli_in(repo, "lint", "--changed")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "no changed python files" in r.stdout

    # the invalid --changed --prune-baseline combination is exit 2
    # even on a clean tree (flag validation precedes the early return)
    r = _cli_in(repo, "lint", "--changed", "--prune-baseline")
    assert r.returncode == 2, r.stdout + r.stderr

    # a brand-NEW untracked file is invisible to `git diff` but must
    # be linted anyway — new files are exactly where new hazards live
    (repo / "fresh.py").write_text(
        "import jax\n\ndef three(key, s):\n"
        "    a = jax.random.normal(key, s)\n"
        "    b = jax.random.normal(key, s)\n"
        "    return a + b\n")
    r = _cli_in(repo, "lint", "--changed")
    assert r.returncode == 1, r.stdout + r.stderr
    assert "fresh.py" in r.stdout
    (repo / "fresh.py").unlink()

    # an UNCOMMITTED hazard in one file: --changed lints exactly it
    (repo / "other.py").write_text(
        "import jax\n\ndef two(key, s):\n"
        "    a = jax.random.normal(key, s)\n"
        "    b = jax.random.normal(key, s)\n"
        "    return a + b\n")
    r = _cli_in(repo, "lint", "--changed")
    assert r.returncode == 1, r.stdout + r.stderr
    assert "prng-reuse" in r.stdout and "other.py" in r.stdout
    assert "clean.py" not in r.stdout
    assert "1 files" in r.stdout       # the clean file was not linted

    # committed: --changed (vs HEAD) goes quiet, --since REF still sees it
    _git(repo, "add", "-A")
    _git(repo, "commit", "-qm", "bug")
    assert _cli_in(repo, "lint", "--changed").returncode == 0
    r = _cli_in(repo, "lint", "--changed", "--since", "HEAD~1")
    assert r.returncode == 1 and "prng-reuse" in r.stdout


def test_cli_changed_outside_git_is_exit_2(tmp_path):
    """No git checkout -> the gate BREAKS (exit 2) rather than passing
    silently green."""
    nowhere = tmp_path / "n"
    nowhere.mkdir()
    env = dict(os.environ)
    env["GIT_CEILING_DIRECTORIES"] = str(tmp_path)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.cli", "lint", "--changed"],
        cwd=str(nowhere), env=env, capture_output=True, text=True,
        timeout=120)
    assert r.returncode == 2, r.stdout + r.stderr


# -- r12: baseline hygiene ----------------------------------------------------

def test_stale_baseline_warning_and_prune(tmp_path):
    bl = tmp_path / "baseline.json"
    ghost = Finding(rule="prng-reuse", path="bigdl_tpu/ghost.py",
                    line=3, col=0, message="gone", symbol="ghost")
    ghost.snippet = "b = jax.random.normal(key, shape)"
    write_baseline(str(bl), [ghost])

    # full sweep: the stale entry WARNS but the exit stays 0
    r = _cli("lint", "--baseline", str(bl))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "stale baseline entry" in r.stderr
    assert "--prune-baseline" in r.stderr

    # a partial lint never judges staleness (it matches almost nothing)
    r = _cli("lint", os.path.join("bigdl_tpu", "compat.py"),
             "--baseline", str(bl))
    assert "stale baseline entry" not in r.stderr

    # --prune-baseline rewrites the file without the dead entry
    r = _cli("lint", "--baseline", str(bl), "--prune-baseline")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "pruned 1 stale" in r.stdout
    assert json.loads(bl.read_text())["entries"] == []

    # pruning demands the full sweep: partial target is a broken gate
    r = _cli("lint", os.path.join("bigdl_tpu", "compat.py"),
             "--baseline", str(bl), "--prune-baseline")
    assert r.returncode == 2


# -- r12: engine observability (--profile + per-rule ledger timings) ----------

def test_profile_flag_and_ledger_rule_timings(tmp_path):
    run_dir = tmp_path / "run"
    r = _cli("lint", "--profile",
             env={"BIGDL_TPU_RUN_DIR": str(run_dir)})
    assert r.returncode == 0, r.stdout + r.stderr
    assert "graftlint profile:" in r.stdout
    assert "<program-model>" in r.stdout
    assert "unguarded-shared-mutation" in r.stdout
    events = []
    for p in run_dir.glob("events-*.jsonl"):
        for line in p.read_text().splitlines():
            events.append(json.loads(line))
    ev = [e for e in events if e["type"] == "lint.run"][0]
    assert ev["wall_ms"] > 0
    assert "<parse>" in ev["rule_ms"]
    for rule in ALL_RULES:
        assert rule.name in ev["rule_ms"], rule.name
    # per-tier rule counts (r19): the event mirrors the registry
    want: dict = {}
    for rule in ALL_RULES:
        want[rule.tier] = want.get(rule.tier, 0) + 1
    assert ev["tiers"] == want
    assert ev["tiers"]["durability"] == 4


# -- r12: docs/fixture drift guard --------------------------------------------

def test_docs_and_fixture_drift_guard():
    """Every module under analysis/rules/ must register a rule, every
    rule must have a catalog entry in docs/static-analysis.md, a
    known-bad fixture finding pinned in EXPECTED, and a known-good case
    in its fixture file — a future rule cannot skip its docs."""
    import importlib
    rules_dir = os.path.join(package_root(), "analysis", "rules")
    declared = set()
    for fname in sorted(os.listdir(rules_dir)):
        if not fname.endswith(".py") or \
                fname in ("__init__.py", "base.py"):
            continue
        mod = importlib.import_module(
            f"bigdl_tpu.analysis.rules.{fname[:-3]}")
        names = {r.name for r in ALL_RULES
                 if type(r).__module__ == mod.__name__}
        assert names, f"rules/{fname} registers no rule in ALL_RULES"
        declared |= names
    assert declared == {r.name for r in ALL_RULES}

    with open(os.path.join(REPO, "docs", "static-analysis.md"),
              encoding="utf-8") as f:
        docs = f.read()
    pinned_bad = {rule for per_file in EXPECTED.values()
                  for rule, _ in per_file}
    for r in ALL_RULES:
        assert f"### `{r.name}`" in docs, \
            f"docs/static-analysis.md catalog entry missing: {r.name}"
        assert r.name in pinned_bad, \
            f"no known-bad fixture finding pinned for {r.name}"
    for name in EXPECTED:
        with open(os.path.join(FIXTURES, name), encoding="utf-8") as f:
            src = f.read()
        assert "good_" in src, f"{name} has no known-good case"


# -- r12: the analyzer still never imports jax --------------------------------

def test_analyzer_never_imports_jax():
    """The whole-program tier (program model + concurrency rules) must
    keep the no-jax contract: the gate runs in build containers with no
    accelerator stack."""
    probe = os.path.join(FIXTURES, "shared_state.py")
    code = (
        "import sys\n"
        "from bigdl_tpu.analysis import run_lint\n"
        "import bigdl_tpu.analysis.program\n"
        f"res = run_lint([{probe!r}], baseline_path=None)\n"
        "assert res.findings, 'probe fixture produced no findings'\n"
        "assert 'jax' not in sys.modules, 'the analyzer imported jax'\n")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr


# -- r12: the ledger close/drain race stays fixed -----------------------------

def test_regression_r12_ledger_close_shape_stays_detectable():
    """Reduced replica of the r12 sweep's true positive: close()
    appended the dropped-count record to the queue WITHOUT the lock,
    racing the drain thread's take-batch (list(q)/q.clear() under the
    lock, the append between them loses the record)."""
    findings = _check_source("""
        import threading

        class Led:
            def __init__(self):
                self._lock = threading.Lock()
                self._q = []
                self._dropped = 0
                self._writer = threading.Thread(target=self._drain)

            def _drain(self):
                with self._lock:
                    batch = list(self._q)
                    self._q.clear()
                return batch

            def emit(self, rec):
                with self._lock:
                    self._q.append(rec)

            def close(self):
                if self._dropped:
                    self._q.append({"type": "dropped"})
    """)
    assert [(f.rule, f.symbol) for f in findings] == \
        [("unguarded-shared-mutation", "Led.close")], \
        "\n".join(f.render() for f in findings)


def test_regression_r12_ledger_fixed_shape_is_clean():
    """Today's RunLedger.close() takes the lock around the append —
    the fixed shape must not flag."""
    findings = _check_source("""
        import threading

        class Led:
            def __init__(self):
                self._lock = threading.Lock()
                self._q = []
                self._dropped = 0
                self._writer = threading.Thread(target=self._drain)

            def _drain(self):
                with self._lock:
                    batch = list(self._q)
                    self._q.clear()
                return batch

            def emit(self, rec):
                with self._lock:
                    self._q.append(rec)

            def close(self):
                with self._lock:
                    if self._dropped:
                        self._q.append({"type": "dropped"})
    """)
    assert findings == []


def test_regression_r12_ledger_dropped_record_survives_racing_close(
        tmp_path):
    """Functional half of the fix: close() racing live emitters still
    lands exactly one ledger.dropped record, and every line in the file
    stays strict JSON."""
    import threading

    from bigdl_tpu.observability.ledger import RunLedger

    led = RunLedger(str(tmp_path / "run"), capacity=8)
    stop = threading.Event()

    def hammer():
        while not stop.is_set():
            led.emit({"type": "noise"})

    threads = [threading.Thread(target=hammer, daemon=True)
               for _ in range(4)]
    for t in threads:
        t.start()
    time.sleep(0.1)                  # capacity 8: thousands of drops
    led.close()                      # close RACES the live emitters
    stop.set()
    for t in threads:
        t.join(timeout=2.0)
    with open(led.path, encoding="utf-8") as f:
        recs = [json.loads(line) for line in f.read().splitlines()]
    dropped = [r for r in recs if r["type"] == "ledger.dropped"]
    assert len(dropped) == 1
    assert dropped[0]["count"] >= 1


# -- r19: the PR 17/18 durability hazards stay detectable ---------------------

def test_regression_pr18_promote_window_rollback_detectable():
    """Reduced replica of the PR 18 HIGH finding: the rollout() except
    handler called _rollback unconditionally — rolling back past the
    durable promote commit point and tearing down the only working
    copy.  The unguarded shape must flag; the shipped fix (read the
    durable phase back, roll forward when it says promote) must not."""
    unguarded = _check_source("""
        from bigdl_tpu.utils.durable_io import atomic_write_json

        FORWARD_PHASES = ("promote",)

        class Controller:
            def _transition(self, phase, **fields):
                atomic_write_json(self._path, {"phase": phase, **fields})

            def _rollback(self, v, reason):
                return {"outcome": "rolled_back", "version": v}

            def rollout(self, v):
                self._transition("canary", target=v)
                try:
                    self._transition("promote", target=v)
                    self.fleet.deregister(self.tenant)
                    self.fleet.register(self.spec)
                except (OSError, RuntimeError) as e:
                    return self._rollback(v, reason=str(e))
    """)
    assert [(f.rule, f.symbol) for f in unguarded] == \
        [("rollback-past-commit", "Controller.rollout")]

    guarded = _check_source("""
        from bigdl_tpu.utils.durable_io import atomic_write_json

        FORWARD_PHASES = ("promote",)

        class Controller:
            def _transition(self, phase, **fields):
                atomic_write_json(self._path, {"phase": phase, **fields})

            def _rollback(self, v, reason):
                return {"outcome": "rolled_back", "version": v}

            def rollout(self, v):
                self._transition("canary", target=v)
                try:
                    self._transition("promote", target=v)
                    self.fleet.deregister(self.tenant)
                    self.fleet.register(self.spec)
                except (OSError, RuntimeError) as e:
                    st = self.state() or {}
                    if st.get("phase") in FORWARD_PHASES and \\
                            st.get("target") == v:
                        return self.recover()
                    return self._rollback(v, reason=str(e))
    """)
    assert not guarded, [(f.rule, f.symbol) for f in guarded]


def test_regression_pr17_claim_anchor_ordering_detectable():
    """Reduced replica of the r17 bus-claim ordering: the emit_critical
    anchor must flush BEFORE the claim context is stamped into the
    durable bus file.  Inverted, a SIGKILL between the two leaves a
    salvager chasing an anchor that never reached disk."""
    inverted = _check_source("""
        from bigdl_tpu.observability import ledger as run_ledger
        from bigdl_tpu.utils.durable_io import atomic_write_json

        def claim(claimed_path, rec, sid):
            rec["claim"] = [sid]
            atomic_write_json(claimed_path, rec)
            run_ledger.emit_critical("event", kind="bus.claim",
                                     span=sid)
    """)
    assert [(f.rule, f.symbol) for f in inverted] == \
        [("ledger-after-mutation", "claim")]

    shipped = _check_source("""
        from bigdl_tpu.observability import ledger as run_ledger
        from bigdl_tpu.utils.durable_io import atomic_write_json

        def claim(claimed_path, rec, sid):
            run_ledger.emit_critical("event", kind="bus.claim",
                                     span=sid)
            rec["claim"] = [sid]
            atomic_write_json(claimed_path, rec)
    """)
    assert not shipped, [(f.rule, f.symbol) for f in shipped]


# -- r12 review fixes: regressions --------------------------------------------

def test_program_entry_lock_fixpoint_mutual_recursion():
    """Mutually recursive helpers only ever entered under the lock keep
    their guard credit — a still-TOP caller must contribute the
    intersection identity, not the empty set."""
    p = _program(m="""
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()

            def entry(self):
                with self._lock:
                    self.f()

            def f(self):
                self.g()

            def g(self):
                self.f()
    """)
    assert p.entry_locks["m::C.f"] == frozenset({"_lock"})
    assert p.entry_locks["m::C.g"] == frozenset({"_lock"})


def test_shared_mutation_chained_assignment_counts_both_targets():
    """`self._a = self._b = 0` writes BOTH attributes — dropping the
    first target from the site census would hide this unguarded write
    of the majority-guarded `_a`."""
    findings = _check_source("""
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self._a = 0
                self._b = 0
                threading.Thread(target=self._loop, daemon=True).start()

            def _loop(self):
                with self._lock:
                    self._a += 1
                with self._lock:
                    self._a -= 1

            def bad_chain(self):
                self._a = self._b = 0
    """)
    assert [(f.rule, f.symbol) for f in findings] == \
        [("unguarded-shared-mutation", "C.bad_chain")], \
        "\n".join(f.render() for f in findings)


def test_wait_rule_negative_maxsize_queue_is_unbounded():
    """queue.Queue(maxsize=-1) is INFINITE per the stdlib contract —
    its put() never blocks and must not flag."""
    findings = _check_source("""
        import queue
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self._q = queue.Queue(maxsize=-1)

            def send(self, item):
                with self._lock:
                    self._q.put(item)
    """)
    assert findings == []


def test_rules_restriction_never_judges_staleness(tmp_path):
    """`--rules X` must neither warn about nor prune baseline entries
    belonging to rules that did not run — pruning them would
    permanently destroy live, justified entries."""
    bl = tmp_path / "baseline.json"
    live = Finding(rule="use-after-donate", path="bigdl_tpu/x.py",
                   line=1, col=0, message="m", symbol="s")
    live.snippet = "x = step(w, g)"
    write_baseline(str(bl), [live])
    r = _cli("lint", "--rules", "prng-reuse", "--baseline", str(bl))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "stale baseline entry" not in r.stderr
    # pruning under a rule restriction is a broken gate, not a rewrite
    r = _cli("lint", "--rules", "prng-reuse", "--baseline", str(bl),
             "--prune-baseline")
    assert r.returncode == 2, r.stdout + r.stderr
    assert len(json.loads(bl.read_text())["entries"]) == 1


def test_shared_mutation_bare_annotation_is_not_a_write():
    """`self._n: int` (AnnAssign without a value) performs no runtime
    write and must not flag."""
    findings = _check_source("""
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0
                threading.Thread(target=self._loop, daemon=True).start()

            def _loop(self):
                with self._lock:
                    self._n += 1
                with self._lock:
                    self._n -= 1

            def declare(self):
                self._n: int
    """)
    assert findings == []


def test_json_format_with_profile_stays_machine_readable():
    r = _cli("lint", os.path.join(FIXTURES, "prng.py"),
             "--format=json", "--profile", "--no-baseline")
    assert r.returncode == 1
    data = json.loads(r.stdout)        # stdout is PURE JSON
    assert "graftlint profile:" not in r.stdout
    assert data["summary"]["timings_ms"]["<parse>"] >= 0
    assert "prng-reuse" in data["summary"]["timings_ms"]


def test_program_bare_name_skips_class_scope():
    """A bare `flush()` inside a method resolves to the MODULE
    function, never to a same-named method of the enclosing class —
    class bodies are not scopes in Python."""
    p = _program(m="""
        import threading
        import time

        def flush():
            pass

        class Led:
            def __init__(self):
                self._lock = threading.Lock()

            def flush(self):
                time.sleep(0.1)

            def close(self):
                with self._lock:
                    flush()
    """)
    callees = {e.callee for e in p.calls_from["m::Led.close"]}
    assert "m::flush" in callees
    assert "m::Led.flush" not in callees
    # and the phantom edge must not manufacture wait-while-holding
    # findings through bogus entry-lock credit
    assert p.entry_locks["m::Led.flush"] == frozenset()


def test_program_typed_foreign_receiver_vetoes_unique_fallback():
    """A receiver provably constructed from a NON-program class
    (queue.Queue) must not resolve through the unique-method fallback
    to an unrelated program class."""
    p = _program(m="""
        import queue
        import threading

        class Alloc:
            def get(self):
                pass

        class Pool:
            def __init__(self):
                self._inbox = queue.Queue()
                threading.Thread(target=self.drain,
                                 daemon=True).start()

            def drain(self):
                self._inbox.get()
    """)
    assert not p.is_mt("m::Alloc.get")


def test_program_nested_class_attrs_stay_off_the_outer_class():
    """A handler class defined inside __init__ (the LiveMetricsServer
    shape) has its own `self` — its lock/queue attributes must not
    type the OUTER class."""
    p = _program(m="""
        import queue
        import threading

        class Outer:
            def __init__(self):
                class Inner:
                    def __init__(self):
                        self._hidden_lock = threading.Lock()
                        self._q = queue.Queue(maxsize=4)

                self.handler = Inner
                self._q = queue.Queue()
    """)
    outer = p.classes["m::Outer"]
    assert "_hidden_lock" not in outer.lock_attrs
    inner = p.classes["m::Outer.__init__.Inner"]
    assert "_hidden_lock" in inner.lock_attrs
    # the outer _q keeps its own (unbounded) constructor
    assert not outer.attr_ctor["_q"].args
    assert not outer.attr_ctor["_q"].keywords
