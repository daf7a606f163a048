"""Rule ``use-after-donate``.

The hazard that produced the seed era's worst crash (checkpoint reading
buffers the jitted step had donated — a device use-after-free, not an
exception): a name passed at a donated position of a
``jax.jit(donate_argnums=...)`` callable is dead after the call; any
later read in the same scope sees a freed buffer.

Detected shapes, per function scope:

* linear: ``out = step(w, g)`` then ``w`` read below without ``w`` being
  rebound (the safe idiom ``w, opt = step(w, opt, ...)`` rebinds in the
  same statement and is not flagged);
* loop-carried: a donating call inside a ``for``/``while`` whose donated
  arg is never rebound in the loop body — iteration 2 passes a buffer
  iteration 1 already donated.

Donating callables are found from direct ``jax.jit`` assignments,
``@partial(jax.jit, donate_argnums=...)`` decorators, and the
cross-module factory registry (``make_distri_train_step``-style functions
and ``LocalOptimizer._build_step``-style methods that *return* the jitted
step).
"""

from __future__ import annotations

import ast
from typing import Iterator, List, Optional, Set

from bigdl_tpu.analysis.context import ModuleContext, dotted, walk_no_nested
from bigdl_tpu.analysis.engine import Finding
from bigdl_tpu.analysis.rules.base import (Rule, enclosing_loops,
                                           names_stored_in,
                                           scope_name_events)


class UseAfterDonate(Rule):
    name = "use-after-donate"
    description = ("a name passed at a donated position of a jitted "
                   "callable is read again after the call")

    def check(self, mod: ModuleContext) -> Iterator[Finding]:
        if not mod.donations:
            return
        for scope in mod.scopes():
            yield from self._check_scope(mod, scope)

    def _donated_args(self, mod: ModuleContext, call: ast.Call,
                      spec) -> List[ast.Name]:
        """Plain-name arguments at donated positions of one call."""
        out: List[ast.Name] = []
        for i, a in enumerate(call.args):
            if not isinstance(a, ast.Name):
                continue
            if spec.argnums is not None and i in spec.argnums:
                out.append(a)
            elif spec.argnums is None and spec.unresolved:
                out.append(a)       # unknown donation list: all suspect
        for kw in call.keywords:
            if kw.arg and kw.arg in spec.argnames and \
                    isinstance(kw.value, ast.Name):
                out.append(kw.value)
        return out

    def _check_scope(self, mod: ModuleContext,
                     scope: ast.AST) -> Iterator[Finding]:
        calls = []
        for n in walk_no_nested(scope):
            if not isinstance(n, ast.Call):
                continue
            fn = dotted(n.func)
            if fn is None:
                continue
            spec = mod.donation_for(scope, fn.split(".")[-1])
            if spec is None:
                continue
            donated = self._donated_args(mod, n, spec)
            if donated:
                calls.append((n, fn, spec, donated))
        if not calls:
            return

        events = scope_name_events(scope)
        for call, fn, spec, donated in calls:
            # names rebound by the same statement (w, o = step(w, o, ...))
            stmt = mod.parents.get(call)
            while stmt is not None and not isinstance(stmt, ast.stmt):
                stmt = mod.parents.get(stmt)
            rebound_here: Set[str] = set()
            if isinstance(stmt, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
                targets = stmt.targets if isinstance(stmt, ast.Assign) \
                    else [stmt.target]
                for t in targets:
                    rebound_here |= names_stored_in(t)

            qualifier = (" (donation list not statically resolvable: "
                         "treating every positional arg as donated)"
                         if spec.unresolved else "")

            loops = enclosing_loops(mod, call, scope)
            for arg in donated:
                if arg.id in rebound_here:
                    # rebound by this statement — but inside a loop the
                    # rebind must reach THIS name before the next
                    # iteration donates again, which it does (same stmt)
                    continue
                # loop-carried reuse: donated in a loop, never rebound
                # inside that loop
                flagged = False
                for loop in loops:
                    if arg.id not in names_stored_in(loop):
                        yield self.finding(
                            mod, arg,
                            f"'{arg.id}' is donated to '{fn}' inside a "
                            f"loop (line {call.lineno}) and never rebound "
                            f"in the loop body — the second iteration "
                            f"passes an already-donated buffer"
                            f"{qualifier}")
                        flagged = True
                        break
                if flagged:
                    continue
                # linear: a later load before any later store
                later_store: Optional[int] = None
                for ev in events:
                    if ev.name != arg.id or ev.kind != "store":
                        continue
                    if (ev.lineno, ev.col) > (call.lineno, call.col_offset):
                        later_store = ev.lineno
                        break
                for ev in events:
                    if ev.name != arg.id or ev.kind != "load":
                        continue
                    if ev.node is arg:
                        continue
                    if (ev.lineno, ev.col) <= (call.lineno,
                                               call.col_offset):
                        continue
                    if later_store is not None and ev.lineno >= later_store:
                        break
                    yield self.finding(
                        mod, ev.node,
                        f"'{arg.id}' was donated to '{fn}' at line "
                        f"{call.lineno} and is read here — donated "
                        f"buffers are freed by XLA; rebind the name from "
                        f"the call's result or copy before the call"
                        f"{qualifier}")
                    break               # one finding per donated arg
