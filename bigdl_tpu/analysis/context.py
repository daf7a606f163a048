"""Per-module AST context shared by every graftlint rule.

The rules need three things no single ``ast.walk`` gives them:

* **Traced regions** — which function bodies execute under a jax trace
  (``jax.jit`` / ``shard_map`` / ``pmap`` / ``vmap`` / ``grad``), whether
  the function is decorated, wrapped at a call site
  (``jax.jit(shard_map(_step, ...))``), or passed through
  ``functools.partial``.  Host side effects are only hazards *inside*
  these regions.
* **Donation sites** — which callables donate which argument positions
  (``donate_argnums`` / ``donate_argnames``), including the repo's
  factory idiom where a module-level function or a method *returns* the
  jitted step (``make_distri_train_step`` → the trainer's ``step``,
  ``LocalOptimizer._build_step``), which a single per-module pass would
  never connect.
* **Ordered scope events** — statement-ordered name loads/stores within
  one function scope (nested ``def``/``lambda`` bodies excluded), which
  the use-after-donate and prng-reuse rules replay as a tiny abstract
  interpretation.

Everything here is stdlib-``ast`` only and never imports jax: the linter
must run anywhere, including build containers without an accelerator
stack.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from functools import cached_property
from typing import Dict, Iterator, List, Optional, Set, Tuple

# callables whose first positional argument is traced by jax.  ``jit``
# and friends are distinctive enough that any dotted path ending in one
# of them counts (jax.jit, compat.shard_map, functools-partial'd jit).
TRACE_WRAPPERS = {
    "jit", "pmap", "vmap", "grad", "value_and_grad", "shard_map",
    "named_call", "checkpoint", "remat", "pallas_call",
}

_PARTIAL = {"partial", "functools.partial"}


def dotted(node: ast.AST) -> Optional[str]:
    """``a.b.c`` for an Attribute/Name chain, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def call_name(call: ast.Call) -> Optional[str]:
    return dotted(call.func)


def is_trace_wrapper(name: Optional[str]) -> bool:
    return name is not None and name.split(".")[-1] in TRACE_WRAPPERS


def walk_no_nested(node: ast.AST,
                   skip_root_check: bool = True) -> Iterator[ast.AST]:
    """``ast.walk`` that does not descend into nested function/class
    bodies — the traversal for single-scope analyses.  The root node
    itself is yielded even when it is a def."""
    todo = [node]
    first = True
    while todo:
        cur = todo.pop()
        if not first and isinstance(
                cur, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda,
                      ast.ClassDef)):
            yield cur            # the binding itself, not its body
            continue
        first = False
        yield cur
        todo.extend(ast.iter_child_nodes(cur))


def stored_names(target: ast.AST) -> Set[str]:
    """Plain names bound by an assignment target (tuples unpacked;
    attribute/subscript stores are mutations, not bindings)."""
    out: Set[str] = set()
    for n in ast.walk(target):
        if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Store):
            out.add(n.id)
    return out


@dataclass
class DonationSpec:
    """One donating callable: positions and/or parameter names donated.
    ``argnums=None`` means the donation list could not be resolved
    statically — rules treat every positional arg as potentially
    donated and say so in the message."""
    argnums: Optional[Set[int]]
    argnames: Set[str] = field(default_factory=set)
    unresolved: bool = False


@dataclass
class FactoryReturn:
    """A module-level function returning a jitted-with-donation callable:
    ``tuple_index`` is the position inside the returned tuple (None for a
    bare return)."""
    spec: DonationSpec
    tuple_index: Optional[int]


class ModuleContext:
    """Parsed module + the derived facts rules consume."""

    def __init__(self, path: str, source: str,
                 factories: Optional[Dict[str, FactoryReturn]] = None):
        self.path = path
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=path)
        # the engine assigns the complete cross-module registry AFTER
        # construction (it needs every module's export_factories first);
        # every factory-dependent fact below is a cached_property, so it
        # materializes on first rule access — construction is parse-only
        # and the engine pays one parse per file, not two
        self.factories = factories or {}
        self._qualnames: Dict[ast.AST, str] = {}

    @cached_property
    def parents(self) -> Dict[ast.AST, ast.AST]:
        out: Dict[ast.AST, ast.AST] = {}
        for parent in ast.walk(self.tree):
            for child in ast.iter_child_nodes(parent):
                out[child] = parent
        return out

    @cached_property
    def jax_random_prefixes(self) -> Set[str]:
        return self._find_jax_random_prefixes()

    @cached_property
    def numpy_aliases(self) -> Set[str]:
        return self._find_numpy_aliases()

    @cached_property
    def observability_names(self) -> Set[str]:
        return self._find_observability_names()

    @cached_property
    def traced_entry_nodes(self) -> List[ast.AST]:
        return self._find_traced_regions()

    @cached_property
    def donations(self) -> Dict[ast.AST,
                                Dict[str, Optional[DonationSpec]]]:
        return self._find_donations()

    # -- names / positions ---------------------------------------------------

    def qualname(self, node: ast.AST) -> str:
        """Dotted name of the enclosing defs, e.g. ``Outer.inner`` —
        '<module>' at top level."""
        if node in self._qualnames:
            return self._qualnames[node]
        parts: List[str] = []
        cur: Optional[ast.AST] = node
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.ClassDef)):
                parts.append(cur.name)
            cur = self.parents.get(cur)
        name = ".".join(reversed(parts)) or "<module>"
        self._qualnames[node] = name
        return name

    def enclosing_scope(self, node: ast.AST) -> ast.AST:
        cur = self.parents.get(node)
        while cur is not None and not isinstance(
                cur, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Module)):
            cur = self.parents.get(cur)
        return cur if cur is not None else self.tree

    def scopes(self) -> Iterator[ast.AST]:
        """The module plus every function def, outermost first."""
        yield self.tree
        for n in ast.walk(self.tree):
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield n

    # -- import surveys ------------------------------------------------------

    def _find_jax_random_prefixes(self) -> Set[str]:
        """Dotted prefixes that denote ``jax.random`` in this module."""
        prefixes = set()
        for n in ast.walk(self.tree):
            if isinstance(n, ast.Import):
                for a in n.names:
                    if a.name == "jax":
                        prefixes.add((a.asname or "jax") + ".random")
                    elif a.name == "jax.random":
                        prefixes.add(a.asname or "jax.random")
            elif isinstance(n, ast.ImportFrom) and n.module == "jax":
                for a in n.names:
                    if a.name == "random":
                        prefixes.add(a.asname or "random")
        return prefixes

    def _find_numpy_aliases(self) -> Set[str]:
        aliases = set()
        for n in ast.walk(self.tree):
            if isinstance(n, ast.Import):
                for a in n.names:
                    if a.name == "numpy":
                        aliases.add(a.asname or "numpy")
            elif isinstance(n, ast.ImportFrom) and n.module == "numpy":
                # "from numpy import asarray" — rare; track the names
                for a in n.names:
                    aliases.add(a.asname or a.name)
        return aliases

    def _find_observability_names(self) -> Set[str]:
        """Local names bound to the observability emission surface
        (``ledger``, ``tracer``, or functions imported from them)."""
        names = set()
        for n in ast.walk(self.tree):
            if isinstance(n, ast.ImportFrom) and n.module and \
                    "observability" in n.module:
                for a in n.names:
                    names.add(a.asname or a.name)
            elif isinstance(n, ast.Import):
                for a in n.names:
                    if "observability" in a.name:
                        names.add((a.asname or a.name).split(".")[0])
        return names

    # -- traced-region discovery ---------------------------------------------

    def _find_traced_regions(self) -> List[ast.AST]:
        traced: Set[ast.AST] = set()
        defs_by_name: Dict[str, List[ast.AST]] = {}
        for n in ast.walk(self.tree):
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs_by_name.setdefault(n.name, []).append(n)

        def mark_name(name: str) -> None:
            for d in defs_by_name.get(name, ()):
                traced.add(d)

        for n in ast.walk(self.tree):
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in n.decorator_list:
                    if self._decorator_traces(dec):
                        traced.add(n)
            elif isinstance(n, ast.Call):
                fn = call_name(n)
                if is_trace_wrapper(fn) and n.args:
                    first = n.args[0]
                    if isinstance(first, ast.Name):
                        mark_name(first.id)
                    elif isinstance(first, ast.Lambda):
                        traced.add(first)
                elif fn in _PARTIAL and n.args and \
                        is_trace_wrapper(dotted(n.args[0])):
                    # partial(jit, ...)(f) or partial(shard_map, f, ...)
                    if len(n.args) > 1 and isinstance(n.args[1], ast.Name):
                        mark_name(n.args[1].id)
                # shard_map(f=..., ...) keyword form
                if is_trace_wrapper(fn):
                    for kw in n.keywords:
                        if kw.arg in ("f", "fun", "func") and \
                                isinstance(kw.value, ast.Name):
                            mark_name(kw.value.id)

        # keep only outermost traced nodes: walking an entry node already
        # covers any traced def nested inside it
        entries = []
        for node in traced:
            cur = self.parents.get(node)
            inside = False
            while cur is not None:
                if cur in traced:
                    inside = True
                    break
                cur = self.parents.get(cur)
            if not inside:
                entries.append(node)
        entries.sort(key=lambda n: n.lineno)
        return entries

    def _decorator_traces(self, dec: ast.AST) -> bool:
        if is_trace_wrapper(dotted(dec)):
            return True
        if isinstance(dec, ast.Call):
            if is_trace_wrapper(dotted(dec.func)):
                return True
            if dotted(dec.func) in _PARTIAL and dec.args and \
                    is_trace_wrapper(dotted(dec.args[0])):
                return True
        return False

    def traced_regions(self) -> Iterator[Tuple[ast.AST, str]]:
        for node in self.traced_entry_nodes:
            yield node, self.qualname(node)

    # Methods that execute under trace by FRAMEWORK CONVENTION rather
    # than lexical wrapping: every trainer step builder jits
    # ``Module.apply``/``Criterion.apply``, so their bodies are traced
    # even though no jit call wraps them in this module.
    _CONVENTION_METHODS = {"apply"}

    def convention_regions(self) -> Iterator[Tuple[ast.AST, str]]:
        """Class methods traced by convention (``Module.apply``), minus
        any already inside a lexical traced region."""
        traced = set(self.traced_entry_nodes)
        for n in ast.walk(self.tree):
            if not isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if n.name not in self._CONVENTION_METHODS:
                continue
            if not isinstance(self.parents.get(n), ast.ClassDef):
                continue
            argnames = {a.arg for a in n.args.args + n.args.kwonlyargs}
            # the Module/Criterion apply shapes: (params, state, input)
            # or (input, target); a generic .apply() is not traced
            if "input" not in argnames and not \
                    {"params", "state"} <= argnames:
                continue
            cur: Optional[ast.AST] = n
            inside = False
            while cur is not None:
                if cur in traced:
                    inside = True
                    break
                cur = self.parents.get(cur)
            if not inside:
                yield n, self.qualname(n)

    # -- donation discovery --------------------------------------------------

    def _resolve_argnums(self, node: ast.AST, scope: ast.AST,
                         depth: int = 0) -> Optional[Set[int]]:
        """Best-effort static value of a ``donate_argnums`` expression:
        int/tuple literals, IfExp (union of branches), and one level of
        name-following within the same scope."""
        if depth > 3 or node is None:
            return None
        if isinstance(node, ast.Constant) and isinstance(node.value, int):
            return {node.value}
        if isinstance(node, (ast.Tuple, ast.List)):
            out: Set[int] = set()
            for el in node.elts:
                got = self._resolve_argnums(el, scope, depth + 1)
                if got is None:
                    return None
                out |= got
            return out
        if isinstance(node, ast.IfExp):
            a = self._resolve_argnums(node.body, scope, depth + 1)
            b = self._resolve_argnums(node.orelse, scope, depth + 1)
            if a is None and b is None:
                return None
            return (a or set()) | (b or set())
        if isinstance(node, ast.Name):
            # nearest assignment to that name in the same scope
            best = None
            for n in walk_no_nested(scope):
                if isinstance(n, ast.Assign) and len(n.targets) == 1 and \
                        isinstance(n.targets[0], ast.Name) and \
                        n.targets[0].id == node.id and \
                        n.lineno <= node.lineno:
                    if best is None or n.lineno > best.lineno:
                        best = n
            if best is not None:
                return self._resolve_argnums(best.value, scope, depth + 1)
        return None

    def _donation_from_call(self, call: ast.Call,
                            scope: ast.AST) -> Optional[DonationSpec]:
        kw = {k.arg: k.value for k in call.keywords if k.arg}
        if "donate_argnums" not in kw and "donate_argnames" not in kw:
            return None
        argnums = None
        unresolved = False
        if "donate_argnums" in kw:
            argnums = self._resolve_argnums(kw["donate_argnums"], scope)
            if argnums is None:
                unresolved = True
            elif not argnums:
                argnums = None      # statically empty: donates nothing
                if "donate_argnames" not in kw:
                    return None
        argnames: Set[str] = set()
        if "donate_argnames" in kw:
            v = kw["donate_argnames"]
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                argnames.add(v.value)
            elif isinstance(v, (ast.Tuple, ast.List)):
                for el in v.elts:
                    if isinstance(el, ast.Constant) and \
                            isinstance(el.value, str):
                        argnames.add(el.value)
                    else:
                        unresolved = True
            else:
                unresolved = True
        return DonationSpec(argnums=argnums, argnames=argnames,
                            unresolved=unresolved)

    def _find_donations(self) -> Dict[ast.AST,
                                      Dict[str, Optional[DonationSpec]]]:
        """Per-scope map of callable name -> DonationSpec for every
        jitted callable visible in this module: direct assignments,
        decorated defs, and results of known donating factories.  A
        non-donating ``jax.jit`` assignment records ``None`` so a local
        ``step`` masks a same-named donating ``step`` from another
        scope."""
        donations: Dict[ast.AST, Dict[str, Optional[DonationSpec]]] = {}

        def record(scope: ast.AST, name: str,
                   spec: Optional[DonationSpec]) -> None:
            donations.setdefault(scope, {})[name] = spec

        for n in ast.walk(self.tree):
            # step = jax.jit(f, donate_argnums=...)   /  self._step = ...
            if isinstance(n, ast.Assign) and isinstance(n.value, ast.Call):
                fn = call_name(n.value)
                if fn is not None and fn.split(".")[-1] == "jit":
                    scope = self.enclosing_scope(n)
                    spec = self._donation_from_call(n.value, scope)
                    for t in n.targets:
                        if isinstance(t, ast.Name):
                            record(scope, t.id, spec)
                        elif isinstance(t, ast.Attribute):
                            record(scope, t.attr, spec)
                # factory results: step, layout, init = make_..._step(...)
                key = fn.split(".")[-1] if fn else None
                fac = self.factories.get(key) if key else None
                if fac is not None and len(n.targets) == 1:
                    scope = self.enclosing_scope(n)
                    t = n.targets[0]
                    if fac.tuple_index is None and isinstance(t, ast.Name):
                        record(scope, t.id, fac.spec)
                    elif fac.tuple_index is not None and \
                            isinstance(t, (ast.Tuple, ast.List)) and \
                            fac.tuple_index < len(t.elts):
                        el = t.elts[fac.tuple_index]
                        if isinstance(el, ast.Name):
                            record(scope, el.id, fac.spec)
                        elif isinstance(el, ast.Attribute):
                            record(scope, el.attr, fac.spec)
            # @partial(jax.jit, donate_argnums=...) above def f
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in n.decorator_list:
                    if isinstance(dec, ast.Call) and (
                            is_trace_wrapper(dotted(dec.func)) or
                            dotted(dec.func) in _PARTIAL):
                        spec = self._donation_from_call(
                            dec, self.enclosing_scope(n))
                        if spec is not None:
                            record(self.enclosing_scope(n), n.name, spec)
        return donations

    def donation_for(self, scope: ast.AST,
                     name: str) -> Optional[DonationSpec]:
        """DonationSpec for calls to ``name`` made from ``scope``,
        resolved through the enclosing-scope chain (nearest binding
        wins; an explicit non-donating binding masks outer ones)."""
        cur: Optional[ast.AST] = scope
        while cur is not None:
            scoped = self.donations.get(cur)
            if scoped is not None and name in scoped:
                return scoped[name]
            if cur is self.tree:
                break
            nxt = self.parents.get(cur)
            while nxt is not None and not isinstance(
                    nxt, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.Module)):
                nxt = self.parents.get(nxt)
            cur = nxt if nxt is not None else self.tree
        return None

    def export_factories(self) -> Dict[str, FactoryReturn]:
        """Module-level functions, and methods of module-level classes
        (``LocalOptimizer._build_step``, called as ``self._build_step()``),
        that RETURN a jitted-with-donation callable (directly or inside a
        tuple) — the cross-module seam the per-module donation map cannot
        see.  Keyed by bare function name; consumed by later modules via
        the shared factory registry."""
        out: Dict[str, FactoryReturn] = {}
        defs = [n for top in self.tree.body
                for n in (top.body if isinstance(top, ast.ClassDef)
                          else [top])]
        for n in defs:
            if not isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            # names bound to donating jit calls inside this function
            local: Dict[str, DonationSpec] = {}
            for sub in walk_no_nested(n):
                if isinstance(sub, ast.Assign) and \
                        isinstance(sub.value, ast.Call):
                    fn = call_name(sub.value)
                    if fn is not None and fn.split(".")[-1] == "jit":
                        spec = self._donation_from_call(sub.value, n)
                        if spec is not None:
                            for t in sub.targets:
                                if isinstance(t, ast.Name):
                                    local[t.id] = spec
            for sub in walk_no_nested(n):
                if not isinstance(sub, ast.Return) or sub.value is None:
                    continue
                val = sub.value
                if isinstance(val, ast.Name) and val.id in local:
                    out[n.name] = FactoryReturn(local[val.id], None)
                elif isinstance(val, ast.Call):
                    fn = call_name(val)
                    if fn is not None and fn.split(".")[-1] == "jit":
                        spec = self._donation_from_call(val, n)
                        if spec is not None:
                            out[n.name] = FactoryReturn(spec, None)
                elif isinstance(val, ast.Tuple):
                    for i, el in enumerate(val.elts):
                        if isinstance(el, ast.Name) and el.id in local:
                            out[n.name] = FactoryReturn(local[el.id], i)
                            break
        return out
