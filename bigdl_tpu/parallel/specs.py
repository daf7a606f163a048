"""Name-based ``PartitionSpec`` registry + the spec-driven SPMD trainer.

The mesh (``parallel/mesh.py``) says which axes exist; this module says
where every parameter LIVES on them.  A registry is an ordered list of
``(name, path_regex, PartitionSpec)`` rules matched against ``/``-joined
parameter pytree paths (first match wins), with a replicated default —
the name-based assignment scheme of SNIPPETS.md [2], made first-class:

* canonical layouts for the transformer zoo (embedding / qkv / ffn /
  layernorm over ``fsdp``/``tp``), plus an ``fsdp`` dim-0 catch-all so
  the CNN zoo's conv/linear weights shard too;
* specs are *clamped* per leaf: a mesh axis that does not divide the
  dimension is dropped (replicated) rather than padded — strictness over
  silent padding, and the reason degenerate axes are free;
* ``explain()`` renders every param -> spec assignment with per-device
  resident bytes, so a registry mistake is visible before a long run
  (``python -m bigdl_tpu.cli mesh-explain``).

``make_spec_train_step`` is the registry's trainer: parameters and
optimizer state are placed as ``NamedSharding``-committed arrays and the
ordinary jitted train step is left to GSPMD — XLA inserts the FSDP
all-gather before each use, the reduce-scatter behind each gradient, and
the tp collectives around the Megatron-sharded matmuls.  Sharding
changes layout, never math: the step is numerically the unsharded step
(``tests/test_mesh.py`` locks this against the flat ZeRO-1 trainer).
Unlike the flat ring (``allreduce.py``), the saved state keeps every
leaf's GLOBAL shape mesh-independent, which is what lets a checkpoint
written on one mesh shape restore onto another (orbax reshards on
restore against the target shardings).
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

from bigdl_tpu.parallel.mesh import (DATA_AXIS, FSDP_AXIS, TP_AXIS,
                                     axis_size, batch_sharding, describe,
                                     dp_axes, dp_size)


def _P(*args):
    from jax.sharding import PartitionSpec
    return PartitionSpec(*args)


@dataclass(frozen=True)
class SpecRule:
    """One assignment rule: ``pattern`` (regex, ``re.search``) against a
    ``/``-joined param path -> ``spec``.  ``name`` labels the rule in
    ``explain()`` output."""
    name: str
    pattern: str
    spec: "jax.sharding.PartitionSpec"


def transformer_rules() -> List[SpecRule]:
    """Canonical transformer-zoo layouts (SNIPPETS.md [2]), adapted to
    this repo's Torch-style ``(out, in)`` weight layout:

    * embeddings (``tok``/``pos``): rows over ``fsdp`` x ``tp``;
    * qkv projections / ffn-up: OUT dim over ``tp`` (Megatron column),
      IN dim over ``fsdp``;
    * attention-out / ffn-down: IN dim over ``tp`` (Megatron row), OUT
      dim over ``fsdp``;
    * column-side biases over ``tp``; everything else falls through to
      the ``fsdp`` dim-0 catch-all (layernorm scales included — the
      SNIPPETS ``layer_norm -> PS(fsdp)`` layout).
    """
    return [
        SpecRule("embedding", r"/(tok|pos)$", _P((FSDP_AXIS, TP_AXIS))),
        SpecRule("qkv", r"/w[qkv]$", _P(TP_AXIS, FSDP_AXIS)),
        SpecRule("qkv-bias", r"/b[qkv]$", _P(TP_AXIS)),
        SpecRule("attn-out", r"/wo$", _P(FSDP_AXIS, TP_AXIS)),
        SpecRule("ffn-up", r"/fc1/weight$", _P(TP_AXIS, FSDP_AXIS)),
        SpecRule("ffn-up-bias", r"/fc1/bias$", _P(TP_AXIS)),
        SpecRule("ffn-down", r"/fc2/weight$", _P(FSDP_AXIS, TP_AXIS)),
    ]


def fsdp_catchall() -> SpecRule:
    """Dim-0 ``fsdp`` sharding for anything the named rules miss: conv
    kernels, plain Linear weights, biases, layernorm scales.  Leaves
    whose dim 0 the axis does not divide are clamped to replicated."""
    return SpecRule("fsdp-default", r"", _P(FSDP_AXIS))


def default_rules() -> List[SpecRule]:
    return transformer_rules() + [fsdp_catchall()]


@dataclass
class ParamAssignment:
    """One resolved param -> spec row (the ``explain()`` unit)."""
    path: str
    shape: Tuple[int, ...]
    dtype: str
    rule: str                    # matching rule name ("<default>" if none)
    spec: "jax.sharding.PartitionSpec"   # after per-leaf clamping
    requested: "jax.sharding.PartitionSpec"
    bytes_total: int
    bytes_per_device: int


class SpecRegistry:
    """Ordered rule list + replicated default, with mesh-aware clamping.

    ``rules``: ``SpecRule`` instances or bare ``(pattern, spec)`` pairs
    (the ``MEGATRON_MLP_RULES`` legacy form).
    """

    def __init__(self, rules: Optional[Sequence] = None, default=None):
        self.rules: List[SpecRule] = []
        for r in (default_rules() if rules is None else rules):
            if isinstance(r, SpecRule):
                self.rules.append(r)
            else:
                pattern, spec = r
                self.rules.append(SpecRule(pattern, pattern, spec))
        self.default = default if default is not None else _P()

    # -- resolution ----------------------------------------------------------

    def rule_for(self, path: str) -> Optional[SpecRule]:
        for rule in self.rules:
            if re.search(rule.pattern, path):
                return rule
        return None

    def spec_for(self, path: str):
        rule = self.rule_for(path)
        return rule.spec if rule is not None else self.default

    @staticmethod
    def clamp(spec, shape, mesh):
        """Adapt a rule's spec to one leaf: drop spec axes that do not
        divide the matching dim (XLA would silently pad; replication is
        the honest fallback), trim entries beyond the leaf's rank (the
        catch-all rules match scalars and 1-D leaves too — a 0-d
        temperature under the ``fsdp`` default must replicate, not
        crash), and strip trailing Nones.  ``explain()`` marks every
        clamped row with the requested spec so a wrong rule stays
        visible."""
        clean = []
        for d, entry in enumerate(spec[:len(shape)]):
            if entry is None:
                clean.append(None)
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            group = 1
            for a in axes:
                group *= axis_size(mesh, a)
            clean.append(entry if group > 1 and
                         shape[d] % group == 0 else None)
        while clean and clean[-1] is None:
            clean.pop()
        return _P(*clean)

    def resolve(self, params, mesh) -> List[ParamAssignment]:
        """Every leaf's final assignment, in tree-flatten order."""
        import numpy as np

        rows: List[ParamAssignment] = []
        for path, leaf in _named_leaves(params):
            rule = self.rule_for(path)
            requested = rule.spec if rule is not None else self.default
            shape = tuple(getattr(leaf, "shape", ()))
            clamped = self.clamp(requested, shape, mesh)
            shards = 1
            for entry in clamped:
                for a in (entry if isinstance(entry, tuple) else (entry,)):
                    if a is not None:
                        shards *= axis_size(mesh, a)
            nbytes = int(np.prod(shape, dtype=np.int64)) * \
                np.dtype(getattr(leaf, "dtype", np.float32)).itemsize
            rows.append(ParamAssignment(
                path=path, shape=shape,
                dtype=str(np.dtype(getattr(leaf, "dtype", np.float32))),
                rule=rule.name if rule is not None else "<default>",
                spec=clamped, requested=requested,
                bytes_total=nbytes,
                bytes_per_device=nbytes // shards))
        return rows

    def shardings(self, params, mesh):
        """Pytree of ``NamedSharding`` matching ``params``."""
        import jax
        from jax.sharding import NamedSharding

        rows = self.resolve(params, mesh)
        leaves, treedef = jax.tree_util.tree_flatten(params)
        if len(rows) != len(leaves):
            # _named_leaves walks dict/list/tuple only; a custom pytree
            # node would silently shift every later spec onto the wrong
            # parameter — fail with the mismatch instead
            raise ValueError(
                f"registry path walk found {len(rows)} leaves but "
                f"tree_flatten found {len(leaves)}: the params pytree "
                "contains nodes the /-path walk does not traverse "
                "(custom pytree types?) — register rules against a "
                "dict/list/tuple tree")
        out = [NamedSharding(mesh, r.spec) for r in rows]
        return jax.tree_util.tree_unflatten(treedef, out)

    def place(self, params, mesh):
        """``device_put`` the pytree per the registry — the entry point
        both trainers and serving use to adopt the mesh."""
        import jax
        return jax.tree_util.tree_map(
            jax.device_put, params, self.shardings(params, mesh))

    # -- reporting -----------------------------------------------------------

    def explain(self, params, mesh) -> str:
        """Human-readable dump of every param -> spec assignment plus the
        resident-bytes story — run BEFORE a long job, not after."""
        rows = self.resolve(params, mesh)
        total = sum(r.bytes_total for r in rows)
        per_dev = sum(r.bytes_per_device for r in rows)
        width = max([len(r.path) for r in rows] + [6])
        L = [f"mesh {describe(mesh)['axes']}  "
             f"(dp={dp_size(mesh)} over {dp_axes(mesh)})",
             f"{'param':<{width}}  {'shape':>18}  {'rule':<14} "
             f"{'spec':<24} per-device"]
        for r in rows:
            note = "" if str(r.spec) == str(r.requested) else \
                f"  (requested {r.requested}, clamped)"
            L.append(f"{r.path:<{width}}  {str(r.shape):>18}  "
                     f"{r.rule:<14} {str(r.spec):<24} "
                     f"{_fmt_bytes(r.bytes_per_device)}{note}")
        L.append(f"{'TOTAL':<{width}}  {'':>18}  {'':<14} {'':<24} "
                 f"{_fmt_bytes(per_dev)} of {_fmt_bytes(total)} "
                 f"replicated ({per_dev / max(total, 1):.3f}x)")
        return "\n".join(L)

    def traffic(self, params, mesh) -> dict:
        """Analytic per-axis collective bytes per device per step for the
        spec-sharded trainer (the ledger/run-report figure).  fsdp pays
        gather-before-use + reduce-scatter-after-grad per parameter; the
        data axis pays the gradient all-reduce of each (possibly
        fsdp-scattered) leaf.  tp traffic is activation-shaped and so
        not statically known from params alone — reported as such."""
        f = axis_size(mesh, FSDP_AXIS)
        d = axis_size(mesh, DATA_AXIS)
        fsdp_bytes = 0
        data_bytes = 0
        for r in self.resolve(params, mesh):
            spec_axes = set()
            for entry in r.spec:
                for a in (entry if isinstance(entry, tuple) else (entry,)):
                    if a is not None:
                        spec_axes.add(a)
            if FSDP_AXIS in spec_axes and f > 1:
                # all-gather for use + reduce-scatter of the gradient
                fsdp_bytes += 2 * r.bytes_total * (f - 1) // f
            if d > 1:
                # ring all-reduce of this leaf's (scattered) gradient
                shard = r.bytes_total if FSDP_AXIS not in spec_axes \
                    else r.bytes_total // f
                data_bytes += 2 * shard * (d - 1) // d
        return {DATA_AXIS: data_bytes, FSDP_AXIS: fsdp_bytes,
                TP_AXIS: None,        # activation-dependent
                "note": "analytic per-device bytes/step; tp traffic "
                        "depends on activation shapes"}


def _named_leaves(params, prefix: str = ""):
    """(path, leaf) pairs in ``tree_flatten`` order (sorted dict keys,
    list/tuple indices) — the same walk ``tensor_parallel
    .named_param_paths`` does, kept in one place."""
    if isinstance(params, dict):
        for k in sorted(params):
            yield from _named_leaves(params[k], f"{prefix}/{k}")
    elif isinstance(params, (list, tuple)):
        for i, v in enumerate(params):
            yield from _named_leaves(v, f"{prefix}/{i}")
    elif params is not None and hasattr(params, "shape"):
        yield (prefix or "/"), params


def _fmt_bytes(n: int) -> str:
    for unit in ("B", "KB", "MB", "GB"):
        if n < 1024 or unit == "GB":
            return f"{n:7.1f}{unit}" if unit != "B" else f"{n:7d}B"
        n = n / 1024
    return f"{n}B"


# -- the spec-driven SPMD train step -----------------------------------------

def make_spec_train_step(model, criterion, optim, mesh, config,
                         registry: Optional[SpecRegistry] = None,
                         guard_nonfinite: bool = True,
                         compute_dtype=None):
    """Build the registry-sharded train step: ordinary jit, GSPMD
    collectives.

    Returns ``(step, init_fn, registry)``; ``init_fn(params)`` places
    the replicated pytree per the registry and builds the optimizer
    state with matching shardings (eager elementwise ops follow their
    input's sharding, so ``optim.init_state`` over placed params lands
    sharded).  The step signature and non-finite-guard semantics match
    ``LocalOptimizer._build_step`` — this IS that step, with layout.
    """
    import jax
    import jax.numpy as jnp

    registry = registry or SpecRegistry()

    def _step(params, opt_state, model_state, data, labels, rng,
              stepno, clr):
        def loss_fn(p):
            from bigdl_tpu.core.precision import training_loss
            return training_loss(model, criterion, p, model_state, data,
                                 labels, rng, compute_dtype=compute_dtype)
        (loss, new_ms), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        cfg = config.clone()
        cfg["clr"] = clr
        with jax.named_scope("update"):
            new_params, new_opt = optim.update(grads, params, opt_state,
                                               cfg, stepno)
        if guard_nonfinite:
            with jax.named_scope("guard"):
                ok = jnp.isfinite(loss)
                for g in jax.tree_util.tree_leaves(grads):
                    ok &= jnp.all(jnp.isfinite(g))
                sel = lambda new, old: jax.tree_util.tree_map(
                    lambda a, b: jnp.where(ok, a, b), new, old)
                new_params = sel(new_params, params)
                new_opt = sel(new_opt, opt_state)
                new_ms = sel(new_ms, model_state)
                loss = jnp.where(ok, loss, jnp.nan)
        return new_params, new_opt, new_ms, loss

    # same donation policy as the flat trainer: params/opt_state buffers
    # are dead after the step on TPU (halves state residency); on the
    # CPU test mesh donation + the compilation cache corrupts the heap
    # (jaxlib 0.4.x) and memory is not the constraint there
    platforms = {d.platform for d in mesh.devices.flat}
    donate = () if platforms <= {"cpu"} else (0, 1)
    step = jax.jit(_step, donate_argnums=donate)
    step.donates_state = bool(donate)

    def init_fn(params):
        from bigdl_tpu.observability import tracer
        with tracer.span("specs.place", mesh=describe(mesh)["axes"]):
            placed = registry.place(params, mesh)
            opt_state = optim.init_state(placed)
        return placed, opt_state

    return step, init_fn, registry


def make_spec_eval_fn(model):
    """Jitted eval forward over registry-sharded params (GSPMD inserts
    the gathers) — validation never reassembles weights on the host."""
    import jax
    from functools import partial
    return jax.jit(partial(model.apply, training=False))


# -- mesh-explain CLI ---------------------------------------------------------

_EXPLAIN_MODELS = ("transformer", "lenet", "inception_v1", "resnet50")


def mesh_explain_main(argv=None) -> int:
    """``python -m bigdl_tpu.cli mesh-explain`` — print the mesh shape
    and every parameter's resolved PartitionSpec + per-device bytes for
    a zoo model, so spec-registry mistakes are visible before a long
    run.  Exit 0 on success, 2 on a bad spec/flag."""
    import argparse

    ap = argparse.ArgumentParser(
        prog="bigdl_tpu.cli mesh-explain",
        description="Dump the param->PartitionSpec assignment of the "
                    "spec registry over a mesh (docs/distributed.md).")
    ap.add_argument("--mesh", default=None,
                    help="mesh shape spec, e.g. data=2,fsdp=2,tp=2 or "
                         "4x2 (default: BIGDL_TPU_MESH or all-data)")
    ap.add_argument("--model", choices=_EXPLAIN_MODELS,
                    default="transformer")
    ap.add_argument("--cpu-devices", type=int, default=0,
                    help="force N virtual CPU devices (test topology)")
    ap.add_argument("--vocab", type=int, default=1024)
    ap.add_argument("--embed", type=int, default=256)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--max-len", type=int, default=256)
    args = ap.parse_args(argv)

    if args.cpu_devices:
        import jax
        from bigdl_tpu.compat import force_cpu_devices
        jax.config.update("jax_platforms", "cpu")
        force_cpu_devices(args.cpu_devices)
    import jax

    from bigdl_tpu.parallel.mesh import build_mesh

    try:
        mesh = build_mesh(args.mesh)
    except ValueError as e:
        print(f"mesh-explain: {e}")
        return 2

    if args.model == "transformer":
        from bigdl_tpu.models.transformer import TransformerLM
        model = TransformerLM(args.vocab, max_len=args.max_len,
                              embed_dim=args.embed, num_heads=args.heads,
                              num_layers=args.layers)
    elif args.model == "lenet":
        from bigdl_tpu.models.lenet import LeNet5
        model = LeNet5(10)
    elif args.model == "inception_v1":
        from bigdl_tpu.models.inception import Inception_v1
        model = Inception_v1(1000)
    else:
        from bigdl_tpu.models.resnet import ResNet
        model = ResNet(1000, depth=50, dataset="imagenet")
    params, _ = model.init(jax.random.PRNGKey(0))

    registry = SpecRegistry()
    print(registry.explain(params, mesh))
    traffic = registry.traffic(params, mesh)
    print(f"analytic collective bytes/device/step: "
          f"data={_fmt_bytes(traffic[DATA_AXIS]).strip()} "
          f"fsdp={_fmt_bytes(traffic[FSDP_AXIS]).strip()} "
          f"tp=activation-dependent")
    return 0
