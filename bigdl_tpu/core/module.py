"""Core module protocol for the TPU-native BigDL rebuild.

Reference parity target: ``nn/abstractnn/AbstractModule.scala:41-325`` in
zzwgit/BigDL (mutable Torch-style modules with ``forward/backward/
updateOutput/updateGradInput/accGradParameters``).  The TPU-native design is a
*functional* module protocol — every module is a pure function of
``(params, state, input)`` so the whole model jits into a single XLA program —
wrapped in a thin stateful facade that preserves the Torch-style user surface
(``forward``, ``backward``, ``zero_grad_parameters``, ``training``/``evaluate``
modes, ``get_parameters``).

Design mapping (SURVEY.md section 7):

* ``updateOutput``           -> ``Module.apply(params, state, x)`` (pure)
* ``updateGradInput`` +
  ``accGradParameters``      -> ``jax.vjp`` over ``apply`` (autodiff; the
                                 stateful ``backward`` facade accumulates into
                                 ``grad_params`` like accGradParameters did)
* cached ``output/gradInput``-> facade attributes, never used under jit
* ``Module.flatten``
  (contiguous param buffer,
  ``nn/Module.scala:44-74``)  -> params stay a pytree; ``get_parameters``
                                 materialises the flat (weights, grads) pair
                                 only for checkpoints / parity tests
* ``training()/evaluate()``  -> a ``training`` kwarg threaded through
                                 ``apply`` (BatchNorm/Dropout consume it)
* per-module RNG (Dropout)   -> explicit ``rng`` threading, split per child

``Activity`` (Tensor-or-Table union, ``nn/abstractnn/Activity.scala``) maps to
"any pytree": inputs/outputs may be jnp arrays, tuples/lists, or dicts.
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

# Public aliases ------------------------------------------------------------

Params = Any   # pytree of jnp.ndarray
State = Any    # pytree of jnp.ndarray (e.g. BatchNorm running stats)
Activity = Any  # jnp.ndarray | pytree of them (the Tensor|Table union)

_uid_lock = threading.Lock()
_uid_counters: dict = {}


def _next_uid(cls_name: str) -> int:
    with _uid_lock:
        n = _uid_counters.get(cls_name, 0) + 1
        _uid_counters[cls_name] = n
        return n


def _is_tracing(*trees) -> bool:
    return any(isinstance(l, jax.core.Tracer)
               for t in trees for l in jax.tree_util.tree_leaves(t))


def _timed_apply(fn):
    """Wrap a subclass ``apply`` so eager calls accumulate ``forward_time``.

    Under any jax transform (jit/vjp/vmap) the inputs are Tracers and timing
    is skipped — the traced program runs as one XLA computation where
    per-layer wall time is meaningless (use the jax profiler there).  Eager
    calls block on the outputs so the numbers cover real device work, like
    the reference's synchronous per-module timers.
    """
    @functools.wraps(fn)
    def timed(self, params, state, input, **kwargs):
        if _is_tracing(params, state, input):
            return fn(self, params, state, input, **kwargs)
        t0 = time.perf_counter_ns()
        out = fn(self, params, state, input, **kwargs)
        jax.block_until_ready(out)
        self.forward_time += time.perf_counter_ns() - t0
        return out
    timed._bigdl_timed = True
    return timed


def tree_zeros_like(tree: Params) -> Params:
    return jax.tree_util.tree_map(jnp.zeros_like, tree)


def tree_add(a: Params, b: Params) -> Params:
    return jax.tree_util.tree_map(jnp.add, a, b)


def tree_scale(tree: Params, s) -> Params:
    return jax.tree_util.tree_map(lambda t: t * s, tree)


def flatten_params(tree: Params) -> jnp.ndarray:
    """Flatten a params pytree into one contiguous 1-D buffer.

    Parity with ``Module.flatten`` (``nn/Module.scala:44-74``) which re-points
    every parameter into one compact storage to enable flat all-reduce.  Under
    XLA we don't need the flat buffer for communication (collectives operate
    on the pytree), so this exists for checkpoints and API parity only.
    """
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return jnp.zeros((0,), jnp.float32)
    return jnp.concatenate([jnp.ravel(l) for l in leaves])


def unflatten_params(flat: jnp.ndarray, like: Params) -> Params:
    leaves, treedef = jax.tree_util.tree_flatten(like)
    out, off = [], 0
    for l in leaves:
        n = int(np.prod(l.shape)) if l.ndim else 1
        out.append(jnp.reshape(flat[off:off + n], l.shape).astype(l.dtype))
        off += n
    return jax.tree_util.tree_unflatten(treedef, out)


class Module:
    """Base class for all layers.

    Subclasses implement:
      * ``init_params(self, rng) -> Params``   (default: no params)
      * ``init_state(self) -> State``          (default: no state)
      * ``apply(self, params, state, input, *, training=False, rng=None)
           -> (output, new_state)``

    Containers override ``init`` / ``apply`` wholesale.
    """

    def __init_subclass__(cls, **kwargs):
        super().__init_subclass__(**kwargs)
        impl = cls.__dict__.get("apply")
        if impl is not None and not getattr(impl, "_bigdl_timed", False):
            cls.apply = _timed_apply(impl)

    def __init__(self) -> None:
        cls = type(self).__name__
        self.name = f"{cls}_{_next_uid(cls)}"
        self.training = True
        # Stateful facade fields (Torch-parity; unused under jit):
        self.params: Params = None
        self.state: State = None
        self.grad_params: Params = None
        self.output: Activity = None
        self.gradInput: Activity = None
        # Wall-clock tracing (``AbstractModule.scala:122-135`` forwardTime/
        # backwardTime).  Only the eager facade accumulates these; under jit
        # the whole model is one XLA program and per-layer timing comes from
        # the jax profiler instead (SURVEY.md section 5.1 mapping).
        self.forward_time: int = 0
        self.backward_time: int = 0

    # -- functional protocol -------------------------------------------------

    def init_params(self, rng: jax.Array) -> Params:
        del rng
        return ()

    def init_state(self) -> State:
        return ()

    def init(self, rng: jax.Array):
        return self.init_params(rng), self.init_state()

    def apply(self, params: Params, state: State, input: Activity, *,
              training: bool = False, rng: Optional[jax.Array] = None):
        raise NotImplementedError(
            f"{type(self).__name__} does not implement apply()")

    # -- stateful Torch-parity facade ---------------------------------------

    def build(self, rng: Optional[jax.Array] = None, seed: int = 0):
        """Materialise params/state on this instance (eager / test usage)."""
        if rng is None:
            rng = jax.random.PRNGKey(seed)
        self.params, self.state = self.init(rng)
        self.grad_params = tree_zeros_like(self.params)
        return self

    def _ensure_built(self):
        if self.params is None:
            self.build()

    def forward(self, input: Activity,
                rng: Optional[jax.Array] = None) -> Activity:
        self._ensure_built()
        out, new_state = self.apply(self.params, self.state, input,
                                    training=self.training, rng=rng)
        self.state = new_state
        self.output = out
        return out

    def __call__(self, input: Activity, rng: Optional[jax.Array] = None):
        return self.forward(input, rng=rng)

    def backward(self, input: Activity, grad_output: Activity,
                 rng: Optional[jax.Array] = None) -> Activity:
        """updateGradInput + accGradParameters in one shot, via jax.vjp.

        Accumulates into ``self.grad_params`` (accGradParameters semantics,
        ``AbstractModule.scala:163-169``) and returns/stores gradInput.
        """
        self._ensure_built()

        def f(params, x):
            y, _ = self.apply(params, self.state, x,
                              training=self.training, rng=rng)
            return y

        t0 = time.perf_counter_ns()
        _, vjp = jax.vjp(f, self.params, input)
        gp, gin = vjp(grad_output)
        jax.block_until_ready((gp, gin))   # async backend: count device time
        self.backward_time += time.perf_counter_ns() - t0
        self.grad_params = tree_add(self.grad_params, gp)
        self.gradInput = gin
        return gin

    def zero_grad_parameters(self) -> None:
        self._ensure_built()
        self.grad_params = tree_zeros_like(self.params)

    def update_parameters(self, learning_rate: float) -> None:
        """weight += -lr * grad (``AbstractModule.updateParameters``)."""
        self._ensure_built()
        self.params = jax.tree_util.tree_map(
            lambda w, g: w - learning_rate * g, self.params, self.grad_params)

    def parameters(self):
        """Returns (params_pytree, grad_pytree) — the Torch pair."""
        self._ensure_built()
        return self.params, self.grad_params

    def get_parameters(self):
        """Flat contiguous (weights, grads) — ``getParameters()`` parity."""
        self._ensure_built()
        return flatten_params(self.params), flatten_params(self.grad_params)

    def set_flat_parameters(self, flat: jnp.ndarray) -> None:
        self._ensure_built()
        self.params = unflatten_params(flat, self.params)

    def get_parameters_table(self):
        """Table of layer-name -> Table of that layer's parameter AND
        gradient arrays — reference key names (weight, bias, gradWeight,
        gradBias; ``getParametersTable``, ``nn/Container.scala:66-74``),
        the by-name weight-addressing surface used by Caffe-style
        interop.  Duplicate layer names raise instead of silently
        dropping parameters."""
        from bigdl_tpu.utils.table import T
        self._ensure_built()
        table = T()

        def grad_key(k: str) -> str:
            return "grad" + k[:1].upper() + k[1:]

        def walk(m, p, g):
            if isinstance(m, Container):
                for i, child in enumerate(m.modules):
                    walk(child, p[i], None if g is None else g[i])
                return
            if not jax.tree_util.tree_leaves(p):
                return
            entry = T()
            if isinstance(p, dict):
                for k, v in p.items():
                    entry[k] = v
                    if isinstance(g, dict) and k in g:
                        entry[grad_key(k)] = g[k]
            else:
                entry["weight"] = p
                if g is not None:
                    entry["gradWeight"] = g
            if m.name in table:
                raise ValueError(
                    f"duplicate module name {m.name!r}; set_name layers "
                    "uniquely before addressing weights by name")
            table[m.name] = entry

        walk(self, self.params, self.grad_params)
        return table

    def copy_status(self, src: "Module") -> "Module":
        """Copy run-time status — the ``state`` pytree (BatchNorm running
        stats etc.) — from ``src`` into this module
        (``AbstractModule.copyStatus``).  Parameters are untouched."""
        self._ensure_built()
        src._ensure_built()
        mine = jax.tree_util.tree_structure(self.state)
        theirs = jax.tree_util.tree_structure(src.state)
        if mine != theirs:
            raise ValueError(
                f"copy_status: state structure mismatch ({mine} vs {theirs})")
        for a, b in zip(jax.tree_util.tree_leaves(self.state),
                        jax.tree_util.tree_leaves(src.state)):
            sa = getattr(a, "shape", None)
            sb = getattr(b, "shape", None)
            if sa != sb:
                raise ValueError(
                    f"copy_status: state shape mismatch ({sa} vs {sb})")
        self.state = jax.tree_util.tree_map(lambda x: x, src.state)
        if isinstance(self, Container):
            self.push_state()
        return self

    # -- mode toggles --------------------------------------------------------

    def training_(self):
        self.training = True
        return self

    def evaluate(self):
        self.training = False
        return self

    # -- misc parity helpers -------------------------------------------------

    def set_name(self, name: str) -> "Module":
        """``AbstractModule.setName`` — used by Caffe/torch name matching."""
        self.name = name
        return self

    def get_name(self) -> str:
        return self.name

    def reset(self, rng: Optional[jax.Array] = None, seed: int = 0):
        """Re-initialise parameters (``AbstractModule.reset``)."""
        return self.build(rng=rng, seed=seed)

    def clone_module(self) -> "Module":
        import copy
        return copy.deepcopy(self)

    def clear_state(self):
        self.output = None
        self.gradInput = None
        return self

    def get_times(self):
        """[(module, forward_ns, backward_ns)] — ``getTimes`` parity
        (containers recurse, ``nn/Container.scala:55-62``)."""
        return [(self, self.forward_time, self.backward_time)]

    def reset_times(self) -> None:
        self.forward_time = 0
        self.backward_time = 0

    def save(self, path: str, overwrite: bool = False):
        """``AbstractModule.save`` parity — native checkpoint via File."""
        from bigdl_tpu.utils.file import save as file_save
        file_save(self, path, overwrite)
        return self

    def save_torch(self, path: str, overwrite: bool = False):
        """``AbstractModule.saveTorch`` parity — Torch7 .t7 format."""
        from bigdl_tpu.utils import torch_file
        torch_file.save_torch(self, path, overwrite=overwrite)
        return self

    def has_params(self) -> bool:
        return len(jax.tree_util.tree_leaves(self.init(
            jax.random.PRNGKey(0))[0])) > 0

    def __repr__(self) -> str:
        return self.name


class Criterion:
    """Loss base — parity with ``AbstractCriterion`` (forward/backward).

    Functional core: ``apply(input, target) -> scalar loss``.
    """

    def __init__(self) -> None:
        self.output = None
        self.gradInput = None

    def apply(self, input: Activity, target: Activity) -> jnp.ndarray:
        raise NotImplementedError

    def forward(self, input: Activity, target: Activity) -> jnp.ndarray:
        self.output = self.apply(input, target)
        return self.output

    def __call__(self, input, target):
        return self.forward(input, target)

    def backward(self, input: Activity, target: Activity) -> Activity:
        self.gradInput = jax.grad(
            lambda x: jnp.sum(self.apply(x, target)))(input)
        return self.gradInput

    def clone_criterion(self) -> "Criterion":
        import copy
        return copy.deepcopy(self)


class Container(Module):
    """Base container — parity with ``nn/Container.scala:14-120``.

    Children are held in ``self.modules``; params/state are lists aligned
    with the children order.
    """

    def __init__(self, *modules: Module) -> None:
        super().__init__()
        self.modules: list = list(modules)

    def add(self, module: Module) -> "Container":
        self.modules.append(module)
        return self

    def child_scope(self, i: int):
        """``jax.named_scope`` of child ``i``: the name it was given
        (``set_name``), else its class and its index here — never the
        default name, whose counter follows the order in which the
        process built its modules.  Every operation the child traces then
        carries the path of the containers above it in its ``op_name``
        (metadata only: the compiled program is the same)."""
        m = self.modules[i]
        cls = type(m).__name__
        default = m.name.startswith(cls + "_") \
            and m.name[len(cls) + 1:].isdigit()
        return jax.named_scope(f"{cls}_{i}" if default else m.name)

    def child_apply(self, i: int, params, state, input, **kwargs):
        """Child ``i``'s ``apply`` under its scope."""
        with self.child_scope(i):
            return self.modules[i].apply(params, state, input, **kwargs)

    def init(self, rng: jax.Array):
        params, state = [], []
        for i, m in enumerate(self.modules):
            p, s = m.init(jax.random.fold_in(rng, i))
            params.append(p)
            state.append(s)
        return params, state

    def training_(self):
        super().training_()
        for m in self.modules:
            m.training_()
        return self

    def evaluate(self):
        super().evaluate()
        for m in self.modules:
            m.evaluate()
        return self

    def push_params(self) -> None:
        """Push this container's params/state lists down onto child module
        instances (the inverse of ``pull_params``)."""
        self._ensure_built()
        for i, m in enumerate(self.modules):
            m.params = self.params[i]
            m.state = self.state[i]
            if isinstance(m, Container):
                m.push_params()

    def push_state(self) -> None:
        """Push ONLY the state list down onto child instances (params are
        left alone — the ``copy_status`` contract)."""
        self._ensure_built()
        for i, m in enumerate(self.modules):
            m.state = self.state[i]
            if isinstance(m, Container):
                m.push_state()

    def pull_params(self) -> None:
        """Rebuild this container's params/state lists from the children
        (after in-place edits on child instances, e.g. CaffeLoader)."""
        for m in self.modules:
            if isinstance(m, Container):
                m.pull_params()
        self.params = [m.params for m in self.modules]
        self.state = [m.state for m in self.modules]

    def get_times(self):
        out = [(self, self.forward_time, self.backward_time)]
        for m in self.modules:
            out.extend(m.get_times())
        return out

    def reset_times(self) -> None:
        super().reset_times()
        for m in self.modules:
            m.reset_times()

    def __repr__(self) -> str:
        inner = ", ".join(repr(m) for m in self.modules)
        return f"{self.name}({inner})"


def get_named_modules(model: Module) -> dict:
    """Flatten a module tree into {name: module}
    (``nn/Utils.getNamedModules`` parity)."""
    out: dict = {}

    def walk(m: Module):
        out[m.name] = m
        if isinstance(m, Container):
            for child in m.modules:
                walk(child)

    walk(model)
    return out


def child_rng(rng: Optional[jax.Array], i: int) -> Optional[jax.Array]:
    return None if rng is None else jax.random.fold_in(rng, i)


def collect_aux_losses(model_state) -> jnp.ndarray:
    """Sum every ``"aux_loss"`` leaf in a model-state pytree.

    Modules that contribute auxiliary training objectives (e.g.
    ``nn.MixtureOfExperts``'s load-balancing loss) publish them in their
    state under this key; the trainers add the collected sum to the
    criterion loss.  Zero (weak-typed) when no module contributes, so
    non-MoE models compile identically.
    """
    total = jnp.zeros((), jnp.float32)
    found = False

    def walk(node):
        nonlocal total, found
        if isinstance(node, dict):
            for k, v in node.items():
                if k == "aux_loss":
                    total = total + jnp.asarray(v, jnp.float32)
                    found = True
                else:
                    walk(v)
        elif isinstance(node, (list, tuple)):
            for v in node:
                walk(v)

    walk(model_state)
    return total if found else jnp.zeros((), jnp.float32)
