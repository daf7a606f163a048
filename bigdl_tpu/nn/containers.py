"""Containers and table (pytree) combinators.

Parity: ``nn/Sequential.scala``, ``nn/Concat.scala`` (parallel branch exec —
under XLA branches become independent subgraphs the scheduler overlaps
automatically), ``nn/ConcatTable``, ``nn/ParallelTable``, ``nn/MapTable``,
``nn/MixtureTable``, ``nn/JoinTable``, ``nn/FlattenTable``, ``nn/NarrowTable``,
``nn/SelectTable``, ``nn/C*Table`` element-wise table reducers,
``nn/Identity``, ``nn/Echo``, ``nn/Copy``, ``nn/Contiguous``, ``nn/Bottle``.

Tables are python lists of arrays (pytrees), matching the Activity union.
"""

from __future__ import annotations

from functools import reduce
from typing import Optional

import jax
import jax.numpy as jnp

from bigdl_tpu.core.module import Container, Module, child_rng


class Sequential(Container):

    def apply(self, params, state, input, *, training=False, rng=None):
        x = input
        new_state = list(state)
        for i in range(len(self.modules)):
            x, new_state[i] = self.child_apply(
                i, params[i], state[i], x, training=training,
                rng=child_rng(rng, i))
        return x, new_state


class Concat(Container):
    """Run branches on the same input, concat outputs on ``dimension``
    (1-based, Torch-style; dim 2 = channels of NCHW)
    (``nn/Concat.scala:73-90``)."""

    def __init__(self, dimension: int):
        super().__init__()
        self.dimension = dimension

    def apply(self, params, state, input, *, training=False, rng=None):
        outs, new_state = [], list(state)
        for i in range(len(self.modules)):
            y, new_state[i] = self.child_apply(
                i, params[i], state[i], input, training=training,
                rng=child_rng(rng, i))
            outs.append(y)
        return jnp.concatenate(outs, axis=self.dimension - 1), new_state


class ConcatTable(Container):
    """Same input to every branch; output is the Table of branch outputs."""

    def apply(self, params, state, input, *, training=False, rng=None):
        outs, new_state = [], list(state)
        for i in range(len(self.modules)):
            y, new_state[i] = self.child_apply(
                i, params[i], state[i], input, training=training,
                rng=child_rng(rng, i))
            outs.append(y)
        return outs, new_state


class ParallelTable(Container):
    """i-th module consumes i-th table element."""

    def apply(self, params, state, input, *, training=False, rng=None):
        outs, new_state = [], list(state)
        for i in range(len(self.modules)):
            y, new_state[i] = self.child_apply(
                i, params[i], state[i], input[i], training=training,
                rng=child_rng(rng, i))
            outs.append(y)
        return outs, new_state


class MapTable(Container):
    """One module applied to every table element with *shared* parameters
    (``nn/MapTable.scala`` clones share storage — here: literally the same
    params pytree)."""

    def __init__(self, module: Optional[Module] = None):
        super().__init__()
        if module is not None:
            self.add(module)

    def init(self, rng):
        p, s = self.modules[0].init(rng)
        return [p], [s]

    def apply(self, params, state, input, *, training=False, rng=None):
        outs = []
        s = state[0]
        for i, x in enumerate(input):
            y, s = self.child_apply(0, params[0], s, x, training=training,
                                    rng=child_rng(rng, i))
            outs.append(y)
        return outs, [s]


class MixtureTable(Module):
    """Input [gates (B,K), experts Table of K (B,...)]; output
    sum_k gate_k * expert_k (``nn/MixtureTable.scala``)."""

    def apply(self, params, state, input, *, training=False, rng=None):
        gates, experts = input[0], input[1]
        stacked = jnp.stack(experts, axis=1)  # (B, K, ...)
        g = jnp.reshape(gates, gates.shape[:2] + (1,) *
                        (stacked.ndim - 2))
        return jnp.sum(stacked * g, axis=1), state


class JoinTable(Module):
    """Concat table elements along ``dimension`` (1-based over the last
    ``n_input_dims`` dims, batch-agnostic like Torch)."""

    def __init__(self, dimension: int, n_input_dims: int = 0):
        super().__init__()
        self.dimension = dimension
        self.n_input_dims = n_input_dims

    def apply(self, params, state, input, *, training=False, rng=None):
        axis = self.dimension - 1
        if self.n_input_dims > 0 and input[0].ndim > self.n_input_dims:
            axis += input[0].ndim - self.n_input_dims
        return jnp.concatenate(list(input), axis=axis), state


class FlattenTable(Module):
    def apply(self, params, state, input, *, training=False, rng=None):
        out = []

        def rec(x):
            if isinstance(x, (list, tuple)):
                for e in x:
                    rec(e)
            else:
                out.append(x)
        rec(input)
        return out, state


class NarrowTable(Module):
    def __init__(self, offset: int, length: int = 1):
        super().__init__()
        self.offset, self.length = offset, length

    def apply(self, params, state, input, *, training=False, rng=None):
        n = self.length if self.length >= 0 \
            else len(input) - self.offset + 1 + self.length + 1
        return list(input)[self.offset - 1:self.offset - 1 + n], state


class SelectTable(Module):
    def __init__(self, index: int):
        super().__init__()
        self.index = index

    def apply(self, params, state, input, *, training=False, rng=None):
        i = self.index - 1 if self.index > 0 else self.index
        return input[i], state


class _CTable(Module):
    _op = None

    def apply(self, params, state, input, *, training=False, rng=None):
        return reduce(self._op, list(input)), state


class CAddTable(_CTable):
    def __init__(self, inplace: bool = False):
        super().__init__()
    _op = staticmethod(jnp.add)


class CSubTable(_CTable):
    _op = staticmethod(jnp.subtract)


class CMulTable(_CTable):
    _op = staticmethod(jnp.multiply)


class CDivTable(_CTable):
    _op = staticmethod(jnp.divide)


class CMaxTable(_CTable):
    _op = staticmethod(jnp.maximum)


class CMinTable(_CTable):
    _op = staticmethod(jnp.minimum)


class Identity(Module):
    def apply(self, params, state, input, *, training=False, rng=None):
        return input, state


class Echo(Module):
    """Prints activation shape on forward (debug aid, ``nn/Echo.scala``)."""

    def apply(self, params, state, input, *, training=False, rng=None):
        leaves = jax.tree_util.tree_leaves(input)
        msg = f"{self.name}: " + "; ".join(str(l.shape) for l in leaves)
        # the reference prints on EVERY forward; a bare print() here
        # would fire once per compile (graftlint: host-call-in-jit), so
        # route through the debug callback, which runs per execution
        # even inside jit
        jax.debug.print("{msg}", msg=msg)
        return input, state


class Copy(Module):
    def apply(self, params, state, input, *, training=False, rng=None):
        return jnp.array(input), state


class Contiguous(Module):
    """No-op under XLA (arrays are always dense); API parity."""

    def apply(self, params, state, input, *, training=False, rng=None):
        return input, state


class Bottle(Container):
    """Collapse leading dims to run an n-D module over higher-D input
    (``nn/Bottle.scala``)."""

    def __init__(self, module: Module, n_input_dim: int = 2,
                 n_output_dim: int = 2):
        super().__init__(module)
        self.n_input_dim = n_input_dim
        self.n_output_dim = n_output_dim

    def apply(self, params, state, input, *, training=False, rng=None):
        lead = input.shape[:input.ndim - self.n_input_dim + 1]
        rest = input.shape[input.ndim - self.n_input_dim + 1:]
        squashed = jnp.reshape(input, (-1,) + rest)
        y, s0 = self.child_apply(0, params[0], state[0], squashed,
                                 training=training, rng=rng)
        y = jnp.reshape(y, lead + y.shape[1:])
        return y, [s0]
