"""Recurrent layers.

Parity: ``nn/Recurrent.scala:20-96`` (time-loop container with truncated
BPTT), ``nn/RNN.scala`` (RnnCell = i2h + h2h -> activation),
``nn/TimeDistributed.scala``.  The reference has no LSTM/GRU at this version
(SURVEY.md section 2.3); LSTM/GRU cells are provided here because the
baseline's "LSTM text classification" config names them
(BASELINE.json configs[4]).

TPU-native design: the reference's per-time-step Scala loop becomes a single
``lax.scan`` — one compiled XLA while-loop whose body is a fused cell step,
so long sequences neither unroll the program nor re-trace.  Inputs are
batch-first (B, T, D); the scan runs time-major internally.

Truncated BPTT divergence: the reference truncates the backward recursion at
``bptt_truncate`` steps from each output.  Here truncation inserts a
``stop_gradient`` on the carried hidden state every ``bptt_truncate`` steps
(chunked truncation) — same asymptotic effect, cheaper under XLA; full BPTT
when ``bptt_truncate`` is 0/None.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from bigdl_tpu.core import init as init_methods
from bigdl_tpu.core.module import Container, Module


class Cell(Module):
    """Recurrent cell protocol: ``step(params, x_t, hidden) -> (y, hidden)``
    plus ``zero_hidden(batch)``."""

    hidden_size: int

    def zero_hidden(self, batch: int):
        return jnp.zeros((batch, self.hidden_size))

    def step(self, params, x_t, hidden):
        raise NotImplementedError

    def apply(self, params, state, input, *, training=False, rng=None):
        # standalone use: input is the Table [x_t, hidden]
        y, h = self.step(params, input[0], input[1])
        return [y, h], state


class RnnCell(Cell):
    """h' = act(W_i x + b_i + W_h h + b_h) (``nn/RNN.scala``)."""

    def __init__(self, input_size: int, hidden_size: int,
                 activation: str = "tanh"):
        super().__init__()
        self.input_size, self.hidden_size = input_size, hidden_size
        self.activation = {"tanh": jnp.tanh,
                           "relu": jax.nn.relu,
                           "sigmoid": jax.nn.sigmoid}[activation]

    def init_params(self, rng):
        k = jax.random.split(rng, 4)
        si = 1.0 / math.sqrt(self.input_size)
        sh = 1.0 / math.sqrt(self.hidden_size)
        return {
            "i2h_w": init_methods.uniform(
                k[0], (self.hidden_size, self.input_size), si),
            "i2h_b": init_methods.uniform(k[1], (self.hidden_size,), si),
            "h2h_w": init_methods.uniform(
                k[2], (self.hidden_size, self.hidden_size), sh),
            "h2h_b": init_methods.uniform(k[3], (self.hidden_size,), sh),
        }

    def step(self, params, x_t, hidden):
        h = self.activation(
            jnp.dot(x_t, params["i2h_w"].T) + params["i2h_b"] +
            jnp.dot(hidden, params["h2h_w"].T) + params["h2h_b"])
        return h, h


class LSTMCell(Cell):
    """Standard LSTM; hidden is the Table (h, c)."""

    def __init__(self, input_size: int, hidden_size: int):
        super().__init__()
        self.input_size, self.hidden_size = input_size, hidden_size

    def zero_hidden(self, batch: int):
        return (jnp.zeros((batch, self.hidden_size)),
                jnp.zeros((batch, self.hidden_size)))

    def init_params(self, rng):
        k = jax.random.split(rng, 3)
        s = 1.0 / math.sqrt(self.hidden_size)
        return {
            "wi": init_methods.uniform(
                k[0], (4 * self.hidden_size, self.input_size), s),
            "wh": init_methods.uniform(
                k[1], (4 * self.hidden_size, self.hidden_size), s),
            "b": init_methods.uniform(k[2], (4 * self.hidden_size,), s),
        }

    def step(self, params, x_t, hidden):
        h, c = hidden
        z = jnp.dot(x_t, params["wi"].T) + jnp.dot(h, params["wh"].T) + \
            params["b"]
        i, f, g, o = jnp.split(z, 4, axis=-1)
        c2 = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h2 = jax.nn.sigmoid(o) * jnp.tanh(c2)
        return h2, (h2, c2)


class GRUCell(Cell):

    def __init__(self, input_size: int, hidden_size: int):
        super().__init__()
        self.input_size, self.hidden_size = input_size, hidden_size

    def init_params(self, rng):
        k = jax.random.split(rng, 3)
        s = 1.0 / math.sqrt(self.hidden_size)
        return {
            "wi": init_methods.uniform(
                k[0], (3 * self.hidden_size, self.input_size), s),
            "wh": init_methods.uniform(
                k[1], (3 * self.hidden_size, self.hidden_size), s),
            "b": init_methods.uniform(k[2], (3 * self.hidden_size,), s),
        }

    def step(self, params, x_t, hidden):
        zi = jnp.dot(x_t, params["wi"].T) + params["b"]
        zh = jnp.dot(hidden, params["wh"].T)
        ri, ui, ni = jnp.split(zi, 3, axis=-1)
        rh, uh, nh = jnp.split(zh, 3, axis=-1)
        r = jax.nn.sigmoid(ri + rh)
        u = jax.nn.sigmoid(ui + uh)
        n = jnp.tanh(ni + r * nh)
        h2 = (1 - u) * n + u * hidden
        return h2, h2

    def zero_hidden(self, batch: int):
        return jnp.zeros((batch, self.hidden_size))


class Recurrent(Container):
    """Scan a cell over the time axis of a (B, T, D) input, returning the
    (B, T, H) hidden sequence (``nn/Recurrent.scala``)."""

    def __init__(self, hidden_size: Optional[int] = None,
                 bptt_truncate: int = 0):
        super().__init__()
        self.hidden_size = hidden_size
        self.bptt_truncate = bptt_truncate

    def apply(self, params, state, input, *, training=False, rng=None):
        cell = self.modules[0]
        p = params[0]
        batch = input.shape[0]
        xs = jnp.swapaxes(input, 0, 1)  # (T, B, D)
        trunc = self.bptt_truncate

        def step(carry, inp):
            h, i = carry
            if trunc and trunc > 0:
                h = jax.tree_util.tree_map(
                    lambda t: jnp.where(i % trunc == 0,
                                        lax.stop_gradient(t), t), h)
            y, h2 = cell.step(p, inp, h)
            return (h2, i + 1), y

        h0 = cell.zero_hidden(batch)
        _, ys = lax.scan(step, (h0, jnp.zeros((), jnp.int32)), xs)
        return jnp.swapaxes(ys, 0, 1), state


class TimeDistributed(Container):
    """Apply the wrapped module independently at every time step of a
    (B, T, ...) input (``nn/TimeDistributed.scala``).  Implemented by
    folding time into the batch — one big fused op instead of T small ones.
    """

    def __init__(self, module: Module):
        super().__init__(module)

    def apply(self, params, state, input, *, training=False, rng=None):
        b, t = input.shape[0], input.shape[1]
        flat = jnp.reshape(input, (b * t,) + input.shape[2:])
        y, s0 = self.child_apply(0, params[0], state[0], flat,
                                 training=training, rng=rng)
        return jnp.reshape(y, (b, t) + y.shape[1:]), [s0]
