"""Linear-algebra and parameterized scalar layers.

Parity: ``nn/Linear.scala``, ``nn/Bilinear.scala``, ``nn/Add.scala``,
``nn/CAdd.scala``, ``nn/CMul.scala``, ``nn/Mul.scala``, ``nn/AddConstant``,
``nn/MulConstant``.  Matmuls go straight to the MXU via jnp.dot / einsum;
weights are stored (out, in) like Torch for checkpoint parity.

Int8 inference: a weight packed by ``ops.quant.quantize_params``
(``{"q8", "scale"}``) routes through the fused dequant-matmul kernel
instead of ``jnp.dot`` — full-precision weights never materialize in
HBM.  The fp path doubles as the calibration surface
(``quant.observe``) so per-tensor activation scales can be collected
for w8a8 packing.
"""

from __future__ import annotations

import math
from typing import Sequence

import jax
import jax.numpy as jnp

from bigdl_tpu.core import init as init_methods
from bigdl_tpu.core.module import Module
from bigdl_tpu.ops import quant


class Linear(Module):
    """y = x W^T + b.  Weight shape (outputSize, inputSize) as in Torch
    (``nn/Linear.scala``)."""

    def __init__(self, input_size: int, output_size: int,
                 with_bias: bool = True,
                 init_method: str = init_methods.DEFAULT):
        super().__init__()
        self.input_size = input_size
        self.output_size = output_size
        self.with_bias = with_bias
        self.init_method = init_method

    def init_params(self, rng):
        wk, bk = jax.random.split(rng)
        w = init_methods.init_weight(
            self.init_method, wk, (self.output_size, self.input_size),
            fan_in=self.input_size, fan_out=self.output_size)
        p = {"weight": w}
        if self.with_bias:
            stdv = 1.0 / math.sqrt(self.input_size)
            p["bias"] = init_methods.uniform(bk, (self.output_size,), stdv)
        return p

    def apply(self, params, state, input, *, training=False, rng=None):
        y = quant.matmul_or_observe(input, params["weight"])
        if self.with_bias:
            y = y + params["bias"]
        return y, state


class Bilinear(Module):
    """y_k = x1^T W_k x2 + b_k over a Table input [x1, x2]
    (``nn/Bilinear.scala``)."""

    def __init__(self, input_size1: int, input_size2: int, output_size: int,
                 bias_res: bool = True):
        super().__init__()
        self.input_size1 = input_size1
        self.input_size2 = input_size2
        self.output_size = output_size
        self.bias_res = bias_res

    def init_params(self, rng):
        wk, bk = jax.random.split(rng)
        stdv = 1.0 / math.sqrt(self.input_size1)
        p = {"weight": init_methods.uniform(
            wk, (self.output_size, self.input_size1, self.input_size2), stdv)}
        if self.bias_res:
            p["bias"] = init_methods.uniform(bk, (self.output_size,), stdv)
        return p

    def apply(self, params, state, input, *, training=False, rng=None):
        x1, x2 = input[0], input[1]
        y = jnp.einsum("bi,kij,bj->bk", x1, params["weight"], x2)
        if self.bias_res:
            y = y + params["bias"]
        return y, state


class Add(Module):
    """Learnable bias vector added to the input (``nn/Add.scala``)."""

    def __init__(self, input_size: int):
        super().__init__()
        self.input_size = input_size

    def init_params(self, rng):
        stdv = 1.0 / math.sqrt(self.input_size)
        return {"bias": init_methods.uniform(rng, (self.input_size,), stdv)}

    def apply(self, params, state, input, *, training=False, rng=None):
        return input + params["bias"], state


class AddConstant(Module):
    def __init__(self, constant_scalar: float, inplace: bool = False):
        super().__init__()
        self.constant_scalar = constant_scalar

    def apply(self, params, state, input, *, training=False, rng=None):
        return input + self.constant_scalar, state


class Mul(Module):
    """Single learnable scalar gain (``nn/Mul.scala``)."""

    def init_params(self, rng):
        return {"weight": init_methods.uniform(rng, (1,), 1.0)}

    def apply(self, params, state, input, *, training=False, rng=None):
        return input * params["weight"][0], state


class MulConstant(Module):
    def __init__(self, scalar: float, inplace: bool = False):
        super().__init__()
        self.scalar = scalar

    def apply(self, params, state, input, *, training=False, rng=None):
        return input * self.scalar, state


class CAdd(Module):
    """Learnable bias of arbitrary broadcastable shape (``nn/CAdd.scala``)."""

    def __init__(self, size: Sequence[int]):
        super().__init__()
        self.size = tuple(size)

    def init_params(self, rng):
        fan = 1
        for s in self.size:
            fan *= s
        stdv = 1.0 / math.sqrt(fan)
        return {"bias": init_methods.uniform(rng, self.size, stdv)}

    def _broadcast(self, t, input):
        if t.ndim < input.ndim:
            t = jnp.reshape(t, (1,) * (input.ndim - t.ndim) + t.shape)
        return t

    def apply(self, params, state, input, *, training=False, rng=None):
        return input + self._broadcast(params["bias"], input), state


class CMul(CAdd):
    """Learnable per-element gain (``nn/CMul.scala``)."""

    def init_params(self, rng):
        fan = 1
        for s in self.size:
            fan *= s
        stdv = 1.0 / math.sqrt(fan)
        return {"weight": init_methods.uniform(rng, self.size, stdv)}

    def apply(self, params, state, input, *, training=False, rng=None):
        # a large 2-D/4-D gain can be key-selected by quantize_params;
        # widen it — this layer consumes the weight elementwise
        w = quant.maybe_unpack(params["weight"], input.dtype)
        return input * self._broadcast(w, input), state


class Scale(Module):
    """CMul followed by CAdd (``nn/Scale.scala``)."""

    def __init__(self, size: Sequence[int]):
        super().__init__()
        self.size = tuple(size)
        self.cmul = CMul(size)
        self.cadd = CAdd(size)

    def init(self, rng):
        k1, k2 = jax.random.split(rng)
        (p1, s1), (p2, s2) = self.cmul.init(k1), self.cadd.init(k2)
        return {"cmul": p1, "cadd": p2}, ()

    def apply(self, params, state, input, *, training=False, rng=None):
        y, _ = self.cmul.apply(params["cmul"], (), input)
        y, _ = self.cadd.apply(params["cadd"], (), y)
        return y, state


class GatedMLP(Module):
    """Bias-free SwiGLU feed-forward (Shazeer 2020): ``(silu(x Wg^T) *
    (x Wu^T)) Wd^T``, weights ``(out, in)`` as :class:`Linear`'s."""

    def __init__(self, embed_dim: int, hidden_dim: int):
        super().__init__()
        self.embed_dim = embed_dim
        self.hidden_dim = hidden_dim

    def init_params(self, rng):
        kg, ku, kd = jax.random.split(rng, 3)
        e, f = self.embed_dim, self.hidden_dim
        return {"w_gate": jax.random.normal(kg, (f, e)) * e ** -0.5,
                "w_up": jax.random.normal(ku, (f, e)) * e ** -0.5,
                "w_down": jax.random.normal(kd, (e, f)) * f ** -0.5}

    def apply(self, params, state, input, *, training=False, rng=None):
        h = jax.nn.silu(quant.matmul_or_observe(input, params["w_gate"])) \
            * quant.matmul_or_observe(input, params["w_up"])
        return quant.matmul_or_observe(h, params["w_down"]), state
