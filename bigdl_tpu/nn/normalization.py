"""Normalization layers.

Parity: ``nn/BatchNormalization.scala`` (673 LoC — running mean/var state,
the reference parallelises over feature maps with Engine.model; XLA fuses the
whole thing), ``nn/SpatialBatchNormalization.scala``,
``nn/SpatialCrossMapLRN.scala`` (inception LRN), ``nn/Normalize.scala``,
``nn/SpatialSubtractiveNormalization``, ``nn/SpatialDivisiveNormalization``,
``nn/SpatialContrastiveNormalization``.

Running statistics are *module state* (pytree threaded through ``apply``) —
the canonical example of the mutable-Torch -> functional-JAX state split
(SURVEY.md section 7 "Hard parts" #1).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from bigdl_tpu.core.module import Module
from bigdl_tpu.nn.conv import _maybe_batched


def _acc_dtype(dtype):
    """Accumulation dtype: at least f32 (bf16 compute accumulates in f32)
    but never a downcast — f64 inputs keep f64 moments (the torch-locked
    trajectory evidence runs in f64, Torch7-style)."""
    return jnp.promote_types(dtype, jnp.float32)


def _batch_moments(x, axes):
    """Batch mean and biased variance via one-pass E[x^2]-mean^2.

    Everything — accumulation, subtraction, clamp — happens in the
    accumulation dtype (>= f32); the clamp catches the epsilon-negative
    results cancellation can still produce when var << mean^2.  Callers
    cast the (tiny, per-channel) results down only where they broadcast
    against activations."""
    xa = x.astype(_acc_dtype(x.dtype))
    mean = jnp.mean(xa, axis=axes)
    var = jnp.maximum(jnp.mean(jnp.square(xa), axis=axes) -
                      jnp.square(mean), 0.0)
    return mean, var


@functools.partial(jax.custom_jvp, nondiff_argnums=(1, 2))
def _bn_normalize(x, axes, eps):
    """(x - batch_mean) * rsqrt(batch_var + eps) with an analytic JVP.

    XLA's autodiff of the naive two-pass formulation re-derives the
    backward through every reduction; the hand-written rule (the
    standard BN adjoint) plus one-pass E[x^2]-E[x]^2 variance measured
    ~1.4x faster fwd+bwd at ResNet shapes (256x256x56x56 bf16:
    8.0 -> 5.6 ms).  The E[x^2]-mean^2 subtraction, clamp and rsqrt all
    stay in f32 — under bf16 compute the subtraction is catastrophic
    cancellation territory (E[x^2] ~ mean^2 leaves ~0 mantissa bits) —
    and only the broadcast mean/inv are cast back to the compute dtype;
    custom_jvp (not vjp) keeps jacfwd/hessian alive."""
    mean, var = _batch_moments(x, axes)
    bshape = [1 if a in axes else s for a, s in enumerate(x.shape)]
    inv = lax.rsqrt(var + eps).astype(x.dtype).reshape(bshape)
    return (x - mean.astype(x.dtype).reshape(bshape)) * inv


@_bn_normalize.defjvp
def _bn_normalize_jvp(axes, eps, primals, tangents):
    (x,), (t,) = primals, tangents
    bshape = [1 if a in axes else s for a, s in enumerate(x.shape)]
    mean32, var32 = _batch_moments(x, axes)
    inv = lax.rsqrt(var32 + eps).astype(x.dtype).reshape(bshape)
    mean = mean32.astype(x.dtype).reshape(bshape)
    xhat = (x - mean) * inv
    acc = _acc_dtype(t.dtype)
    tm = jnp.mean(t, axis=axes, dtype=acc).astype(t.dtype).reshape(bshape)
    tv = 2.0 * jnp.mean((x - mean) * t, axis=axes,
                        dtype=acc).astype(t.dtype).reshape(bshape)
    dy = inv * (t - tm) - 0.5 * xhat * inv * inv * tv
    return xhat, dy


class BatchNormalization(Module):
    """Per-feature BN over a (N, D) input.

    Training normalises by the biased batch variance; running_var accumulates
    the unbiased estimate (Torch semantics).  ``momentum`` follows Torch:
    running = (1-momentum)*running + momentum*batch.
    """

    _reduce_axes = (0,)

    def __init__(self, n_output: int, eps: float = 1e-5,
                 momentum: float = 0.1, affine: bool = True):
        super().__init__()
        self.n_output = n_output
        self.eps = eps
        self.momentum = momentum
        self.affine = affine

    def init_params(self, rng):
        if not self.affine:
            return {}
        return {"weight": jax.random.uniform(rng, (self.n_output,)),
                "bias": jnp.zeros((self.n_output,))}

    def init_state(self):
        return {"running_mean": jnp.zeros((self.n_output,)),
                "running_var": jnp.ones((self.n_output,))}

    def _shape_for_broadcast(self, input):
        shape = [1] * input.ndim
        shape[1] = self.n_output
        return shape

    def apply(self, params, state, input, *, training=False, rng=None):
        axes = tuple(a for a in range(input.ndim) if a != 1)
        bshape = self._shape_for_broadcast(input)
        if training:
            # running-stat updates (XLA CSEs these reductions with the
            # ones inside _bn_normalize); stats stay f32 end-to-end —
            # running_mean/var are f32 state and the E[x^2]-mean^2
            # subtraction must not happen in bf16
            mean, var = _batch_moments(input, axes)
            n = 1
            for a in axes:
                n *= input.shape[a]
            unbiased = var * (n / max(1, n - 1))
            m = self.momentum
            new_state = {
                "running_mean": (1 - m) * state["running_mean"] + m * mean,
                "running_var": (1 - m) * state["running_var"] + m * unbiased,
            }
            y = _bn_normalize(input, axes, self.eps)
        else:
            mean, var = state["running_mean"], state["running_var"]
            new_state = state
            # rsqrt in f32 like the training path: casting var to bf16
            # first quantizes it to 8 mantissa bits and drops eps entirely
            inv = lax.rsqrt(var.astype(_acc_dtype(input.dtype)) +
                            self.eps).astype(
                input.dtype).reshape(bshape)
            y = (input - mean.reshape(bshape).astype(input.dtype)) * inv
        if self.affine:
            y = y * params["weight"].reshape(bshape) + \
                params["bias"].reshape(bshape)
        return y, new_state


class SpatialBatchNormalization(BatchNormalization):
    """4-D (N,C,H,W) wrapper (``nn/SpatialBatchNormalization.scala``) —
    same math, reduction over N,H,W."""


class SpatialCrossMapLRN(Module):
    """Local response normalisation across channels
    (``nn/SpatialCrossMapLRN.scala``):
    y = x / (k + alpha/size * sum_{c in window} x_c^2)^beta.

    Runs XLA's fused reduce_window path by default (measured faster than
    the hand-written Pallas kernel at training scale); set
    ``BIGDL_TPU_LRN_PALLAS=1`` to use the Pallas kernel in ``ops/lrn.py``
    (unrolled shift-and-add window sum in VMEM, custom-VJP backward).
    """

    def __init__(self, size: int = 5, alpha: float = 1.0,
                 beta: float = 0.75, k: float = 1.0):
        super().__init__()
        self.size = size
        self.alpha, self.beta, self.k = alpha, beta, k

    def apply(self, params, state, input, *, training=False, rng=None):
        from bigdl_tpu.ops import cross_map_lrn

        def run(x):
            return cross_map_lrn(x, self.size, self.alpha, self.beta,
                                 self.k)
        return _maybe_batched(run, input), state


class Normalize(Module):
    """Unit Lp-norm over dim 1 (``nn/Normalize.scala``)."""

    def __init__(self, p: float = 2.0, eps: float = 1e-10):
        super().__init__()
        self.p, self.eps = p, eps

    def apply(self, params, state, input, *, training=False, rng=None):
        if self.p == float("inf"):
            norm = jnp.max(jnp.abs(input), axis=1, keepdims=True)
        else:
            norm = jnp.power(
                jnp.sum(jnp.power(jnp.abs(input), self.p), axis=1,
                        keepdims=True), 1.0 / self.p)
        return input / (norm + self.eps), state


def _gaussian_kernel_2d(size: int) -> jnp.ndarray:
    """Default kernel used by the Spatial*Normalization trio when none is
    given (Torch uses a normalised gaussian)."""
    import numpy as np
    sigma = 0.25 * size
    xs = np.arange(size) - (size - 1) / 2.0
    g = np.exp(-(xs ** 2) / (2 * sigma ** 2))
    k = np.outer(g, g)
    return jnp.asarray((k / k.sum()).astype(np.float32))


class SpatialSubtractiveNormalization(Module):
    """Subtract the kernel-weighted neighbourhood mean (across channels and
    window), with border coefficient correction
    (``nn/SpatialSubtractiveNormalization.scala``)."""

    def __init__(self, n_input_plane: int = 1, kernel=None):
        super().__init__()
        self.n_input_plane = n_input_plane
        k = _gaussian_kernel_2d(9) if kernel is None else jnp.asarray(
            kernel, jnp.float32)
        if k.ndim == 1:
            k = jnp.outer(k, k)  # 1-D kernel means separable
        self.kernel = k / (jnp.sum(k) * n_input_plane)

    def _local_mean(self, x):
        n, c, h, w = x.shape
        kh, kw = self.kernel.shape
        pad = ((kh // 2, (kh - 1) // 2), (kw // 2, (kw - 1) // 2))
        w4 = jnp.broadcast_to(self.kernel, (1, c, kh, kw))
        # kernel is pre-normalised to sum 1/nInputPlane per channel, so the
        # channel-summed conv gives the neighbourhood mean directly in the
        # interior; ``coef`` (< 1 at borders) rescales partial windows.
        mean = lax.conv_general_dilated(
            x, w4, (1, 1), pad,
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        ones = jnp.ones((1, c, h, w), x.dtype)
        coef = lax.conv_general_dilated(
            ones, w4, (1, 1), pad,
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        return mean / jnp.maximum(coef, 1e-12)

    def apply(self, params, state, input, *, training=False, rng=None):
        def run(x):
            adj = self._local_mean(x)
            return x - adj
        return _maybe_batched(run, input), state


class SpatialDivisiveNormalization(Module):
    """Divide by the thresholded kernel-weighted neighbourhood std
    (``nn/SpatialDivisiveNormalization.scala``)."""

    def __init__(self, n_input_plane: int = 1, kernel=None,
                 threshold: float = 1e-4, thresval: float = 1e-4):
        super().__init__()
        self.sub = SpatialSubtractiveNormalization(n_input_plane, kernel)
        self.threshold, self.thresval = threshold, thresval

    def apply(self, params, state, input, *, training=False, rng=None):
        def run(x):
            local_var = self.sub._local_mean(x * x)
            local_std = jnp.sqrt(jnp.maximum(local_var, 0.0))
            thr = jnp.where(local_std > self.threshold, local_std,
                            self.thresval)
            return x / thr
        return _maybe_batched(run, input), state


class SpatialContrastiveNormalization(Module):
    """Subtractive then divisive normalisation
    (``nn/SpatialContrastiveNormalization.scala``)."""

    def __init__(self, n_input_plane: int = 1, kernel=None,
                 threshold: float = 1e-4, thresval: float = 1e-4):
        super().__init__()
        self.sub = SpatialSubtractiveNormalization(n_input_plane, kernel)
        self.div = SpatialDivisiveNormalization(n_input_plane, kernel,
                                                threshold, thresval)

    def apply(self, params, state, input, *, training=False, rng=None):
        y, _ = self.sub.apply((), (), input)
        y, _ = self.div.apply((), (), y)
        return y, state


class LayerNorm(Module):
    """Layer normalisation over the last dimension.

    No reference analogue (BigDL of this vintage pre-dates LayerNorm) —
    required by the transformer family (``models/transformer.py``), the
    TPU-native long-context extension.  Normalises each position's feature
    vector to zero mean / unit variance, then applies a learned affine.
    """

    def __init__(self, normalized_size: int, eps: float = 1e-5,
                 affine: bool = True):
        super().__init__()
        self.normalized_size = normalized_size
        self.eps = eps
        self.affine = affine

    def init_params(self, rng):
        del rng
        if not self.affine:
            return {}
        return {"weight": jnp.ones((self.normalized_size,), jnp.float32),
                "bias": jnp.zeros((self.normalized_size,), jnp.float32)}

    def apply(self, params, state, input, *, training=False, rng=None):
        mean = jnp.mean(input, axis=-1, keepdims=True)
        var = jnp.var(input, axis=-1, keepdims=True)
        y = (input - mean) * jax.lax.rsqrt(var + self.eps)
        if self.affine:
            y = y * params["weight"] + params["bias"]
        return y, state


class RMSNorm(Module):
    """Root-mean-square normalisation over the last dimension (Zhang &
    Sennrich 2019): ``x / sqrt(mean(x^2) + eps) * weight``, no mean
    subtraction and no bias.  Computed in float32 whatever the input's
    dtype (the weight may arrive in bfloat16 from a served tree) and
    returned in the input's."""

    def __init__(self, normalized_size: int, eps: float = 1e-6):
        super().__init__()
        self.normalized_size = normalized_size
        self.eps = eps

    def init_params(self, rng):
        del rng
        return {"weight": jnp.ones((self.normalized_size,), jnp.float32)}

    def apply(self, params, state, input, *, training=False, rng=None):
        x = input.astype(jnp.float32)
        y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                              + self.eps)
        y = y * params["weight"].astype(jnp.float32)
        return y.astype(input.dtype), state
