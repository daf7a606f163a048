"""Mixed-precision (bf16 compute / f32 master) policy.

The reference trains in fp32 MKL with an fp16 *wire* codec only
(``parameters/FP16CompressedTensor.scala`` — communication, not compute).
On TPU the MXU's native high-throughput dtype is bfloat16, so the idiomatic
policy is the standard mixed-precision split:

* **master weights + optimizer state**: f32 (updates stay well-conditioned)
* **forward/backward compute**: bf16 (matmuls/convs hit the MXU fast path;
  activations halve HBM traffic)
* **gradients**: f32 out of autodiff — the bf16 casts sit INSIDE the traced
  loss so ``value_and_grad`` w.r.t. the f32 params returns f32 grads
  (a cast's vjp casts back), with no separate unscale pass
* **loss / criterion**: f32 (reductions and logs stay accurate)

bf16 shares f32's 8-bit exponent, so there is no loss-scaling machinery —
the reason the reference's truncation codec (keep the top 16 bits of an
IEEE754 float, i.e. exactly bf16) was safe on the wire is the same reason
it is safe in compute.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp


def cast_tree(tree: Any, dtype) -> Any:
    """Cast every floating-point leaf; integer/bool leaves pass through."""
    def cast(leaf):
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype,
                                                     jnp.floating):
            return leaf.astype(dtype)
        return leaf
    return jax.tree_util.tree_map(cast, tree)


def cast_like(tree: Any, like: Any) -> Any:
    """Cast ``tree``'s leaves to the dtypes of the matching ``like`` leaves
    (restore model-state dtypes after a bf16 forward)."""
    return jax.tree_util.tree_map(
        lambda t, l: t.astype(l.dtype) if hasattr(l, "dtype") else t,
        tree, like)


def mixed_forward(model, params, model_state, data, *,
                  compute_dtype=jnp.bfloat16, training=True, rng=None):
    """One policy-applying forward: bf16 params/data in, f32 logits and
    original-dtype state out.  Differentiating through this w.r.t. the f32
    ``params`` yields f32 gradients."""
    y, new_ms = model.apply(cast_tree(params, compute_dtype), model_state,
                            cast_tree(data, compute_dtype),
                            training=training, rng=rng)
    return cast_tree(y, jnp.float32), cast_like(new_ms, model_state)


def training_loss(model, criterion, params, model_state, data, labels, rng,
                  compute_dtype=None):
    """The loss every trainer differentiates: the model's forward (under
    the mixed-precision policy when ``compute_dtype`` is given), the
    criterion and the modules' auxiliary losses.  Returns ``(loss,
    new_model_state)``.  The ``forward`` and ``loss`` scopes name the two
    parts in the device trace; their gradients carry the same names
    inside ``transpose(jvp(...))``."""
    import jax

    from bigdl_tpu.core.module import collect_aux_losses
    with jax.named_scope("forward"):
        if compute_dtype is not None:
            y, new_ms = mixed_forward(model, params, model_state, data,
                                      compute_dtype=compute_dtype,
                                      training=True, rng=rng)
        else:
            y, new_ms = model.apply(params, model_state, data,
                                    training=True, rng=rng)
    with jax.named_scope("loss"):
        return criterion.apply(y, labels) + collect_aux_losses(new_ms), \
            new_ms
