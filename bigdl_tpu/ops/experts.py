"""The held experts' product over the experts a call HITS, as a Pallas
kernel (``parallel/expert.py`` ``held_experts_apply``'s hit form).

A decode step's few rows send about one pair to each held expert, so a
third or more of the held experts get none; the dense form multiplies
every held expert over every row and so reads every held expert's
weights.  Here the grid walks a list of the held experts with the hit
ones first: at step ``i < n_hit`` expert ``hit[i]``'s two matrices come
through VMEM, all rows of ``x`` (resident) go through it, its gate column
(zero for a row not routed there) scales the hidden row before the second
product, exactly as the dense form does, and the rows are added into a
float32 ``(T, E)`` sum.  Past ``n_hit`` the index maps repeat the last hit
expert's blocks, so the pipeline copies nothing, and the products are
skipped: the weights read are the hit experts', once each.

Off the TPU the kernel runs in the interpreter under
``BIGDL_TPU_PALLAS_INTERPRET=1`` (tests); otherwise the caller takes the
dense form, which is also the kernel's oracle.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: an expert's form, by what its first matrix's columns are: gate and up
#: side by side, ``silu(x Wg) * (x Wu)``, or up alone, ``relu(x Wu)^2``
EXPERT_FORMS = ("swiglu", "relu2")

# VMEM, as (what one grid step may plan for, what the call declares): a
# step holds both matrices of the expert at work and of the next one
# arriving (``ling3_flash_vl``: 2 x (7.9 + 3.9) MB), the resident rows and
# the float32 sum.  The compiler's default scoped limit of 16 MiB is under
# that; a v5e's VMEM is 128 MiB (``ops/attention.py`` `_PAGED_VMEM_WIDE`
# declares the same).  Experts too wide for the plan take the dense form.
_HIT_VMEM = (96 * 1024 * 1024, 112 * 1024 * 1024)


def _interpret() -> bool:
    return os.environ.get("BIGDL_TPU_PALLAS_INTERPRET", "0") == "1"


def expert_hidden(h, f: int, form: str):
    """An expert's hidden row of ``f`` columns from the first product's."""
    if form == "relu2":
        return jnp.square(jax.nn.relu(h))
    return jax.nn.silu(h[..., :f]) * h[..., f:]


def _step_bytes(t, width, w_gate_up, w_down):
    """VMEM of one grid step: both matrices twice, the rows and their
    float32 sum twice, the gate column (a lane tile a row) twice, and the
    float32 hidden rows and product of the expert at work."""
    wsize = jnp.dtype(w_gate_up.dtype).itemsize
    first = w_gate_up.shape[2]
    f = w_down.shape[1]
    return (2 * (width * first + f * width) * wsize
            + 2 * t * width * (wsize + 4) + 2 * t * 128 * 4
            + t * (first + f + width) * 4)


def hit_kernel_fits(t: int, w_gate_up, w_down) -> bool:
    """Whether the hit form's kernel serves a call of ``t`` rows over
    these weights (arrays or ``ShapeDtypeStruct``s): the Pallas kernels
    are on (a TPU, or the interpreter) and one grid step fits its VMEM
    plan."""
    from bigdl_tpu.ops import pallas_enabled

    if not (pallas_enabled() or _interpret()):
        return False
    return _step_bytes(t, w_gate_up.shape[1], w_gate_up, w_down) \
        <= _HIT_VMEM[0]


def held_experts_hit(x, gate, hit, n_hit, w_gate_up, w_down,
                     form: str = "swiglu"):
    """What the held experts give ``x`` (T, E), reading the weights of the
    first ``n_hit`` of ``hit`` only.  ``gate`` (T, held) float32 is each
    held expert's gate for each row, zero where the row was not routed
    there; ``hit`` (held,) int32 lists the held experts with at least one
    pair first; ``w_gate_up`` (held, E, 2F) or, for ``form`` ``"relu2"``,
    (held, E, F), and ``w_down`` (held, F, E).  Returns (T, E) float32,
    the sum of every hit expert's gated output (zeros where ``n_hit`` is
    0)."""
    return _hit_call(hit.astype(jnp.int32),
                     jnp.reshape(n_hit, (1,)).astype(jnp.int32), x,
                     gate.T[:, :, None], w_gate_up, w_down, form=form,
                     interpret=_interpret())


def _hit_kernel(hit_ref, n_ref, x_ref, g_ref, wgu_ref, wd_ref, o_ref, *,
                form, f):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(i < n_ref[0])
    def _expert():
        h = jnp.dot(x_ref[...], wgu_ref[0],
                    preferred_element_type=jnp.float32)
        h = expert_hidden(h, f, form) * g_ref[0]
        o_ref[...] += jnp.dot(h.astype(x_ref.dtype), wd_ref[0],
                              preferred_element_type=jnp.float32)


@functools.partial(jax.jit, inline=True,
                   static_argnames=("form", "interpret"))
def _hit_call(hit, n_hit, x, gate, w_gate_up, w_down, *, form, interpret):
    """``held_experts_hit`` with its gates as (held, T, 1) columns: one
    trace and one lowering for every expert layer of a program."""
    from jax.experimental.pallas import tpu as pltpu

    t, width = x.shape
    held, _, first = w_gate_up.shape
    f = w_down.shape[1]

    def expert(i, hit_ref, n_ref):
        # past the hit list the last hit expert again: the pipeline sees
        # the block it holds and copies nothing
        return hit_ref[jnp.minimum(i, jnp.maximum(n_ref[0] - 1, 0))]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(held,),
        in_specs=[
            pl.BlockSpec((t, width), lambda i, h, n: (0, 0)),
            pl.BlockSpec((1, t, 1), lambda i, h, n: (expert(i, h, n), 0, 0)),
            pl.BlockSpec((1, width, first),
                         lambda i, h, n: (expert(i, h, n), 0, 0)),
            pl.BlockSpec((1, f, width),
                         lambda i, h, n: (expert(i, h, n), 0, 0)),
        ],
        out_specs=pl.BlockSpec((t, width), lambda i, h, n: (0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_hit_kernel, form=form, f=f),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, width), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            # one float32 sum across the whole walk
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_HIT_VMEM[1]),
        interpret=interpret,
        name="held_experts_hit",
    )(hit, n_hit, x, gate, w_gate_up, w_down)
