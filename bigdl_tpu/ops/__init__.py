"""Pallas TPU kernels — the native-kernel layer of the framework.

Reference parity target: ``native/mkl/src/main/c/jni/mkl.c`` (the reference's
hand-written native kernel library behind its JNI boundary).  On TPU the bulk
of that layer disappears into XLA; what remains hand-written here are the ops
XLA has no good primitive for (SURVEY.md section 2.1):

* ``lrn``          — fused cross-map LRN forward/backward
                     (``nn/SpatialCrossMapLRN.scala``); opt-in via
                     ``BIGDL_TPU_LRN_PALLAS=1`` — XLA's own fusion
                     measured faster at training scale, the honest
                     default
* ``fp16`` codec   — the truncation-based wire codec of
                     ``parameters/FP16CompressedTensor.scala:173-266``
                     as bit-twiddling VPU kernels
* ``attention``    — fused flash-style attention (scores stay in VMEM),
                     the default ``nn.MultiHeadAttention`` path on TPU

Every kernel has a pure-jnp reference implementation; dispatch picks the
Pallas path on TPU backends (except ``lrn``, whose Pallas kernel is
opt-in — see above) and the jnp path elsewhere.  Tests run the kernels
in interpreter mode on CPU against the jnp references.
"""

from __future__ import annotations

import os

import jax

__all__ = [
    "pallas_enabled",
    "attention_reference",
    "fused_attention",
    "cross_map_lrn",
    "lrn_reference",
    "fp16_compress",
    "fp16_decompress",
    "fp16_add",
    "fp16_compress_reference",
    "fp16_decompress_reference",
    "int8_matmul",
    "int8_matmul_reference",
    "int8_conv2d",
    "quantize_channelwise",
    "dequantize_channelwise",
    "quantize_params",
    "dequantize_params",
    "calibrate",
]

# Tile selection for every kernel family above goes through the r14
# autotuner registry (``bigdl_tpu/ops/tuning.py``): hand-picked
# constants are the always-present fallback rung; ``cli tune``
# pre-warms the on-disk per-platform winner store.


def pallas_enabled() -> bool:
    """True when the compiled Pallas kernels should be used (TPU backend,
    not disabled via ``BIGDL_TPU_DISABLE_PALLAS=1``).  A backend that
    cannot initialise raises here: a busy or missing chip must not turn
    every kernel into its jnp reference unseen."""
    if os.environ.get("BIGDL_TPU_DISABLE_PALLAS", "0") == "1":
        return False
    return jax.default_backend() == "tpu"


from bigdl_tpu.ops.attention import (  # noqa: E402
    attention_reference,
    fused_attention,
)
from bigdl_tpu.ops.lrn import cross_map_lrn, lrn_reference  # noqa: E402
from bigdl_tpu.ops.fp16 import (  # noqa: E402
    fp16_compress,
    fp16_decompress,
    fp16_add,
    fp16_compress_reference,
    fp16_decompress_reference,
)
from bigdl_tpu.ops.quant import (  # noqa: E402
    calibrate,
    dequantize_channelwise,
    dequantize_params,
    int8_conv2d,
    int8_matmul,
    int8_matmul_reference,
    quantize_channelwise,
    quantize_params,
)
