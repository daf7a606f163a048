"""Torch-style layer library, TPU-native.

Mirrors the reference's ``com.intel.analytics.bigdl.nn`` public surface
(SURVEY.md section 2.3 inventory) so that model code written against the
reference maps 1:1 onto this package.
"""

from bigdl_tpu.core.module import (Container, Criterion, Module,
                                   flatten_params, unflatten_params)
from bigdl_tpu.nn.attention import (GroupedQueryAttention, LatentAttention,
                                    MultiHeadAttention,
                                    SparseGroupedQueryAttention)
from bigdl_tpu.nn.linear_attention import DeltaAttention
from bigdl_tpu.nn.state_space import Mamba2Mixer
from bigdl_tpu.parallel.expert import MixtureOfExperts
from bigdl_tpu.nn.activation import (ELU, Abs, Clamp, Exp, GradientReversal,
                                     HardShrink, HardTanh, LeakyReLU, Log,
                                     LogSigmoid, LogSoftMax, Power, PReLU,
                                     ReLU, ReLU6, RReLU, Sigmoid, SoftMax,
                                     SoftMin, SoftPlus, SoftShrink, SoftSign,
                                     Sqrt, Square, Tanh, TanhShrink,
                                     Threshold)
from bigdl_tpu.nn.containers import (Bottle, CAddTable, CDivTable, CMaxTable,
                                     CMinTable, CMulTable, Concat,
                                     ConcatTable, Contiguous, Copy, CSubTable,
                                     Echo, FlattenTable, Identity, JoinTable,
                                     MapTable, MixtureTable, NarrowTable,
                                     ParallelTable, SelectTable, Sequential)
from bigdl_tpu.nn.conv import (SpatialConvolution, SpatialConvolutionMap,
                               SpatialDilatedConvolution,
                               SpatialFullConvolution,
                               SpatialShareConvolution)
from bigdl_tpu.nn.criterion import (AbsCriterion, BCECriterion,
                                    ClassNLLCriterion, ClassSimplexCriterion,
                                    CosineEmbeddingCriterion, CriterionTable,
                                    CrossEntropyCriterion, DistKLDivCriterion,
                                    HingeEmbeddingCriterion, L1Cost,
                                    L1HingeEmbeddingCriterion,
                                    MarginCriterion, MarginRankingCriterion,
                                    MSECriterion, MultiCriterion,
                                    MultiLabelMarginCriterion,
                                    MultiLabelSoftMarginCriterion,
                                    MultiMarginCriterion, ParallelCriterion,
                                    SmoothL1Criterion,
                                    SmoothL1CriterionWithWeights,
                                    SoftMarginCriterion,
                                    SoftmaxWithCriterion,
                                    TimeDistributedCriterion)
from bigdl_tpu.nn.distance import (MM, MV, Cosine, CosineDistance, DotProduct,
                                   Euclidean, L1Penalty, PairwiseDistance)
from bigdl_tpu.nn.dropout import Dropout, LookupTable
from bigdl_tpu.nn.linear import (Add, AddConstant, Bilinear, CAdd, CMul,
                                 GatedMLP, Linear, Mul, MulConstant, Scale)
from bigdl_tpu.nn.normalization import (BatchNormalization, LayerNorm,
                                        Normalize, RMSNorm,
                                        SpatialBatchNormalization,
                                        SpatialContrastiveNormalization,
                                        SpatialCrossMapLRN,
                                        SpatialDivisiveNormalization,
                                        SpatialSubtractiveNormalization)
from bigdl_tpu.nn.nms import Nms
from bigdl_tpu.nn.pooling import (RoiPooling, SpatialAveragePooling,
                                  SpatialMaxPooling)
from bigdl_tpu.nn.recurrent import (Cell, GRUCell, LSTMCell, Recurrent,
                                    RnnCell, TimeDistributed)
from bigdl_tpu.nn.shape_ops import (Index, InferReshape, MaskedSelect, Max,
                                    Mean, Min, Narrow, Padding, Replicate,
                                    Reshape, Select, Squeeze, Sum,
                                    SpatialZeroPadding, Transpose, Unsqueeze,
                                    View)

# -- Module-level load helpers (``nn/Module.scala:30-42`` parity) -----------

def load(path):
    """Load a module saved with ``Module.save`` (``Module.load``)."""
    from bigdl_tpu.utils.file import load as file_load
    return file_load(path)


def load_torch(path):
    """Load a Torch7 .t7 module file (``Module.loadTorch``)."""
    from bigdl_tpu.utils import torch_file
    return torch_file.load_torch(path)


def load_caffe(model, prototxt_path, model_path, match_all=True):
    """Copy weights from a caffemodel into ``model`` (``Module.loadCaffe``)."""
    from bigdl_tpu.utils.caffe_loader import CaffeLoader
    return CaffeLoader.load(model, prototxt_path, model_path,
                            match_all=match_all)
