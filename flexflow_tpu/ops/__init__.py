"""Operator library — TPU-native equivalents of reference src/ops/*.cu.

Each op is a pure-functional JAX computation (forward only; backward comes
from autodiff of the whole step). The hot ops additionally have Pallas
kernels under flexflow_tpu/kernels/.
"""

from .linear import Linear
from .conv import Conv2D, Pool2D, BatchNorm, Flat
from .elementwise import ElementUnary, ElementBinary, Dropout, LayerNorm, RMSNorm, Reduce, Softmax
from .tensor_ops import (
    Concat,
    Split,
    Reshape,
    Transpose,
    Reverse,
    TopK,
    BatchMatmul,
)
from .embedding import DistributedEmbedding, Embedding
from .attention import MultiHeadAttention
from .moe import GroupBy, Aggregate
from .moe_ffn import MoEFFN
from .ssm import SelectiveScanMixer
from .short_conv import GatedShortConv
from .linear_attention import LightningAttention
from .gated_delta import GatedDeltaNet
from .gated_attention import GatedAttention
from .sparse_attention import SparseAttention
from .gated import GatedFFN, GatedMemoryUnit, TiedHead
from .diff_attention import DifferentialAttention
from .pipeline import PipelineBlocks
from .rnn import LSTM

__all__ = [
    "Linear",
    "Conv2D",
    "Pool2D",
    "BatchNorm",
    "Flat",
    "ElementUnary",
    "ElementBinary",
    "Reduce",
    "Dropout",
    "Softmax",
    "LayerNorm",
    "RMSNorm",
    "Concat",
    "Split",
    "Reshape",
    "Transpose",
    "Reverse",
    "TopK",
    "BatchMatmul",
    "DistributedEmbedding",
    "Embedding",
    "MultiHeadAttention",
    "GroupBy",
    "Aggregate",
    "MoEFFN",
    "SelectiveScanMixer",
    "GatedShortConv",
    "LightningAttention",
    "GatedDeltaNet",
    "GatedAttention",
    "SparseAttention",
    "GatedFFN",
    "GatedMemoryUnit",
    "TiedHead",
    "DifferentialAttention",
    "PipelineBlocks",
    "LSTM",
]
