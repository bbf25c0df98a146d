"""FFModel: the graph-builder + training driver.

Mirrors the reference `FFModel` public surface (include/model.h:266-536 —
one builder method per layer type, then compile/fit/forward/backward/
update/zero_gradients) so reference examples translate 1:1, while the
implementation is TPU-native: compile() produces jitted JAX steps instead
of Legion partitions/launchers (SURVEY.md section 7).
"""

from __future__ import annotations

import os
import time
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from .config import CompMode, FFConfig
from .core.executor import Executor, TrainState
from .core.optimizers import AdamOptimizer, Optimizer, SGDOptimizer
from .op import Op
from .ops import (
    LSTM,
    Aggregate,
    MoEFFN,
    PipelineBlocks,
    BatchMatmul,
    BatchNorm,
    Concat,
    Conv2D,
    Dropout,
    ElementBinary,
    ElementUnary,
    Embedding,
    Flat,
    GroupBy,
    Linear,
    MultiHeadAttention,
    Pool2D,
    Reduce,
    Reshape,
    Reverse,
    Softmax,
    Split,
    TopK,
    Transpose,
)
from .parallel.mesh import default_mesh, make_mesh
from .parallel.sharding import place_global
from .parallel.pconfig import OpStrategy, Strategy
from .tensor import Tensor


def _resolve_steps_per_dispatch(spd, grad_accum_steps: int = 1) -> int:
    """"auto" -> 8 steps per device dispatch on TPU backends (where
    dispatch latency is real), 1 elsewhere and under grad accumulation
    (its grouping carries the semantics). The one rule for fit() and
    evaluate(). The reference traces every iteration
    (begin/end_trace, alexnet.cc:106-111); this is the
    dispatch-grouped analog as a default rather than an opt-in."""
    if spd == "auto":
        return (8 if (jax.devices()[0].platform == "tpu"
                      and grad_accum_steps <= 1) else 1)
    return int(spd)


class FFModel:
    def __init__(self, config: Optional[FFConfig] = None,
                 mesh: Optional[Mesh] = None,
                 strategy: Optional[Strategy] = None):
        self.config = config or FFConfig()
        self.ops: List[Op] = []
        self.input_tensors: List[Tensor] = []
        self._name_counts: Dict[str, int] = {}
        self.mesh = mesh
        self.strategy = strategy
        self.executor: Optional[Executor] = None
        self.state: Optional[TrainState] = None
        self.simulator = None  # set by calibrate_simulator()
        self.search_stats = None  # set by search.mcmc.optimize*
        # (profiling.search_report renders it)
        self.last_train_stats = None  # set by fit()
        self.telemetry = None         # set by fit() (utils/telemetry)
        # (profiling.train_report renders it)
        # what this model's starts cost, phase by phase (setup_phase)
        from .core.programs import boot_phases
        self._boot_phases = boot_phases()
        self.label_tensor: Optional[Tensor] = None
        # pretrained weights staged by frontends before compile()
        # (applied after init_state; reference Parameter::set_weights role)
        self.imported_weights: Dict[str, Dict[str, np.ndarray]] = {}
        # non-trainable state staged the same way (BN running stats)
        self.imported_states: Dict[str, Dict[str, np.ndarray]] = {}
        self._rng = jax.random.PRNGKey(self.config.seed)

    # ---------------- tensors ----------------
    def create_tensor(self, shape: Sequence[int], dtype=jnp.float32,
                      name: Optional[str] = None) -> Tensor:
        t = Tensor(tuple(shape), dtype,
                   name=name or self._fresh_name("input"), is_input=True)
        self.input_tensors.append(t)
        return t

    def _fresh_name(self, base: str) -> str:
        n = self._name_counts.get(base, 0)
        self._name_counts[base] = n + 1
        return base if n == 0 else f"{base}_{n}"

    def add_op(self, op: Op) -> Op:
        op.finalize()
        self.ops.append(op)
        return op

    # ---------------- layer builders (include/model.h:276-410) ----------
    def conv2d(self, input: Tensor, out_channels: int, kernel_h: int,
               kernel_w: int, stride_h: int, stride_w: int, padding_h: int,
               padding_w: int, activation=None, groups: int = 1,
               use_bias: bool = True, name: Optional[str] = None,
               kernel_initializer="glorot", bias_initializer="zeros") -> Tensor:
        op = Conv2D(self, name or self._fresh_name("conv2d"), [input],
                    out_channels, kernel_h, kernel_w, stride_h, stride_w,
                    padding_h, padding_w, activation or "none", groups,
                    use_bias, kernel_initializer, bias_initializer)
        return self.add_op(op).output

    def dense(self, input: Tensor, out_channels: int, activation=None,
              use_bias: bool = True, name: Optional[str] = None,
              kernel_initializer="glorot", bias_initializer="zeros") -> Tensor:
        op = Linear(self, name or self._fresh_name("dense"), [input],
                    out_channels, activation or "none", use_bias,
                    kernel_initializer, bias_initializer)
        return self.add_op(op).output

    def embedding(self, input: Tensor, num_entries: int, out_dim: int,
                  aggr: str = "sum", name: Optional[str] = None,
                  kernel_initializer="glorot", dtype=None,
                  emit_table: bool = False):
        """`emit_table`: -> (embedded, the table itself), for a head
        tied to it (`tied_head`)."""
        op = Embedding(self, name or self._fresh_name("embedding"), [input],
                       num_entries, out_dim, aggr, kernel_initializer,
                       dtype=dtype, emit_table=emit_table)
        self.add_op(op)
        return tuple(op.outputs) if emit_table else op.output

    def distributed_embedding(self, inputs: Sequence[Tensor],
                              num_entries: int, out_dim: int,
                              aggr: str = "sum",
                              name: Optional[str] = None,
                              kernel_initializer="glorot",
                              dtype=None) -> List[Tensor]:
        """E same-vocab embedding bags as one table-axis-shardable stacked
        weight — the executable form of the reference's per-device table
        placement (DLRM strategies, dlrm_strategy.cc:1-50). Returns one
        (batch, out_dim) tensor per input, in order."""
        from .ops import DistributedEmbedding
        op = DistributedEmbedding(
            self, name or self._fresh_name("dist_embedding"), list(inputs),
            num_entries, out_dim, aggr, kernel_initializer, dtype)
        self.add_op(op)
        return list(op.outputs)

    def pool2d(self, input: Tensor, kernel_h: int, kernel_w: int,
               stride_h: int, stride_w: int, padding_h: int, padding_w: int,
               pool_type: str = "max", activation=None,
               name: Optional[str] = None) -> Tensor:
        op = Pool2D(self, name or self._fresh_name("pool2d"), [input],
                    kernel_h, kernel_w, stride_h, stride_w, padding_h,
                    padding_w, pool_type, activation or "none")
        return self.add_op(op).output

    def batch_norm(self, input: Tensor, relu: bool = True,
                   name: Optional[str] = None) -> Tensor:
        op = BatchNorm(self, name or self._fresh_name("batch_norm"),
                       [input], relu)
        return self.add_op(op).output

    def layer_norm(self, input: Tensor, eps: float = 1e-5,
                   elementwise_affine: bool = True,
                   name: Optional[str] = None,
                   use_bias: bool = True) -> Tensor:
        from .ops import LayerNorm
        op = LayerNorm(self, name or self._fresh_name("layer_norm"),
                       [input], eps, elementwise_affine, use_bias)
        return self.add_op(op).output

    def rms_norm(self, input: Tensor, eps: float = 1e-5,
                 name: Optional[str] = None, zero_centered: bool = False,
                 scale_init=None) -> Tensor:
        """`zero_centered`: scale 1 + w; `scale_init` (lo, hi): w starts
        uniform in it (ops/elementwise.RMSNorm)."""
        from .ops import RMSNorm
        op = RMSNorm(self, name or self._fresh_name("rms_norm"), [input],
                     eps, zero_centered=zero_centered,
                     scale_init=scale_init)
        return self.add_op(op).output

    def reduce_mean(self, input: Tensor, axis: int, keepdims: bool = False,
                    name: Optional[str] = None) -> Tensor:
        op = Reduce(self, name or self._fresh_name("reduce_mean"),
                    [input], "mean", axis, keepdims)
        return self.add_op(op).output

    def reduce_sum(self, input: Tensor, axis: int, keepdims: bool = False,
                   name: Optional[str] = None) -> Tensor:
        op = Reduce(self, name or self._fresh_name("reduce_sum"),
                    [input], "sum", axis, keepdims)
        return self.add_op(op).output

    def reduce_max(self, input: Tensor, axis: int, keepdims: bool = False,
                   name: Optional[str] = None) -> Tensor:
        op = Reduce(self, name or self._fresh_name("reduce_max"),
                    [input], "max", axis, keepdims)
        return self.add_op(op).output

    def batch_matmul(self, a: Tensor, b: Tensor,
                     a_seq_length_dim: int = -1, b_seq_length_dim: int = -1,
                     name: Optional[str] = None) -> Tensor:
        op = BatchMatmul(self, name or self._fresh_name("batch_matmul"),
                         [a, b], a_seq_length_dim, b_seq_length_dim)
        return self.add_op(op).output

    def dropout(self, input: Tensor, rate: float, seed: int = 0,
                name: Optional[str] = None) -> Tensor:
        op = Dropout(self, name or self._fresh_name("dropout"), [input],
                     rate, seed)
        return self.add_op(op).output

    def multihead_attention(self, query: Tensor, key: Tensor, value: Tensor,
                            embed_dim: int, num_heads: int, kdim: int = 0,
                            vdim: int = 0, dropout: float = 0.0,
                            bias: bool = True, add_bias_kv: bool = False,
                            add_zero_attn: bool = False,
                            causal: bool = False,
                            name: Optional[str] = None,
                            kernel_initializer="glorot",
                            use_flash=None, positions: Tensor = None,
                            rotary_theta: float = 0.0,
                            qk_norm: bool = False,
                            qk_norm_eps: float = 1e-5,
                            num_kv_heads: int = 0, window: int = 0,
                            rotary_interleaved: bool = False,
                            head_dim: int = 0,
                            qk_norm_init=None,
                            key_multiplier: float = 1.0,
                            qk_norm_per_head: bool = False) -> Tensor:
        """`positions` ((batch, seq) int32) with `rotary_theta` > 0
        rotates q and k per head at those absolute positions
        (`rotary_interleaved`: neighbouring pairs, GPT-J's);
        `qk_norm` RMS-normalises the whole q and k projections first;
        `num_kv_heads` < num_heads: query head j reads key/value head
        j // (num_heads / num_kv_heads); `window` > 0: token t sees
        keys t - window + 1 .. t; `head_dim` > 0: heads of that size
        (num_heads * head_dim wide inside, embed_dim out);
        `qk_norm_init` (lo, hi): where the QK-norm's scales start
        (core/initializers.range_init; None: at 1); `key_multiplier`:
        a scalar on the key projection's output; `qk_norm_per_head`:
        the QK-norm over EACH head's dims with one (head_dim,) weight
        the heads share (LFM2's), which grouped heads may take."""
        inputs = [query, key, value] \
            + ([positions] if positions is not None else [])
        op = MultiHeadAttention(
            self, name or self._fresh_name("attention"), inputs,
            embed_dim, num_heads, kdim, vdim, dropout, bias, add_bias_kv,
            add_zero_attn, causal, kernel_initializer, use_flash,
            rotary_theta, qk_norm, qk_norm_eps, num_kv_heads, window,
            rotary_interleaved, head_dim, qk_norm_init, key_multiplier,
            qk_norm_per_head)
        return self.add_op(op).output

    # elementwise unary (model.h exp/relu/sigmoid/tanh/elu/scalar ops)
    def _unary(self, mode, input, name=None, scalar=None) -> Tensor:
        op = ElementUnary(self, name or self._fresh_name(mode), [input],
                          mode, scalar)
        return self.add_op(op).output

    def exp(self, input, name=None):
        return self._unary("exp", input, name)

    def relu(self, input, name=None):
        return self._unary("relu", input, name)

    def sigmoid(self, input, name=None):
        return self._unary("sigmoid", input, name)

    def tanh(self, input, name=None):
        return self._unary("tanh", input, name)

    def elu(self, input, name=None):
        return self._unary("elu", input, name)

    def gelu(self, input, name=None):
        return self._unary("gelu", input, name)

    def identity(self, input, name=None):
        return self._unary("identity", input, name)

    def scalar_multiply(self, input, scalar, name=None):
        return self._unary("scalar_multiply", input, name, scalar=scalar)

    # elementwise binary
    def _binary(self, mode, a, b, name=None) -> Tensor:
        op = ElementBinary(self, name or self._fresh_name(mode), [a, b], mode)
        return self.add_op(op).output

    def add(self, a, b, name=None):
        return self._binary("add", a, b, name)

    def subtract(self, a, b, name=None):
        return self._binary("subtract", a, b, name)

    def multiply(self, a, b, name=None):
        return self._binary("multiply", a, b, name)

    def divide(self, a, b, name=None):
        return self._binary("divide", a, b, name)

    def max(self, a, b, name=None):
        return self._binary("max", a, b, name)

    def min(self, a, b, name=None):
        return self._binary("min", a, b, name)

    # shape ops
    def concat(self, tensors: Sequence[Tensor], axis: int,
               name: Optional[str] = None) -> Tensor:
        op = Concat(self, name or self._fresh_name("concat"), list(tensors),
                    axis)
        return self.add_op(op).output

    def split(self, input: Tensor, sizes: Union[int, Sequence[int]],
              axis: int, name: Optional[str] = None) -> List[Tensor]:
        if isinstance(sizes, int):
            total = input.shape[axis % len(input.shape)]
            assert total % sizes == 0
            sizes = [total // sizes] * sizes
        op = Split(self, name or self._fresh_name("split"), [input],
                   list(sizes), axis)
        return list(self.add_op(op).outputs)

    def flat(self, input: Tensor, name: Optional[str] = None) -> Tensor:
        op = Flat(self, name or self._fresh_name("flat"), [input])
        return self.add_op(op).output

    def reshape(self, input: Tensor, shape: Sequence[int],
                name: Optional[str] = None) -> Tensor:
        op = Reshape(self, name or self._fresh_name("reshape"), [input],
                     tuple(shape))
        return self.add_op(op).output

    def transpose(self, input: Tensor, perm: Sequence[int],
                  name: Optional[str] = None) -> Tensor:
        op = Transpose(self, name or self._fresh_name("transpose"), [input],
                       list(perm))
        return self.add_op(op).output

    def reverse(self, input: Tensor, axis: int,
                name: Optional[str] = None) -> Tensor:
        op = Reverse(self, name or self._fresh_name("reverse"), [input], axis)
        return self.add_op(op).output

    def top_k(self, input: Tensor, k: int, sorted: bool = True,
              name: Optional[str] = None) -> Tuple[Tensor, Tensor]:
        op = TopK(self, name or self._fresh_name("topk"), [input], k, sorted)
        self.add_op(op)
        return op.outputs[0], op.outputs[1]

    def softmax(self, input: Tensor, axis: int = -1,
                name: Optional[str] = None) -> Tensor:
        op = Softmax(self, name or self._fresh_name("softmax"), [input], axis)
        return self.add_op(op).output

    def group_by(self, data: Tensor, assign: Tensor, n: int, alpha: float,
                 name: Optional[str] = None) -> List[Tensor]:
        op = GroupBy(self, name or self._fresh_name("group_by"),
                     [data, assign], n, alpha)
        return list(self.add_op(op).outputs)

    def aggregate(self, gate_preds: Tensor, gate_assign: Tensor,
                  exp_preds: Sequence[Tensor], n: int,
                  name: Optional[str] = None) -> Tensor:
        op = Aggregate(self, name or self._fresh_name("aggregate"),
                       [gate_preds, gate_assign] + list(exp_preds), n)
        return self.add_op(op).output


    def moe_ffn(self, input: Tensor, num_experts: int, k: int,
                hidden_dim: int, out_dim: int = None,
                capacity_factor: float = 1.25, activation="relu",
                aux_loss_weight: float = 1e-2,
                name: Optional[str] = None, norm_topk: bool = True,
                dropless: bool = False, score: str = "softmax",
                shared_experts: int = 0, experts_held=None,
                shared_gate: bool = False,
                kernel_initializer="glorot",
                expert_bias=None) -> Tensor:
        """Fused expert-parallel MoE FFN (TPU-first EP; the composable
        reference path softmax+topk+group_by+aggregate also exists).
        `dropless`: bias-free gated experts, (act(x wg) * (x wu)) wd,
        and every token reaches all its k experts whatever the load (no
        capacity); `norm_topk=False` keeps the k router probabilities
        as they are. A dropless layer's `score` ("softmax" |
        "sigmoid"), `shared_experts`, `shared_gate`, `experts_held`
        (first, count) and `expert_bias` (a selection bias; how its
        leaf starts): ops/moe_ffn.py."""
        op = MoEFFN(self, name or self._fresh_name("moe_ffn"), [input],
                    num_experts, k, hidden_dim, out_dim, capacity_factor,
                    activation, aux_loss_weight,
                    kernel_initializer=kernel_initializer,
                    norm_topk=norm_topk, dropless=dropless, score=score,
                    shared_experts=shared_experts,
                    experts_held=experts_held, shared_gate=shared_gate,
                    expert_bias=expert_bias)
        return self.add_op(op).output


    # ---- the decoder-hybrid-decoder block (models/phi4flash.py) ----
    def selective_scan_mixer(self, input: Tensor, d_inner: int,
                             d_state: int = 16, d_conv: int = 4,
                             dt_rank: int = 0, emit_memory: bool = False,
                             name: Optional[str] = None):
        """The Mamba-1 mixer (ops/ssm.py). `emit_memory`: -> (out, the
        scan's output before its gate), what a `gated_memory_unit`
        reads."""
        from .ops.ssm import SelectiveScanMixer
        op = SelectiveScanMixer(
            self, name or self._fresh_name("ssm"), [input], d_inner,
            d_state, d_conv, dt_rank, emit_memory)
        self.add_op(op)
        return tuple(op.outputs) if emit_memory else op.output

    # ---- MiniCPM-SALA's two mixers (models/minicpm_sala.py) ----
    def lightning_attention(self, input: Tensor, positions: Tensor,
                            num_heads: int, head_dim: int,
                            layer_index: int = 0, published_layers: int = 1,
                            rotary_theta: float = 10000.0,
                            eps: float = 1e-6,
                            name: Optional[str] = None) -> Tensor:
        """Lightning linear attention (ops/linear_attention.py)."""
        from .ops.linear_attention import LightningAttention
        op = LightningAttention(
            self, name or self._fresh_name("linear"), [input, positions],
            num_heads, head_dim, layer_index, published_layers,
            rotary_theta, eps)
        return self.add_op(op).output

    def gated_delta_net(self, input: Tensor, key_heads: int,
                        value_heads: int, key_dim: int, value_dim: int,
                        d_conv: int = 4, eps: float = 1e-6,
                        dt_range=(1e-3, 1e-1), norm_init=(1.0, 1.0),
                        kernel_initializer="glorot",
                        allow_neg_eigval: bool = False,
                        name: Optional[str] = None) -> Tensor:
        """The gated delta rule's mixer (ops/gated_delta.py)."""
        from .ops.gated_delta import GatedDeltaNet
        op = GatedDeltaNet(
            self, name or self._fresh_name("delta"), [input], key_heads,
            value_heads, key_dim, value_dim, d_conv, eps, dt_range,
            norm_init, kernel_initializer, allow_neg_eigval)
        return self.add_op(op).output

    def mamba2_mixer(self, input: Tensor, heads: int, head_dim: int,
                     groups: int, d_state: int, d_conv: int = 4,
                     eps: float = 1e-5, in_multiplier: float = 1.0,
                     multipliers=(1.0,) * 5, out_multiplier: float = 1.0,
                     dt_range=(1e-3, 1e-1), a_range=(1.0, 16.0),
                     norm_init=(1.0, 1.0), kernel_initializer="glorot",
                     out_initializer=None,
                     name: Optional[str] = None) -> Tensor:
        """The Mamba-2 mixer (ops/ssd.py)."""
        from .ops.ssd import Mamba2Mixer
        op = Mamba2Mixer(
            self, name or self._fresh_name("mamba2"), [input], heads,
            head_dim, groups, d_state, d_conv, eps, in_multiplier,
            multipliers, out_multiplier, dt_range, a_range, norm_init,
            kernel_initializer, out_initializer)
        return self.add_op(op).output

    def gated_short_conv(self, input: Tensor, taps: int = 3,
                         kernel_initializer="glorot",
                         name: Optional[str] = None) -> Tensor:
        """The gated short convolution (ops/short_conv.py)."""
        from .ops.short_conv import GatedShortConv
        op = GatedShortConv(
            self, name or self._fresh_name("short_conv"), [input], taps,
            kernel_initializer)
        return self.add_op(op).output

    def gated_attention(self, input: Tensor, positions: Tensor,
                        num_heads: int, num_kv_heads: int, head_dim: int,
                        rotary_theta: float = 1e7, rotary_dim: int = 0,
                        eps: float = 1e-6, qk_norm_init=(0.0, 0.0),
                        kernel_initializer="glorot",
                        name: Optional[str] = None) -> Tensor:
        """Gated softmax attention (ops/gated_attention.py)."""
        from .ops.gated_attention import GatedAttention
        op = GatedAttention(
            self, name or self._fresh_name("gated_attn"),
            [input, positions], num_heads, num_kv_heads, head_dim,
            rotary_theta, rotary_dim, eps, qk_norm_init,
            kernel_initializer)
        return self.add_op(op).output

    def sparse_attention(self, input: Tensor, num_heads: int,
                         num_kv_heads: int, head_dim: int, sparse=None,
                         eps: float = 1e-6, qk_norm_init: float = 1.0,
                         name: Optional[str] = None) -> Tensor:
        """Block-sparse attention over a learned selection
        (ops/sparse_attention.py); `sparse` a SparseConfig."""
        from .ops.sparse_attention import SparseAttention, SparseConfig
        op = SparseAttention(
            self, name or self._fresh_name("sparse"), [input], num_heads,
            num_kv_heads, head_dim, sparse or SparseConfig(), eps,
            qk_norm_init=qk_norm_init)
        return self.add_op(op).output

    def gated_memory_unit(self, input: Tensor, memory: Tensor,
                          name: Optional[str] = None) -> Tensor:
        from .ops.gated import GatedMemoryUnit
        op = GatedMemoryUnit(self, name or self._fresh_name("gmu"),
                             [input, memory])
        return self.add_op(op).output

    def gated_ffn(self, input: Tensor, hidden_dim: int,
                  name: Optional[str] = None,
                  kernel_initializer="glorot",
                  multipliers=(1.0, 1.0)) -> Tensor:
        """`multipliers`: scalars on the gate projection's output and
        on the down projection's (ops/gated.gated_ffn)."""
        from .ops.gated import GatedFFN
        op = GatedFFN(self, name or self._fresh_name("gated_ffn"), [input],
                      hidden_dim, kernel_initializer, multipliers)
        return self.add_op(op).output

    def tied_head(self, input: Tensor, table: Tensor,
                  name: Optional[str] = None,
                  scale: float = 1.0) -> Tensor:
        from .ops.gated import TiedHead
        op = TiedHead(self, name or self._fresh_name("tied_head"),
                      [input, table], scale)
        return self.add_op(op).output

    def differential_attention(self, input: Tensor, num_heads: int,
                               num_kv_heads: int, head_dim: int,
                               layer_index: int, window: int = 0,
                               kv: Optional[Sequence[Tensor]] = None,
                               kv_from: str = "", emit_kv: bool = False,
                               eps: float = 1e-5,
                               name: Optional[str] = None):
        """Differential attention with grouped key/value heads
        (ops/diff_attention.py). `kv` = (k, v) of the layer `kv_from`
        names: this layer then has no wk, wv. `emit_kv`: -> (y, k, v)."""
        from .ops.diff_attention import DifferentialAttention
        op = DifferentialAttention(
            self, name or self._fresh_name("diff_attention"),
            [input] + list(kv or ()), num_heads, num_kv_heads, head_dim,
            layer_index, window, kv_from, emit_kv, eps)
        self.add_op(op)
        return tuple(op.outputs) if emit_kv else op.output

    def pipeline_blocks(self, input: Tensor, block_builder, num_layers: int,
                        num_microbatches: int = 4,
                        name: Optional[str] = None) -> Tensor:
        """Stack of identical shape-preserving blocks with first-class
        pipeline parallelism (GPipe schedule when the strategy maps the
        `layer` axis to a mesh `pipe` axis). block_builder(sub_model, t)
        builds one block with the normal layer API."""
        op = PipelineBlocks(self, name or self._fresh_name("pipeline"),
                            [input], block_builder, num_layers,
                            num_microbatches)
        return self.add_op(op).output

    def lstm(self, input: Tensor, hidden_size: int,
             return_sequences: bool = True,
             name: Optional[str] = None, use_pallas=None) -> Tensor:
        op = LSTM(self, name or self._fresh_name("lstm"), [input],
                  hidden_size, return_sequences, use_pallas=use_pallas)
        return self.add_op(op).output

    # ---------------- compile / train ----------------
    @property
    def final_tensor(self) -> Tensor:
        return self.ops[-1].outputs[0]

    def compile(self, optimizer: Optional[Optimizer] = None,
                loss_type: Optional[str] = "sparse_categorical_crossentropy",
                metrics: Optional[Sequence[str]] = None,
                comp_mode: str = CompMode.TRAINING,
                mesh: Optional[Mesh] = None,
                strategy: Optional[Strategy] = None) -> None:
        """Reference: FFModel::compile (model.cc:1551-1796). Runs strategy
        search when config.search_budget > 0, builds the executor, and
        initializes parameters (sharded per strategy). One set-up
        phase, `model_compile`, over `search` (where one runs),
        `lower_strategy`, `build_step` and `init_state`
        (docs/observability.md "Set-up phases")."""
        with self.setup_phase("model_compile"):
            self._compile(optimizer, loss_type, metrics, comp_mode, mesh,
                          strategy)

    def setup_phase(self, name: str, args: Optional[dict] = None):
        """One set-up phase of this model, through the bus's `timed`:
        kept in `boot_stats["phases"]` whether or not a bus is on, and
        on track ("model", "setup") of `self.telemetry` where one is."""
        from .utils.telemetry import SETUP_THREAD, telemetry_for
        tel = self.telemetry if self.telemetry is not None \
            else telemetry_for()
        return tel.timed(("model", SETUP_THREAD), name, args,
                         keep=self._boot_phases)

    @property
    def boot_stats(self) -> dict:
        """What starting this model cost: its set-up `phases` in order
        (`(name, parent, t_start_s, dur_s, args)`), `setup_s` (their
        roots' sum) and, once a step has run, the executor's registry's
        `compiles` / `compile_s` / `restore_s` / `families` — the shape
        of ServeEngine.boot_stats."""
        rec = self.executor.boot_record() if self.executor is not None \
            else {}
        from .utils.telemetry import roots_s
        rec["phases"] = list(self._boot_phases)
        rec["setup_s"] = roots_s(rec["phases"])
        return rec

    def _compile(self, optimizer, loss_type, metrics, comp_mode, mesh,
                 strategy) -> None:
        self.config.validate()  # catch post-construction field edits
        if mesh is not None:
            self.mesh = mesh
        if strategy is not None:
            self.strategy = strategy
        if optimizer is None:
            optimizer = SGDOptimizer(lr=self.config.learning_rate)
        self.optimizer = optimizer

        if self.strategy is None and self.config.import_strategy_file:
            self.strategy = self._load_strategy_file(
                self.config.import_strategy_file)

        if self.config.search_budget > 0:
            if self.config.search_mesh_shapes:
                # joint (strategy, mesh-factorization) search — the
                # degree dimension of the reference's space (model.cc:512)
                from .search.mcmc import optimize_with_mesh
                self.strategy, self.mesh = optimize_with_mesh(
                    self, budget=self.config.search_budget,
                    alpha=self.config.search_alpha)
            else:
                from .search.mcmc import optimize
                self.strategy = optimize(
                    self, budget=self.config.search_budget,
                    alpha=self.config.search_alpha)
            if self.config.export_strategy_file:
                self.strategy.save(self.config.export_strategy_file)

        with self.setup_phase("lower_strategy"):
            stage_of, pipe_axis = self._lower_strategy()
        # Executor validates comp_mode; assign OURS only after it
        # succeeds so a rejected compile leaves the previous mode live
        with self.setup_phase("build_step"):
            if stage_of is not None and pipe_axis is not None:
                from .core.staged import StagedExecutor
                self.executor = StagedExecutor(
                    self, optimizer, loss_type, metrics, mesh=self.mesh,
                    strategy=self.strategy, comp_mode=comp_mode,
                    stage_of=stage_of, pipe_axis=pipe_axis,
                    num_microbatches=self.config.pipeline_microbatches,
                    schedule=self.config.pipeline_schedule)
            else:
                self.executor = Executor(
                    self, optimizer, loss_type, metrics,
                    mesh=self.mesh, strategy=self.strategy,
                    comp_mode=comp_mode)
        self.comp_mode = comp_mode
        args = {}
        with self.setup_phase("init_state", args):
            self.state = self.executor.init_state(self._next_rng())
            leaves = jax.tree_util.tree_leaves(self.state)
            args.update(leaves=len(leaves),
                        bytes=int(sum(x.nbytes for x in leaves)))
        self._host_step = 0  # mirrors state.step for the train rng
        for op_name, ws in self.imported_weights.items():
            self.set_weights(op_name, ws)
        for op_name, ss in self.imported_states.items():
            self.set_states(op_name, ss)

    def _lower_strategy(self):
        """The strategy's pipeline block and device pins lowered to
        (stage_of, pipe_axis): None, None where the model runs as one
        SPMD program."""
        # a search-discovered interleaved pipeline rides the strategy's
        # `pipeline` block (pins cannot express v stages per device) —
        # apply it to the config knobs the auto-cut lowering below
        # reads, so --import replays the whole exported plan
        pl = (getattr(self.strategy, "pipeline", None)
              if self.strategy is not None else None)
        if pl:
            if not isinstance(pl, dict) \
                    or not isinstance(pl.get("stages"), int) \
                    or pl["stages"] < 1:
                # Strategy.load validates files; this guards strategies
                # constructed in code with a malformed block
                raise ValueError(
                    f"strategy.pipeline must be a dict with an int "
                    f"\"stages\" >= 1 (got {pl!r})")
            self.config.pipeline_stages = pl["stages"]
            self.config.pipeline_virtual_stages = int(
                pl.get("virtual_stages", 1))
            self.config.pipeline_schedule = pl.get(
                "schedule", self.config.pipeline_schedule)
            self.config.pipeline_microbatches = int(pl.get(
                "microbatches", self.config.pipeline_microbatches))
            self.config.validate()

        # device-explicit placement lowering. Per-table ids on
        # distributed_embedding execute via the slot layout
        # (ops/embedding.py apply_placement). Whole-op pins on other ops
        # execute as PIPELINE STAGES: stage order = device-id order,
        # microbatches stream over the mesh pipe axis
        # (core/staged.py; the executable analog of slice_task routing,
        # mapper.cc:346-440). Pins that cannot form a forward pipeline
        # (or lack a matching mesh axis) fall back to replication with
        # a warning.
        stage_of = None
        pipe_axis = None
        vstages_applied = False
        if self.strategy is not None and self.mesh is not None:
            from .parallel.graph_pipeline import (
                assignment_from_pins, build_stage_plan, pick_pipe_axis)
            try:
                stage_of = assignment_from_pins(self, self.strategy)
                if stage_of is not None:
                    build_stage_plan(self, stage_of)  # viability check
            except (ValueError, NotImplementedError) as e:
                import warnings
                warnings.warn(
                    f"strategy pins ops to explicit devices but the "
                    f"placement cannot execute as a pipeline "
                    f"({e}); falling back to replication")
                stage_of = None
            if stage_of is not None:
                n_stages = max(stage_of.values()) + 1
                if n_stages < 2:
                    import warnings
                    warnings.warn(
                        "strategy pins every op to one device; a "
                        "single-stage placement has no pipelined "
                        "lowering — executing as plain (replicated) "
                        "SPMD")
                    stage_of = None
                else:
                    pipe_axis = pick_pipe_axis(self.mesh, n_stages)
                    if pipe_axis is None:
                        import warnings
                        warnings.warn(
                            f"strategy pins ops across {n_stages} "
                            f"devices but the mesh {self.mesh.shape} "
                            f"has no non-data axis of that size to "
                            f"pipeline over; executing as replication")
                        stage_of = None
        if stage_of is None and self.config.pipeline_stages > 1:
            from .parallel.graph_pipeline import (
                balanced_stages, pick_pipe_axis)
            # interleaving: v round-robin stage chunks per pipe device
            # (Megatron virtual stages; executes under 1f1b)
            vstages = max(1, self.config.pipeline_virtual_stages)
            vstages_applied = True
            stage_of = balanced_stages(
                self, self.config.pipeline_stages * vstages)
            n_stages = max(stage_of.values()) + 1  # clamped to op count
            if n_stages % vstages != 0:
                raise ValueError(
                    f"pipeline_virtual_stages={vstages} needs "
                    f"{self.config.pipeline_stages * vstages} stages "
                    f"but this graph only supports {n_stages} (too few "
                    f"ops); lower the stage or virtual-stage count")
            pipe_axis = (pick_pipe_axis(self.mesh, n_stages // vstages)
                         if self.mesh is not None else None)
            if pipe_axis is None:
                raise ValueError(
                    f"pipeline_stages={self.config.pipeline_stages} "
                    f"(=> {n_stages} stages for this graph) needs a "
                    f"mesh axis of size "
                    f"{max(1, n_stages // vstages)} to pipeline over "
                    f"(mesh: {self.mesh.shape if self.mesh else None})")
        if (stage_of is None and self.strategy is not None
                and self.mesh is None):
            # meshless compile: pins cannot execute at all — surface it
            # (the mesh path warns through the lowering above)
            pinned = [op.name for op in self.ops
                      if self.strategy.for_op(op.name).device_ids
                      and op.op_type != "distributed_embedding"]
            if pinned:
                import warnings
                warnings.warn(
                    f"strategy pins {pinned} to explicit devices but "
                    f"there is no mesh; placement is ignored "
                    f"(replicated single-device execution)")

        if self.config.pipeline_virtual_stages > 1 \
                and not vstages_applied:
            import warnings
            warnings.warn(
                "pipeline_virtual_stages > 1 only applies to auto-cut "
                "pipelines (--pipeline-stages); this compile's stages "
                "come from pins or no pipeline at all — interleaving "
                "was NOT applied")

        return stage_of, pipe_axis

    def _load_strategy_file(self, path: str) -> Strategy:
        """--import-strategy dispatch: our JSON format, the reference's
        FFProtoBuf .pb artifacts, or strategy.cc's text stream."""
        from .parallel.strategy_io import load_reference_strategy_file
        if not path.endswith(".pb"):
            try:
                return Strategy.load(path)
            except (ValueError, UnicodeDecodeError):
                pass  # not our JSON: try the reference text format
        if self.mesh is None:
            raise ValueError(
                f"importing the reference strategy format from {path!r} "
                f"needs a mesh (splits/device ids resolve against mesh "
                f"axes); pass mesh= or use the native JSON format")
        return load_reference_strategy_file(self, self.mesh, path)

    def _next_rng(self):
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def _train_rng(self):
        """Per-step training rng (dropout etc.), keyed on a host-side
        step mirror instead of a split chain so a checkpoint-resumed run
        reproduces the exact stream of the uninterrupted one (the mirror
        is re-synced from state.step at resume, fit())."""
        sub = jax.random.fold_in(self._rng, self._host_step)
        self._host_step += 1
        return sub

    # reference-parity train-loop primitives (model.cc:1414-1461). On TPU
    # forward/backward/update are one fused jitted step; these methods keep
    # the imperative API by staging a batch and running the step on update.
    def init_layers(self):
        if self.state is None:
            self.compile()

    def forward(self, batch: Dict[str, np.ndarray]):
        batch = self.executor.shard_batch(batch)
        logits, metrics = self.executor.eval_step(self.state, batch)
        return logits

    def zero_gradients(self):
        pass  # gradients are pure values on TPU; nothing to zero

    def compile_counts(self) -> Dict[str, int]:
        """Exact compiles per train-program family this process
        performed (the executor's ProgramRegistry query — the serving
        engines' zero-recompile instrument, extended to fit). Empty
        before the first train dispatch; a step resolved from a
        --program-cache-dir snapshot counts zero."""
        return self.executor.compile_counts()

    def train_batch(self, batch: Dict[str, np.ndarray]):
        """One optimizer step; returns metrics dict of scalars. The
        host's part is one phase span, `train_step`, over `shard_batch`,
        `rng` and `dispatch` (Telemetry.timed: docs/observability.md
        "Phase spans")."""
        from .utils.telemetry import telemetry_for
        tel = self.telemetry if self.telemetry is not None \
            else telemetry_for()
        timed, track = tel.timed, ("train", "dispatch")
        compiles = self.compile_counts().get("train_step", 0)
        with timed(track, "train_step"):
            with timed(track, "shard_batch"):
                batch = self.executor.shard_batch(batch)
            with timed(track, "rng"):
                rng = self._train_rng()
            with timed(track, "dispatch"):
                self.state, metrics = self.executor.train_step(
                    self.state, batch, rng)
        if self.compile_counts().get("train_step", 0) != compiles:
            # the step was traced and compiled just now: say once which
            # core each attention op resolved to
            tel.instant(("train", "compile"), "attn_impl",
                        args=self.attn_impl_counts())
        return metrics

    def train_batches(self, batches: Sequence[Dict[str, np.ndarray]]):
        """Run len(batches) optimizer steps in ONE device dispatch
        (`lax.scan` over the step axis) — the TPU analog of the
        reference's per-iteration Legion trace replay (begin_trace/
        end_trace, alexnet.cc:106-111): dependence analysis and dispatch
        cost are paid once for the whole group, not per step. The RNG
        stream is identical to calling
        `train_batch` len(batches) times.

        Returns the metrics dict with a leading (K,) step axis on every
        value (one bulk `jax.device_get` fetches the whole group —
        per-step slicing would reintroduce a dispatch per scalar).

        `batches` may also be a group pre-staged by `stage_batches`
        (reused across calls without re-staging — the synthetic-data
        training-loop pattern, reference `syntheticInput`
        config.h:131)."""
        if isinstance(batches, dict):  # pre-staged by stage_batches
            stacked = batches
            k = int(next(iter(stacked.values())).shape[0])
        else:
            k = len(batches)
            if k == 0:
                return {}
            stacked = self.executor.shard_batch_stacked(list(batches))
        rngs = jnp.stack([jax.random.fold_in(self._rng, self._host_step + i)
                          for i in range(k)])
        self._host_step += k
        self.state, metrics = self.executor.train_step_multi(
            self.state, stacked, rngs)
        return metrics

    def train_batch_accum(self, microbatches:
                          Sequence[Dict[str, np.ndarray]]):
        """ONE optimizer step over K microbatches (gradient
        accumulation): gradients are computed per microbatch under
        `lax.scan`, summed, and applied once — the large-batch result
        without K x the activation memory. Sparse embedding rows
        concatenate across microbatches into a single scatter update, so
        the step equals a K x-sized batch exactly (BN stats advance per
        microbatch). Returns one metrics dict (loss = mean; sum-style
        metrics folded over the group)."""
        k = len(microbatches)
        if k == 0:
            return {}
        stacked = self.executor.shard_batch_stacked(list(microbatches))
        # ONE optimizer step -> _host_step advances by ONE (it mirrors
        # state.step, which checkpoint resume resyncs from); the K
        # microbatch keys are sub-keys of this step's key (double
        # fold_in), so they never collide with other steps' streams
        base = jax.random.fold_in(self._rng, self._host_step)
        rngs = jnp.stack([jax.random.fold_in(base, i) for i in range(k)])
        self._host_step += 1
        self.state, metrics = self.executor.train_step_accum(
            self.state, stacked, rngs)
        return metrics

    def stage_batches(self, batches: Sequence[Dict[str, np.ndarray]]):
        """Pre-stage K batches as one stacked device-resident group for
        repeated `train_batches` calls. One host->device transfer total;
        pass the result to `train_batches` as many times as needed."""
        return self.executor.shard_batch_stacked(list(batches))

    def calibrate_simulator(self, batch: Optional[Dict] = None,
                            steps: int = 10):
        """Ground the execution simulator in a real measured step (the
        analog of the reference grounding every simulated cost in real
        on-device kernel timings, src/runtime/model.cu:20-62): measure
        `steps` training steps, set the simulator's end-to-end time
        scale, and keep it as `self.simulator` for later queries.

        Returns (measured_step_seconds, predicted_step_seconds) where the
        prediction is the simulator's PRE-calibration estimate — the
        number to hold against the MLSys'19 <30% simulator-error envelope
        (BASELINE.md). Requires compile() first."""
        from .parallel.mesh import single_device_mesh
        from .search.measure import calibrated_machine_model
        from .search.simulator import Simulator

        assert self.executor is not None, "compile() before calibrating"
        if batch is None:
            from .core.dataloader import synthetic_batch
            batch = synthetic_batch(self)
        mesh = self.mesh or single_device_mesh()
        sim = Simulator(
            self, mesh,
            calibrated_machine_model(
                mesh, machine_file=self.config.machine_model_file))
        strategy = self.strategy or Strategy()
        predicted = sim.simulate(strategy)
        # warmup (jit compile), then measure; a device->host scalar fetch
        # closes each timing region
        m = self.train_batch(batch)
        float(m["loss"])
        t0 = time.perf_counter()
        for _ in range(steps):
            m = self.train_batch(batch)
        float(m["loss"])
        measured = (time.perf_counter() - t0) / steps
        sim.calibrate_end_to_end(strategy, measured)
        self.simulator = sim
        return measured, predicted

    def fit(self, x: Dict[str, np.ndarray], y: np.ndarray,
            batch_size: Optional[int] = None, epochs: Optional[int] = None,
            shuffle: bool = True, verbose: bool = True,
            checkpoint_dir: Optional[str] = None,
            checkpoint_every: int = 1,
            steps_per_dispatch="auto",
            prefetch: bool = False,
            grad_accum_steps: int = 1):
        """Keras-style fit over host numpy arrays (reference:
        base_model.py:195-255 + _train loop :347-424).

        `checkpoint_dir` enables the elastic-recovery story the reference
        lacks (SURVEY 5: no failure handling): the full TrainState is
        saved asynchronously every `checkpoint_every` epochs, and a
        re-run with the same directory resumes from the newest epoch —
        kill the process at any point and simply run it again.

        `grad_accum_steps=K` turns each group of K consecutive
        microbatches into ONE optimizer step (train_batch_accum):
        effective batch K*batch_size without the activation memory.

        `steps_per_dispatch="auto"` (default) groups 8 steps per device
        dispatch on TPU backends and 1 elsewhere — the reference traces
        EVERY training iteration (begin/end_trace, alexnet.cc:106-111),
        and this is the dispatch-grouped analog; pass an int to pin."""
        steps_per_dispatch = _resolve_steps_per_dispatch(
            steps_per_dispatch, grad_accum_steps)
        if grad_accum_steps > 1 and steps_per_dispatch > 1:
            raise ValueError(
                "grad_accum_steps and steps_per_dispatch are both dispatch "
                "groupings; use one or the other")
        bs = batch_size or self.config.batch_size
        ep = epochs or self.config.epochs
        names = list(x.keys())
        n = len(y)
        steps = n // bs
        # persistent across fit() calls so per-epoch shuffles differ even
        # when a wrapper drives one epoch at a time (keras frontend);
        # _fit_epochs_drawn counts permutations already consumed so a
        # checkpoint resume replays exactly the missing prefix
        fit_loader = None  # local: bound to this call's x/y arrays
        if not hasattr(self, "_fit_rng"):
            self._fit_rng = np.random.RandomState(self.config.seed)
            self._fit_epochs_drawn = 0
        rng = self._fit_rng

        def draw_perm():
            self._fit_epochs_drawn += 1
            return rng.permutation(n)

        # pipelined host dispatch (core/overlap.DispatchWindow): up to
        # `train_dispatch_depth` dispatches stay in flight before the
        # OLDEST step's metrics are pulled to host, so retrieval of step
        # N overlaps device execution of step N+1 — the host never
        # blocks on the newest dispatch except at epoch/checkpoint
        # boundaries (window drain). Each dispatch is a marked fault
        # site ("train.dispatch") fired BEFORE the jitted call so an
        # injected fault never consumes the donated state buffers.
        from .core.overlap import DispatchWindow
        from .utils import faults as _faults
        from .utils.telemetry import telemetry_for, train_metrics
        inj = _faults.injector_for(self.config)
        # observability (utils/telemetry.py): dispatch/fetch spans on
        # the train tracks, the metrics registry train_report renders
        # from, and the per-epoch simulator-drift sample (measured
        # step time vs the overlap-exact graph's prediction). All
        # host-side — telemetry on vs off trains bit-identically.
        tel = telemetry_for(self.config)
        self.telemetry = tel
        # re-price the drift prediction per fit(): the strategy, mesh
        # or bucket layout may have changed since the last fit, and a
        # transient pricing failure must not latch None forever
        self.__dict__.pop("_drift_predicted_step_s", None)
        _compiles = None
        if tel.enabled:
            # process-wide backend-compile counter (the serve engine's
            # zero-recompile instrument): an epoch whose window saw a
            # compile (epoch 0's jit, a mid-fit new shape signature)
            # must not feed the drift calibrator — compile seconds are
            # not step time, and one contaminated sample poisons the
            # regime average
            from .core.programs import CompileEvents
            if CompileEvents.install():
                _compiles = CompileEvents
        win = DispatchWindow(
            getattr(self.config, "train_dispatch_depth", 2),
            telemetry=tel)
        gaps: List[float] = []   # host time between dispatches (prep)
        n_dispatches = [0]
        last_end = [None]

        def _dispatch(fn, *args):
            t = time.perf_counter()
            if last_end[0] is not None:
                gaps.append(t - last_end[0])
            inj.fire("train.dispatch")
            out = fn(*args)
            last_end[0] = time.perf_counter()
            n_dispatches[0] += 1
            if tel.enabled:
                tel.span(("train", "dispatch"), "dispatch", t,
                         last_end[0],
                         args={"dispatch": n_dispatches[0] - 1})
            return out

        history = []
        start_epoch = 0
        ckptr = None  # one async checkpointer reused across the run
        if checkpoint_dir:
            from .core.checkpoint import restore_model, save_checkpoint
            # the name filter also skips uncommitted crash leftovers:
            # save_checkpoint stages into `epoch_N.tmp` / `epoch_N.old`
            # and only an atomic promote produces a bare `epoch_N`, so
            # a kill-mid-save run resumes from the newest COMMITTED
            # epoch (docs/robustness.md). A promote killed inside its
            # rename window strands the committed dir at `.old` —
            # recover those first so the scan can see them.
            if os.path.isdir(checkpoint_dir):
                from .core.checkpoint import recover_promoted
                for d in os.listdir(checkpoint_dir):
                    if d.startswith("epoch_") and d.endswith(".old"):
                        recover_promoted(
                            os.path.join(checkpoint_dir, d[:-len(".old")]))
            done = sorted(
                int(d[len("epoch_"):]) for d in (
                    os.listdir(checkpoint_dir)
                    if os.path.isdir(checkpoint_dir) else [])
                if d.startswith("epoch_")
                and d[len("epoch_"):].isdigit())
            while done:
                # a committed dir can still be damaged out-of-band
                # (disk fault, manual edit): fall back epoch by epoch
                # rather than failing the whole run
                try:
                    restore_model(self, os.path.join(
                        checkpoint_dir, f"epoch_{done[-1]}"))
                    start_epoch = done[-1] + 1
                    break
                except Exception as e:
                    import warnings
                    warnings.warn(
                        f"checkpoint epoch_{done[-1]} unreadable "
                        f"({type(e).__name__}: {e}); falling back to "
                        f"the previous epoch")
                    done.pop()
            if start_epoch:
                # replay ONLY the missing prefix of the shuffle stream so
                # resumed epochs see the permutations the uninterrupted
                # run would have (a same-object continuation has already
                # consumed _fit_epochs_drawn of them)
                if shuffle:
                    while self._fit_epochs_drawn < start_epoch:
                        draw_perm()
                if verbose:
                    print(f"resuming from {checkpoint_dir} at epoch "
                          f"{start_epoch}")
        try:
            for epoch in range(start_epoch, ep):
                idx = draw_perm() if shuffle else np.arange(n)
                t0 = time.time()
                t0pc = time.perf_counter()
                compiles0 = _compiles.count if _compiles else 0
                spd = max(1, steps_per_dispatch)

                if prefetch:
                    # host row-gather on the native loader's background
                    # thread (double-buffered, csrc/dataloader.cc) — the
                    # prefetch analog of the reference's next_batch index
                    # launches — driven by fit's OWN permutation so the
                    # checkpoint-resume shuffle replay is unchanged
                    if fit_loader is None:
                        from .core.dataloader import DataLoaderSet
                        fit_loader = DataLoaderSet(
                            {**{k: x[k] for k in names}, "label": y},
                            bs, mesh=self.mesh, shuffle=False,
                            dtypes=self.executor.declared_input_dtypes)
                    it = fit_loader.iter_with_order(idx)

                    def mk_batch(s):
                        return next(it)
                else:
                    def mk_batch(s):
                        sel = idx[s * bs:(s + 1) * bs]
                        batch = {k: x[k][sel] for k in names}
                        batch["label"] = y[sel]
                        return batch

                # full groups go through the scanned multi-step (one
                # dispatch per group, trace-replay analog) or the
                # accumulation step (one UPDATE per group). Tails differ:
                # for dispatch grouping the split is semantics-neutral so
                # the tail takes single steps (only two program shapes
                # compile); for ACCUMULATION the grouping IS the
                # semantics, so the tail is accumulated as one smaller
                # group rather than demoted to microbatch-sized updates.
                # epoch_metrics entries: (metrics, loss_weight) where
                # loss_weight = microbatches represented by the entry's
                # (mean) loss; None = per-step stacked losses.
                gas = max(1, grad_accum_steps)
                group = gas if gas > 1 else spd
                if group == 1:
                    # plain single-step path: no scan-of-1 wrapper, no
                    # per-step np.stack — leaner default dispatch
                    for s in range(steps):
                        win.push(
                            (_dispatch(self.train_batch, mk_batch(s)),
                             1))
                    tail = []
                else:
                    for s0 in range(0, steps - steps % group, group):
                        mbs = [mk_batch(s) for s in range(s0, s0 + group)]
                        if gas > 1:
                            win.push((_dispatch(self.train_batch_accum,
                                                mbs), len(mbs)))
                        else:
                            win.push((_dispatch(self.train_batches,
                                                mbs), None))
                    tail = list(range(steps - steps % group, steps))
                if tail and gas > 1:
                    mbs = [mk_batch(s) for s in tail]
                    win.push((_dispatch(self.train_batch_accum, mbs),
                              len(mbs)))
                else:
                    for s in tail:
                        win.push(
                            (_dispatch(self.train_batch, mk_batch(s)),
                             1))
                # fold metrics on host (reference: UPDATE_METRICS future
                # fold). The dispatch window already pulled all but the
                # last depth-1 entries while later steps ran on device;
                # the epoch-boundary drain fetches the remainder —
                # per-scalar float(v) would issue steps*keys tiny
                # transfers; reference folds through futures too
                # (model.cc:2084-2108).
                epoch_metrics = win.drain()
                agg = {}
                loss_terms = 0
                for m, w in epoch_metrics:
                    for k, v in m.items():
                        if k == "loss":
                            # weight each entry's (mean) loss by the
                            # microbatches it represents so the epoch
                            # loss is the true per-microbatch mean
                            if w is None:  # (K,) per-step losses
                                agg[k] = agg.get(k, 0.0) + float(np.sum(v))
                                loss_terms += int(np.size(v))
                            else:
                                agg[k] = agg.get(k, 0.0) + float(v) * w
                                loss_terms += w
                        else:
                            agg[k] = agg.get(k, 0.0) + float(np.sum(v))
                dt = time.time() - t0
                if tel.enabled:
                    t1pc = time.perf_counter()
                    tel.span(("train", "epoch"), f"epoch {epoch}",
                             t0pc, t1pc, args={"steps": steps})
                    # the train half of the drift calibrator: measured
                    # wall per step (dispatch + device + fetch, the
                    # number a capacity planner sees) against the
                    # overlap-exact task graph's prediction for this
                    # model/mesh/bucket layout
                    # an epoch containing a backend compile records no
                    # drift sample (when the compile counter is
                    # unavailable, the first epoch — where the cold
                    # jit lives — is skipped instead)
                    compiled = (_compiles.count > compiles0 if _compiles
                                else epoch == start_epoch)
                    if steps and not compiled:
                        pred = self._predicted_step_s()
                        if pred and pred[0]:
                            tel.record_drift(
                                "train",
                                f"bs={bs} group={group} "
                                f"accum={grad_accum_steps}",
                                pred[0], (t1pc - t0pc) / steps,
                                breakdown=pred[1])
                out = {"epoch": epoch,
                       "loss": agg.get("loss", 0.0) / max(1, loss_terms),
                       "throughput": steps * bs / dt}
                if "correct" in agg:
                    out["accuracy"] = agg["correct"] / agg["count"]
                history.append(out)
                if verbose:
                    acc = (f" accuracy={out['accuracy']:.4f}"
                           if "accuracy" in out else "")
                    print(f"epoch {epoch}: loss={out['loss']:.4f}{acc} "
                          f"({out['throughput']:.1f} samples/s)")
                if checkpoint_dir \
                        and (epoch + 1) % max(1, checkpoint_every) == 0:
                    # reused AsyncCheckpointer: orbax serializes against
                    # the in-flight save itself
                    ckptr = save_checkpoint(
                        os.path.join(checkpoint_dir, f"epoch_{epoch}"),
                        self.state, use_async=True, checkpointer=ckptr)
        finally:
            # drain the window even on a mid-epoch fault: in-flight
            # dispatches already mutated self.state, so their results
            # must be consumed (not leaked as device handles) before
            # the exception propagates
            in_flight_at_exit = win.pending()
            try:
                win.drain()
            except Exception:
                pass
            self.last_train_stats = self._train_stats(
                win, gaps, n_dispatches[0], in_flight_at_exit)
            if tel.enabled:
                # fold into the canonical registry train_report renders
                # from, then flush the Chrome trace when --trace-out
                # asked for one (the finally runs on faults too, so
                # chaos runs leave a trace behind)
                train_metrics(self.last_train_stats,
                              registry=tel.metrics)
                trace_out = getattr(self.config, "trace_out", None)
                if trace_out:
                    try:
                        tel.export_chrome_trace(trace_out)
                    except OSError:
                        pass  # an unwritable path must not fail fit
            if ckptr is not None:  # commit in-flight saves even on
                ckptr.wait_until_finished()  # Ctrl-C / mid-epoch errors
                ckptr.close()
            if fit_loader is not None:  # release the native prefetch
                fit_loader.close()      # thread + double buffers
            # snapshot freshly compiled train executables to
            # --program-cache-dir (core/programs.py) so the next
            # process over this config resolves fit's step from disk
            # instead of recompiling (no-op when unarmed/clean)
            try:
                self.executor.save_programs()
            except Exception:
                pass  # an unwritable cache dir must not fail fit
        return history

    def _train_stats(self, win, gaps, n_dispatches, in_flight_at_exit):
        """Overlap-runtime instrumentation for one fit() run — rendered
        by utils/profiling.train_report."""
        waits = sorted(win.fetch_waits_s)
        sg = sorted(gaps)
        buckets = (self.executor.grad_bucket_info()
                   if hasattr(self.executor, "grad_bucket_info")
                   else {"count": 0, "bucket_mb": 0.0, "bytes": []})
        dp = (self.mesh.shape.get("data", 1)
              if self.mesh is not None else 1)
        nb = buckets["count"]
        # structural estimate: every bucket except the last-completing
        # one can hide its all-reduce behind remaining backward compute
        est_hidden = (1.0 - 1.0 / nb) if (nb > 1 and dp > 1) else 0.0
        return {
            "dispatches": n_dispatches,
            "dispatch_depth": win.depth,
            "max_in_flight": win.max_in_flight,
            "in_flight_at_exit": in_flight_at_exit,
            "pending_after_drain": win.pending(),
            "dispatch_gap_s_mean": (sum(sg) / len(sg)) if sg else 0.0,
            "dispatch_gap_s_p50": sg[len(sg) // 2] if sg else 0.0,
            "dispatch_gap_s_max": sg[-1] if sg else 0.0,
            "fetch_wait_s_total": sum(waits),
            "fetch_wait_s_max": waits[-1] if waits else 0.0,
            "grad_buckets": buckets,
            "data_parallel": dp,
            "est_comm_hidden": est_hidden,
        }

    def _predicted_step_s(self) -> Optional[tuple]:
        """(predicted seconds per training step, per-task-class
        breakdown) for THIS model on its mesh/strategy — the
        overlap-exact task graph the strategy search prices
        (search/simulator.Simulator), which is exactly what the
        telemetry drift calibrator must compare measured steps against
        (the breakdown is the attribution vector drift_report folds
        per task class). Cached on the model for the duration
        of one fit() — fit's prologue drops the cache, so a strategy/
        mesh/bucket change between fits re-prices and a transient
        failure cannot latch None forever; None when the model/mesh
        cannot be priced (drift simply goes unrecorded)."""
        if not hasattr(self, "_drift_predicted_step_s"):
            try:
                from .parallel.pconfig import Strategy
                from .search.simulator import Simulator
                mesh = self.mesh
                if mesh is None:
                    mesh = make_mesh((1,), ("data",))
                sim = Simulator(self, mesh)
                strat = (self.strategy if self.strategy is not None
                         else Strategy())
                self._drift_predicted_step_s = (
                    float(sim.simulate(strat)),
                    sim.step_breakdown(strat))
            except Exception:
                self._drift_predicted_step_s = None
        return self._drift_predicted_step_s

    def memory_ledger(self) -> dict:
        """Per-device HBM byte accounting for training — params and
        optimizer state from the LIVE device buffers (shard-aware
        nbytes, search/explain.pytree_device_bytes) next to the
        simulator's HBM-penalty input (Simulator.memory_per_device —
        weights + optimizer mirror + activation estimate per op), with
        the residual reported as the activation estimate. Components
        land as ``train_hbm_bytes{component=...}`` gauges when a fit()
        telemetry bus is live."""
        from .search.explain import pytree_device_bytes
        params = opt = 0.0
        if self.state is not None:
            params = pytree_device_bytes(self.state.params)
            opt = pytree_device_bytes(self.state.opt_state)
        sim_bytes = None
        try:
            from .parallel.pconfig import Strategy
            from .search.simulator import Simulator
            mesh = self.mesh
            if mesh is None:
                mesh = make_mesh((1,), ("data",))
            sim = Simulator(self, mesh)
            sim_bytes = float(sim.memory_per_device(
                self.strategy if self.strategy is not None
                else Strategy()))
            hbm = float(sim.mm.spec.hbm_capacity)
        except Exception:
            hbm = None
        ledger = {
            "params_bytes": params,
            "optimizer_bytes": opt,
            "live_bytes": params + opt,
            "sim_hbm_input_bytes": sim_bytes,
            # the cost model's activation/workspace share: its memory
            # input beyond the live persistent buffers
            "activation_est_bytes": (max(0.0, sim_bytes - params - opt)
                                     if sim_bytes is not None else None),
        }
        if hbm:
            ledger["hbm_capacity_bytes"] = hbm
            ledger["hbm_utilization"] = (
                (sim_bytes if sim_bytes is not None
                 else params + opt) / hbm)
        tel = self.telemetry
        if tel is not None and tel.enabled:
            for comp in ("params", "optimizer", "live"):
                tel.metrics.set("train_hbm_bytes",
                                ledger[f"{comp}_bytes"],
                                component=comp)
            if sim_bytes is not None:
                tel.metrics.set("train_hbm_bytes", sim_bytes,
                                component="sim_hbm_input")
        return ledger

    def evaluate(self, x: Dict[str, np.ndarray], y: np.ndarray,
                 batch_size: Optional[int] = None,
                 steps_per_dispatch="auto"):
        bs = batch_size or self.config.batch_size
        names = list(x.keys())
        n = len(y)
        steps = max(1, n // bs)
        spd = max(1, _resolve_steps_per_dispatch(steps_per_dispatch))
        step_metrics = []

        def mk_batch(s):
            sel = slice(s * bs, (s + 1) * bs)
            batch = {k: x[k][sel] for k in names}
            batch["label"] = y[sel]
            return batch

        # grouped read-only dispatches (scan), single-step ragged tail
        for s0 in range(0, steps - steps % spd, spd):
            stacked = self.executor.shard_batch_stacked(
                [mk_batch(s) for s in range(s0, s0 + spd)])
            step_metrics.append(
                self.executor.eval_step_multi(self.state, stacked))
        for s in range(steps - steps % spd, steps):
            sharded = self.executor.shard_batch(mk_batch(s))
            _, m = self.executor.eval_step(self.state, sharded)
            step_metrics.append(m)  # device scalars; convert once at end
        step_metrics = jax.device_get(step_metrics)  # one bulk transfer
        agg: Dict[str, float] = {}
        for m in step_metrics:
            for k, v in m.items():
                # scalar (single-step) or (K,)-stacked (grouped)
                agg[k] = agg.get(k, 0.0) + float(np.sum(v))
        out = {"loss": agg.get("loss", 0.0) / steps}
        if "correct" in agg:
            out["accuracy"] = agg["correct"] / agg["count"]
        return out

    def create_data_loader(self, tensor_or_name, data) -> "SingleDataLoader":
        """Reference parity: FFModel.create_data_loader (cbinding :1618)
        — one loader per (tensor, full numpy dataset)."""
        from .core.dataloader import SingleDataLoader
        name = (tensor_or_name if isinstance(tensor_or_name, str)
                else tensor_or_name.name)
        return SingleDataLoader(name, data, self.config.batch_size,
                                mesh=self.mesh)

    # ---------------- weight access (reference Parameter::get/set) ------
    def get_weights(self, op_name: str) -> Dict[str, np.ndarray]:
        """Host copy of an op's weights (reference Parameter::get_weights,
        model.cu:439-452). Under multi-controller SPMD a weight sharded
        across processes is all-gathered — a COLLECTIVE, so call from
        every process (the normal SPMD discipline)."""
        if hasattr(self.executor, "get_op_weights"):
            # staged (pipelined) executor: weights live flat-packed in
            # per-stage rows; the hook unpacks the op's view
            return self.executor.get_op_weights(self.state, op_name)
        op = next((o for o in self.ops if o.name == op_name), None)
        out = {}
        for k, v in self.state.params[op_name].items():
            if isinstance(v, jax.Array) and not v.is_fully_addressable \
                    and not v.is_fully_replicated:
                # genuinely cross-process-sharded: only a collective can
                # materialize it (replicated weights fetch locally —
                # no communication, callable from one process alone)
                from jax.experimental import multihost_utils
                out[k] = np.asarray(
                    multihost_utils.process_allgather(v, tiled=True))
            else:
                out[k] = np.asarray(v)
            if k == "kernel" and hasattr(op, "to_table_order"):
                # placed stacked embeddings expose TABLE order (pads
                # dropped) — a balanced placement permutes slots, and a
                # raw slot-order copy into another layout would install
                # the wrong rows with no shape error
                out[k] = op.to_table_order(out[k])
        return out

    def set_weights(self, op_name: str, weights: Dict[str, np.ndarray]):
        if hasattr(self.executor, "set_op_weights"):
            self.executor.set_op_weights(self.state, op_name, weights)
            return
        cur = self.state.params[op_name]
        op = next((o for o in self.ops if o.name == op_name), None)
        for k, v in weights.items():
            if (k == "kernel" and hasattr(op, "from_table_order")
                    and getattr(op, "placement", None)
                    and v.shape[0] == op.num_tables
                    and tuple(v.shape[1:]) == tuple(cur[k].shape[1:])):
                # TABLE-ordered kernel (the get_weights form): scatter
                # into the placed slot layout, pads untouched
                v = op.from_table_order(
                    v, np.asarray(cur[k], dtype=np.dtype(cur[k].dtype))
                    if cur[k].is_fully_addressable
                    else np.zeros(cur[k].shape, np.dtype(cur[k].dtype)))
            assert cur[k].shape == v.shape, (op_name, k, cur[k].shape, v.shape)
            # convert on HOST, then device_put with the parameter's
            # sharding: only each device's shard transfers, and the
            # strategy's placement survives (a bare jnp.asarray would
            # stage the whole array on the default device — an OOM for
            # weights that are sharded precisely because they don't fit)
            host = np.asarray(v, dtype=np.dtype(cur[k].dtype))
            cur[k] = place_global(host, cur[k].sharding)

    def set_states(self, op_name: str, states: Dict[str, np.ndarray]):
        """Host set of non-trainable op state (e.g. BN running stats) —
        same role as set_weights for the reference's non-Parameter
        regions."""
        if hasattr(self.executor, "set_op_states"):
            self.executor.set_op_states(self.state, op_name, states)
            return
        cur = self.state.states[op_name]
        for k, v in states.items():
            assert cur[k].shape == v.shape, (op_name, k, cur[k].shape, v.shape)
            host = np.asarray(v, dtype=np.dtype(cur[k].dtype))
            cur[k] = place_global(host, cur[k].sharding)

    def set_learning_rate(self, lr: float) -> None:
        """Runtime LR control (reference keras LearningRateScheduler,
        python/flexflow/keras/callbacks.py:49-62, which rewrote the
        config's lr each epoch): rescales the compiled step's TRACED
        lr input, so a schedule never recompiles the step."""
        base = float(getattr(self.optimizer, "lr", 0.0) or 0.0)
        if base == 0.0:
            raise ValueError(
                "optimizer has no nonzero base lr to schedule against")
        self.executor._lr_scale = float(lr) / base

    def get_learning_rate(self) -> float:
        base = float(getattr(self.optimizer, "lr", 0.0) or 0.0)
        return base * float(getattr(self.executor, "_lr_scale", 1.0))

    def get_states(self, op_name: str) -> Dict[str, np.ndarray]:
        """Host view of non-trainable op state (e.g. BN running
        stats)."""
        if hasattr(self.executor, "get_op_states"):
            return self.executor.get_op_states(self.state, op_name)
        return {k: np.asarray(jax.device_get(v))
                for k, v in self.state.states[op_name].items()}

    def attn_impl_counts(self) -> Dict[str, int]:
        """How many attention ops' cores the last trace resolved to the
        Pallas flash kernels and how many to the XLA path
        (`MultiHeadAttention.attn_impl`; an op not traced yet counts
        under neither)."""
        counts = {"flash": 0, "xla": 0}
        for op in self.ops:
            impl = getattr(op, "attn_impl", None)
            if impl is not None:
                counts[impl] += 1
        return counts

    def summary(self) -> str:
        lines = [f"{'op':30s} {'type':20s} {'output':24s} {'params':>12s}"]
        total = 0
        for op in self.ops:
            n = sum(int(np.prod(s.shape)) for s in op.weight_specs().values())
            total += n
            lines.append(f"{op.name:30s} {op.op_type:20s} "
                         f"{str(op.outputs[0].shape):24s} {n:>12,d}")
        lines.append(f"total params: {total:,d}")
        impl = self.attn_impl_counts()
        if any(impl.values()):
            lines.append("attention cores: " + ", ".join(
                f"{k} {n}" for k, n in impl.items()))
        return "\n".join(lines)
