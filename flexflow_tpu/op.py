"""Op base class and registry.

The reference `Op` (include/model.h:188-254) owns Legion index spaces,
per-worker `OpMeta*`, and implements a 7-method contract of
init/forward/backward/partitioning/cost tasks. The TPU-native contract is
much smaller because XLA supplies scheduling, autodiff supplies backward,
and GSPMD supplies partitioning:

  * ``output_shapes``  — static shape inference (replaces
    create_output_and_partition, model.cc:589-657 shape math).
  * ``weight_specs``   — declares trainable parameters (replaces
    create_weights).
  * ``forward``        — pure JAX computation for one (sharded) step; the
    global train step is differentiated with `jax.grad`, so no hand-written
    backward tasks (SURVEY.md section 7 step 2).
  * ``logical axes``   — names each tensor dimension so a strategy can map
    it to a mesh axis (replaces ParallelConfig dims + the mapper's
    slice_task routing, mapper.cc:346-440).
  * ``flops`` / ``bytes`` hooks — feed the analytic cost model used by the
    MCMC strategy search (replaces measure_operator_cost).
"""

from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .tensor import Tensor

if TYPE_CHECKING:
    from .model import FFModel

# Logical axis vocabulary. "sample" is the batch dim; splitting it = DP
# (reference: sample-parallel). "channel*" splits = TP (reference:
# parameter/attribute parallel, linear.cu:144-270). "seq" split = SP/CP
# (new, absent in reference). "expert" split = EP (new).
SAMPLE = "sample"
CHANNEL = "channel"
CHANNEL_IN = "channel_in"
CHANNEL_OUT = "channel_out"
SEQ = "seq"
HEAD = "head"
HEIGHT = "height"
WIDTH = "width"
EXPERT = "expert"
VOCAB = "vocab"
LAYER = "layer"
TABLE = "table"  # stacked embedding tables (DLRM per-table placement)
REPLICA = None  # dimension never split


@dataclasses.dataclass
class WeightSpec:
    """Declaration of one trainable parameter of an op.

    ``fan_in``/``fan_out`` override shape-derived fans for fan-scaled
    initializers — needed for stacked weights (MoE experts (E, D, H),
    attention (E, H, Dh)) where the generic shape heuristic is wrong.
    """

    shape: Tuple[int, ...]
    dtype: jnp.dtype = jnp.float32
    initializer: str = "glorot"  # name into core.initializers registry
    axes: Tuple[Optional[str], ...] = None  # logical axis per dim
    custom_init: Optional[Callable] = None  # overrides `initializer`
    fan_in: Optional[int] = None
    fan_out: Optional[int] = None
    # stored as `dtype` whatever FFConfig.param_dtype says (a router's
    # f32 selection bias beside bf16 masters)
    keep_dtype: bool = False

    def __post_init__(self):
        if self.axes is None:
            self.axes = tuple([None] * len(self.shape))


@dataclasses.dataclass
class StateSpec:
    """Non-trainable per-op state (e.g. batch-norm running stats).

    The reference keeps these in dedicated Realm instances
    (include/model.h:883-899); here they live in a `state` pytree threaded
    functionally through the step.
    """

    shape: Tuple[int, ...]
    dtype: jnp.dtype = jnp.float32
    init_value: float = 0.0


class OpContext:
    """Per-invocation context handed to ``Op.forward``.

    ``mesh``/``op_strategy`` let collective-aware ops (ring attention for
    SP, fused MoE for EP) pick explicit shard_map implementations when
    their strategy maps an axis to a >1-sized mesh axis.
    """

    __slots__ = ("training", "rng", "seq_length", "state_in", "state_out",
                 "mesh", "op_strategy", "aux_loss", "nhwc_in", "nhwc_out")

    def __init__(self, training: bool, rng=None, seq_length: int = -1,
                 state_in: Optional[dict] = None, mesh=None,
                 op_strategy=None, nhwc_in: bool = False,
                 nhwc_out: bool = False):
        self.training = training
        self.rng = rng
        self.seq_length = seq_length
        self.state_in = state_in or {}
        self.state_out: dict = {}
        self.mesh = mesh
        self.op_strategy = op_strategy
        # ops may set a scalar auxiliary loss (e.g. MoE load-balancing);
        # the executor adds it to the training objective.
        self.aux_loss = None
        # NHWC layout residency (executor._compute_nhwc_resident): under
        # conv_layout="NHWC", values flow channels-last BETWEEN
        # conv-family ops; nhwc_in says this op's tensor inputs already
        # arrive NHWC-permuted, nhwc_out says its outputs should stay
        # NHWC (a consumer will read them that way). Both False outside
        # the executor walk — ops then do their own boundary transposes.
        self.nhwc_in = nhwc_in
        self.nhwc_out = nhwc_out

    def mesh_axis_size(self, logical_axis: str) -> int:
        """Size of the mesh axis a logical axis maps to (1 if unmapped)."""
        if self.mesh is None or self.op_strategy is None:
            return 1
        ax = self.op_strategy.mesh_axis_for(logical_axis)
        if ax is None or not isinstance(ax, str):
            return 1
        return self.mesh.shape.get(ax, 1)

    def mesh_axis_name(self, logical_axis: str):
        if self.op_strategy is None:
            return None
        ax = self.op_strategy.mesh_axis_for(logical_axis)
        return ax if isinstance(ax, str) else None


class Op:
    """Base class for all layers. Subclasses are pure-functional: they own
    no arrays, only shapes/attrs; arrays live in the executor's pytrees."""

    op_type: str = "op"

    def __init__(self, model: "FFModel", name: str, inputs: Sequence[Tensor]):
        self.model = model
        self.name = name
        self.inputs: List[Tensor] = list(inputs)
        self.outputs: List[Tensor] = []
        self.attrs: Dict = {}
        # finalize() is called by FFModel.add_op after subclass __init__.

    # ---- static graph contract ----
    def output_shapes(self) -> List[Tuple[int, ...]]:
        raise NotImplementedError

    def output_dtypes(self) -> List[jnp.dtype]:
        src = self.inputs[0].dtype if self.inputs else jnp.float32
        return [src for _ in self.output_shapes()]

    def weight_specs(self) -> Dict[str, WeightSpec]:
        return {}

    def state_specs(self) -> Dict[str, StateSpec]:
        return {}

    # Does the TRAINING-mode output depend on ctx.state_in? BatchNorm
    # reads state_in only to produce state_out (running-stat momentum)
    # — its training output uses batch statistics — so gradients are
    # state-independent and 1F1B's backward recompute may read the
    # already-advanced state row as a constant
    # (parallel/graph_pipeline.pipeline_1f1b_grads). A stateful op
    # whose training output DOES read state_in (e.g. a streaming/EMA
    # norm) must override this to True; StagedExecutor then rejects it
    # under the 1f1b schedule instead of silently mis-differentiating.
    training_output_reads_state: bool = False

    # ---- execution contract ----
    def forward(self, params: Dict[str, jax.Array], xs: List[jax.Array],
                ctx: OpContext) -> List[jax.Array]:
        raise NotImplementedError

    # ---- sharding contract ----
    def output_axes(self) -> List[Tuple[Optional[str], ...]]:
        """Logical axis name per output dim; default: sample on dim 0."""
        out = []
        for shp in [t.shape for t in self.outputs]:
            axes = [None] * len(shp)
            if len(shp) > 0:
                axes[0] = SAMPLE
            out.append(tuple(axes))
        return out

    def input_axes(self) -> List[Tuple[Optional[str], ...]]:
        """Logical axis name per input dim (used for resharding cost)."""
        out = []
        for t in self.inputs:
            axes = [None] * len(t.shape)
            if len(t.shape) > 0:
                axes[0] = SAMPLE
            out.append(tuple(axes))
        return out

    # ---- cost-model contract (replaces measure_operator_cost) ----
    def flops(self) -> float:
        """Forward FLOPs for the full (unsharded) op."""
        return 0.0

    def bytes_accessed(self) -> float:
        total = 0
        for t in list(self.inputs) + list(self.outputs):
            total += t.size_bytes()
        for spec in self.weight_specs().values():
            n = 1
            for s in spec.shape:
                n *= s
            total += n * jnp.dtype(spec.dtype).itemsize
        return float(total)

    def weight_bytes(self) -> float:
        total = 0
        for spec in self.weight_specs().values():
            n = 1
            for s in spec.shape:
                n *= s
            total += n * jnp.dtype(spec.dtype).itemsize
        return float(total)

    # ---- plumbing ----
    def finalize(self) -> None:
        """Create output Tensor handles from ``output_shapes``."""
        shapes = self.output_shapes()
        dtypes = self.output_dtypes()
        self.outputs = [
            Tensor(s, d, owner_op=self, owner_idx=i, name=f"{self.name}:out{i}")
            for i, (s, d) in enumerate(zip(shapes, dtypes))
        ]

    @property
    def output(self) -> Tensor:
        return self.outputs[0]

    def __repr__(self):
        ins = ", ".join(str(t.shape) for t in self.inputs)
        outs = ", ".join(str(t.shape) for t in self.outputs)
        return f"{type(self).__name__}({self.name}: [{ins}] -> [{outs}])"


# Registry: op_type string -> class, used by strategy file I/O, the ONNX
# importer and the torch.fx importer to construct ops by name.
OP_REGISTRY: Dict[str, type] = {}


def register_op(cls):
    OP_REGISTRY[cls.op_type] = cls
    return cls
