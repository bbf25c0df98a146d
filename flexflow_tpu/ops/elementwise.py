"""Elementwise unary/binary ops, dropout, softmax.

Reference: src/ops/element_unary.cu, element_binary.cu, dropout.cu,
softmax.cu. The reference's in-place output machinery
(can_inplace_output + compile-time in-place pass, model.cc:1580-1609) has
no TPU analog: XLA does buffer reuse itself.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

from ..op import SAMPLE, CHANNEL, SEQ, Op, OpContext, WeightSpec, register_op
from .common import rms_norm


def _passthrough_axes(shape):
    """Logical axes for rank-preserving ops: (sample, seq, channel) for
    rank-3 sequence tensors, sample-only otherwise (conv NCHW tensors are
    handled by the conv ops' own overrides)."""
    n = len(shape)
    axes = [None] * n
    if n >= 1:
        axes[0] = SAMPLE
    if n == 3:
        axes[1] = SEQ
        axes[2] = CHANNEL
    return [tuple(axes)]


class PassthroughAxesMixin:
    """Shared logical-axis labeling for rank-preserving ops: outputs
    carry the same SAMPLE/SEQ/CHANNEL labels as the input."""

    def output_axes(self):
        return _passthrough_axes(self.outputs[0].shape)

    def input_axes(self):
        return [_passthrough_axes(t.shape)[0] for t in self.inputs]



_UNARY = {
    "relu": jax.nn.relu,
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "elu": jax.nn.elu,
    "exp": jnp.exp,
    "gelu": jax.nn.gelu,
    "identity": lambda x: x,
    "scalar_multiply": None,  # uses attrs["scalar"]
}

_BINARY = {
    "add": jnp.add,
    "subtract": jnp.subtract,
    "multiply": jnp.multiply,
    "divide": jnp.divide,
    "max": jnp.maximum,
    "min": jnp.minimum,
}


@register_op
class ElementUnary(PassthroughAxesMixin, Op):
    op_type = "element_unary"

    def __init__(self, model, name, inputs, mode: str, scalar: float = None):
        super().__init__(model, name, inputs)
        assert mode in _UNARY, f"unknown unary mode {mode}"
        self.mode = mode
        self.scalar = scalar
        self.attrs = {"mode": mode, "scalar": scalar}

    def output_shapes(self):
        return [tuple(self.inputs[0].shape)]

    def forward(self, params, xs, ctx: OpContext):
        (x,) = xs
        if self.mode == "scalar_multiply":
            return [x * self.scalar]
        return [_UNARY[self.mode](x)]

    def flops(self) -> float:
        return float(self.inputs[0].num_elements)




@register_op
class Reduce(Op):
    """Axis reduction (mean/sum/max). No single reference analog — the
    reference reaches reductions through pooling/softmax kernels; this
    is the generic form frontends need (ONNX ReduceMean/Sum/Max, torch
    .mean(dim)); lowers to one jnp reduction."""

    op_type = "reduce"
    _FNS = {"mean": jnp.mean, "sum": jnp.sum, "max": jnp.max}

    def __init__(self, model, name, inputs, mode: str, axis: int,
                 keepdims: bool = False):
        super().__init__(model, name, inputs)
        if mode not in self._FNS:
            raise ValueError(f"unknown reduce mode {mode!r}")
        rank = len(inputs[0].shape)
        axis = axis if axis >= 0 else axis + rank
        if not 0 < axis < rank:
            raise ValueError(
                f"reduce axis {axis} out of range for rank {rank} "
                f"(the sample dim 0 cannot be reduced)")
        self.mode = mode
        self.axis = axis
        self.keepdims = bool(keepdims)
        self.attrs = {"mode": mode, "axis": axis, "keepdims": keepdims}

    def output_shapes(self):
        s = list(self.inputs[0].shape)
        if self.keepdims:
            s[self.axis] = 1
        else:
            s.pop(self.axis)
        return [tuple(s)]

    def forward(self, params, xs, ctx: OpContext):
        (x,) = xs
        from ..core.precision import policy_active
        if self.mode in ("mean", "sum") and x.dtype != jnp.float32 \
                and jnp.issubdtype(x.dtype, jnp.floating) \
                and policy_active(self.model.config):
            # f32 reduction accumulator (mixed-precision policy): a
            # long bf16 sum drifts by O(n * eps); max needs no
            # accumulator. Output returns to the activation dtype.
            # Policy-gated like Softmax above — builder-level bf16
            # under the f32 default keeps exact pre-policy numerics.
            return [self._FNS[self.mode](
                x, axis=self.axis, keepdims=self.keepdims,
                dtype=jnp.float32).astype(x.dtype)]
        return [self._FNS[self.mode](x, axis=self.axis,
                                     keepdims=self.keepdims)]

    def output_axes(self):
        in_axes = list(_passthrough_axes(self.inputs[0].shape)[0])
        if self.keepdims:
            in_axes[self.axis] = None
        else:
            in_axes.pop(self.axis)
        return [tuple(in_axes)]

    def input_axes(self):
        return [_passthrough_axes(self.inputs[0].shape)[0]]

    def flops(self) -> float:
        return float(self.inputs[0].num_elements)


@register_op
class ElementBinary(PassthroughAxesMixin, Op):
    op_type = "element_binary"

    def __init__(self, model, name, inputs, mode: str):
        super().__init__(model, name, inputs)
        assert mode in _BINARY, f"unknown binary mode {mode}"
        # Reference requires same-shape (element_binary.cu: broadcasting NOT
        # general); we allow numpy broadcasting as a superset.
        self.mode = mode
        self.attrs = {"mode": mode}

    def output_shapes(self):
        a, b = self.inputs[0].shape, self.inputs[1].shape
        return [tuple(jnp.broadcast_shapes(a, b))]

    def forward(self, params, xs, ctx: OpContext):
        a, b = xs
        return [_BINARY[self.mode](a, b)]

    def flops(self) -> float:
        return float(self.outputs[0].num_elements)




@register_op
class Dropout(PassthroughAxesMixin, Op):
    """Reference: src/ops/dropout.cu (cuDNN dropout with reserve space —
    here: stateless jax.random.bernoulli keyed off the per-step rng)."""

    op_type = "dropout"

    def __init__(self, model, name, inputs, rate: float, seed: int = 0):
        super().__init__(model, name, inputs)
        self.rate = float(rate)
        self.seed = seed
        self.attrs = {"rate": rate, "seed": seed}

    def output_shapes(self):
        return [tuple(self.inputs[0].shape)]

    def forward(self, params, xs, ctx: OpContext):
        (x,) = xs
        if not ctx.training or self.rate <= 0.0:
            return [x]
        keep = 1.0 - self.rate
        mask = jax.random.bernoulli(ctx.rng, keep, x.shape)
        return [jnp.where(mask, x / keep, 0.0).astype(x.dtype)]




@register_op
class Softmax(PassthroughAxesMixin, Op):
    """Reference: src/ops/softmax.cu (cuDNN accurate-mode softmax =
    max-subtracted, which is exactly jax.nn.softmax)."""

    op_type = "softmax"

    def __init__(self, model, name, inputs, axis: int = -1):
        super().__init__(model, name, inputs)
        self.axis = axis
        self.attrs = {"axis": axis}

    def output_shapes(self):
        return [tuple(self.inputs[0].shape)]

    def forward(self, params, xs, ctx: OpContext):
        (x,) = xs
        from ..core.precision import policy_active
        if x.dtype != jnp.float32 \
                and jnp.issubdtype(x.dtype, jnp.floating) \
                and policy_active(self.model.config):
            # max/exp/sum statistics in f32 (the mixed-precision policy
            # and the flash-attention convention): a bf16 sum over the
            # class dim loses exactly the normalization the loss reads.
            # Output returns to the activation dtype. Gated on the
            # POLICY, not the input dtype alone: builder-level bf16
            # models under the f32 default keep their exact pre-policy
            # numerics (the compatibility promise in core/precision.py).
            return [jax.nn.softmax(x.astype(jnp.float32),
                                   axis=self.axis).astype(x.dtype)]
        return [jax.nn.softmax(x, axis=self.axis)]

    def flops(self) -> float:
        return 5.0 * self.inputs[0].num_elements


@register_op
class LayerNorm(PassthroughAxesMixin, Op):
    """Normalize over the LAST dim with learned scale/bias.

    No reference analog — FlexFlow ships only BatchNorm
    (src/ops/batch_norm.cu); this is a TPU-first addition because
    modern transformer blocks (pre-LN) depend on it. Statistics in f32
    regardless of activation dtype (mirrors BatchNorm here).
    """

    op_type = "layer_norm"

    def __init__(self, model, name, inputs, eps: float = 1e-5,
                 elementwise_affine: bool = True, use_bias: bool = True):
        super().__init__(model, name, inputs)
        self.eps = float(eps)
        self.elementwise_affine = elementwise_affine
        self.use_bias = bool(use_bias)      # False: a learned scale alone
        self.num_channels = inputs[0].shape[-1]
        self.attrs = {"eps": eps,
                      "elementwise_affine": elementwise_affine}
        if not self.use_bias:
            self.attrs["use_bias"] = False

    def output_shapes(self):
        return [tuple(self.inputs[0].shape)]

    def weight_specs(self):
        if not self.elementwise_affine:
            return {}
        c = self.num_channels
        specs = {"scale": WeightSpec((c,), initializer="ones",
                                     axes=(CHANNEL,))}
        if self.use_bias:
            specs["bias"] = WeightSpec((c,), initializer="zeros",
                                       axes=(CHANNEL,))
        return specs

    def forward(self, params, xs, ctx: OpContext):
        (x,) = xs
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + self.eps)
        if self.elementwise_affine:
            y = y * params["scale"].astype(jnp.float32)
            if self.use_bias:
                y = y + params["bias"].astype(jnp.float32)
        return [y.astype(x.dtype)]

    def flops(self) -> float:
        return 8.0 * self.inputs[0].num_elements


@register_op
class RMSNorm(PassthroughAxesMixin, Op):
    """x * rsqrt(mean(x^2) + eps) * scale over the LAST dim: no mean
    subtracted, no bias (the modern decoder block's norm). Statistics
    in f32 regardless of activation dtype, like LayerNorm here.
    `zero_centered`: the scale is 1 + w (Qwen3-Next's norm, whose w
    starts at 0); `scale_init` (lo, hi): w starts uniform in it — or,
    (lo, hi, "signed"), with magnitudes in it and a random sign."""

    op_type = "rms_norm"

    def __init__(self, model, name, inputs, eps: float = 1e-5,
                 zero_centered: bool = False, scale_init=None):
        super().__init__(model, name, inputs)
        self.eps = float(eps)
        self.zero_centered = bool(zero_centered)
        self.scale_init = None if scale_init is None else tuple(scale_init)
        self.num_channels = inputs[0].shape[-1]
        self.attrs = {"eps": eps}
        if self.zero_centered:
            self.attrs["zero_centered"] = True

    def output_shapes(self):
        return [tuple(self.inputs[0].shape)]

    def weight_specs(self):
        if self.scale_init is not None:
            from ..core.initializers import range_init
            return {"scale": WeightSpec(
                (self.num_channels,), axes=(CHANNEL,),
                custom_init=range_init(self.scale_init))}
        return {"scale": WeightSpec(
            (self.num_channels,), axes=(CHANNEL,),
            initializer="zeros" if self.zero_centered else "ones")}

    def forward(self, params, xs, ctx: OpContext):
        (x,) = xs
        w = params["scale"]
        if self.zero_centered:
            w = 1.0 + w.astype(jnp.float32)
        return [rms_norm(x, w, self.eps)]

    def flops(self) -> float:
        return 4.0 * self.inputs[0].num_elements
