"""Gated units of the decoder-hybrid-decoder block (arXiv:2507.06607)
and the head tied to the token table.

  GatedFFN:         (g, u) = split(h W_gu);  y = (silu(g) * u) W_down
  GatedMemoryUnit:  y = (silu(h W_1) * m) W_2, m the MEMORY an earlier
                    state-space layer handed on for the same token
                    (ops/ssm.py): no cache, no recurrence
  TiedHead:         logits = x E^T, E the token table an Embedding with
                    `emit_table` gives as its second output

No bias anywhere. Products accumulate in f32 and round to the
activation dtype.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..op import (CHANNEL_IN, CHANNEL_OUT, SAMPLE, SEQ, Op, OpContext,
                  WeightSpec, register_op)

F32 = jnp.float32


def mm(x, w):
    return jnp.dot(x, w.astype(x.dtype),
                   preferred_element_type=F32).astype(x.dtype)


def gated_ffn(p, h, multipliers=(1.0, 1.0)):
    """`multipliers`: scalars on the gate projection's output and on
    the down projection's (Falcon-H1's muP `mlp_multipliers`)."""
    gate_m, down_m = multipliers
    g, u = jnp.split(mm(h, p["w_gu"]), 2, axis=-1)
    if gate_m != 1.0:
        g = g * gate_m
    y = mm(jax.nn.silu(g) * u, p["w_down"])
    return y * down_m if down_m != 1.0 else y


def gated_memory(p, h, m):
    return mm(jax.nn.silu(mm(h, p["w1"])) * m.astype(h.dtype), p["w2"])


def _tokens(shape) -> int:
    n = 1
    for s in shape[:-1]:
        n *= s
    return n


def _axes(shape):
    """(sample[, seq], feature) of a (B[, S], E) tensor."""
    return (SAMPLE,) + (SEQ,) * (len(shape) == 3) + (None,)


class _SeqOp(Op):
    """(B[, S], E) in, (B[, S], out_dim) out."""

    def output_shapes(self):
        return [tuple(self.inputs[0].shape[:-1]) + (self.out_dim,)]

    def output_axes(self):
        return [_axes(self.outputs[0].shape)]

    def input_axes(self):
        return [_axes(t.shape) for t in self.inputs]


@register_op
class GatedFFN(_SeqOp):
    op_type = "gated_ffn"

    def __init__(self, model, name, inputs, hidden_dim: int,
                 kernel_initializer: str = "glorot",
                 multipliers=(1.0, 1.0)):
        super().__init__(model, name, inputs)
        self.in_dim = self.out_dim = int(inputs[0].shape[-1])
        self.hidden_dim = int(hidden_dim)
        self.kernel_initializer = kernel_initializer
        self.multipliers = tuple(map(float, multipliers))
        self.attrs = {"hidden_dim": self.hidden_dim}

    def weight_specs(self):
        e, f = self.in_dim, self.hidden_dim
        from ..core.initializers import named
        init = lambda w: named(self.kernel_initializer, w)
        return {
            "w_gu": WeightSpec((e, 2 * f), axes=(CHANNEL_IN, CHANNEL_OUT),
                               initializer=init("w_gu"),
                               fan_in=e, fan_out=f),
            "w_down": WeightSpec((f, e), axes=(CHANNEL_IN, CHANNEL_OUT),
                                 initializer=init("w_down")),
        }

    def forward(self, params, xs, ctx: OpContext):
        return [gated_ffn(params, xs[0], self.multipliers)]

    def flops(self) -> float:
        return 6.0 * _tokens(self.inputs[0].shape) * self.in_dim \
            * self.hidden_dim


@register_op
class GatedMemoryUnit(_SeqOp):
    """Two inputs: h (B, S, E) and the memory m (B, S, d_inner)."""

    op_type = "gated_memory_unit"

    def __init__(self, model, name, inputs,
                 kernel_initializer: str = "glorot"):
        super().__init__(model, name, inputs)
        self.in_dim = self.out_dim = int(inputs[0].shape[-1])
        self.memory_dim = int(inputs[1].shape[-1])
        self.kernel_initializer = kernel_initializer
        self.attrs = {"memory_dim": self.memory_dim}

    def weight_specs(self):
        e, m = self.in_dim, self.memory_dim
        return {
            "w1": WeightSpec((e, m), axes=(CHANNEL_IN, CHANNEL_OUT),
                             initializer=self.kernel_initializer),
            "w2": WeightSpec((m, e), axes=(CHANNEL_IN, CHANNEL_OUT),
                             initializer=self.kernel_initializer),
        }

    def forward(self, params, xs, ctx: OpContext):
        return [gated_memory(params, xs[0], xs[1])]

    def flops(self) -> float:
        return 4.0 * _tokens(self.inputs[0].shape) * self.in_dim \
            * self.memory_dim


@register_op
class TiedHead(_SeqOp):
    """Two inputs: x (B, S, E) and the token table (V, E). No weight of
    its own. `scale` (a config's `logit_scale`) multiplies the logits
    in f32; 1 leaves them as they are."""

    op_type = "tied_head"

    def __init__(self, model, name, inputs, scale: float = 1.0):
        super().__init__(model, name, inputs)
        self.out_dim = int(inputs[1].shape[0])
        self.scale = float(scale)
        self.attrs = {"vocab": self.out_dim}
        if self.scale != 1.0:
            self.attrs["scale"] = self.scale

    def forward(self, params, xs, ctx: OpContext):
        x, table = xs
        y = jnp.dot(x, table.astype(x.dtype).T, preferred_element_type=F32)
        if self.scale != 1.0:
            y = y * self.scale
        return [y.astype(x.dtype)]

    def input_axes(self):
        return [_axes(self.inputs[0].shape), (None, None)]

    def flops(self) -> float:
        return 2.0 * _tokens(self.inputs[0].shape) * self.out_dim \
            * self.inputs[0].shape[-1]
