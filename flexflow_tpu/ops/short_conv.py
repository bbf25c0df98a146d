"""The gated short convolution (LFM2's `Lfm2MoeShortConv`; the sequence
mixer of three layers of four of LiquidAI/LFM2-24B-A2B, `model_type`
lfm2_moe).

Per token t of a sequence, h (E) the layer's normed input:

  [B | C | z] = h W_in          W_in: E -> 3 E, no bias; thirds in THIS order
  u_t = B_t * z_t               elementwise, E channels
  c_t = w[0] u_{t-2} + w[1] u_{t-1} + w[2] u_t
                                `taps` (3) a channel, depthwise, causal, no
                                bias, zeros before the sequence's first
                                token, NO activation
  m_t = (C_t * c_t) W_out       W_out: E -> E, no bias

What a sequence keeps from one step to the next is u_{t-2}, u_{t-1}: the
PRODUCT B * z of its last taps - 1 tokens (not h), (taps - 1) x E values a
layer — a convolution TAIL and nothing else: no state, no pages.

u is rounded to the activation dtype where it is made, so the value a
later token's taps read is the same whether it comes from the lanes of
this step or from the slot's tail: where a step cuts a sequence changes
no bit. The taps and the second gate run in f32.

Two forms that tests hold together (tests/test_lfm2_moe.py): `whole`
over whole sequences from nothing (the definition, the graph op's
forward) and `segmented` over the LANES of a serving step — runs of
consecutive lanes of one sequence, each resuming from its slot's tail
(ops/ssm.segmented_conv with 3 taps and no `conv_b`: zeros where the
sequence starts in the run, the tail written back for a run's last live
lanes by ops/ssm.run_tail_lanes — a gather of at most `slots` rows and a
select, never a loop over lanes).
"""

from __future__ import annotations

import jax.numpy as jnp

from ..op import CHANNEL_IN, CHANNEL_OUT, SAMPLE, SEQ, Op, OpContext, \
    WeightSpec, register_op
from .ssm import causal_conv, segmented_conv

F32 = jnp.float32


def project(p, h):
    """h (..., E) -> (B, C, z), each (..., E): the in-projection's
    thirds in the published order, f32 accumulation, rounded to h's
    dtype."""
    bcz = jnp.dot(h, p["w_in"].astype(h.dtype),
                  preferred_element_type=F32).astype(h.dtype)
    return jnp.split(bcz, 3, axis=-1)


def gate_in(b, z):
    """u = B * z, what the taps read and the tail keeps."""
    return b * z


def gate_out(c, conv):
    """C * (the taps' output, f32) -> the out-projection's input in C's
    dtype. No activation stands between the taps and this gate."""
    return (c.astype(F32) * conv).astype(c.dtype)


def out_project(p, y):
    return jnp.dot(y, p["w_out"].astype(y.dtype),
                   preferred_element_type=F32).astype(y.dtype)


def whole(p, h):
    """The definition over whole sequences h (B, S, E) -> (B, S, E)."""
    b, c, z = project(p, h)
    return out_project(p, gate_out(c, causal_conv(p, gate_in(b, z))))


def segmented(p, b, c, z, tail, lane_slots, positions, offsets, tail_lanes):
    """The gates and the taps over a step's lanes, between the two
    projections: b, c, z (T, E) the in-projection's thirds; tail
    (slots + 1, (taps - 1) * E) each slot's last products, flat.
    -> (the out-projection's input (T, E), tail)."""
    conv, tail = segmented_conv(p, gate_in(b, z), tail, lane_slots,
                                positions, offsets, tail_lanes)
    return gate_out(c, conv), tail


@register_op
class GatedShortConv(Op):
    """x (B, S, E) -> (B, S, E): the equations at the top of this file.
    `kernel_initializer`: one for all three leaves or a dict by name
    (`w_in`, `conv_w`, `w_out`)."""

    op_type = "gated_short_conv"

    def __init__(self, model, name, inputs, taps: int = 3,
                 kernel_initializer="glorot"):
        super().__init__(model, name, inputs)
        self.embed_dim = int(inputs[0].shape[-1])
        self.taps = int(taps)
        if self.taps < 2:
            raise ValueError(f"{name}: a short convolution of {taps} taps "
                             f"keeps no tail")
        self.kernel_initializer = kernel_initializer
        self.attrs = {"taps": self.taps}

    def output_shapes(self):
        return [tuple(self.inputs[0].shape)]

    def weight_specs(self):
        from ..core.initializers import named
        e = self.embed_dim
        init = lambda w: named(self.kernel_initializer, w)
        return {
            "w_in": WeightSpec((e, 3 * e), initializer=init("w_in"),
                               axes=(CHANNEL_IN, CHANNEL_OUT),
                               fan_in=e, fan_out=e),
            # w[j] multiplies u_{t - (taps - 1 - j)}: the published
            # conv.weight[:, 0, j]; no bias (conv_bias false)
            "conv_w": WeightSpec((self.taps, e), initializer=init("conv_w"),
                                 fan_in=self.taps, fan_out=self.taps),
            "w_out": WeightSpec((e, e), initializer=init("w_out"),
                                axes=(CHANNEL_IN, CHANNEL_OUT)),
        }

    def forward(self, params, xs, ctx: OpContext):
        return [whole(params, xs[0])]

    def output_axes(self):
        return [(SAMPLE, SEQ, None)]

    def input_axes(self):
        return [(SAMPLE, SEQ, None)]

    def flops(self) -> float:
        n_tok = 1
        for s in self.inputs[0].shape[:-1]:
            n_tok *= s
        e = self.embed_dim
        return n_tok * (2.0 * (3 * e * e + e * e) + e * (2 * self.taps + 2))
