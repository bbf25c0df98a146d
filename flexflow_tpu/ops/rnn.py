"""LSTM layer.

Reference: nmt/lstm.cu (574 LoC) — cuDNN RNN API over per-timestep Legion
tasks, with `SharedVariable` weights spanning timesteps (nmt/rnn.h:60-160).
The reference builds its *own* mini-framework for this (nmt/); per
SURVEY.md section 7 step 8 we instead make LSTM an ordinary op of the main
framework: `lax.scan` over time — XLA compiles the recurrence into a single
fused loop — with the gate matmuls batched into one (D+H, 4H) GEMM per step
so they hit the MXU. A Pallas cell kernel can slot in under the same op.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..op import CHANNEL_IN, CHANNEL_OUT, SAMPLE, SEQ, Op, OpContext, WeightSpec, register_op


@register_op
class LSTM(Op):
    """input (B, T, D) -> output (B, T, H); single layer, unidirectional.

    Gate layout in the fused kernel: [i, f, g, o] along the 4H axis.
    """

    op_type = "lstm"

    def __init__(self, model, name, inputs, hidden_size: int,
                 return_sequences: bool = True,
                 kernel_initializer: str = "glorot",
                 use_pallas=None):
        super().__init__(model, name, inputs)
        self.hidden_size = int(hidden_size)
        self.in_dim = inputs[0].shape[-1]
        self.return_sequences = return_sequences
        self.kernel_initializer = kernel_initializer
        # tri-state like attention's use_flash: None = scan (default
        # until the kernel is measured profitable on hardware), True =
        # force the Pallas multi-timestep kernel (kernels/lstm_scan.py —
        # wh resident in VMEM across steps instead of re-read from HBM
        # every timestep), False = never.
        self.use_pallas = use_pallas
        self.attrs = {"hidden_size": hidden_size,
                      "return_sequences": return_sequences}

    def output_shapes(self):
        b, t, _ = self.inputs[0].shape
        if self.return_sequences:
            return [(b, t, self.hidden_size)]
        return [(b, self.hidden_size)]

    def weight_specs(self):
        h = self.hidden_size
        return {
            "wx": WeightSpec((self.in_dim, 4 * h),
                             initializer=self.kernel_initializer,
                             axes=(CHANNEL_IN, CHANNEL_OUT)),
            "wh": WeightSpec((h, 4 * h), initializer=self.kernel_initializer,
                             axes=(None, CHANNEL_OUT)),
            "b": WeightSpec((4 * h,), initializer="zeros",
                            axes=(CHANNEL_OUT,)),
        }

    def forward(self, params, xs, ctx: OpContext):
        (x,) = xs
        b, t, _ = x.shape
        h = self.hidden_size
        wx, wh, bias = params["wx"], params["wh"], params["b"]
        # Precompute input contributions for all timesteps in one big GEMM
        # (time-batched: (B*T, D) @ (D, 4H) keeps the MXU busy).
        xg = (jnp.dot(x.reshape(b * t, -1), wx.astype(x.dtype),
                      preferred_element_type=jnp.float32)
              .reshape(b, t, 4 * h) + bias)
        xg = jnp.swapaxes(xg, 0, 1)  # (T, B, 4H) for scan

        if self.use_pallas:
            from ..kernels.lstm_scan import lstm_sequence
            ys = lstm_sequence(xg.astype(x.dtype), wh.astype(x.dtype),
                               jnp.zeros((b, h), x.dtype),
                               jnp.zeros((b, h), x.dtype))
            if self.return_sequences:
                return [jnp.swapaxes(ys, 0, 1)]
            return [ys[-1]]

        def cell(carry, xg_t):
            h_prev, c_prev = carry
            gates = xg_t + jnp.dot(h_prev, wh.astype(h_prev.dtype),
                                   preferred_element_type=jnp.float32)
            i, f, g, o = jnp.split(gates, 4, axis=-1)
            i = jax.nn.sigmoid(i)
            f = jax.nn.sigmoid(f)
            g = jnp.tanh(g)
            o = jax.nn.sigmoid(o)
            c = f * c_prev + i * g
            hy = o * jnp.tanh(c)
            return (hy.astype(x.dtype), c.astype(x.dtype)), hy.astype(x.dtype)

        init = (jnp.zeros((b, h), x.dtype), jnp.zeros((b, h), x.dtype))
        (h_last, _), ys = lax.scan(cell, init, xg)
        if self.return_sequences:
            return [jnp.swapaxes(ys, 0, 1)]
        return [h_last]

    def output_axes(self):
        if self.return_sequences:
            return [(SAMPLE, SEQ, CHANNEL_OUT)]
        return [(SAMPLE, CHANNEL_OUT)]

    def input_axes(self):
        return [(SAMPLE, SEQ, CHANNEL_IN)]

    def flops(self) -> float:
        b, t, d = self.inputs[0].shape
        h = self.hidden_size
        return 2.0 * b * t * (d + h) * 4 * h
