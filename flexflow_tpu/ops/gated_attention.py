"""Gated softmax attention (the `full_attention` layers of Qwen3-Next,
`model_type` qwen3_next): grouped heads, a zero-centred QK-norm, rotary
over the first dims of a head alone, and an output GATE that is born in
the query projection.

Per token t, h (E), H query heads and Hk key/value heads of D:
  h W_q is (H, 2 D): a head's first D are q, its last D its gate;
  q = RMSNorm0_D(q), k = RMSNorm0_D(h W_k), v = h W_v
      (RMSNorm0(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w), over a
      head's D dims, f32 statistics);
  q, k rotated half-split over dims 0 .. R - 1 (pairs (j, j + R / 2),
      angle position * theta^(-2j / R)); dims R .. D - 1 pass;
  o = causal softmax(q k^T / sqrt(D)) v, query head j on key/value head
      j // (H / Hk);
  out = (o * sigmoid(gate)) W_o.       No bias anywhere.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..op import CHANNEL_IN, CHANNEL_OUT, HEAD, SAMPLE, SEQ, Op, \
    OpContext, WeightSpec, register_op
from .common import rms_norm, rotary

F32 = jnp.float32


def rms_norm0(x, w, eps: float):
    """The zero-centred RMSNorm: scale 1 + w."""
    return rms_norm(x, 1.0 + w.astype(F32), eps)


def partial_rotary(x, positions, theta: float, rotary_dim: int):
    """x (..., H, D): the first `rotary_dim` dims rotated (half-split
    among themselves), the others as they are."""
    if rotary_dim >= x.shape[-1]:
        return rotary(x, positions, theta)
    return jnp.concatenate(
        [rotary(x[..., :rotary_dim], positions, theta),
         x[..., rotary_dim:]], axis=-1)


def project_qkv(p, h, positions, theta: float, rotary_dim: int,
                eps: float):
    """h (..., E), positions (...) -> q (..., H, D) and k (..., Hk, D)
    normed and rotated, v (..., Hk, D), gate (..., H, D), in h's
    dtype."""
    qg, k, v = (jnp.einsum("...e,ehd->...hd", h, p[w].astype(h.dtype))
                for w in ("wq", "wk", "wv"))
    d = k.shape[-1]
    q, gate = qg[..., :d], qg[..., d:]
    q = partial_rotary(rms_norm0(q, p["q_norm"], eps), positions, theta,
                       rotary_dim)
    k = partial_rotary(rms_norm0(k, p["k_norm"], eps), positions, theta,
                       rotary_dim)
    return q, k, v, gate


def gate_output(o, gate):
    """o * sigmoid(gate), the sigmoid in f32, in o's dtype."""
    return (o.astype(F32) * jax.nn.sigmoid(gate.astype(F32))
            ).astype(o.dtype)


@register_op
class GatedAttention(Op):
    """x (B, S, E), positions (B, S) -> out (B, S, E). `qk_norm_init`
    (lo, hi[, "signed"]): where the zero-centred q_norm and k_norm
    scales w start (core/initializers.range_init; a checkpoint's start
    is 0)."""

    op_type = "gated_attention"

    def __init__(self, model, name, inputs, num_heads: int,
                 num_kv_heads: int, head_dim: int,
                 rotary_theta: float = 1e7, rotary_dim: int = 0,
                 eps: float = 1e-6, qk_norm_init=(0.0, 0.0),
                 kernel_initializer="glorot"):
        super().__init__(model, name, inputs)
        self.embed_dim = int(inputs[0].shape[-1])
        self.num_heads, self.num_kv_heads = int(num_heads), int(num_kv_heads)
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"{name}: {num_heads} query heads do not "
                             f"divide over {num_kv_heads} key/value heads")
        self.head_dim = int(head_dim)
        self.rotary_theta = float(rotary_theta)
        self.rotary_dim = int(rotary_dim) or self.head_dim
        self.eps = float(eps)
        self.qk_norm_init = tuple(qk_norm_init)
        self.kernel_initializer = kernel_initializer
        self.attrs = {"num_heads": self.num_heads,
                      "num_kv_heads": self.num_kv_heads,
                      "head_dim": self.head_dim,
                      "rotary_dim": self.rotary_dim}

    def output_shapes(self):
        return [tuple(self.inputs[0].shape)]

    def output_dtypes(self):
        return [self.inputs[0].dtype]

    def weight_specs(self):
        from ..core.initializers import range_init
        e, h, hk, d = (self.embed_dim, self.num_heads, self.num_kv_heads,
                       self.head_dim)
        init = self.kernel_initializer
        proj = lambda n, w: WeightSpec(
            (e, n, w), initializer=init, axes=(CHANNEL_IN, HEAD, None),
            fan_in=e, fan_out=n * w)
        norm = lambda: WeightSpec(
            (d,), custom_init=range_init(self.qk_norm_init))
        return {"wq": proj(h, 2 * d), "wk": proj(hk, d), "wv": proj(hk, d),
                "q_norm": norm(), "k_norm": norm(),
                "wo": WeightSpec((h, d, e), initializer=init,
                                 axes=(HEAD, None, CHANNEL_OUT),
                                 fan_in=h * d, fan_out=e)}

    def forward(self, params, xs, ctx: OpContext):
        x, positions = xs
        q, k, v, gate = project_qkv(params, x, positions,
                                    self.rotary_theta, self.rotary_dim,
                                    self.eps)
        b, s, h, d = q.shape
        hk = self.num_kv_heads
        # a dense masked softmax, probabilities f32 through the product
        # with v (the paged kernels' convention)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk",
                            q.reshape(b, s, hk, h // hk, d), k,
                            preferred_element_type=F32) / math.sqrt(d)
        pos = jnp.arange(s)
        probs = jax.nn.softmax(jnp.where(
            pos[:, None] >= pos[None, :], logits, -jnp.inf), axis=-1)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(F32),
                       preferred_element_type=F32)
        o = gate_output(o.reshape(b, s, h, d).astype(v.dtype), gate)
        return [jnp.einsum("bshd,hde->bse", o,
                           params["wo"].astype(o.dtype))]

    def output_axes(self):
        return [(SAMPLE, SEQ, None)]

    def input_axes(self):
        return [(SAMPLE, SEQ, None), (SAMPLE, SEQ)]

    def flops(self) -> float:
        b, s = self.inputs[0].shape[:2]
        e, h, hk, d = (self.embed_dim, self.num_heads, self.num_kv_heads,
                       self.head_dim)
        proj = 2.0 * b * s * e * d * (3 * h + 2 * hk)
        return proj + 4.0 * b * h * s * s * d
