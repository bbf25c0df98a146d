"""Differential attention (arXiv:2410.05258) with grouped key/value
heads, a sliding window, and K/V taken from another layer (YOCO's
cross layers, arXiv:2405.05254).

H query heads and Hk key/value heads of D. Query heads split even / odd
into q1, q2 (H/2 each), key and value heads even / odd into k1, k2, v1,
v2 (Hk/2 each); query head j of a half reads key/value head j // g of
the matching half (g = H / Hk). With A1 = attn(q1, k1, [v1 ; v2]) and
A2 = attn(q2, k2, [v1 ; v2]) (values concatenated to 2 D), each an
ordinary softmax attention under the layer's mask,

  out = RMSNorm_2D(A1 - lam * A2) * (1 - lam0),  flattened (H/2 x 2D),
  lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0.

THE GROUPED IDENTITY (`grouped_qkv`): read the pair (k_2m, k_2m+1) as
ONE 2D-wide key head and [v_2m ; v_2m+1] as one 2D-wide value head, and
pad each query with zeros into its half: this is plain grouped-query
attention with H query heads over Hk/2 key/value heads of 2D, group
2g, on the same cache bytes. The serving step runs it as one call of
the paged kernel (serve/arch.py); this op's forward runs the two
attentions as written above.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..core.initializers import make_normal
from ..op import (CHANNEL_IN, CHANNEL_OUT, HEAD, SAMPLE, SEQ, Op, OpContext,
                  WeightSpec, register_op)
from .common import rms_norm

F32 = jnp.float32


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def diff_lambda(p, lam0: float):
    """lam, f32, from the layer's four learned D-vectors."""
    f = lambda n: p[n].astype(F32)
    return (jnp.exp(jnp.sum(f("lq1") * f("lk1")))
            - jnp.exp(jnp.sum(f("lq2") * f("lk2"))) + lam0)


def diff_combine(p, a1, a2, lam0: float, eps: float):
    """a1, a2 (..., H/2, 2D): the two attentions' outputs ->
    RMSNorm(a1 - lam a2) * (1 - lam0), in a1's dtype."""
    d = a1.astype(F32) - diff_lambda(p, lam0) * a2.astype(F32)
    return (rms_norm(d, p["subln"], eps) * (1.0 - lam0)).astype(a1.dtype)


def project(p, h, name: str):
    """h (..., E) -> (..., heads, D) through w<name> (+ b<name>)."""
    y = jnp.einsum("...e,ehd->...hd", h, p["w" + name].astype(h.dtype))
    b = p.get("b" + name)
    return y if b is None else y + b.astype(y.dtype)


def grouped_qkv(q, k, v):
    """The grouped identity's operands: q (..., H, D) -> (..., H, 2D)
    with even heads in the first half and odd heads in the second, zeros
    in the other; k, v (..., Hk, D) -> (..., Hk/2, 2D) (a reshape: the
    pair of heads is contiguous). Either of k, v may be None."""
    d = q.shape[-1]
    odd = (jnp.arange(q.shape[-2]) % 2 == 1)[:, None]
    z = jnp.zeros_like(q)
    q2 = jnp.concatenate([jnp.where(odd, z, q), jnp.where(odd, q, z)],
                         axis=-1)
    pair = lambda a: None if a is None else a.reshape(
        a.shape[:-2] + (a.shape[-2] // 2, 2 * d))
    return q2, pair(k), pair(v)


def split_grouped(o):
    """The grouped call's output (..., H, 2D) -> (a1, a2), each
    (..., H/2, 2D): even heads are q1's, odd heads q2's."""
    return o[..., 0::2, :], o[..., 1::2, :]


@register_op
class DifferentialAttention(Op):
    """h (B, S, E) [, k, v (B, S, Hk, D) of the layer named by
    `kv_from`] -> [y (B, S, E)] and, with `emit_kv`, this layer's k and
    v (B, S, Hk, D) as second and third outputs. Causal; `window` > 0
    lets token t see keys t - window + 1 .. t."""

    op_type = "differential_attention"

    def __init__(self, model, name, inputs, num_heads: int,
                 num_kv_heads: int, head_dim: int, layer_index: int,
                 window: int = 0, kv_from: str = "", emit_kv: bool = False,
                 eps: float = 1e-5, kernel_initializer: str = "glorot"):
        super().__init__(model, name, inputs)
        self.embed_dim = int(inputs[0].shape[-1])
        self.num_heads = int(num_heads)
        self.num_kv_heads = int(num_kv_heads)
        self.head_dim = int(head_dim)
        self.layer_index = int(layer_index)
        self.lam0 = lambda_init(self.layer_index)
        self.window = int(window)
        self.kv_from = str(kv_from)
        self.emit_kv = bool(emit_kv)
        self.eps = float(eps)
        self.causal = True
        self.differential = True
        self.kernel_initializer = kernel_initializer
        if bool(self.kv_from) != (len(inputs) == 3):
            raise ValueError(
                f"{name}: a layer that reads another's K/V (kv_from="
                f"{kv_from!r}) takes h, k, v; any other takes h alone")
        if self.num_heads % self.num_kv_heads or self.num_kv_heads % 2:
            raise ValueError(
                f"{name}: {num_heads} query heads do not group over "
                f"{num_kv_heads} key/value heads in two halves")
        self.attrs = {"num_heads": num_heads, "num_kv_heads": num_kv_heads,
                      "head_dim": head_dim, "layer_index": layer_index,
                      "window": window, "kv_from": self.kv_from,
                      "emit_kv": self.emit_kv, "differential": True}

    def output_shapes(self):
        b, s = self.inputs[0].shape[:2]
        out = [(b, s, self.embed_dim)]
        if self.emit_kv:
            out += [(b, s, self.num_kv_heads, self.head_dim)] * 2
        return out

    def output_dtypes(self):
        return [self.inputs[0].dtype] * (3 if self.emit_kv else 1)

    def weight_specs(self):
        e, h, hk, d = (self.embed_dim, self.num_heads, self.num_kv_heads,
                       self.head_dim)
        init = self.kernel_initializer

        def w(heads):
            return WeightSpec((e, heads, d), initializer=init,
                              axes=(CHANNEL_IN, HEAD, None), fan_in=e,
                              fan_out=heads * d)

        def b(heads):
            return WeightSpec((heads, d), initializer="zeros",
                              axes=(HEAD, None))

        specs = {"wq": w(h), "bq": b(h)}
        if not self.kv_from:
            specs.update(wk=w(hk), bk=b(hk), wv=w(hk), bv=b(hk))
        specs["wo"] = WeightSpec((h // 2, 2 * d, e), initializer=init,
                                 axes=(HEAD, None, CHANNEL_OUT),
                                 fan_in=h * d, fan_out=e)
        specs["bo"] = WeightSpec((e,), initializer="zeros",
                                 axes=(CHANNEL_OUT,))
        for n in ("lq1", "lk1", "lq2", "lk2"):
            specs[n] = WeightSpec((d,), custom_init=make_normal(0.0, 0.1))
        specs["subln"] = WeightSpec((2 * d,), initializer="ones")
        return specs

    def forward(self, params, xs, ctx: OpContext):
        p = params
        h = xs[0]
        q = project(p, h, "q")                       # (B, S, H, D)
        if self.kv_from:
            k, v = xs[1], xs[2]
        else:
            k, v = project(p, h, "k"), project(p, h, "v")
        s = h.shape[1]
        pos = jnp.arange(s)
        mask = pos[:, None] >= pos[None, :]
        if self.window:
            mask &= pos[:, None] - pos[None, :] < self.window
        g = self.num_heads // self.num_kv_heads
        vv = jnp.concatenate([v[:, :, 0::2], v[:, :, 1::2]], axis=-1)
        scale = 1.0 / math.sqrt(self.head_dim)

        def attend(qh, kh):
            """qh (B, S, H/2, D), kh (B, S, Hk/2, D) over vv."""
            b_, _, hq, d = qh.shape
            qg = qh.reshape(b_, s, hq // g, g, d)
            sc = jnp.einsum("bqmgd,bkmd->bmgqk", qg, kh,
                            preferred_element_type=F32) * scale
            sc = jnp.where(mask, sc, -jnp.inf)
            pr = jax.nn.softmax(sc, axis=-1)
            o = jnp.einsum("bmgqk,bkmd->bqmgd", pr, vv.astype(F32),
                           preferred_element_type=F32)
            return o.reshape(b_, s, hq, 2 * d).astype(qh.dtype)

        a1 = attend(q[:, :, 0::2], k[:, :, 0::2])
        a2 = attend(q[:, :, 1::2], k[:, :, 1::2])
        o = diff_combine(p, a1, a2, self.lam0, self.eps)
        y = jnp.einsum("bshd,hde->bse", o, p["wo"].astype(o.dtype)) \
            + p["bo"].astype(o.dtype)
        return [y, k, v] if self.emit_kv else [y]

    def output_axes(self):
        return [(SAMPLE, SEQ, None)] + \
            [(SAMPLE, SEQ, None, None)] * (len(self.outputs) - 1)

    def input_axes(self):
        return [(SAMPLE, SEQ, None)] + \
            [(SAMPLE, SEQ, None, None)] * (len(self.inputs) - 1)

    def flops(self) -> float:
        b, s = self.inputs[0].shape[:2]
        e, h, hk, d = (self.embed_dim, self.num_heads, self.num_kv_heads,
                       self.head_dim)
        kv = 0 if self.kv_from else 2 * e * hk * d
        keys = min(s, self.window) if self.window else s
        return 2.0 * b * s * (e * h * d + kv + h * d * e) \
            + 2.0 * b * s * keys * h * d * 3
