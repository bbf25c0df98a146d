"""Multi-head attention.

Reference: src/ops/attention.cu — a single cuDNN fused-MHA call
(cudnnMultiHeadAttnForward, attention.cu:245) with one packed 3-D weight
tensor holding {Wq,Wk,Wv,Wo} per head (attention.cu:88-104).

TPU-native design: separate (E, H, D) projection weights whose `head`
logical axis maps to a mesh axis for TP (Megatron-style), and a Pallas
flash-attention kernel (flexflow_tpu/kernels/flash_attention.py) for the
core softmax(QK^T)V — the op the north star explicitly calls out for
replacement. Long-sequence SP/CP shards the `seq` axis; see
flexflow_tpu/parallel/ring_attention.py.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec

from ..op import (
    CHANNEL_IN,
    CHANNEL_OUT,
    HEAD,
    SAMPLE,
    SEQ,
    Op,
    OpContext,
    WeightSpec,
    register_op,
)
from .common import rms_norm, rotary


@register_op
class MultiHeadAttention(Op):
    op_type = "multihead_attention"

    def __init__(self, model, name, inputs, embed_dim: int, num_heads: int,
                 kdim: int = 0, vdim: int = 0, dropout: float = 0.0,
                 use_bias: bool = False, add_bias_kv: bool = False,
                 add_zero_attn: bool = False, causal: bool = False,
                 kernel_initializer: str = "glorot",
                 use_flash=None, rotary_theta: float = 0.0,
                 qk_norm: bool = False, qk_norm_eps: float = 1e-5,
                 num_kv_heads: int = 0, window: int = 0,
                 rotary_interleaved: bool = False, head_dim: int = 0,
                 qk_norm_init=None, key_multiplier: float = 1.0,
                 qk_norm_per_head: bool = False):
        super().__init__(model, name, inputs)
        # a fourth input, (batch, seq) int32 absolute positions, turns
        # the rotary embedding on (rotary_theta > 0 needs it)
        q, k, v = inputs[:3]
        self.rotary_theta = float(rotary_theta)
        self.qk_norm = bool(qk_norm)
        self.qk_norm_eps = float(qk_norm_eps)
        # the QK-norm over EACH head's dims, one (head_dim,) weight the
        # heads share (LFM2's q_layernorm / k_layernorm), not over the
        # whole projection; before the rotation either way
        self.qk_norm_per_head = bool(qk_norm_per_head)
        if self.qk_norm_per_head and not self.qk_norm:
            raise ValueError(f"{name}: qk_norm_per_head says how qk_norm "
                             f"norms (qk_norm=True)")
        # where the QK-norm's scales start, (lo, hi); None: at 1
        self.qk_norm_init = None if qk_norm_init is None \
            else tuple(qk_norm_init)
        self.rotary_interleaved = bool(rotary_interleaved)
        # a scalar on the key projection's output, before the rotation
        # (Falcon-H1's muP `key_multiplier`; 1: none)
        self.key_multiplier = float(key_multiplier)
        # GROUPED heads: query head j reads key/value head j // group;
        # `window` > 0: token t sees keys t - window + 1 .. t. Both run
        # as the dense masked softmax below (the flash kernel and the
        # sequence-parallel lowerings take neither yet: ROADMAP M3/M4)
        self.num_kv_heads = int(num_kv_heads) or int(num_heads)
        self.window = int(window)
        if int(num_heads) % self.num_kv_heads:
            raise ValueError(
                f"{name}: {num_heads} query heads do not divide over "
                f"{self.num_kv_heads} key/value heads")
        # a whole-projection norm has an (heads, head_dim) weight, which
        # the grouped keys do not span; a per-head one fits any count
        if (self.num_kv_heads != int(num_heads) or self.window) and (
                (qk_norm and not self.qk_norm_per_head) or add_bias_kv
                or add_zero_attn or not causal):
            raise ValueError(
                f"{name}: grouped heads and a window are built for plain "
                f"causal attention (no whole-projection qk_norm, bias_kv "
                f"or zero_attn)")
        if (self.rotary_theta > 0) != (len(inputs) == 4):
            raise ValueError(
                f"{name}: rotary attention takes q, k, v AND positions "
                f"(rotary_theta={rotary_theta}, {len(inputs)} inputs)")
        self.embed_dim = int(embed_dim)
        self.num_heads = int(num_heads)
        self.kdim = int(kdim) if kdim > 0 else self.embed_dim
        self.vdim = int(vdim) if vdim > 0 else self.embed_dim
        assert self.embed_dim % self.num_heads == 0
        # `head_dim` > 0: heads of a size of their own (num_heads *
        # head_dim wide inside, embed_dim out), else embed_dim's split
        self.head_dim = int(head_dim) or self.embed_dim // self.num_heads
        self.dropout = dropout
        self.use_bias = use_bias
        self.add_bias_kv = add_bias_kv
        self.add_zero_attn = add_zero_attn
        self.causal = causal
        self.use_flash = use_flash
        # the core-attention implementation the last trace resolved
        # ("flash" | "xla"; None before the first forward)
        self.attn_impl = None
        self.q_in = q.shape[-1]
        self.k_in = k.shape[-1]
        self.v_in = v.shape[-1]
        # self-attention detected at GRAPH level (same input tensor
        # wired to q/k/v) — runtime array identity is unreliable:
        # jax.checkpoint re-flattens duplicated leaves into distinct
        # tracers, which would silently disable the fused path under
        # remat
        self._fused_qkv = (q is k and k is v
                           and self.q_in == self.k_in == self.v_in
                           and self.num_kv_heads == self.num_heads)
        # cross-attention (seq2seq decoders): K and V read the SAME
        # encoder output — fuse their projections into one 2x-wide GEMM
        self._fused_kv = (not self._fused_qkv and k is v
                          and self.k_in == self.v_in)
        self.kernel_initializer = kernel_initializer
        self.attrs = {"embed_dim": embed_dim, "num_heads": num_heads,
                      "dropout": dropout, "use_bias": use_bias,
                      "causal": causal}
        if self.rotary_theta > 0 or self.qk_norm:
            self.attrs.update(rotary_theta=self.rotary_theta,
                              qk_norm=self.qk_norm)
        if self.qk_norm_per_head:
            self.attrs["qk_norm_per_head"] = True
        if self.num_kv_heads != self.num_heads or self.window \
                or self.rotary_interleaved:
            self.attrs.update(num_kv_heads=self.num_kv_heads,
                              window=self.window, head_dim=self.head_dim,
                              rotary_interleaved=self.rotary_interleaved)

    def output_shapes(self):
        q = self.inputs[0]
        return [(q.shape[0], q.shape[1], self.embed_dim)]

    def weight_specs(self):
        h, d = self.num_heads, self.head_dim
        e = self.embed_dim
        hk = self.num_kv_heads
        from ..core.initializers import named
        init = lambda w: named(self.kernel_initializer, w)
        specs = {
            "wq": WeightSpec((self.q_in, h, d), initializer=init("wq"),
                             axes=(CHANNEL_IN, HEAD, None),
                             fan_in=self.q_in, fan_out=h * d),
            "wk": WeightSpec((self.k_in, hk, d), initializer=init("wk"),
                             axes=(CHANNEL_IN, HEAD, None),
                             fan_in=self.k_in, fan_out=hk * d),
            "wv": WeightSpec((self.v_in, hk, d), initializer=init("wv"),
                             axes=(CHANNEL_IN, HEAD, None),
                             fan_in=self.v_in, fan_out=hk * d),
            "wo": WeightSpec((h, d, e), initializer=init("wo"),
                             axes=(HEAD, None, CHANNEL_OUT),
                             fan_in=h * d, fan_out=e),
        }
        if self.use_bias:
            specs["bo"] = WeightSpec((self.embed_dim,), initializer="zeros",
                                     axes=(CHANNEL_OUT,))
        if self.qk_norm:
            # one RMSNorm over the WHOLE projection (all heads), before
            # the split into heads: OLMoE's q_norm / k_norm
            start = {}
            if self.qk_norm_init is not None:
                from ..core.initializers import range_init
                start["custom_init"] = range_init(self.qk_norm_init)
            # ... or, per head, one (head_dim,) weight the heads share
            shape, axes = ((d,), (None,)) if self.qk_norm_per_head \
                else ((h, d), (HEAD, None))
            specs["q_norm"] = WeightSpec(shape, initializer="ones",
                                         axes=axes, **start)
            specs["k_norm"] = WeightSpec(shape, initializer="ones",
                                         axes=axes, **start)
        if self.add_bias_kv:
            # one learned extra kv position (torch MultiheadAttention
            # bias_k/bias_v semantics)
            specs["bias_k"] = WeightSpec((1, h, d), initializer="zeros",
                                         axes=(None, HEAD, None))
            specs["bias_v"] = WeightSpec((1, h, d), initializer="zeros",
                                         axes=(None, HEAD, None))
        return specs

    def forward(self, params, xs, ctx: OpContext):
        q_in, k_in, v_in = xs[:3]
        if self._fused_qkv:
            # self-attention: ONE fused (E, 3·H·D) projection GEMM
            # instead of three E x H·D GEMMs — same math, wider MXU
            # call (XLA does not horizontally fuse parallel dots; the
            # reference's cuDNN MHA packs a single QKV weight tensor
            # for the same reason, attention.cu:88-104). The stack of
            # the three weight leaves is a few MB of HBM, trivially
            # amortized by the 3x-wider GEMM.
            w = jnp.stack([params["wq"], params["wk"], params["wv"]],
                          axis=1).astype(q_in.dtype)  # (E, 3, H, D)
            qkv = jnp.einsum("bse,exhd->xbshd", q_in, w)
            q, k, v = qkv[0], qkv[1], qkv[2]
        else:
            q = jnp.einsum("bse,ehd->bshd", q_in,
                           params["wq"].astype(q_in.dtype))
            if self._fused_kv:
                # one 2x-wide GEMM over the shared encoder output
                w = jnp.stack([params["wk"], params["wv"]],
                              axis=1).astype(k_in.dtype)  # (E, 2, H, D)
                kv = jnp.einsum("bse,exhd->xbshd", k_in, w)
                k, v = kv[0], kv[1]
            else:
                k = jnp.einsum("bse,ehd->bshd", k_in,
                               params["wk"].astype(k_in.dtype))
                v = jnp.einsum("bse,ehd->bshd", v_in,
                               params["wv"].astype(v_in.dtype))
        if self.qk_norm:
            q = rms_norm(q, params["q_norm"], self.qk_norm_eps)
            k = rms_norm(k, params["k_norm"], self.qk_norm_eps)
        if self.key_multiplier != 1.0:
            k = k * self.key_multiplier
        if self.rotary_theta > 0:
            q = rotary(q, xs[3], self.rotary_theta, self.rotary_interleaved)
            k = rotary(k, xs[3], self.rotary_theta, self.rotary_interleaved)
        if self.add_bias_kv:
            b = k.shape[0]
            bk = jnp.broadcast_to(params["bias_k"].astype(k.dtype),
                                  (b,) + params["bias_k"].shape)
            bv = jnp.broadcast_to(params["bias_v"].astype(v.dtype),
                                  (b,) + params["bias_v"].shape)
            k = jnp.concatenate([k, bk], axis=1)
            v = jnp.concatenate([v, bv], axis=1)

        o = self._attend(q, k, v, ctx)

        y = jnp.einsum("bshd,hde->bse", o, params["wo"].astype(o.dtype))
        if self.use_bias:
            y = y + params["bo"].astype(y.dtype)
        if self.dropout > 0.0 and ctx.training and ctx.rng is not None:
            keep = 1.0 - self.dropout
            mask = jax.random.bernoulli(ctx.rng, keep, y.shape)
            y = jnp.where(mask, y / keep, 0.0).astype(y.dtype)
        return [y]

    def _attend(self, q, k, v, ctx: OpContext):
        """softmax(QK^T/sqrt(d))V, (b, s, h, d) layout."""
        if self.num_kv_heads != self.num_heads or self.window:
            return self._attend_grouped(q, k, v)
        has_seq_trunc = ctx.seq_length is not None and ctx.seq_length >= 0
        # Sequence parallelism: when the strategy maps `seq` to a mesh
        # axis, run ring attention over that axis (K/V rotate over ICI).
        # Guards mirror spec_for_axes' graceful degradation: fall back to
        # the XLA path when shapes don't divide the mesh axes or when kv
        # carries extra rows (bias_kv/zero_attn).
        seq_size = ctx.mesh_axis_size("seq")
        if (seq_size > 1 and not has_seq_trunc
                and not self.add_zero_attn and not self.add_bias_kv
                and q.shape[1] % seq_size == 0
                and k.shape[1] % seq_size == 0):
            from ..parallel.ring_attention import ring_attention
            from ..parallel.ulysses import alltoall_attention, sp_mode_for
            data_ax = ctx.mesh_axis_name("sample") or "data"
            data_size = (ctx.mesh.shape.get(data_ax, 1)
                         if ctx.mesh is not None else 1)
            if q.shape[0] % max(1, data_size) == 0:
                # two SP lowerings: ring (K/V rotate, never materializes
                # scores) vs all-to-all (heads scatter, full-seq blocks
                # on the MXU); sp_mode_for is the single policy both
                # execution and the cost model consult
                mode = sp_mode_for(
                    getattr(self.model.config, "sp_attention", "auto"),
                    num_heads=self.num_heads, seq_size=seq_size,
                    batch_local=q.shape[0] // max(1, data_size),
                    seq_q=q.shape[1], seq_kv=k.shape[1])
                if mode == "alltoall":
                    return alltoall_attention(
                        q, k, v, ctx.mesh,
                        seq_axis=ctx.mesh_axis_name("seq"),
                        batch_axis=data_ax, causal=self.causal,
                        scale=1.0 / math.sqrt(self.head_dim),
                        use_flash=self.use_flash)
                return ring_attention(
                    q, k, v, ctx.mesh, seq_axis=ctx.mesh_axis_name("seq"),
                    batch_axis=data_ax, causal=self.causal,
                    scale=1.0 / math.sqrt(self.head_dim))
        if self.add_zero_attn:
            zero = jnp.zeros(k.shape[:1] + (1,) + k.shape[2:], k.dtype)
            k = jnp.concatenate([k, zero], axis=1)
            v = jnp.concatenate([v, zero], axis=1)
        # flash path handles neither seq_length truncation nor the
        # (now off-block-size) zero-attn row; use XLA for those.
        #
        # use_flash is tri-state: None = auto (a tpu backend, a shape
        # the kernel takes, and the measured flash_profitable gate —
        # kernels/flash_attention.resolve_flash, shared with the
        # all-to-all SP lowering), True = force the Pallas kernel,
        # False = never. The decision is made BEFORE the call: a kernel
        # that is chosen and then raises, raises.
        #
        # Under a mesh of several devices the call is made PER SHARD,
        # inside shard_map over the mesh axes that carry this op's
        # `sample` and `head` (GSPMD cannot partition a Mosaic call),
        # and the gate reads the per-shard shapes. An axis that does
        # not divide its dimension stays whole (spec_for_axes' graceful
        # degradation). On one device the call is made directly.
        b, sq, h, d = q.shape
        sk = k.shape[1]
        from ..kernels.flash_attention import (flash_attention_bshd,
                                               resolve_flash)
        mesh = ctx.mesh if ctx.mesh is not None and ctx.mesh.size > 1 \
            else None

        def axis_over(logical, n):
            """(mesh axis, size) this op's `logical` axis splits n
            over, else (None, 1)."""
            size = ctx.mesh_axis_size(logical) if mesh is not None else 1
            return (ctx.mesh_axis_name(logical), size) \
                if size > 1 and n % size == 0 else (None, 1)

        data_ax, nd = axis_over(SAMPLE, b)
        head_ax, nh = axis_over(HEAD, h)
        if head_ax == data_ax:
            head_ax, nh = None, 1
        self.attn_impl = "flash" if (
            not has_seq_trunc and not self.add_zero_attn
            and resolve_flash(self.use_flash, b // nd, h // nh, sq, sk, d,
                              jnp.dtype(q.dtype).itemsize)) else "xla"
        if self.attn_impl == "flash":
            call = functools.partial(flash_attention_bshd,
                                     causal=self.causal)
            if mesh is not None:
                spec = PartitionSpec(data_ax, None, head_ax, None)
                call = shard_map(call, mesh=mesh, in_specs=(spec,) * 3,
                                 out_specs=spec, check_vma=False)
            return call(q, k, v)
        scale = 1.0 / math.sqrt(self.head_dim)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                            preferred_element_type=jnp.float32) * scale
        if self.causal:
            # top-left alignment (query i attends keys j <= i), matching
            # the Pallas forward kernel's qpos >= kpos mask.
            lq, lk = logits.shape[-2], logits.shape[-1]
            mask = jnp.tril(jnp.ones((lq, lk), dtype=bool))
            logits = jnp.where(mask, logits, -jnp.inf)
        if ctx.seq_length is not None and ctx.seq_length >= 0:
            kidx = jnp.arange(logits.shape[-1])
            logits = jnp.where(kidx[None, None, None, :] < ctx.seq_length,
                               logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    def _attend_grouped(self, q, k, v):
        """Causal attention with grouped key/value heads and, where
        `window` > 0, the last `window` keys alone: a dense masked
        softmax, probabilities f32 through the product with v (the
        paged kernels' convention)."""
        b, s, h, d = q.shape
        hk = self.num_kv_heads
        q = q.reshape(b, s, hk, h // hk, d)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                            preferred_element_type=jnp.float32) \
            / math.sqrt(d)
        pos = jnp.arange(s)
        mask = pos[:, None] >= pos[None, :]
        if self.window:
            mask &= pos[:, None] - pos[None, :] < self.window
        probs = jax.nn.softmax(jnp.where(mask, logits, -jnp.inf), axis=-1)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        return o.reshape(b, s, h, d).astype(v.dtype)

    def output_axes(self):
        return [(SAMPLE, SEQ, CHANNEL_OUT)]

    def input_axes(self):
        return [(SAMPLE, SEQ, CHANNEL_IN)] * 3 \
            + [(SAMPLE, SEQ)] * (len(self.inputs) - 3)

    def flops(self) -> float:
        b, lq = self.inputs[0].shape[:2]
        lk = self.inputs[1].shape[1]
        e, h, d = self.embed_dim, self.num_heads, self.head_dim
        kv = self.num_kv_heads * d          # == e without grouped heads
        proj = 2.0 * b * (lq * self.q_in * h * d
                          + lk * (self.k_in + self.v_in) * kv)
        attn = 2.0 * b * h * lq * lk * d * 2
        out = 2.0 * b * lq * h * d * e
        return proj + attn + out
