"""Qwen3-Next (Qwen/Qwen3-Next-80B-A3B, `model_type` qwen3_next): the
language model's decoder — three layers of the gated delta rule
(`linear_attention`, ops/gated_delta.py) to one of gated softmax
attention (`full_attention`, ops/gated_attention.py), every layer's
feed-forward a top-k expert layer beside a gated shared expert.

  x_0 = E[token]
  per layer i:  x = x + mixer_i(RMSNorm0(x));  x = x + moe(RMSNorm0(x))
      RMSNorm0(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)
      mixer_i = full_attention where (i + 1) % full_attention_interval
                == 0, else linear_attention
      p = softmax_f32(h W_r) over ALL the experts; the k largest,
          renormalised over the k; E(h; g, u, d) = (silu(h g) * (h u)) d
      moe(h) = sum_j p_j E_j(h) + sigmoid(h w_sg) * E_shared(h)
  logits = RMSNorm0(x) W_head, head untied

`experts_held` (first, count) builds ONE SHARE of an expert-parallel
deployment of the layer, as models/cmdaplus.py's does: the router keeps
its `num_experts` outputs, `count` experts' weights exist, and the
routed sum holds this share's part. The checkpoint's multi-token-
prediction module is not in config.json and is not built.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

import jax.numpy as jnp

from ..config import FFConfig
from ..core.initializers import make_normal
from ..model import FFModel
from .phi4flash import FULL

DELTA = "delta"                  # the serve engine's mixer kind
LINEAR_ATTENTION, FULL_ATTENTION = "linear_attention", "full_attention"


def layer_types(num_layers: int, full_attention_interval: int) -> list:
    """The config's layer pattern: layer i is full attention where
    (i + 1) % full_attention_interval == 0."""
    return [FULL_ATTENTION if (i + 1) % full_attention_interval == 0
            else LINEAR_ATTENTION for i in range(num_layers)]


def mixer_kinds(types: Sequence[str]) -> list:
    """`layer_types` as the serve engine's mixer kinds."""
    kinds = {LINEAR_ATTENTION: DELTA, FULL_ATTENTION: FULL}
    try:
        return [kinds[t] for t in types]
    except KeyError as e:
        raise ValueError(f"layer_types holds {e.args[0]!r}; known: "
                         f"{sorted(kinds)}") from None


def build_qwen3_next_lm(config: Optional[FFConfig] = None,
                        vocab_size: int = 151936,
                        max_seq_len: int = 262144, batch_size: int = None,
                        hidden: int = 2048, num_layers: int = 48,
                        full_attention_interval: int = 4,
                        num_heads: int = 16, num_kv_heads: int = 2,
                        head_dim: int = 256,
                        partial_rotary_factor: float = 0.25,
                        rope_theta: float = 1e7, key_heads: int = 16,
                        value_heads: int = 32, key_dim: int = 128,
                        value_dim: int = 128, conv_kernel: int = 4,
                        num_experts: int = 512, experts_per_token: int = 10,
                        expert_dim: int = 512, shared_expert_dim: int = 512,
                        experts_held=None, rms_eps: float = 1e-6,
                        norm_init=(0.0, 0.0), qk_norm_init=(0.0, 0.0),
                        delta_norm_init=(1.0, 1.0),
                        dt_range=(1e-3, 1e-1), init_std: float = 0.0,
                        dtype=None, mesh=None, strategy=None) -> FFModel:
    """The op NAMES are the contract serve/arch.py reads the weights
    through: tok_embed / layer{i}_{norm1, delta | attn, norm2, moe} /
    final_norm / lm_head. `max_seq_len` is the graph's sequence length
    and, rotary having no table, the positions the serve engine takes it
    to serve. The `*_init` ranges are where the norms' scales start
    ((lo, hi) uniform or (lo, hi, "signed"): core/initializers.range_init;
    a checkpoint's zero-centred scales start at 0), `dt_range` the delta
    layers' steps (ops/gated_delta.py) and `init_std` the deviation every
    projection, expert and router matrix starts at (normal, the model
    code's `initializer_range`; 0: the program's glorot)."""
    cfg = config or FFConfig()
    if dtype is None:
        dtype = jnp.dtype(cfg.compute_dtype)
    if shared_expert_dim != expert_dim:
        raise ValueError(
            f"the shared expert is built at the routed experts' width "
            f"({expert_dim}), not {shared_expert_dim}")
    bs = batch_size or cfg.batch_size
    matrices = make_normal(0.0, init_std) if init_std else "glorot"
    ff = FFModel(cfg, mesh=mesh, strategy=strategy)
    tokens = ff.create_tensor((bs, max_seq_len), dtype=jnp.int32,
                              name="tokens")
    positions = ff.create_tensor((bs, max_seq_len), dtype=jnp.int32,
                                 name="positions")
    norm = lambda x, name: ff.rms_norm(
        x, eps=rms_eps, name=name, zero_centered=True,
        scale_init=norm_init)
    # token rows of unit variance, as models/olmoe.py's untied table
    t = ff.embedding(tokens, vocab_size, hidden, aggr="none",
                     name="tok_embed", dtype=dtype,
                     kernel_initializer="normal")
    kinds = mixer_kinds(layer_types(num_layers, full_attention_interval))
    for i, kind in enumerate(kinds):
        h = norm(t, f"layer{i}_norm1")
        if kind == DELTA:
            m = ff.gated_delta_net(
                h, key_heads, value_heads, key_dim, value_dim,
                d_conv=conv_kernel, eps=rms_eps, dt_range=dt_range,
                norm_init=delta_norm_init, kernel_initializer=matrices,
                name=f"layer{i}_delta")
        else:
            m = ff.gated_attention(
                h, positions, num_heads, num_kv_heads, head_dim,
                rotary_theta=rope_theta,
                rotary_dim=int(head_dim * partial_rotary_factor),
                eps=rms_eps, qk_norm_init=qk_norm_init,
                kernel_initializer=matrices, name=f"layer{i}_attn")
        t = ff.add(m, t, name=f"layer{i}_res1")
        h = norm(t, f"layer{i}_norm2")
        f = ff.moe_ffn(h, num_experts=num_experts, k=experts_per_token,
                       hidden_dim=expert_dim, activation="silu",
                       norm_topk=True, dropless=True, shared_experts=1,
                       shared_gate=True, experts_held=experts_held,
                       kernel_initializer=matrices, name=f"layer{i}_moe")
        t = ff.add(f, t, name=f"layer{i}_res2")
    t = norm(t, "final_norm")
    # head columns such that the logits of a normalised state have unit
    # deviation: its rows have mean square E[(1 + w)^2]
    lo, hi, *signed = norm_init
    mean_sq = 1.0 + (lo * lo + lo * hi + hi * hi) / 3.0 \
        + (0.0 if signed else lo + hi)
    ff.dense(t, vocab_size, use_bias=False, name="lm_head",
             kernel_initializer=make_normal(
                 0.0, 1.0 / math.sqrt(hidden * mean_sq)))
    return ff
