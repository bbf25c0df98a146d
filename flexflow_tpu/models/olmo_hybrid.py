"""Olmo-Hybrid (allenai/Olmo-Hybrid-7B, `model_type` olmo_hybrid): the
language model's decoder — three layers of the gated delta rule with
negative eigenvalues (`linear_attention`, ops/gated_delta.py with
`allow_neg_eigval`) to one of plain multi-head softmax attention with
QK-norm (`full_attention`), a dense SwiGLU feed-forward in every layer,
in the olmo family's POST-NORM block.

  x_0 = E[token]
  per layer i:  x = x + RMSNorm(mixer_i(x); w_i^a)
                x = x + RMSNorm(mlp(x); w_i^f)
      RMSNorm(x; w) = w * x / sqrt(mean(x^2) + eps)      (plain, not 1 + w)
      mixer and feed-forward read the residual stream ITSELF; the norm
      stands on the sub-layer's output, before the add
      mixer_i = full_attention where layer_types[i] says so
                ((i + 1) % 4 == 0 as published), else linear_attention
      full_attention: q = RMSNorm(x W_q; w_q), k = RMSNorm(x W_k; w_k)
          over the WHOLE projection, v = x W_v; heads of hidden / H;
          `rope_theta` None: no rotation (the row's null), a number: the
          half-split rotation over the whole head; causal softmax of
          q k^T / sqrt(D); out = o W_o. No bias, no gate
      linear_attention: ops/gated_delta.py, beta = 2 sigmoid(b)
      mlp(x) = (silu(x W_g) * (x W_u)) W_d
  logits = RMSNorm(x; w_final) W_head, head untied
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

import jax.numpy as jnp

from ..config import FFConfig
from ..core.initializers import make_normal
from ..model import FFModel
from .phi4flash import FULL  # noqa: F401 (re-exported: __all__)
from .qwen3_next import DELTA, layer_types, mixer_kinds

__all__ = ["build_olmo_hybrid_lm", "DELTA", "FULL"]


def build_olmo_hybrid_lm(config: Optional[FFConfig] = None,
                         vocab_size: int = 100352,
                         max_seq_len: int = 65536, batch_size: int = None,
                         hidden: int = 3840, num_layers: int = 32,
                         types: Optional[Sequence[str]] = None,
                         num_heads: int = 30, ff_dim: int = 11008,
                         rope_theta: Optional[float] = None,
                         key_heads: int = 30, value_heads: int = 30,
                         key_dim: int = 96, value_dim: int = 192,
                         conv_kernel: int = 4,
                         allow_neg_eigval: bool = True,
                         rms_eps: float = 1e-6,
                         post_norm_init=(1.0, 1.0),
                         final_norm_init=(1.0, 1.0),
                         qk_norm_init=(1.0, 1.0),
                         delta_norm_init=(1.0, 1.0),
                         dt_range=(1e-3, 1e-1), init_std: float = 0.0,
                         dtype=None, mesh=None, strategy=None) -> FFModel:
    """The op NAMES are the contract serve/arch.py reads the weights
    through: tok_embed / layer{i}_{delta | attn, post_norm1, mlp,
    post_norm2} / final_norm / lm_head. `types`: the config's
    `layer_types` (None: a full layer every fourth). `max_seq_len` is
    the graph's sequence length and, there being no table, the positions
    the serve engine takes it to serve. `rope_theta` None builds no
    rotation; a number, the half-split rotation over a head's every dim.
    The `*_init` ranges are where the norms' scales start ((lo, hi)
    uniform: core/initializers.range_init) — with the norm AFTER a
    sub-layer, `post_norm_init` is the size of every branch beside the
    stream, whatever the matrices start at — `dt_range` the delta
    layers' steps (ops/gated_delta.py) and `init_std` the deviation
    every projection matrix starts at (normal; 0: the program's
    glorot), as build_qwen3_next_lm's."""
    cfg = config or FFConfig()
    if dtype is None:
        dtype = jnp.dtype(cfg.compute_dtype)
    bs = batch_size or cfg.batch_size
    matrices = make_normal(0.0, init_std) if init_std else "glorot"
    ff = FFModel(cfg, mesh=mesh, strategy=strategy)
    tokens = ff.create_tensor((bs, max_seq_len), dtype=jnp.int32,
                              name="tokens")
    rotated = rope_theta is not None
    positions = ff.create_tensor((bs, max_seq_len), dtype=jnp.int32,
                                 name="positions") if rotated else None
    norm = lambda x, name, init: ff.rms_norm(x, eps=rms_eps, name=name,
                                             scale_init=init)
    # token rows of unit variance, as models/olmoe.py's untied table
    t = ff.embedding(tokens, vocab_size, hidden, aggr="none",
                     name="tok_embed", dtype=dtype,
                     kernel_initializer="normal")
    kinds = mixer_kinds(types if types is not None
                        else layer_types(num_layers, 4))
    if len(kinds) != num_layers:
        raise ValueError(f"layer_types names {len(kinds)} layers of "
                         f"{num_layers}")
    for i, kind in enumerate(kinds):
        if kind == DELTA:
            m = ff.gated_delta_net(
                t, key_heads, value_heads, key_dim, value_dim,
                d_conv=conv_kernel, eps=rms_eps, dt_range=dt_range,
                norm_init=delta_norm_init, kernel_initializer=matrices,
                allow_neg_eigval=allow_neg_eigval, name=f"layer{i}_delta")
        else:
            m = ff.multihead_attention(
                t, t, t, hidden, num_heads, bias=False, causal=True,
                positions=positions,
                rotary_theta=float(rope_theta) if rotated else 0.0,
                qk_norm=True, qk_norm_eps=rms_eps,
                qk_norm_init=qk_norm_init, kernel_initializer=matrices,
                name=f"layer{i}_attn")
        m = norm(m, f"layer{i}_post_norm1", post_norm_init)
        t = ff.add(m, t, name=f"layer{i}_res1")
        f = ff.gated_ffn(t, ff_dim, name=f"layer{i}_mlp",
                         kernel_initializer=matrices)
        f = norm(f, f"layer{i}_post_norm2", post_norm_init)
        t = ff.add(f, t, name=f"layer{i}_res2")
    t = norm(t, "final_norm", final_norm_init)
    # head columns such that the logits of a normalised state have unit
    # deviation: its rows have mean square E[w^2]
    lo, hi = final_norm_init
    mean_sq = (lo * lo + lo * hi + hi * hi) / 3.0
    ff.dense(t, vocab_size, use_bias=False, name="lm_head",
             kernel_initializer=make_normal(
                 0.0, 1.0 / math.sqrt(hidden * mean_sq)))
    return ff
