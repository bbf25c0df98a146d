"""OLMoE: a decoder LM of RMSNorm, rotary attention with QK-norm and
dropless top-k SwiGLU experts (allenai/OLMoE-1B-7B-0125-Instruct,
`model_type` olmoe; arXiv:2409.02060).

Per layer, x (tokens, E):
  h = rms(x; norm1); q = rms(h Wq; q_norm), k = rms(h Wk; k_norm) over
  the WHOLE projection before the split into heads; v = h Wv; rotary on
  q and k per head at the token's position; causal attention;
  x = x + o Wo.
  h = rms(x; norm2); p = softmax_f32(h Wr); the k largest p as they
  are (not renormalised); y = sum_j p_j (silu(h Wg_j) * (h Wu_j)) Wd_j;
  x = x + y.
Then rms(x; final_norm) and an untied head. No bias anywhere.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from ..config import FFConfig
from ..model import FFModel


def build_olmoe_lm(config: Optional[FFConfig] = None,
                   vocab_size: int = 50304, max_seq_len: int = 4096,
                   batch_size: int = None, hidden: int = 2048,
                   num_heads: int = 16, num_layers: int = 16,
                   num_experts: int = 64, experts_per_token: int = 8,
                   expert_dim: int = 1024, rope_theta: float = 10000.0,
                   rms_eps: float = 1e-5, norm_topk: bool = False,
                   dtype=None, mesh=None, strategy=None) -> FFModel:
    """The op NAMES are the contract serve/arch.py reads the weights
    through: tok_embed / layer{i}_{norm1,attn,norm2,moe} / final_norm /
    lm_head. `max_seq_len` is the graph's sequence length and, rotary
    having no table, the positions the serve engine takes it to
    serve."""
    cfg = config or FFConfig()
    if dtype is None:
        dtype = jnp.dtype(cfg.compute_dtype)
    bs = batch_size or cfg.batch_size
    ff = FFModel(cfg, mesh=mesh, strategy=strategy)
    tokens = ff.create_tensor((bs, max_seq_len), dtype=jnp.int32,
                              name="tokens")
    positions = ff.create_tensor((bs, max_seq_len), dtype=jnp.int32,
                                 name="positions")
    # token rows of unit variance: a block's output has unit variance
    # under the default initialisers, and a table at glorot's scale
    # (0.006 here) would leave the residual stream 99 % history and
    # the router blind to the token, which no trained model is
    t = ff.embedding(tokens, vocab_size, hidden, aggr="none",
                     name="tok_embed", dtype=dtype,
                     kernel_initializer="normal")
    for i in range(num_layers):
        h = ff.rms_norm(t, eps=rms_eps, name=f"layer{i}_norm1")
        a = ff.multihead_attention(
            h, h, h, hidden, num_heads, bias=False, causal=True,
            positions=positions, rotary_theta=rope_theta, qk_norm=True,
            qk_norm_eps=rms_eps, name=f"layer{i}_attn")
        t = ff.add(a, t, name=f"layer{i}_res1")
        h = ff.rms_norm(t, eps=rms_eps, name=f"layer{i}_norm2")
        m = ff.moe_ffn(h, num_experts=num_experts, k=experts_per_token,
                       hidden_dim=expert_dim, activation="silu",
                       norm_topk=norm_topk, dropless=True,
                       name=f"layer{i}_moe")
        t = ff.add(m, t, name=f"layer{i}_res2")
    t = ff.rms_norm(t, eps=rms_eps, name="final_norm")
    ff.dense(t, vocab_size, use_bias=False, name="lm_head")
    return ff
