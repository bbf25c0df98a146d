"""Falcon-H1 (tiiuae/Falcon-H1-34B-Instruct, `model_type` falcon_h1;
"Falcon-H1: A Family of Hybrid-Head Language Models"): the language
model's decoder — in EVERY block Mamba-2 heads (ops/ssd.py) and grouped
softmax attention heads IN PARALLEL, read from one norm and added to the
stream together, then a gated feed-forward under a norm of its own;
scalar muP multipliers on the activations.

All norms RMSNorm(x; w) = w * x / sqrt(mean(x^2) + eps), f32 statistics.

  x_0   = table[token] * embedding_multiplier
  layer:  h = RMSNorm(x; w_in)
   SSM    p   = ((h * ssm_in_multiplier) W_in) * m        W_in: E -> 2 d_ssm + 2 G N + H, no bias
          m   = ssm_multipliers[0..4] spread over the column groups [z | x | B | C | dt]
          [x|B|C] = silu(conv_causal([x|B|C]; d_conv taps a channel, WITH bias))
          x: H heads of P;  B, C: G groups of N;  head j reads group j // (H / G)
          dt_j = softplus(dt_j + dt_bias_j);  a_j = exp(-exp(A_log_j) * dt_j)      one scalar a head a token
          S_j <- a_j S_j + dt_j * x_j (outer) B_g                                  S_j: P x N, f32
          y_j = S_j C_g + D_j x_j
          y   = RMSNorm_per_group(y * silu(z); w_y)       the gate first, a group's d_ssm / G channels
          s   = (y W_out) * ssm_out_multiplier            W_out: d_ssm -> E
   ATTN   u   = h * attention_in_multiplier
          q = u W_q (Hq x D), k = (u W_k) * key_multiplier (Hk x D), v = u W_v (Hk x D); no bias
          q, k rotated half-split over all D dims at rope_theta, the token's absolute position
          o   = causal softmax(q k^T / sqrt(D)) v over the whole context, Hq / Hk query heads a key-value head
          a   = (o W_o) * attention_out_multiplier        W_o: Hq D -> E
          x   = x + s + a                                 BOTH branches read the SAME h
   FFN    h2  = RMSNorm(x; w_ff)
          f   = ((silu((h2 W_gate) * mlp_multipliers[0]) * (h2 W_up)) W_down) * mlp_multipliers[1]
          x   = x + f
  logits = (RMSNorm(x; w_final) W_head) * lm_head_multiplier       head untied

Every multiplier is applied where it stands — a scalar on an
activation, `m` on the in-projection's output — by an op of the graph
(`scalar_multiply`, or the mixer's, the attention's and the
feed-forward's own argument); none is folded into a matrix.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax.numpy as jnp

from ..config import FFConfig
from ..core.initializers import make_normal_as
from ..model import FFModel

__all__ = ["build_falcon_h1_lm", "SSD_ATTN"]

SSD_ATTN = "ssd_attn"            # the serve engine's mixer kind


def build_falcon_h1_lm(config: Optional[FFConfig] = None,
                       vocab_size: int = 261120, max_seq_len: int = 262144,
                       batch_size: int = None, hidden: int = 5120,
                       num_layers: int = 72, num_heads: int = 20,
                       num_kv_heads: int = 4, head_dim: int = 128,
                       ff_dim: int = 21504, rope_theta: float = 1e11,
                       ssm_heads: int = 32, ssm_head_dim: int = 128,
                       ssm_groups: int = 2, ssm_state: int = 256,
                       conv_kernel: int = 4, rms_eps: float = 1e-5,
                       embedding_multiplier: float = 1.0,
                       lm_head_multiplier: float = 1.0,
                       ssm_in_multiplier: float = 1.0,
                       ssm_multipliers: Sequence[float] = (1.0,) * 5,
                       ssm_out_multiplier: float = 1.0,
                       attention_in_multiplier: float = 1.0,
                       attention_out_multiplier: float = 1.0,
                       key_multiplier: float = 1.0,
                       mlp_multipliers: Sequence[float] = (1.0, 1.0),
                       norm_init=(1.0, 1.0), dt_range=(1e-3, 1e-1),
                       a_range=(1.0, 16.0), stds: Optional[dict] = None,
                       dtype=None, mesh=None, strategy=None) -> FFModel:
    """The op NAMES are the contract serve/arch.py reads the weights
    through: tok_embed, embed_scale / layer{i}_{ln, ssm, attn_in, attn,
    attn_scale, mixed, res1, ln2, mlp, res2} / final_norm / lm_head,
    logit_scale. `max_seq_len` is the graph's sequence length and,
    there being no table, the positions the serve engine takes it to
    serve. Every multiplier is a key of the published config.json (1:
    none). `norm_init` (lo, hi): where every norm's scale starts
    (uniform: core/initializers.range_init); `dt_range` / `a_range`:
    the Mamba-2 heads' steps and decays (ops/ssd.py); `stds`: the
    deviation each matrix starts at (normal), by name — "table", "head",
    "ssm_in", "ssm_out", "wq", "wk", "wv", "wo", "gate_up", "down" — a
    name left out: the program's glorot."""
    cfg = config or FFConfig()
    if dtype is None:
        dtype = jnp.dtype(cfg.compute_dtype)
    bs = batch_size or cfg.batch_size
    stds = dict(stds or {})
    # drawn a block of rows at a time and stored as the parameters are:
    # the head alone is 5 GB in f32
    start = lambda name: make_normal_as(
        float(stds[name]), cfg.param_dtype) if name in stds else "glorot"
    ff = FFModel(cfg, mesh=mesh, strategy=strategy)
    tokens = ff.create_tensor((bs, max_seq_len), dtype=jnp.int32,
                              name="tokens")
    positions = ff.create_tensor((bs, max_seq_len), dtype=jnp.int32,
                                 name="positions")
    norm = lambda x, name: ff.rms_norm(x, eps=rms_eps, name=name,
                                       scale_init=norm_init)
    t = ff.embedding(tokens, vocab_size, hidden, aggr="none",
                     name="tok_embed", dtype=dtype,
                     kernel_initializer=start("table")
                     if "table" in stds else "normal")
    t = ff.scalar_multiply(t, float(embedding_multiplier),
                           name="embed_scale")
    for i in range(num_layers):
        h = norm(t, f"layer{i}_ln")
        s = ff.mamba2_mixer(
            h, ssm_heads, ssm_head_dim, ssm_groups, ssm_state,
            d_conv=conv_kernel, eps=rms_eps,
            in_multiplier=ssm_in_multiplier, multipliers=ssm_multipliers,
            out_multiplier=ssm_out_multiplier, dt_range=dt_range,
            a_range=a_range, norm_init=norm_init,
            kernel_initializer=start("ssm_in"),
            out_initializer=start("ssm_out"), name=f"layer{i}_ssm")
        u = ff.scalar_multiply(h, float(attention_in_multiplier),
                               name=f"layer{i}_attn_in")
        a = ff.multihead_attention(
            u, u, u, hidden, num_heads, bias=False, causal=True,
            positions=positions, rotary_theta=float(rope_theta),
            num_kv_heads=num_kv_heads, head_dim=head_dim,
            key_multiplier=key_multiplier,
            kernel_initializer={w: start(w)
                                for w in ("wq", "wk", "wv", "wo")},
            name=f"layer{i}_attn")
        a = ff.scalar_multiply(a, float(attention_out_multiplier),
                               name=f"layer{i}_attn_scale")
        m = ff.add(s, a, name=f"layer{i}_mixed")
        t = ff.add(t, m, name=f"layer{i}_res1")
        f = ff.gated_ffn(norm(t, f"layer{i}_ln2"), ff_dim,
                         name=f"layer{i}_mlp",
                         kernel_initializer={"w_gu": start("gate_up"),
                                             "w_down": start("down")},
                         multipliers=mlp_multipliers)
        t = ff.add(t, f, name=f"layer{i}_res2")
    t = norm(t, "final_norm")
    t = ff.dense(t, vocab_size, use_bias=False, name="lm_head",
                 kernel_initializer=start("head"))
    ff.scalar_multiply(t, float(lm_head_multiplier), name="logit_scale")
    return ff
