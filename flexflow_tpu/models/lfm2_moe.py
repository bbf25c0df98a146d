"""LFM2-MoE: a decoder LM of gated short convolutions in three layers
of four, grouped rotary attention with per-head QK-norm in the fourth,
leading dense layers and then sigmoid-routed experts chosen under a
selection bias (LiquidAI/LFM2-24B-A2B, `model_type` lfm2_moe; the
family's published model code is `Lfm2Moe*`).

E the hidden size; tokens t of a sequence; every norm
RMSNorm(x; w) = w * x / sqrt(mean(x^2) + norm_eps), f32 statistics.

  x_0   = table[token]
  layer i:  h = RMSNorm(x; w_op)                              "operator_norm"
   CONV   (layer_types[i] == "conv")                          ops/short_conv.py
          [B | C | z] = h W_in      W_in: E -> 3 E, no bias; thirds in THIS order
          u_t = B_t * z_t
          c_t = w[0] u_{t-2} + w[1] u_{t-1} + w[2] u_t        conv_L_cache taps a channel,
                                    depthwise, causal, no bias, zeros before the
                                    sequence's first token, NO activation
          m_t = (C_t * c_t) W_out   W_out: E -> E, no bias
          cache: u_{t-2}, u_{t-1}   the PRODUCT B * z, not h
   ATTN   (layer_types[i] == "full_attention")
          q = h W_q (H x D), k = h W_k (Hk x D), v = h W_v (Hk x D); no bias
          q = RMSNorm(q_head; w_qn), k = RMSNorm(k_head; w_kn)
                                    over EACH head's D dims, one (D,) weight shared
                                    by the heads, BEFORE the rotation
          q, k rotated half-split over all D dims at rope_theta, the token's
          absolute position
          o = causal softmax(q k^T / sqrt(D)) v over the whole context,
              H / Hk query heads a key-value head
          m = o W_o                 W_o: H D -> E, no bias
   x  = x + m
   h2 = RMSNorm(x; w_ffn)                                     "ffn_norm"
   i < num_dense_layers:  f = (silu(h2 W1) * (h2 W3)) W2      E -> intermediate_size -> E
   else:  s   = sigmoid(h2 W_r)     num_experts scores, logits and scores in f32
          sel = the k largest of (s + b)        b: expert_bias, f32; chooses, never weighs
          g_e = s_e / (sum_{e in sel} s_e + 1e-6) * routed_scaling_factor
          f   = sum_{e in sel} g_e (silu(h2 W1_e) * (h2 W3_e)) W2_e
                                    E -> moe_intermediate_size -> E; no shared expert
   x  = x + f
  logits = RMSNorm(x; w_final) table^T      the family's "embedding_norm" stands
                                    AFTER the layers; the head is the table (tied)
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax.numpy as jnp

from ..config import FFConfig
from ..core.initializers import make_normal, make_normal_as, range_init
from ..model import FFModel
from .phi4flash import FULL

CONV = "conv"                       # the mixer kind serve/mixers.py runs
# config.json's `layer_types` entries -> the serve engine's mixer kinds
LAYER_TYPES = {"conv": CONV, "full_attention": FULL}


def mixer_kinds(layer_types: Sequence[str]) -> list:
    try:
        return [LAYER_TYPES[t] for t in layer_types]
    except KeyError as e:
        raise ValueError(f"layer_types holds {e.args[0]!r}; known: "
                         f"{sorted(LAYER_TYPES)}") from None


def build_lfm2_moe_lm(config: Optional[FFConfig] = None,
                      vocab_size: int = 65536, max_seq_len: int = 4096,
                      batch_size: int = None, hidden: int = 2048,
                      layer_types: Sequence[str] = ("conv", "conv",
                                                    "full_attention", "conv"),
                      num_dense_layers: int = 2, num_heads: int = 32,
                      num_kv_heads: int = 8, ff_dim: int = 11776,
                      num_experts: int = 64, experts_per_token: int = 4,
                      expert_dim: int = 1536, conv_kernel: int = 3,
                      rope_theta: float = 1e6, rms_eps: float = 1e-5,
                      norm_topk: bool = True, use_expert_bias: bool = True,
                      norm_init=(1.0, 1.0), final_norm_init=None,
                      qk_norm_init=None,
                      tap_init=None, expert_bias_std: float = 0.0,
                      stds: Optional[dict] = None, dtype=None, mesh=None,
                      strategy=None) -> FFModel:
    """The op NAMES are the contract serve/arch.py reads the weights
    through: tok_embed / layer{i}_{operator_norm, conv | attn, res1,
    ffn_norm, mlp | moe, res2} / embedding_norm / lm_head (tied: no
    weight). `max_seq_len` is the graph's sequence length and, rotary
    having no table, the positions the serve engine takes it to serve.
    `routed_scaling_factor` is 1 as published and stands nowhere.

    How the leaves start: `norm_init` (lo, hi) every norm's scale;
    `final_norm_init` (lo, hi) the final norm's (None: as the others):
    the head is the table, so a table of unit rows gives logits of unit
    deviation only under a final scale near hidden^-0.5;
    `qk_norm_init` (lo, hi) the per-head norms' (None: at 1);
    `tap_init` (lo, hi[, "signed"]) the taps (None: glorot);
    `expert_bias_std` the selection bias's deviation (0: at 0);
    `stds`: the deviation each matrix starts at (normal), by name —
    "table", "conv_in", "conv_out", "wq", "wk", "wv", "wo", "gate_up",
    "down", "router", "expert_in", "expert_out" — a name left out: the
    program's glorot (the table: hidden^-0.5, a tied table's choice)."""
    cfg = config or FFConfig()
    if dtype is None:
        dtype = jnp.dtype(cfg.compute_dtype)
    bs = batch_size or cfg.batch_size
    stds = dict(stds or {})
    # drawn a block of rows at a time and stored as the parameters are:
    # an expert stack is 0.8 GB in f32
    start = lambda name: make_normal_as(
        float(stds[name]), cfg.param_dtype) if name in stds else "glorot"
    ff = FFModel(cfg, mesh=mesh, strategy=strategy)
    tokens = ff.create_tensor((bs, max_seq_len), dtype=jnp.int32,
                              name="tokens")
    positions = ff.create_tensor((bs, max_seq_len), dtype=jnp.int32,
                                 name="positions")
    norm = lambda x, name: ff.rms_norm(x, eps=rms_eps, name=name,
                                       scale_init=norm_init)
    t, table = ff.embedding(
        tokens, vocab_size, hidden, aggr="none", name="tok_embed",
        dtype=dtype, emit_table=True,
        kernel_initializer=start("table") if "table" in stds
        else make_normal(0.0, hidden ** -0.5))
    for i, kind in enumerate(mixer_kinds(layer_types)):
        h = norm(t, f"layer{i}_operator_norm")
        if kind == CONV:
            m = ff.gated_short_conv(
                h, taps=conv_kernel, name=f"layer{i}_conv",
                kernel_initializer={
                    "w_in": start("conv_in"), "w_out": start("conv_out"),
                    "conv_w": "glorot" if tap_init is None
                    else range_init(tap_init)})
        else:
            m = ff.multihead_attention(
                h, h, h, hidden, num_heads, bias=False, causal=True,
                positions=positions, rotary_theta=float(rope_theta),
                num_kv_heads=num_kv_heads, qk_norm=True,
                qk_norm_per_head=True, qk_norm_eps=rms_eps,
                qk_norm_init=qk_norm_init,
                kernel_initializer={w: start(w)
                                    for w in ("wq", "wk", "wv", "wo")},
                name=f"layer{i}_attn")
        t = ff.add(m, t, name=f"layer{i}_res1")
        h = norm(t, f"layer{i}_ffn_norm")
        if i < num_dense_layers:
            f = ff.gated_ffn(h, ff_dim, name=f"layer{i}_mlp",
                             kernel_initializer={
                                 "w_gu": start("gate_up"),
                                 "w_down": start("down")})
        else:
            f = ff.moe_ffn(
                h, num_experts=num_experts, k=experts_per_token,
                hidden_dim=expert_dim, activation="silu",
                norm_topk=norm_topk, dropless=True, score="sigmoid",
                expert_bias=(float(expert_bias_std) or True)
                if use_expert_bias else None,
                kernel_initializer={
                    "gate": start("router"), "wg": start("expert_in"),
                    "wu": start("expert_in"), "wd": start("expert_out")},
                name=f"layer{i}_moe")
        t = ff.add(f, t, name=f"layer{i}_res2")
    t = ff.rms_norm(t, eps=rms_eps, name="embedding_norm",
                    scale_init=final_norm_init or norm_init)
    ff.tied_head(t, table, name="lm_head")
    return ff
