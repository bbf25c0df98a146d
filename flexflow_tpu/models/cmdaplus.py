"""Command A+ (CohereLabs/command-a-plus-05-2026, `model_type`
cohere2_moe): the language model's decoder, a PARALLEL block of grouped
attention — window layers with rotary, full layers with no position
signal — beside sigmoid-routed experts and averaged shared experts.

Per layer i, x (tokens, E), ONE norm a layer:
  h = LN(x; w_i)            scale only, no bias; f32 statistics
  q = h Wq (H heads of D), k = h Wk, v = h Wv (Hk heads of D); no bias
  window layer: q, k rotated at the token's absolute position over all
                D dims, interleaved pairs (x[2j], x[2j+1]); token t
                sees keys t - window + 1 .. t
  full layer:   no rotation; token t sees keys 0 .. t
  query head j reads key/value head j // (H / Hk);  a = concat(o) Wo
  s = sigmoid_f32(h Wr); the k largest s, p_j = s_j / their sum
  f = sum_j p_j E_j(h) + (1 / M) sum_m S_m(h)
      E(h; g, u, d) = (silu(h g) * (h u)) d, routed and shared alike
  x = x + a + f
Then LN(x; w_final) and logits = . Emb^T * logit_scale (tied).

`experts_held` (first, count) builds ONE SHARE of an expert-parallel
deployment of the layer: the router keeps its `num_experts` outputs,
only `count` experts' weights exist, and f holds this share's part of
the routed sum (ops/moe_ffn.py). The layer kinds are models/phi4flash's
WINDOW and FULL.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax.numpy as jnp

from ..config import FFConfig
from ..core.initializers import make_normal
from ..model import FFModel
from .phi4flash import FULL, WINDOW

SLIDING, GLOBAL = "sliding_attention", "full_attention"


def mixer_kinds(layer_types: Sequence[str]) -> list:
    """The config's `layer_types` as the serve engine's mixer kinds."""
    kinds = {SLIDING: WINDOW, GLOBAL: FULL}
    try:
        return [kinds[t] for t in layer_types]
    except KeyError as e:
        raise ValueError(f"layer_types holds {e.args[0]!r}; known: "
                         f"{sorted(kinds)}") from None


def build_cmdaplus_lm(config: Optional[FFConfig] = None,
                      vocab_size: int = 262144, max_seq_len: int = 8192,
                      batch_size: int = None, hidden: int = 4096,
                      num_heads: int = 128, num_kv_heads: int = 8,
                      head_dim: int = 128,
                      layer_types: Sequence[str] = (SLIDING,) * 3
                      + (GLOBAL,),
                      window: int = 4096, num_experts: int = 128,
                      experts_per_token: int = 8, expert_dim: int = 4096,
                      shared_experts: int = 4, experts_held=None,
                      rope_theta: float = 50000.0, ln_eps: float = 1e-5,
                      logit_scale: float = 1.0, dtype=None, mesh=None,
                      strategy=None) -> FFModel:
    """The op NAMES are the contract serve/arch.py reads the weights
    through: tok_embed / layer{i}_{ln, attn, moe, res} / final_ln /
    lm_head (tied: no weight). `max_seq_len` is the graph's sequence
    length and, rotary having no table, the positions the serve engine
    takes it to serve."""
    cfg = config or FFConfig()
    if dtype is None:
        dtype = jnp.dtype(cfg.compute_dtype)
    bs = batch_size or cfg.batch_size
    ff = FFModel(cfg, mesh=mesh, strategy=strategy)
    tokens = ff.create_tensor((bs, max_seq_len), dtype=jnp.int32,
                              name="tokens")
    positions = ff.create_tensor((bs, max_seq_len), dtype=jnp.int32,
                                 name="positions")
    # token rows of deviation hidden^-0.5, models/phi4flash.py's choice
    # for a tied table: the logits of a normalised state then have unit
    # deviation
    t, table = ff.embedding(
        tokens, vocab_size, hidden, aggr="none", name="tok_embed",
        dtype=dtype, emit_table=True,
        kernel_initializer=make_normal(0.0, hidden ** -0.5))
    for i, kind in enumerate(mixer_kinds(layer_types)):
        h = ff.layer_norm(t, eps=ln_eps, use_bias=False, name=f"layer{i}_ln")
        # the full layers: no window and no position signal
        rot = kind == WINDOW
        a = ff.multihead_attention(
            h, h, h, hidden, num_heads, head_dim=head_dim, bias=False,
            causal=True, positions=positions if rot else None,
            rotary_theta=rope_theta if rot else 0.0,
            rotary_interleaved=rot, num_kv_heads=num_kv_heads,
            window=window if rot else 0, name=f"layer{i}_attn")
        m = ff.moe_ffn(h, num_experts=num_experts, k=experts_per_token,
                       hidden_dim=expert_dim, activation="silu",
                       norm_topk=True, dropless=True, score="sigmoid",
                       shared_experts=shared_experts,
                       experts_held=experts_held, name=f"layer{i}_moe")
        # the parallel block: both branches read h, one residual sum
        t = ff.add(ff.add(a, m, name=f"layer{i}_branches"), t,
                   name=f"layer{i}_res")
    t = ff.layer_norm(t, eps=ln_eps, use_bias=False, name="final_ln")
    ff.tied_head(t, table, scale=logit_scale, name="lm_head")
    return ff
