"""MiniCPM-SALA (openbmb/MiniCPM-SALA, `model_type` minicpm_sala): a
dense decoder whose mixers are, one layer in four, block-sparse
attention over a learned selection of the context (`minicpm4`,
ops/sparse_attention.py) and, in the other three, lightning linear
attention (`lightning-attn`, ops/linear_attention.py), under muP
scalings.

  x_0 = scale_emb * E[token]
  per layer:  x = x + r * mixer(RMSNorm(x));  x = x + r * ffn(RMSNorm(x))
              r = scale_depth / sqrt(PUBLISHED depth)
              ffn(h) = (silu(h W_g) * (h W_u)) W_d, no bias
  logits = (RMSNorm(x) / (hidden / dim_model_base)) W_head, head untied

`layers_kept` builds a CUT of the published stack: the published
indices of the layers that stay (their mixers from `mixer_types`, which
is kept whole). A kept layer takes its published index where the
equations name one (the lightning decays) and the published depth in r.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

import jax.numpy as jnp

from ..config import FFConfig
from ..core.initializers import make_normal
from ..model import FFModel
from ..ops.sparse_attention import SparseConfig

SPARSE, LINEAR = "sparse", "linear"      # the serve engine's mixer kinds
MINICPM4, LIGHTNING = "minicpm4", "lightning-attn"


def mixer_kinds(mixer_types: Sequence[str],
                layers_kept: Optional[Sequence[int]] = None) -> list:
    """The kept layers' mixer kinds, from the config's `mixer_types`."""
    kinds = {MINICPM4: SPARSE, LIGHTNING: LINEAR}
    kept = range(len(mixer_types)) if layers_kept is None else layers_kept
    try:
        return [kinds[mixer_types[i]] for i in kept]
    except KeyError as e:
        raise ValueError(f"mixer_types holds {e.args[0]!r}; known: "
                         f"{sorted(kinds)}") from None


def build_minicpm_sala_lm(config: Optional[FFConfig] = None,
                          vocab_size: int = 73448, max_seq_len: int = 65536,
                          batch_size: int = None, hidden: int = 4096,
                          num_heads: int = 32, num_kv_heads: int = 2,
                          head_dim: int = 128, lightning_heads: int = 32,
                          lightning_head_dim: int = 128,
                          ff_dim: int = 16384,
                          mixer_types: Sequence[str] = (MINICPM4,)
                          + (LIGHTNING,) * 3,
                          layers_kept: Optional[Sequence[int]] = None,
                          sparse: SparseConfig = SparseConfig(),
                          rope_theta: float = 10000.0,
                          rms_eps: float = 1e-6, scale_emb: float = 12.0,
                          scale_depth: float = 1.4,
                          dim_model_base: int = 256,
                          sparse_qk_norm_init: float = 1.0, dtype=None,
                          mesh=None, strategy=None) -> FFModel:
    """The op NAMES are the contract serve/arch.py reads the weights
    through: tok_embed / layer{i}_{norm1, sparse | linear, norm2, ffn} /
    final_norm / lm_head. `max_seq_len` is the graph's sequence length
    and, rotary having no table, the positions the serve engine takes
    it to serve. `sparse_qk_norm_init`: what the sparse layers' q_norm
    and k_norm scales start at (ops/sparse_attention.SparseAttention)."""
    cfg = config or FFConfig()
    if dtype is None:
        dtype = jnp.dtype(cfg.compute_dtype)
    bs = batch_size or cfg.batch_size
    published = len(mixer_types)
    kept = list(range(published) if layers_kept is None else layers_kept)
    kinds = mixer_kinds(mixer_types, kept)
    residual = scale_depth / math.sqrt(published)
    ff = FFModel(cfg, mesh=mesh, strategy=strategy)
    tokens = ff.create_tensor((bs, max_seq_len), dtype=jnp.int32,
                              name="tokens")
    positions = ff.create_tensor((bs, max_seq_len), dtype=jnp.int32,
                                 name="positions")
    # token rows of deviation 1 / scale_emb: x_0 then has unit rows, as
    # models/olmoe.py's untied table gives its block
    t = ff.embedding(tokens, vocab_size, hidden, aggr="none",
                     name="tok_embed", dtype=dtype,
                     kernel_initializer=make_normal(0.0, 1.0 / scale_emb))
    t = ff.scalar_multiply(t, scale_emb, name="embed_scale")
    for i, (kind, pub) in enumerate(zip(kinds, kept)):
        h = ff.rms_norm(t, eps=rms_eps, name=f"layer{i}_norm1")
        if kind == SPARSE:
            m = ff.sparse_attention(
                h, num_heads, num_kv_heads, head_dim, sparse=sparse,
                eps=rms_eps, qk_norm_init=sparse_qk_norm_init,
                name=f"layer{i}_sparse")
        else:
            m = ff.lightning_attention(
                h, positions, lightning_heads, lightning_head_dim,
                layer_index=pub, published_layers=published,
                rotary_theta=rope_theta, eps=rms_eps,
                name=f"layer{i}_linear")
        m = ff.scalar_multiply(m, residual, name=f"layer{i}_scale1")
        t = ff.add(m, t, name=f"layer{i}_res1")
        h = ff.rms_norm(t, eps=rms_eps, name=f"layer{i}_norm2")
        f = ff.gated_ffn(h, ff_dim, name=f"layer{i}_ffn")
        f = ff.scalar_multiply(f, residual, name=f"layer{i}_scale2")
        t = ff.add(f, t, name=f"layer{i}_res2")
    t = ff.rms_norm(t, eps=rms_eps, name="final_norm")
    t = ff.scalar_multiply(t, dim_model_base / hidden, name="head_scale")
    # head columns of deviation (hidden / dim_model_base) / sqrt(hidden)
    # (0.25 as published): the logits of a normalised state, after the
    # division above, then have unit deviation
    ff.dense(t, vocab_size, use_bias=False, name="lm_head",
             kernel_initializer=make_normal(
                 0.0, (hidden / dim_model_base) / math.sqrt(hidden)))
    return ff
