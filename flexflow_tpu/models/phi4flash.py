"""Phi-4-mini-flash-reasoning: the SambaY decoder-hybrid-decoder
(microsoft/Phi-4-mini-flash-reasoning, `model_type` phi4flash;
arXiv:2507.06607 over YOCO arXiv:2405.05254, Mamba arXiv:2312.00752 and
differential attention arXiv:2410.05258).

Every sub-layer is pre-norm, x = x + f(LN(x)), LayerNorm with scale and
bias; a final LayerNorm; logits = LN(x) E^T with the token table E
tied; NO positional encoding. Layer i (0-based) of L, with the
self-decoder's last layer at `full = L // 2 + 1` (17 of 32):

  mixer   i even, i <  full  -> ssm     (ops/ssm.py; layer full - 1
                                         also hands on its MEMORY)
          i odd,  i <  full  -> window  differential attention, keys
                                         t - window + 1 .. t
          i == full          -> full    differential attention, causal;
                                         its K, V are the only ones any
                                         later layer reads
          i even, i >  full  -> gmu     gated memory unit on the memory
          i odd,  i >  full  -> cross   differential attention over the
                                         full layer's K, V: W_q, W_o only
  then    x = x + gated_ffn(LN(x))      every layer
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from ..config import FFConfig
from ..core.initializers import make_normal
from ..model import FFModel

SSM, WINDOW, FULL, GMU, CROSS = "ssm", "window", "full", "gmu", "cross"


def full_layer(num_layers: int) -> int:
    return num_layers // 2 + 1


def mixer_kinds(num_layers: int) -> list:
    """The mixer kind of every layer (`mb_per_layer` 2)."""
    full = full_layer(num_layers)
    if num_layers < 4 or num_layers % 4:
        raise ValueError(
            f"the layer pattern (state-space and attention layers "
            f"alternating, the full layer odd) needs a multiple of 4 "
            f"layers, got {num_layers}")
    kinds = []
    for i in range(num_layers):
        if i == full:
            kinds.append(FULL)
        elif i < full:
            kinds.append(SSM if i % 2 == 0 else WINDOW)
        else:
            kinds.append(GMU if i % 2 == 0 else CROSS)
    return kinds


def build_phi4flash_lm(config: Optional[FFConfig] = None,
                       vocab_size: int = 200064, max_seq_len: int = 8192,
                       batch_size: int = None, hidden: int = 2560,
                       num_heads: int = 40, num_kv_heads: int = 20,
                       num_layers: int = 32, ff_dim: int = 10240,
                       window: int = 512, d_state: int = 16,
                       d_conv: int = 4, expand: int = 2,
                       ln_eps: float = 1e-5, dtype=None, mesh=None,
                       strategy=None) -> FFModel:
    """The op NAMES are the contract serve/arch.py reads the weights
    through: tok_embed / layer{i}_{ln1, ssm | attn | gmu, ln2, ffn} /
    final_ln / lm_head (tied: no weight). `max_seq_len` is the graph's
    sequence length and, there being no positional table, the positions
    the serve engine takes it to serve."""
    cfg = config or FFConfig()
    if dtype is None:
        dtype = jnp.dtype(cfg.compute_dtype)
    bs = batch_size or cfg.batch_size
    head_dim = hidden // num_heads
    kinds = mixer_kinds(num_layers)
    full = full_layer(num_layers)
    ff = FFModel(cfg, mesh=mesh, strategy=strategy)
    tokens = ff.create_tensor((bs, max_seq_len), dtype=jnp.int32,
                              name="tokens")
    # token rows of deviation hidden^-0.5 (0.02 at 2560, the usual
    # start of a tied table): the head is this table, so the logits of
    # a normalised hidden state then have unit deviation; rows of unit
    # variance (models/olmoe.py's choice for an untied table) would
    # give logits of deviation sqrt(hidden) = 50
    t, table = ff.embedding(
        tokens, vocab_size, hidden, aggr="none", name="tok_embed",
        dtype=dtype, emit_table=True,
        kernel_initializer=make_normal(0.0, hidden ** -0.5))
    memory = kv = None
    for i, kind in enumerate(kinds):
        h = ff.layer_norm(t, eps=ln_eps, name=f"layer{i}_ln1")
        if kind == SSM:
            emit = i == full - 1
            m = ff.selective_scan_mixer(
                h, expand * hidden, d_state, d_conv, emit_memory=emit,
                name=f"layer{i}_ssm")
            if emit:
                m, memory = m
        elif kind == GMU:
            m = ff.gated_memory_unit(h, memory, name=f"layer{i}_gmu")
        else:
            m = ff.differential_attention(
                h, num_heads, num_kv_heads, head_dim, layer_index=i,
                window=window if kind == WINDOW else 0,
                kv=kv if kind == CROSS else None,
                kv_from=f"layer{full}_attn" if kind == CROSS else "",
                emit_kv=kind == FULL, eps=ln_eps, name=f"layer{i}_attn")
            if kind == FULL:
                m, *kv = m
        t = ff.add(m, t, name=f"layer{i}_res1")
        h = ff.layer_norm(t, eps=ln_eps, name=f"layer{i}_ln2")
        f = ff.gated_ffn(h, ff_dim, name=f"layer{i}_ffn")
        t = ff.add(f, t, name=f"layer{i}_res2")
    t = ff.layer_norm(t, eps=ln_eps, name="final_ln")
    ff.tied_head(t, table, name="lm_head")
    return ff
