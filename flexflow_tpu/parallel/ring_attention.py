"""Ring attention — sequence/context parallelism over ICI.

The reference has NO sequence-parallel axis (SURVEY.md 2.4: "SP/CP ...
absent"); this is a designed-in new capability. Q, K, V are sharded over
the mesh `seq` axis; each device keeps its Q shard resident and the K/V
shards rotate around the ring via `lax.ppermute`, with online-softmax
(flash-style m/l rescaling) accumulation so the full score matrix never
materializes. Per-step compute is (s_local x s_local) — XLA overlaps the
ppermute with the block matmuls.

Causal masking uses *global* positions derived from `lax.axis_index`, so
results are exactly those of unsharded top-left-causal attention.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map


def _block_scores(q, k, scale):
    # q: (b, sq, h, d), k: (b, sk, h, d) -> (b, h, sq, sk) fp32
    return jnp.einsum("bqhd,bkhd->bhqk", q, k,
                      preferred_element_type=jnp.float32) * scale


def _ring_attn_local(q, k, v, *, axis_name, causal, scale):
    """Runs inside shard_map: q,k,v are local seq-shards."""
    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    sk = k.shape[1]

    qf = q.astype(jnp.float32)
    m = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l = jnp.zeros((b, h, sq), jnp.float32)
    acc = jnp.zeros((b, sq, h, d), jnp.float32)

    def step(carry, step_idx):
        m, l, acc, k_cur, v_cur = carry
        # shard currently held = (my_idx - step_idx) mod axis_size
        src = (my_idx - step_idx) % axis_size
        s = _block_scores(qf, k_cur.astype(jnp.float32), scale)
        if causal:
            qpos = (my_idx * sq
                    + lax.broadcasted_iota(jnp.int32, (sq, sk), 0))
            kpos = (src * sk
                    + lax.broadcasted_iota(jnp.int32, (sq, sk), 1))
            s = jnp.where((qpos >= kpos)[None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # guard fully-masked rows: keep m finite so exp() stays 0, not nan
        m_safe = jnp.where(jnp.isinf(m_new), 0.0, m_new)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(jnp.isinf(m_new)[..., None], 0.0, p)
        alpha = jnp.where(jnp.isinf(m), 0.0, jnp.exp(m - m_safe))
        l_new = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p, v_cur.astype(jnp.float32))
        acc_new = acc * alpha.transpose(0, 2, 1)[..., None] + pv
        # rotate k/v one hop around the ring
        perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (m_new, l_new, acc_new, k_nxt, v_nxt), None

    (m, l, acc, _, _), _ = lax.scan(
        step, (m, l, acc, k, v), jnp.arange(axis_size))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = acc / l_safe.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_attention(q, k, v, mesh: Mesh, *, seq_axis: str = "seq",
                   batch_axis: str = "data", causal: bool = False,
                   scale: float = None):
    """(b, s, h, d) attention with s sharded over `seq_axis`.

    Call under jit with global arrays; shard_map partitions internally.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    batch_ax = batch_axis if batch_axis in mesh.shape else None
    spec = P(batch_ax, seq_axis, None, None)
    fn = partial(_ring_attn_local, axis_name=seq_axis, causal=causal,
                 scale=scale)
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)
