"""All-to-all (DeepSpeed-Ulysses-style) sequence parallelism.

The second SP lowering next to ring attention (ring_attention.py; the
reference has NO sequence axis at all — SURVEY.md 2.4). Instead of
keeping Q resident and rotating K/V shards around the ring, two
`lax.all_to_all`s re-partition the problem: heads scatter over the
`seq` mesh axis while the sequence gathers, so each device runs
STANDARD full-sequence attention for h/n heads, then the output
all-to-alls back to sequence shards.

TPU tradeoff vs ring:
  * all-to-all rides the ICI torus at bisection bandwidth (priced by
    machine_model.all_to_all) and the attention itself is one big
    (s x s) block per head group — full MXU tiles and full
    flash-kernel compatibility, where ring computes n smaller
    (s/n x s/n) blocks with a ppermute between each.
  * memory: scores materialize (b, h/n, s, s) per device unless the
    flash path takes over, so very long sequences still want the ring
    (the `auto` policy in `sp_mode_for` draws that line).
Head-count divisibility (h % n == 0) is required; ring has no such
constraint.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

# score-matrix bytes per device above which `auto` falls back to ring
# attention (which never materializes scores). Mirrors the flash
# heuristic's working-set bound (ops/attention.py).
ALLTOALL_SCORE_BYTES_LIMIT = 2 << 30


def sp_mode_for(cfg_mode: str, *, num_heads: int, seq_size: int,
                batch_local: int, seq_q: int, seq_kv: int) -> str:
    """Resolve the SP attention lowering: explicit "ring"/"alltoall"
    pass through (alltoall still requires head divisibility); "auto"
    picks alltoall when heads divide AND the per-device (sq x sk)
    score matrix fits, else ring. Shared by the executing op
    (ops/attention.py) and the cost model so the search prices what
    actually runs."""
    if num_heads % seq_size != 0:
        return "ring"
    if cfg_mode in ("ring", "alltoall"):
        return cfg_mode
    score_bytes = (4.0 * batch_local * (num_heads // seq_size)
                   * seq_q * seq_kv)
    return "alltoall" if score_bytes <= ALLTOALL_SCORE_BYTES_LIMIT \
        else "ring"


def _a2a(x, axis_name, *, split_axis, concat_axis):
    return lax.all_to_all(x, axis_name, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


def _alltoall_attn_local(q, k, v, *, axis_name, causal, scale,
                         use_flash):
    """Runs inside shard_map: q,k,v are (b, s_local, h, d) seq-shards."""
    # heads scatter, sequence gathers -> (b, s_global, h_local, d)
    q = _a2a(q, axis_name, split_axis=2, concat_axis=1)
    k = _a2a(k, axis_name, split_axis=2, concat_axis=1)
    v = _a2a(v, axis_name, split_axis=2, concat_axis=1)
    # full-sequence blocks mean the flash kernel applies unchanged —
    # the point of this lowering at long s (ring's per-hop blocks are
    # s/n x s/n). Same tri-state + measured gate as the unsharded
    # dispatch (ops/attention.py); the kernel bakes in 1/sqrt(d), so a
    # caller-custom scale falls back to the XLA path.
    from ..kernels.flash_attention import (flash_attention_bshd,
                                           resolve_flash)
    b, sq, h, d = q.shape
    sk = k.shape[1]
    if (abs(scale * math.sqrt(d) - 1.0) < 1e-6
            and resolve_flash(use_flash, b, h, sq, sk, d,
                              jnp.dtype(q.dtype).itemsize)):
        out = flash_attention_bshd(q, k, v, causal=causal)
        return _a2a(out, axis_name, split_axis=1, concat_axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale
    if causal:
        # top-left alignment over the GLOBAL (sq x sk) score block,
        # matching ring attention's cross-attention handling
        qpos = lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        kpos = lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        s = jnp.where((qpos >= kpos)[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p,
                     v.astype(jnp.float32)).astype(q.dtype)
    # sequence scatters back, heads gather -> (b, s_local, h, d)
    return _a2a(out, axis_name, split_axis=1, concat_axis=2)


def alltoall_attention(q, k, v, mesh: Mesh, *, seq_axis: str = "seq",
                       batch_axis: str = "data", causal: bool = False,
                       scale: float = None, use_flash=None):
    """(b, s, h, d) attention with s sharded over `seq_axis`, lowered
    via head-scatter/seq-gather all-to-alls. Exact (softmax over the
    full sequence); numerics match unsharded attention. Requires
    h % axis_size == 0. `use_flash` is the op's tri-state (None=auto /
    True=force / False=never) for the per-device kernel."""
    n = int(mesh.shape[seq_axis])
    if q.shape[2] % n != 0:
        raise ValueError(
            f"alltoall SP needs heads ({q.shape[2]}) divisible by the "
            f"{seq_axis!r} axis size ({n}); use ring attention")
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    batch_ax = batch_axis if batch_axis in mesh.shape else None
    spec = P(batch_ax, seq_axis, None, None)
    fn = partial(_alltoall_attn_local, axis_name=seq_axis,
                 causal=causal, scale=scale, use_flash=use_flash)
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)
