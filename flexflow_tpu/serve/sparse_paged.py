"""Block-sparse attention over a learned selection for the lanes of a
serving step, through the page pool (ops/sparse_attention.py holds the
equations and, on plain arrays, the selection's one definition;
the SPARSE body of serve/mixers.py calls these two).

A selecting model's pool keeps each key/value head's pages as a POOL
LAYER of their own (serve/kv_cache.KVCacheConfig.split_heads; pool
layers `head_layers(i)`, one head of D a page row): each head selects
its own blocks, and a block's pages are then whole rows of the pool —
(slot, D) tiles of 4 KB at the served size — which a row gather moves
as they lie. With the heads packed in one row the compiler cut a head's
columns out of every row through a copy of the pool laid out anew
(2 GiB a leaf at the served size)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..ops.sparse_attention import (F32, _NEG, SparseConfig, group_probs,
                                    mean_keys, select_blocks)
from .kv_cache import KVPool

# lanes whose gathered rows stand in memory at once in the serving
# step: seventeen stretches of a 544-lane step, 32 MB of keys a head
# each (my chip runs, PR 45: at 136 lanes a stretch the scores' products
# take 94 ms of a step, at 32 lanes 35)
LANE_TILE = 32


def stride_keys(pool: KVPool, layer: int, tables, positions,
                sc: SparseConfig):
    """The compressed key a lane's token COMPLETES, from the keys the
    step has just written: the pool and its `layer`
    (one key/value head's), tables (T, pages) each lane's page-table
    row, positions (T,) -> ((T, D) the mean of the lane's page and the
    page before it, (T,) bool whether the lane's token is the last of
    a stride that has its whole kernel). The page size is the
    stride."""
    ps = sc.kernel_stride
    page = positions // ps
    pair = jnp.stack([jnp.maximum(page - 1, 0), page], axis=1)   # (T, 2)
    rows, _ = pool.gather(layer, jnp.take_along_axis(tables, pair, axis=1))
    t = positions.shape[0]
    done = (positions % ps == ps - 1) & (positions >= sc.kernel_size - 1)
    return mean_keys(rows.reshape(t, 2 * ps, -1)), done


def paged_sparse_attention(q, pool: KVPool, layers, page_tables,
                           lane_slots, positions, sc: SparseConfig):
    """Steps 2-5 for the lanes of a serving step, through the pool
    (pages (layer, page, slot, D), selector rows (layer, page, D)):
    q (T, H, D); `layers` the G pool layers of this layer's key/value
    heads; page_tables (slots, pages). Three scopes,
    each over all the lanes: `sparse_score` (every lane against its
    OWN sequence's compressed keys: stride j's key is in the page of
    logical index j + 1, `stride_keys`), `sparse_select` (block
    scores, forced blocks, top-k) and `sparse_attn` (each lane gathers
    its OWN selected blocks' pages). The two that gather take `LANE_TILE`
    lanes at a time, one stretch after another in the program itself
    (a loop's operations would carry no scope in a device trace), so
    the gathered rows of all lanes never stand in memory at once; no
    fetch is shared between lanes, of compressed
    keys or of blocks (a later change's lever: a grouped product over
    the lanes of a run, `jax.lax.ragged_dot`, was tried for the scores
    and is no shortcut on this compiler). A lane under `dense_len` gets
    a finite answer nobody reads (the step takes those lanes' from the
    dense call). -> o (T, H, D) in q's dtype."""
    t, h, d = q.shape
    g = len(layers)
    i = h // g
    ps = pool.k.shape[2]
    pp = page_tables.shape[1]
    bp = sc.block_size // ps                 # pages a block
    if ps != sc.kernel_stride or pp % bp or pool.heads != 1:
        raise ValueError(
            f"the selector's stride ({sc.kernel_stride}) is the page size "
            f"({ps}), a table ({pp} pages) holds whole blocks, and a pool "
            f"layer holds one key/value head ({pool.heads})")
    scope = jax.named_scope

    def by_tile(fn, *arrays):
        """fn over the lanes, LANE_TILE of them at a time."""
        return jnp.concatenate([
            fn(*(a[lo:lo + LANE_TILE] for a in arrays))
            for lo in range(0, t, LANE_TILE)])

    def score(qt, slot, pos):                                # (R, ...)
        r = qt.shape[0]
        strides = jnp.roll(jnp.take(page_tables, slot, axis=0), -1, axis=1)
        qg = qt.reshape(r, g, i, d)
        s = jnp.stack([jnp.einsum(
            "rid,rjd->rij", qg[:, j],
            pool.selector_rows(layer, strides).astype(qt.dtype),
            preferred_element_type=F32) for j, layer in enumerate(layers)],
            axis=1) / math.sqrt(d)                           # (R, G, I, pp)
        return group_probs(s, pos, sc)                       # (R, G, pp)

    def attend_head(qh, layer, tables, pos, blk, ok):
        """One key/value head of a trip: qh (R, I, D), blk, ok (R, K)."""
        r = qh.shape[0]
        col = (blk[..., None] * bp + jnp.arange(bp)).reshape(r, -1)
        ks, vs = pool.gather(layer, jnp.take_along_axis(tables, col, axis=1))
        n = col.shape[-1] * ps
        ks, vs = ks.reshape(r, n, d), vs.reshape(r, n, d)    # (R, N, D)
        key_pos = (col[..., None] * ps + jnp.arange(ps)).reshape(r, n)
        seen = ((key_pos <= pos[:, None])
                & jnp.repeat(ok, bp * ps, axis=-1))[:, None, :]
        a = jnp.einsum("rid,rnd->rin", qh, ks,
                       preferred_element_type=F32) / math.sqrt(d)
        a = jnp.where(seen, a, _NEG)
        p = jnp.where(seen, jnp.exp(a - jnp.max(a, axis=-1, keepdims=True)),
                      0.0)
        l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        if vs.dtype == jnp.bfloat16:
            # bf16 values stay as gathered: p in two bf16 halves (16
            # bits of mantissa), as the paged kernel weighs its V
            hi = p.astype(jnp.bfloat16)
            lo = (p - hi.astype(F32)).astype(jnp.bfloat16)
            return sum(jnp.einsum("rin,rnd->rid", part, vs,
                                  preferred_element_type=F32)
                       for part in (hi, lo)) / l
        return jnp.einsum("rin,rnd->rid", p, vs.astype(F32)) / l

    def attend(qt, slot, pos, blk, ok):
        r = qt.shape[0]
        tables = jnp.take(page_tables, slot, axis=0)
        qg = qt.reshape(r, g, i, d)
        o = jnp.stack([attend_head(qg[:, j], layer, tables, pos, blk[:, j],
                                   ok[:, j])
                       for j, layer in enumerate(layers)], axis=1)
        return o.reshape(r, h, d).astype(qt.dtype)

    with scope("sparse_score"):
        probs = by_tile(score, q, lane_slots, positions)     # (T, G, pp)
    with scope("sparse_select"):
        blocks, chosen = select_blocks(probs, positions, sc)  # (T, G, K)
    with scope("sparse_attn"):
        return by_tile(attend, q, lane_slots, positions, blocks, chosen)
