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

# lanes of one STRETCH: what gathers a copy of rows a lane takes the
# step's lanes a stretch at a time (seventeen of a 544-lane step), so
# the rows gathered for all lanes never stand in memory at once: 32 MB
# of compressed keys a head (my chip runs, PR 45: at 136 lanes a
# stretch the scores' products take 94 ms of a step, at 32 lanes 35).
# Since PR 55 the scores gather so only for the STRAY lanes (below);
# the lanes of a stretch that hold its main sequence share one fetch
# of that sequence's keys, 1 MB a head, and their 32 lanes x 16 heads
# are the 512 rows of ONE product
LANE_TILE = 32
# stray lanes a trip of the scores' second pass: a step of long chunks
# holds a few decode lanes, and the first trip runs whatever is live
# (my chip runs, PR 55: the scores 1.04 ms a layer at 8 lanes a trip,
# 1.74 at 32, with 8 strays; 5.1 | 5.6 with 256)
STRAY_TILE = 8


def main_slots(lane_slots, live, xp=jnp, tile: int = LANE_TILE):
    """The sequence each stretch of `tile` lanes scores against ONE
    fetch of compressed keys, and the lanes it leaves over:
    lane_slots (T,), live (T,) bool -> (main (n,) the slot most of a
    stretch's live lanes hold (the first such lane's on a tie), stray
    (T,) bool the live lanes of any other slot). A dead lane's answer
    is read by nobody, so it is no stray and a chunk's last stretch or
    an empty one has none. numpy where the host counts what the step
    moves (serve/mixers.step_counts), jax.numpy where the step itself
    follows the rule."""
    t = lane_slots.shape[0]
    pad = -t % tile
    slots = xp.pad(lane_slots, (0, pad)).reshape(-1, tile)
    alive = xp.pad(live, (0, pad)).reshape(-1, tile)
    # for each lane, the live lanes of its stretch that hold its slot
    votes = ((slots[:, :, None] == slots[:, None, :])
             & alive[:, None, :]).sum(axis=2)
    main = xp.take_along_axis(
        slots, xp.argmax(xp.where(alive, votes, 0), axis=1)[:, None], axis=1)
    stray = alive & (slots != main)
    return main[:, 0], stray.reshape(-1)[:t]


def stray_batches(stray, xp=jnp, tile: int = STRAY_TILE):
    """The trips of `tile` stray lanes the scores make: one always (it
    stands in the program unconditionally), then as many as the stray
    lanes past it fill."""
    return xp.maximum(1, -(-stray.sum() // tile))


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


def lane_probs(q, pool: KVPool, layers, page_tables, lane_slots, positions,
               live, sc: SparseConfig):
    """Step 2 and the first half of step 3 for the lanes of a serving
    step: every lane against its own sequence's compressed keys
    (stride j's key is in the page of logical index j + 1,
    `stride_keys`) -> P (T, G, pages) f32 (`group_probs`), in two
    passes chosen by `main_slots`. First every stretch against the
    keys of its MAIN sequence, fetched once a head — (pages, D), for
    all the stretches in one gather — and met by all the stretch's
    heads in one (R * I, D) x (D, pages) product. Then the STRAY lanes,
    those of another sequence than their stretch's main one (decode
    lanes beside a chunk, the shorter side where two chunks meet),
    each against its own gathered copy, a stretch of them at a time:
    the first stretch of strays stands in the program as it is, any
    further ones in a loop of as many trips as they fill (none in a
    step of long chunks; a conditional a stretch was tried first and
    its branch ran the per-lane gather 3.6 times slower than the same
    operations outside one). The same terms summed in f32 either
    way."""
    t, h, d = q.shape
    g = len(layers)
    i = h // g
    main, stray = main_slots(lane_slots, live)
    table = pool.selector_table()

    def keys(layer, slot):
        """The compressed keys of the table row(s) `slot`, a stride's
        page first: slot.shape + (pp, D)."""
        strides = jnp.roll(jnp.take(page_tables, slot, axis=0), -1, axis=-1)
        return pool.selector_rows(table, layer, strides).astype(q.dtype)

    def probs_of(form, rows, qt, pos, keys_of):
        """form over each head's queries, laid out as `rows`, and its
        keys -> (R, G, pp); a head at a time, so the heads' scores (R,
        I, pp) f32 are never stacked."""
        r = qt.shape[0]
        qg = qt.reshape(r, g, i, d)
        return jnp.concatenate([group_probs(jnp.einsum(
            form, qg[:, j].reshape(rows), keys_of(j),
            preferred_element_type=F32).reshape(r, 1, i, -1)
            / math.sqrt(d), pos, sc) for j in range(g)], axis=1)

    once = [keys(layer, main) for layer in layers]           # (n, pp, D)
    probs = jnp.concatenate([
        probs_of("nd,jd->nj", (-1, d), q[lo:lo + LANE_TILE],
                 positions[lo:lo + LANE_TILE], lambda j: once[j][n])
        for n, lo in enumerate(range(0, t, LANE_TILE))])

    # the stray lanes first, `t` (no lane) after them
    order = jnp.pad(jnp.nonzero(stray, size=t, fill_value=t)[0],
                    (0, -t % STRAY_TILE), constant_values=t)

    def own(batch, probs):
        lanes = jax.lax.dynamic_slice(order, (batch * STRAY_TILE,),
                                      (STRAY_TILE,))
        at = jnp.minimum(lanes, t - 1)
        slot = lane_slots[at]
        return probs.at[lanes].set(probs_of(
            "rid,rjd->rij", (-1, i, d), q[at], positions[at],
            lambda j: keys(layers[j], slot)), mode="drop")

    return jax.lax.fori_loop(1, stray_batches(stray), own, own(0, probs))


def paged_sparse_attention(q, pool: KVPool, layers, page_tables,
                           lane_slots, positions, live, sc: SparseConfig):
    """Steps 2-5 for the lanes of a serving step, through the pool
    (pages (layer, page, slot, D), selector rows (layer, page, D)):
    q (T, H, D); `layers` the G pool layers of this layer's key/value
    heads; page_tables (slots, pages); live (T,) bool the lanes that
    hold a token. Three scopes, each over all the lanes: `sparse_score`
    (`lane_probs`), `sparse_select` (block scores, forced blocks,
    top-k) and `sparse_attn` (each lane gathers its OWN selected
    blocks' pages). The two that gather take `LANE_TILE` lanes at a
    time, so the gathered rows of all lanes never stand in memory at
    once. The scores share a sequence's compressed keys among the
    lanes of a stretch that hold its main sequence; the selected
    blocks' fetch is shared by no two lanes (a later change's lever;
    a grouped product over the lanes of a run, `jax.lax.ragged_dot`,
    was tried for the scores and is no shortcut on this compiler). A
    lane under `dense_len` gets a finite answer nobody reads (the step
    takes those lanes' from the dense call). -> o (T, H, D) in q's
    dtype."""
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
        probs = lane_probs(q, pool, layers, page_tables, lane_slots,
                           positions, live, sc)              # (T, G, pp)
    with scope("sparse_select"):
        blocks, chosen = select_blocks(probs, positions, sc)  # (T, G, K)
    with scope("sparse_attn"):
        return by_tile(attend, q, lane_slots, positions, blocks, chosen)
