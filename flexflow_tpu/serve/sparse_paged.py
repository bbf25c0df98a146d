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

from ..kernels.paged_ragged_v2 import (JNP, PALLAS_INTERPRET, Q_ROWS,
                                       build_select_lists,
                                       paged_attention_ragged_v2,
                                       select_call_tiles, select_counts)
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


def selection_geometry(sc: SparseConfig, page_size: int, pages_per_seq: int,
                       block_pages: int, num_lanes: int,
                       slot_changes=None):
    """How the selected blocks go through the paged kernel at a pool
    geometry: (`block_pages`, the kernel's kv-block cut to whole
    selection blocks that divide the table; the lanes a call takes and
    a call's bound on its list — `select_call_tiles`, the proof)."""
    sp = sc.block_size // page_size          # pages a selection block
    bp = max(sp, block_pages // sp * sp)
    while pages_per_seq % bp:
        bp -= sp
    tiles, bound = select_call_tiles(
        num_lanes, pages_per_seq, bp, sp, min(sc.topk, pages_per_seq // sp),
        slot_changes=slot_changes)
    return bp, tiles * Q_ROWS, bound


def whole_calls(xp, a, call_lanes: int):
    """Lane array a (T, ...) padded to whole calls of `call_lanes`
    lanes, the lanes past T zeros: dead lanes on slot 0, as a step's
    inactive ones are."""
    pad = -a.shape[0] % call_lanes
    return xp.concatenate([a, xp.zeros((pad,) + a.shape[1:], a.dtype)])


def attend_selected(q, pool: KVPool, layers, page_tables, lane_slots,
                    positions, live, blocks, chosen, sc: SparseConfig, *,
                    impl: str = JNP, block_pages: int, call_lanes: int,
                    max_items: int):
    """Step 5 for the lanes of a serving step: every live lane at or
    past `dense_len` attends the tokens at or before its own in the
    blocks it chose (blocks, chosen (T, G, K): `select_blocks`), in one
    of two forms by `impl` (`paged_sparse_attention` says which; the
    other arguments are its own). -> (o (T, H, D) in f32, (2,) int32
    what the selection's calls walk: their grid steps summed over the
    heads, and the selection blocks those items fetch). o is the f32
    accumulator over the f32 sum as it is: the gate that follows reads
    f32, so no rounding to bf16 stands between them (the served cell's
    `logit_rms_err` read 0.0279 with one and 0.0270 without: PERF.md
    section 6, PR 57)."""
    t, h, d = q.shape
    g = len(layers)
    i = h // g
    ps = pool.k.shape[2]
    pp = page_tables.shape[1]
    bp = sc.block_size // ps                 # pages a block
    s_words = block_pages // bp
    # the lanes that select, and every lane array in whole calls
    rows, slots, picked, chose = (
        whole_calls(jnp, a, call_lanes) for a in (
            live & (positions >= sc.dense_len), lane_slots, blocks, chosen))
    calls = range(0, rows.shape[0], call_lanes)

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

    def attend(lo):
        """The twin's trip: LANE_TILE lanes, each on its own gathered
        copy of its blocks, so the rows gathered for all lanes never
        stand in memory at once."""
        cut = slice(lo, lo + LANE_TILE)
        qg = q[cut].reshape(-1, g, i, d)
        tables = jnp.take(page_tables, lane_slots[cut], axis=0)
        o = jnp.stack([attend_head(qg[:, j], layer, tables, positions[cut],
                                   blocks[cut, j], chosen[cut, j])
                       for j, layer in enumerate(layers)], axis=1)
        return o.reshape(-1, h, d)

    if impl == JNP:
        # the twin builds no list: it counts what one would hold
        walked = sum(
            jnp.stack(select_counts(
                jnp, picked[lo:lo + call_lanes, j],
                chose[lo:lo + call_lanes, j], rows[lo:lo + call_lanes],
                slots[lo:lo + call_lanes], num_blocks=pp // bp,
                mask_words=s_words))
            for lo in calls for j in range(g))
        return (jnp.concatenate([attend(lo)
                                 for lo in range(0, t, LANE_TILE)]),
                walked * jnp.array([1, s_words], jnp.int32))
    # the list form: a call a key/value head and stretch of `call_lanes`
    # lanes, each on a list of that head's selection over those lanes
    lens = whole_calls(jnp, positions + 1, call_lanes)
    works = build_select_lists(
        picked, chose, rows, slots,
        whole_calls(jnp, jnp.take(page_tables, lane_slots, axis=0),
                    call_lanes),
        lens, block_pages=block_pages, select_pages=bp,
        call_lanes=call_lanes, max_items=max_items)
    qs = whole_calls(jnp, q, call_lanes)
    outs, walked = [], jnp.zeros(2, jnp.int32)
    for lo, of_call in zip(calls, works):
        cut = slice(lo, lo + call_lanes)
        heads = []
        for j, (layer, (work, real)) in enumerate(zip(layers, of_call)):
            k_pages, v_pages, _, _, base = pool.layer(layer)
            heads.append(paged_attention_ragged_v2(
                qs[cut, j * i:(j + 1) * i], k_pages, v_pages, page_tables,
                slots[cut], lens[cut], scale=1.0 / math.sqrt(d), work=work,
                page_base=base, use_pallas=True,
                interpret=impl == PALLAS_INTERPRET, out_dtype=F32))
            walked = walked + jnp.stack([work.count, s_words * real])
        outs.append(jnp.concatenate(heads, axis=1))
    return jnp.concatenate(outs)[:t], walked


def paged_sparse_attention(q, pool: KVPool, layers, page_tables,
                           lane_slots, positions, live, sc: SparseConfig,
                           *, impl: str = JNP, block_pages: int,
                           call_lanes: int, max_items: int):
    """Steps 2-5 for the lanes of a serving step, through the pool
    (pages (layer, page, slot, D), selector rows (layer, page, D)):
    q (T, H, D); `layers` the G pool layers of this layer's key/value
    heads; page_tables (slots, pages); live (T,) bool the lanes that
    hold a token. Three scopes, each over all the lanes: `sparse_score`
    (`lane_probs`), `sparse_select` (block scores, forced blocks,
    top-k) and `sparse_attn` (`attend_selected`), the selected blocks'
    keys and values in one of two forms by `impl`:

    the LIST form (the paged kernel, compiled or interpreted): for each
    key/value head a work list MADE OF THE SELECTION
    (kernels/paged_ragged_v2.build_select_list) — an item a run of a
    tile's rows and a kv-block of `block_pages` pages that holds a
    block some row of the run chose, fetched ONCE for all of them, a
    row-mask word a selection block — and one masked call of the kernel
    on that head's pool layer where it lies (`KVPool.layer`), the
    step's lanes `call_lanes` a call (`selection_geometry`: a call's
    list, as long as its proven bound `max_items`, has to fit the
    kernel's SMEM budget). Only the live
    lanes at or past `dense_len` have bits: a dead lane, a lane under
    it and a tile of neither make one item on the sink page, and their
    rows come out 0;

    the per-lane TWIN (`impl` "jnp": the oracle the list form is held
    to, as `_ragged_jnp` is the dense call's): each lane gathers its
    OWN selected blocks' pages, `LANE_TILE` lanes at a time.

    The scores share a sequence's compressed keys among the lanes of a
    stretch that hold its main sequence (`lane_probs`). A lane under
    `dense_len` gets a finite answer nobody reads (the step takes those
    lanes' from the dense call). -> `attend_selected`'s two."""
    ps = pool.k.shape[2]
    pp = page_tables.shape[1]
    bp = sc.block_size // ps                 # pages a block
    if ps != sc.kernel_stride or pp % bp or pool.heads != 1:
        raise ValueError(
            f"the selector's stride ({sc.kernel_stride}) is the page size "
            f"({ps}), a table ({pp} pages) holds whole blocks, and a pool "
            f"layer holds one key/value head ({pool.heads})")
    scope = jax.named_scope
    with scope("sparse_score"):
        probs = lane_probs(q, pool, layers, page_tables, lane_slots,
                           positions, live, sc)              # (T, G, pp)
    with scope("sparse_select"):
        blocks, chosen = select_blocks(probs, positions, sc)  # (T, G, K)
    with scope("sparse_attn"):
        return attend_selected(
            q, pool, layers, page_tables, lane_slots, positions, live,
            blocks, chosen, sc, impl=impl, block_pages=block_pages,
            call_lanes=call_lanes, max_items=max_items)
