"""Ragged paged attention v2 (the "Ragged Paged Attention" TPU design,
PAPERS.md arxiv 2604.15464) + quantized KV-page support.

The PR-3 kernel (`flash_attention._paged_ragged_pallas`) dispatches a
(T, pages_per_seq) grid: every lane visits every page-table column, one
page per grid step, full masked compute at every step. Correct, but
first-cut — three structural costs the mature design removes:

  * PER-LANE DISPATCH: a lane resident for 1 page still burns
    pages_per_seq grid steps of full (H, page_size) softmax work; the
    masking throws the work away but the VPU/MXU already spent it.
  * ONE PAGE PER STEP: the DMA unit is a single page
    (page_size, H, D) — typically a few KB — so short blocks bound the
    kernel on DMA issue overhead, not bandwidth.
  * UNPACKED HEAD LAYOUT: blocks arrive as (page_size, H, D); for
    small head_dim (D < 128 lanes) the trailing dim wastes most of
    every VMEM tile ((8,128) f32 tiling).

This module rebuilds the kernel along the paper's lines:

  * A WORK LIST instead of a lanes x columns grid. Consecutive lanes
    [g * Q_ROWS, (g + 1) * Q_ROWS) are a TILE; the lanes of a tile that
    name one slot are a RUN (a chunk's tokens, a decode lane with its
    draft tokens, one decode lane); a work item is (run, kv-block). The
    list is built from the lane arrays on the device, once a step
    (`build_work_list`; the engine's 24 layers share it), and reaches
    the kernel by scalar prefetch. The grid's length is the LIST's own
    (`WorkList.count`, a device scalar made with the list: the live
    lanes' items and one a tile of inactive lanes), not lanes x table
    columns and not the plan's worst case: a call walks the step's
    work. The ARRAYS are as long as a static bound on the list
    (`max_work_items`: (tiles + slot changes) x kv-blocks for a caller
    that can bound the changes, lanes x kv-blocks for any other) — the
    proof that no item is lost, and what SMEM has to hold; their
    entries past the list's end repeat the last item and do nothing
    (the Pallas interpreter, which takes no traced bound, walks them).
  * SEVERAL PAGES AN ITEM: a kv-block covers `block_kv_pages` pages —
    one BlockSpec per page slot, so Mosaic pipelines their DMAs — sized
    so that fetching it takes longer than a grid step costs.
  * ONE FETCH FOR ALL THE ROWS OF A RUN: the rows of a run attend the
    same K/V block in one item. Every row is masked by its own
    `lane_lens` entry; grouping decides what is fetched together, never
    what a row may see.
  * A BODY FOR ONE LANE: where a tile's product is 128 rows or more
    (four query heads a key/value head), the item of a one-lane run —
    a decode lane — computes on the 16 rows that hold the lane, chosen
    per item at run time inside the one call (`has_short_body`).
  * RAGGED SKIPPING: a run has items only for the kv-blocks that start
    below its longest lane, and a page slot past its last live page
    keeps the page it held (no DMA, positions masked).
  * HEAD PACKING: page blocks stream as (page_size, H*D) rows, which
    fills 128-lane VMEM tiles where (page_size, H, D) tiling padded
    D < 128 up to 128, and STAY packed — Mosaic cannot split a lane
    dimension. A head-PACKED pool (serve/kv_cache.KVPool: its leaves
    are stored as those rows) is read IN PLACE: the call takes the
    whole leaf as rows of all its layers and the layer's first row as
    a scalar-prefetch operand (`page_base`), added to every page the
    index maps fetch — no slice, no copy, one trace for all the
    layers. Of an UNPACKED pool (OPT's, OLMoE's) the engine hands a
    layer's slice, and XLA copies the slab out and lays it out anew
    for every call: PERF.md section 5, ROADMAP S2. The per-head
    products are MXU matmuls over a run's rows, one 128-lane slab of
    the packed axis at a time (see the kernel section). Every page
    format and head size takes this path; only the operands'
    precision differs (below).
  * TUNABLE KV-BLOCK SHAPES: `block_kv` (tokens per work item; FFConfig
    serve_attn_block_kv / --serve-attn-block-kv) with an
    autotune-by-shape table supplying defaults: measured entries for
    the geometries the repo serves, the analytic rule for the rest
    (`choose_block_kv`); `register_block_kv` overrides either.
  * QUANTIZED KV PAGES: int8 K/V pages ride with per-page scale arrays
    (one f32 scale per head per in-page slot — see serve/kv_cache.py
    for why scales are per-slot, not per-whole-page); the kernel DMAs
    the int8 block + its scale rows and applies the scales to the
    scores and the probabilities (algebraically the dequantized
    product) inside the online-softmax accumulation. bf16 pages need
    no scales (values upcast exactly like v1's bf16 handling).

Numerics contract: the jnp path (`_ragged_jnp`: gather, one
dot_general, single-pass softmax, divide after the matmul) is on fp32
bit-identical, lane by lane, to contiguous full-prefill attention over
the same K/V (tests/test_serve.py, tests/test_kv_quant.py) — the oracle
every serve parity test is built on. The Pallas kernel is the same online softmax summed
in another order, statistics and accumulator in f32. With f32 q or f32
pages every product is f32 (HIGHEST precision on the MXU); with bf16 q
and bf16 / int8 / fp8 pages the operands of q.k are exact in bf16 and
the probabilities go to the MXU as two bf16 halves (16 bits), both
accumulated in f32. It agrees with the jnp path to f32 rounding on
every page format (2e-6 in the interpreter, tests/test_kv_quant.py; on
the chip tests_tpu/test_serve_tpu.py and chip_smoke.py state their
tolerances); the QUANTIZATION error itself is gated by the
bounded-error + greedy-parity tests (tests/test_kv_quant.py).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu


# --------------------------------------------------------- quantization
INT8_QMAX = 127.0


def _qmax_for(dtype) -> float:
    """Largest representable magnitude of a page storage format: 127
    for int8, finfo.max (448) for float8_e4m3fn. Rows scale their amax
    to this value so the full dynamic range of the format is used."""
    dtype = jnp.dtype(dtype)
    if dtype == jnp.dtype(jnp.int8):
        return INT8_QMAX
    return float(jnp.finfo(dtype).max)


def quantize_kv_rows(x, dtype=jnp.int8):
    """Per-row symmetric quantization of K/V vectors into a narrow
    page storage format (int8 or float8_e4m3fn — the fp8 path reuses
    this machinery verbatim, scales and all).

    x (..., D) float -> (q (..., D) `dtype`, scales (...) f32) with
    q = round(x / scale), scale = amax(|x|, -1) / qmax (127 for int8,
    448 for e4m3). An all-zero row gets scale 0 and q 0 (dequant
    reproduces the zeros exactly) — the sink-page / padding-lane case.
    Each row quantizes independently of every other token, which is
    what makes the serving path's quantized content invariant to chunk
    boundaries, preemption replays, and speculative rollbacks
    (serve/engine.py). fp8 rows round at the dtype cast (the scaled
    values are <= the format's max finite by construction, so the
    saturating e4m3fn cast never produces NaN)."""
    dtype = jnp.dtype(dtype)
    qmax = _qmax_for(dtype)
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = amax / qmax
    # rows with scale 0 are all-zero: divide by 1 instead and the
    # zeros quantize to 0 regardless
    safe = jnp.where(scale > 0, scale, 1.0)
    y = xf / safe[..., None]
    if dtype == jnp.dtype(jnp.int8):
        q = jnp.clip(jnp.rint(y), -INT8_QMAX, INT8_QMAX)
    else:
        q = y  # the cast below rounds to the format's grid
    return q.astype(dtype), scale


def dequantize_kv(q, scale):
    """Inverse of quantize_kv_rows: q (..., D) int8 * scale (...) f32
    broadcast over D. Exactly the in-register dequant the kernel runs."""
    return q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)


# --------------------------------------------- kv-block shape autotuning
# One work item is one grid step of a pipelined Mosaic call, and a grid
# step costs a fixed time whatever it does (measured on a v5e: PERF.md
# section 6, PR 25). A block is sized so that FETCHING it takes longer
# than that: STEP_FETCH_BYTES of K+V per item (1 MiB = 1.3 us at 819
# GB/s), never more than MAX_BLOCK_TOKENS (one page operand per page of
# the block: the pipeline's bookkeeping grows with them), with the
# resident K/V (+ scale) blocks under VMEM_BUDGET_BYTES (Pallas
# double-buffers them, hence the /2).
STEP_FETCH_BYTES = 1024 * 1024
MAX_BLOCK_TOKENS = 256
VMEM_BUDGET_BYTES = 8 * 1024 * 1024

# Query rows of one work item: consecutive lanes [g * Q_ROWS, (g + 1) *
# Q_ROWS) form a TILE; the lanes of a tile that belong to one sequence
# share every K/V block they attend. Two packed bf16 tiles of rows: the
# sweep's best of 8, 16, 32 and 64 at the serving cell's geometry (an
# item's time is set by moving K and V through the MXU, not by its
# rows; more rows a tile are fewer tiles, and past 32 the decode
# lanes' items slow down).
Q_ROWS = 32

# (page_size, num_heads, head_dim, kv_itemsize, pages_per_seq) ->
# block_kv tokens. Measured entries (the sweep of PERF.md section 6,
# PR 25, at the geometries the repo serves) come first; every other
# geometry is seeded analytically on first use. register_block_kv
# overrides either — the "autotune-by-shape table".
_BLOCK_KV_TABLE: Dict[Tuple[int, int, int, int, int], int] = {
    # OPT-1.3B on one chip: pages of 16, 32 heads of 64, bf16, 2048
    # positions (benchmark/configs/opt-1.3b.json)
    (16, 32, 64, 2, 128): 256,
}


def register_block_kv(page_size: int, num_heads: int, head_dim: int,
                      kv_itemsize: int, pages_per_seq: int,
                      block_kv: int) -> None:
    """Pin a measured kv-block shape for a geometry (overrides the
    analytic default for every later choose_block_kv on that shape)."""
    _BLOCK_KV_TABLE[(page_size, num_heads, head_dim, kv_itemsize,
                     pages_per_seq)] = int(block_kv)


def choose_block_kv(page_size: int, pages_per_seq: int, num_heads: int,
                    head_dim: int, kv_itemsize: int = 4) -> int:
    """KV tokens per work item for a pool geometry: the autotune table
    entry if one is registered, else the analytic pick — the smallest
    whole-page multiple whose K+V fetch reaches STEP_FETCH_BYTES,
    capped by MAX_BLOCK_TOKENS, the VMEM budget and the table width.
    Always a multiple of page_size and >= one page."""
    key = (page_size, num_heads, head_dim, kv_itemsize, pages_per_seq)
    got = _BLOCK_KV_TABLE.get(key)
    if got is not None:
        return got
    per_tok = 2 * num_heads * head_dim * kv_itemsize  # K + V
    if kv_itemsize == 1:  # quantized (int8/fp8) pages also stream
        per_tok += 2 * num_heads * 4  # their f32 scale rows
    want = -(-STEP_FETCH_BYTES // (per_tok * page_size))
    cap = min((VMEM_BUDGET_BYTES // 2) // (per_tok * page_size),
              MAX_BLOCK_TOKENS // page_size)
    ppb = max(1, min(want, cap, pages_per_seq))
    block = ppb * page_size
    _BLOCK_KV_TABLE[key] = block
    return block


def max_work_items(num_lanes: int, pages_per_seq: int,
                   block_kv_pages: int, q_rows: int = Q_ROWS,
                   slot_changes: Optional[int] = None,
                   window_blocks: int = 0) -> int:
    """The most work items any lane arrays of this geometry can make:
    the static length of the list's arrays, and the most a call's grid
    walks.

    A RUN is a maximal stretch of consecutive lanes of one tile that
    name one slot; it has one item per kv-block up to its longest
    lane, at most ceil(pages_per_seq / block_kv_pages). A run starts at
    a tile's first lane or where the slot changes from one lane to the
    next, so there are at most tiles + `slot_changes` of them; a caller
    that cannot bound the changes (None) gets one run a lane, the safe
    lanes x blocks. Under a window a run has at most `window_blocks`
    items (`window_block_bound`), whatever the table's width."""
    nb = -(-pages_per_seq // max(1, min(block_kv_pages, pages_per_seq)))
    if window_blocks:
        nb = min(nb, window_blocks)
    tiles = -(-num_lanes // q_rows)
    runs = tiles * q_rows if slot_changes is None \
        else min(tiles * q_rows, tiles + slot_changes)
    return runs * nb


def window_block_bound(window: int, block_kv: int,
                       q_rows: int = Q_ROWS) -> int:
    """The most kv-blocks a run can have items for under a window: its
    rows' lengths span at most q_rows - 1, so the keys any of them sees
    are window + q_rows - 1 consecutive positions."""
    return -(-(window + q_rows - 2) // block_kv) + 1


def ragged_dispatch_passes(num_lanes: int, pages_per_seq: int,
                           block_kv_pages: int, q_rows: int = Q_ROWS,
                           slot_changes: Optional[int] = None
                           ) -> Dict[str, int]:
    """Grid-step accounting for the serve bench: the v1 kernel runs one
    grid step per (lane, page); v2's grid is at most `max_work_items`
    long — (tiles + slot changes) x kv-blocks for a caller that bounds
    the changes, lanes x kv-blocks otherwise — and as long as the
    step's list."""
    return {"v1": num_lanes * pages_per_seq,
            "v2": max_work_items(num_lanes, pages_per_seq,
                                 block_kv_pages, q_rows, slot_changes)}


def _kv_heads(k_pages, head_dim: int) -> int:
    """Key/value heads of pages (page, slot, head, dim) or, head-packed
    as a packed pool stores them, (row, slot, head * dim)."""
    return k_pages.shape[2] // (head_dim if k_pages.ndim == 3 else 1)


# ------------------------------------------------------------ jnp paths
def _ragged_jnp(q, k_pages, v_pages, page_tables, lane_slots, lane_lens,
                scale, k_scales=None, v_scales=None, window=0,
                page_base=None):
    """Vectorized fallback over the flattened ragged layout.

    Gathers each lane's pages (int8 gathers move 1/4 the bytes of f32),
    dequantizes, and runs one dot_general, a masked single-pass
    softmax and the divide after the matmul — so fp32 outputs are
    bit-identical to contiguous full-prefill attention per lane (the
    oracle every serve parity test is built on), and the one jnp twin
    the Pallas kernel is held to. Grouped heads (q has `group` times
    the pages' heads: query head j reads key/value head j // group) and
    a `window` (a lane sees its last `window` positions) take the same
    path; one group and no window trace what they always did. Rows of
    many layers (`page_base`, packed pages (rows, ps, H*D)): the base
    is added to the lanes' page tables, and the rows gathered are the
    layer's own."""
    b, hq, d = q.shape
    h = _kv_heads(k_pages, d)
    # head-packed rows: a free view here
    k_pages, v_pages = (a.reshape(a.shape[:2] + (h, d))
                        for a in (k_pages, v_pages))
    group = hq // h
    ps = k_pages.shape[1]
    lane_tables = jnp.take(page_tables, lane_slots, axis=0)  # (T, pp)
    if page_base is not None:
        lane_tables = lane_tables + jnp.asarray(page_base, jnp.int32)
    pp = lane_tables.shape[1]
    k = jnp.take(k_pages, lane_tables, axis=0)  # (T, pp, ps, H, D)
    v = jnp.take(v_pages, lane_tables, axis=0)
    if k_scales is not None:
        ks = jnp.take(k_scales, lane_tables, axis=0)  # (T, pp, ps, H)
        vs = jnp.take(v_scales, lane_tables, axis=0)
        k = dequantize_kv(k, ks)
        v = dequantize_kv(v, vs)
    k = k.reshape(b, pp * ps, h, d)
    v = v.reshape(b, pp * ps, h, d)
    if group > 1:
        # the group's query heads as rows of their key/value head
        q = q.reshape(b, h, group, d)
        s = jnp.einsum("thgd,tkhd->thgk", q, k,
                       preferred_element_type=jnp.float32) * scale
        s = s.reshape(b, hq, pp * ps)
    else:
        s = jax.lax.dot_general(
            q, k, (((2,), (3,)), ((0, 1), (0, 2))),
            preferred_element_type=jnp.float32) * scale  # (T, H, pp*ps)
    pos = jax.lax.broadcasted_iota(jnp.int32, (b, 1, pp * ps), 2)
    seen = pos < lane_lens[:, None, None]
    if window:
        seen &= pos >= lane_lens[:, None, None] - window
    s = jnp.where(seen, s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    if group > 1:
        o = jnp.einsum("thgk,tkhd->thgd", p.reshape(b, h, group, -1),
                       v.astype(jnp.float32),
                       preferred_element_type=jnp.float32
                       ).reshape(b, hq, d)
    else:
        o = jax.lax.dot_general(
            p, v.astype(jnp.float32), (((2,), (1,)), ((0, 1), (0, 2))),
            preferred_element_type=jnp.float32)
    return (o / l).astype(q.dtype)


# ------------------------------------------------------------ work list
# The kernel's grid is a LIST of work items, built from the step's lane
# arrays once (on the device, above the engine's layer loop: every
# layer's call shares it) and handed to the kernel by scalar prefetch.
#
#   tile   lanes [g * q_rows, (g + 1) * q_rows): the rows of q and of
#          the output one item holds.
#   run    a maximal stretch of consecutive lanes of one tile that name
#          one slot — a chunk's tokens, a decode lane with its draft
#          tokens, a single decode lane, a stretch of inactive lanes.
#   item   (run, kv-block): the run's rows attend kv positions
#          [blk * block_kv, (blk + 1) * block_kv) of the run's sequence;
#          the block is fetched ONCE for all of them. A run has items
#          for the blocks that start below its LONGEST lane.
#
# Items are ordered by run (so by tile), then by block. Grouping only
# decides what is fetched together: inside an item every row is masked
# by its OWN lane_lens entry, and the rows of the tile outside the run
# are masked whole, so any lane arrays give the jnp twin's answer.
#
# The arrays are as long as the caller's bound says (`max_work_items`),
# not as long as the step's work: the entries past the last item
# repeat it with every flag clear — they re-select the blocks already
# resident and do nothing — and the compiled kernel's grid ends before
# them, at the list's `count`. A page slot of a live item that lies past
# the run's last live page likewise repeats the page the slot held in
# the item before it: nothing is fetched for it, and its positions are
# masked.
_FIRST, _LAST, _LIVE = 1 << 16, 1 << 17, 1 << 18     # flags in `meta`
# The list is a scalar-prefetch operand: 3 + block_pages words an item
# in SMEM (1 MiB a v5e core). Half of that is the budget a call's list
# may take; a caller's own bound has to fit it.
SMEM_LIST_WORDS = 128 * 1024


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["tile", "blk", "meta", "pages", "lens", "count", "masks"],
    meta_fields=["q_rows", "block_pages"])
@dataclasses.dataclass(frozen=True)
class WorkList:
    """The kernel's scalar-prefetch operands (n = the caller's bound on
    the list; one entry more than that, because Mosaic's pipeline
    evaluates the index maps one step past the grid's end) and the
    list's own length, which is the grid's."""
    tile: Any    # (n + 1,) the item's tile
    blk: Any     # (n + 1,) its kv-block
    meta: Any    # (n + 1,) row_lo | row_hi << 8 | _FIRST | _LAST | _LIVE
    pages: Any   # ((n + 1) * block_pages,) physical page per page slot
    lens: Any    # (tiles * q_rows, 128) lane_lens, along the lanes
    count: Any   # () the step's items, in [1, n]: the steps a call walks
    q_rows: int
    block_pages: int
    # a list made of a SELECTION (`build_select_list`): ((n + 1) * S,)
    # one word for each of the S selection blocks of an item's kv-block,
    # bit r set where row r of the tile may see that block's keys. None:
    # every row of the run sees every key up to its own length
    masks: Any = None


def _work_arrays(xp, cummax, page_tables, lane_slots, lane_lens, *,
                 page_size, block_pages, q_rows, max_items, window=0):
    """The work list's arrays, in numpy or jax.numpy (`xp`; `cummax`
    is its running maximum along axis 0) — ONE definition, so the
    counters on the host (`work_items`, `kv_read_bytes`) walk exactly
    the list the device builds. `max_items` None (numpy only) sizes
    the list by the step's own items: n = their count. Under a
    `window` a run's items start at the block that holds the oldest key
    its SHORTEST lane sees, and the pages wholly behind that key are
    not fetched. -> (tile, blk, meta, pages (n, bp), the items'
    count)."""
    t = lane_slots.shape[0]
    pp = page_tables.shape[1]
    bp, qb = block_pages, q_rows
    tiles = -(-t // qb)
    pad = tiles * qb - t
    i32 = xp.int32
    slots = xp.concatenate([lane_slots.astype(i32), xp.zeros(pad, i32)])
    lens = xp.concatenate([lane_lens.astype(i32), xp.ones(pad, i32)])
    lane = xp.arange(tiles * qb, dtype=i32)
    prev = xp.concatenate([slots[:1], slots[:-1]])
    starts = (lane % qb == 0) | (slots != prev)
    # per lane: its run's first row, end row and longest length, by
    # comparing run ids inside the tile (q_rows x q_rows a tile)
    rid = xp.cumsum(starts.astype(i32)).reshape(tiles, qb)
    same = rid[:, :, None] == rid[:, None, :]
    row = xp.arange(qb, dtype=i32)[None, None, :]
    run_len = xp.max(xp.where(same, lens.reshape(tiles, 1, qb), 0),
                     axis=-1).reshape(-1)
    run_lo = xp.min(xp.where(same, row, qb), axis=-1).reshape(-1)
    run_hi = xp.max(xp.where(same, row + 1, 0), axis=-1).reshape(-1)
    bs = bp * page_size
    nblk = xp.where(starts, -(-run_len // bs), 0).astype(i32)
    if window:
        run_min = xp.min(xp.where(same, lens.reshape(tiles, 1, qb),
                                  np.iinfo(np.int32).max),
                         axis=-1).reshape(-1)
        oldest = xp.maximum(run_min - window, 0)    # first key seen
        blk_lo = (oldest // bs).astype(i32)
        nblk = xp.where(starts, nblk - blk_lo, 0).astype(i32)
    ends = xp.cumsum(nblk).astype(i32)      # items up to and with lane
    total = ends[-1]
    n = int(total) if max_items is None else max_items
    w = xp.arange(n, dtype=i32)
    live = w < total
    wc = xp.minimum(w, total - 1)           # past the end: the last one
    head = xp.searchsorted(ends, wc, side="right").astype(i32)
    blk = wc - (ends[head] - nblk[head])
    lo, hi = run_lo[head], run_hi[head]
    first = live & (blk == 0) & (lo == 0)
    last = live & (blk == nblk[head] - 1) & (hi == qb)
    if window:
        blk = blk + blk_lo[head]
    meta = (lo | (hi << 8) | xp.where(first, _FIRST, 0)
            | xp.where(last, _LAST, 0) | xp.where(live, _LIVE, 0))
    # page slot i of item w: table column blk * bp + i while that page
    # holds a position the run can see, else what the slot held before
    col = blk[:, None] * bp + xp.arange(bp, dtype=i32)[None, :]
    fresh = live[:, None] & (col * page_size < run_len[head][:, None])
    if window:
        fresh &= (col + 1) * page_size > oldest[head][:, None]
    page = page_tables.astype(i32)[slots[head][:, None],
                                   xp.minimum(col, pp - 1)]
    src = cummax(xp.where(fresh, w[:, None], -1))
    pages = xp.where(
        src >= 0, xp.take_along_axis(page, xp.maximum(src, 0), axis=0), 0)
    return (head // qb).astype(i32), blk, meta.astype(i32), pages, total


def build_work_list(page_tables, lane_slots, lane_lens, *, page_size: int,
                    block_pages: int, q_rows: int = Q_ROWS,
                    max_items: Optional[int] = None,
                    window: int = 0) -> WorkList:
    """The step's work list, on the device (jax.numpy; a few small
    fusions over the lane arrays). `max_items` is the caller's proof of
    the most items its lane arrays can make (`max_work_items` with the
    slot changes it can bound); None is the bound that holds for any
    arrays. A list longer than the bound would lose its tail, so a
    caller that passes one also checks it where it makes the arrays
    (`work_items(...)`: "total" <= "grid"; ServeSession._pack does)."""
    t, pp = lane_slots.shape[0], page_tables.shape[1]
    bp = max(1, min(int(block_pages), pp))
    if max_items is None:
        max_items = max_work_items(t, pp, bp, q_rows)
    # one entry more than the grid: the step past its end repeats the
    # last item like every entry past the list's end
    tile, blk, meta, pages, total = _work_arrays(
        jnp, lambda x: jax.lax.cummax(x, axis=0), page_tables,
        lane_slots, lane_lens, page_size=page_size, block_pages=bp,
        q_rows=q_rows, max_items=max_items + 1, window=window)
    tiles = -(-t // q_rows)
    lens = jnp.concatenate([lane_lens.astype(jnp.int32),
                            jnp.ones(tiles * q_rows - t, jnp.int32)])
    return WorkList(
        tile=tile, blk=blk, meta=meta, pages=pages.reshape(-1),
        lens=jnp.broadcast_to(lens[:, None], (tiles * q_rows, 128)),
        count=jnp.clip(total, 1, max_items).astype(jnp.int32),
        q_rows=q_rows, block_pages=bp)


def kv_page_bytes(page_size, num_heads, head_dim, kv_itemsize, quantized):
    """Bytes one fetch of a page slot moves: the K and the V page, and
    their f32 scale rows on a quantized pool."""
    per_page = 2 * page_size * num_heads * head_dim * kv_itemsize
    if quantized:
        per_page += 2 * page_size * num_heads * 4
    return per_page


def has_short_body(group: int, q_rows: int = Q_ROWS) -> bool:
    """Whether a call whose query has `group` heads a key/value head
    holds the one-lane item's body beside the whole-tile one (a rule on
    the call's shapes alone; the kernel section says why)."""
    rows = group * q_rows       # of one head in the whole-tile product
    return (rows >= SHORT_MIN_ROWS and rows % SHORT_ROWS == 0
            and SHORT_ROWS % group == 0)


def work_items(lane_lens, lane_slots, page_tables, *, page_size: int,
               block_kv_pages: int = 1, q_rows: int = Q_ROWS,
               max_items: Optional[int] = None,
               live_lanes: Optional[int] = None,
               window: int = 0, group: int = 1) -> Dict[str, int]:
    """What one call of the kernel has to do for these lanes (numpy;
    host side, no device work): `grid` the list's static bound
    (`max_items`, else the bound for any arrays), `total` the items of
    all lanes — the grid steps the call walks, `WorkList.count` (more
    than `grid` would lose work: the caller's bound was wrong),
    `page_fetches` the (K, V) page pairs the call fetches from
    HBM, and — among the first `live_lanes` lanes (all when None; the
    lanes behind them are the step's inactive padding) — `items` and
    the query `rows` they hold. rows / items is how often sharing
    engages: 1.0 when every run is one decode lane, near q_rows inside
    a long chunk. `short_items` are those of them that take the
    one-lane body: the items of one-lane runs where a call at `group`
    query heads a key/value head has that body, else 0.

    `page_fetches` walks the list the kernel is given: page slot i of
    item w is one pipelined operand whose block index is `pages[w, i]`,
    and Pallas's pipeline fetches a block only when its index differs
    from the previous grid step's. So a run fetches each of its live
    pages once for all of its rows, a slot past the run's last live
    page and every item past the list's end fetch nothing, and a page
    that a slot already held (the stretch of inactive tiles on the sink
    page) is not fetched again."""
    pt = np.asarray(page_tables)
    t, pp = len(lane_slots), pt.shape[1]
    bp = max(1, min(int(block_kv_pages), pp))
    grid = max_work_items(t, pp, bp, q_rows) if max_items is None \
        else int(max_items)
    # numpy sizes the arrays by the step's own items: the entries past
    # them repeat the last one, fetch nothing and count nothing
    tile, _, meta, pages, _ = _work_arrays(
        np, lambda x: np.maximum.accumulate(x, axis=0), pt,
        np.asarray(lane_slots), np.asarray(lane_lens),
        page_size=page_size, block_pages=bp, q_rows=q_rows,
        max_items=None, window=window)
    live = t if live_lanes is None else int(live_lanes)
    lo, hi = meta & 0xFF, (meta >> 8) & 0xFF    # the run's rows
    first = tile * q_rows + lo                  # its first lane
    mine = first < live
    short = mine & (hi - lo == 1) & has_short_body(group, q_rows)
    return {"grid": grid, "total": len(tile),
            "items": int(np.sum(mine)), "short_items": int(np.sum(short)),
            "rows": int(np.sum(np.minimum(hi - lo, live - first)[mine])),
            "page_fetches": bp + int(np.sum(pages[1:] != pages[:-1]))}


def kv_read_bytes(lane_lens, lane_slots, page_tables, *, page_size: int,
                  num_heads: int, head_dim: int, kv_itemsize: int,
                  block_kv_pages: int = 1, quantized: bool = False,
                  q_rows: int = Q_ROWS) -> int:
    """Bytes of K and V pages (and their scale rows on a quantized
    pool) that ONE call of the kernel fetches from HBM, for the whole
    step's lanes: `work_items(...)["page_fetches"]` page slots of
    `kv_page_bytes` each. Heads are counted whole: a tensor-parallel step
    fetches the same bytes summed over its chips."""
    return work_items(
        lane_lens, lane_slots, page_tables, page_size=page_size,
        block_kv_pages=block_kv_pages, q_rows=q_rows)["page_fetches"] \
        * kv_page_bytes(page_size, num_heads, head_dim, kv_itemsize,
                      quantized)


# --------------------------------------------- a list made of a selection
# A model that SELECTS its context (serve/sparse_paged.py) hands the
# kernel a list whose items are CHOSEN: row r of the step attends the
# `select_pages`-page selection blocks `blocks[r, :]` where `chosen`,
# and no other key. The list keeps the dense one's shape — tiles, runs,
# (run, kv-block) items, one fetch for all the rows of a run, `blk` the
# kv-block's index in the run's table row — with two differences: a run
# has an item only for a kv-block that holds a block SOME row of it
# chose (the items are no longer consecutive blocks), and an item
# carries a ROW-MASK WORD for each of the S = block_pages /
# select_pages selection blocks of its kv-block: bit r set where row r
# of the tile is in the run, may select at all (`rows`) and chose that
# block. The kernel's masked variant lets a row see a key iff its bit
# for the key's selection block is set and the key lies under the row's
# own length. A tile none of whose rows chose anything has ONE item on
# the sink page with no bit (what an inactive tile has in the dense
# list), so every tile's output is written.
#
# BOUND (`max_select_items`): a run of r rows chooses at most r * topk
# blocks and its table row has pages_per_seq / block_pages kv-blocks, so
# it has at most min(kv-blocks, r * topk) items; over a call's lanes
# that is at most min(runs * kv-blocks, rows * topk), and never under
# one a tile. The list with its mask words has to fit SMEM_LIST_WORDS;
# where the whole step's does not, the lanes go in several calls, whole
# tiles each, cut STATICALLY (`select_call_tiles`).


def max_select_items(num_lanes: int, pages_per_seq: int, block_pages: int,
                     topk: int, q_rows: int = Q_ROWS,
                     slot_changes: Optional[int] = None) -> int:
    """The most items a selection list over `num_lanes` lanes can hold
    (the section above; `slot_changes` as in `max_work_items`)."""
    nbk = -(-pages_per_seq // block_pages)
    tiles = -(-num_lanes // q_rows)
    rows = tiles * q_rows
    runs = rows if slot_changes is None else min(rows, tiles + slot_changes)
    return max(tiles, min(runs * nbk, rows * topk))


def select_call_tiles(num_lanes: int, pages_per_seq: int, block_pages: int,
                      select_pages: int, topk: int, q_rows: int = Q_ROWS,
                      slot_changes: Optional[int] = None):
    """The static cut of a step's lanes into the selection's calls:
    (tiles a call, a call's bound on its items) — as many whole tiles a
    call as keep its list, 3 + block_pages + S words an item and one
    entry more than the bound, inside SMEM_LIST_WORDS, in calls of EQUAL
    size (the last one's lanes past the step's are dead: one sink item a
    tile), so that one batch of operations builds every call's list."""
    words = 3 + block_pages + block_pages // select_pages
    tiles = -(-num_lanes // q_rows)

    def bound(n_tiles):
        return max_select_items(n_tiles * q_rows, pages_per_seq,
                                block_pages, topk, q_rows, slot_changes)

    each = max((n for n in range(1, tiles + 1)
                if (bound(n) + 1) * words <= SMEM_LIST_WORDS), default=0)
    if not each:
        raise ValueError(
            f"one tile's selection list ({bound(1)} items of {words} "
            f"words) does not fit SMEM_LIST_WORDS ({SMEM_LIST_WORDS})")
    each = -(-tiles // -(-tiles // each))       # as few calls, evened out
    return each, bound(each)


def _select_runs(xp, blocks, chosen, rows, lane_slots, *, num_blocks,
                 mask_words, q_rows):
    """The first half of a selection list, in numpy or jax.numpy: for
    every lane that STARTS a run and every kv-block of its table row,
    the S row-mask words of the run's rows (`words` (lanes, kv-blocks,
    S) int32: 0 on a lane that starts no run); the pairs that are ITEMS
    — some word is set, or it is the one sink item of a tile with none
    — as running counts, `upto` (lanes, kv-blocks) along a lane's
    kv-blocks and `ends` (lanes,) along the lanes; `run` (lanes,) =
    row_lo | row_hi << 8 of the run a lane is in. blocks, chosen (T, K) one head's selection, rows (T,) bool
    the lanes that select."""
    t = lane_slots.shape[0]
    qb, nb, s_words = q_rows, num_blocks, mask_words
    tiles = -(-t // qb)
    pad = tiles * qb - t
    i32 = xp.int32
    slots = xp.concatenate([lane_slots.astype(i32), xp.zeros(pad, i32)])
    rows = xp.concatenate([rows, xp.zeros(pad, bool)])
    blocks = xp.concatenate(
        [blocks.astype(i32), xp.zeros((pad,) + blocks.shape[1:], i32)])
    chosen = xp.concatenate(
        [chosen, xp.zeros((pad,) + chosen.shape[1:], bool)])
    # hit[lane, b]: the lane selects and chose block b
    hit = xp.any((blocks[:, :, None] == xp.arange(nb, dtype=i32))
                 & chosen[:, :, None], axis=1) & rows[:, None]
    lane = xp.arange(tiles * qb, dtype=i32)
    prev = xp.concatenate([slots[:1], slots[:-1]])
    starts = (lane % qb == 0) | (slots != prev)
    rid = xp.cumsum(starts.astype(i32)).reshape(tiles, qb)
    same = rid[:, :, None] == rid[:, None, :]
    row = xp.arange(qb, dtype=i32)
    run_lo = xp.min(xp.where(same, row, qb), axis=-1).reshape(-1)
    run_hi = xp.max(xp.where(same, row + 1, 0), axis=-1).reshape(-1)
    # bit r of a word is row r of the tile (q_rows <= 32; the sums are
    # of distinct bits, so they are the bitwise or)
    bit = xp.left_shift(xp.ones(qb, i32), row)
    of_tile = xp.sum(xp.where(hit.reshape(tiles, qb, nb),
                              bit[None, :, None], 0), axis=1, dtype=i32)
    of_run = xp.sum(xp.where(same, bit, 0), axis=-1,
                    dtype=i32).reshape(-1)
    words = xp.where(
        starts[:, None],
        xp.repeat(of_tile, qb, axis=0) & of_run[:, None], 0
    ).reshape(tiles * qb, nb // s_words, s_words)
    item = xp.any(words != 0, axis=-1)                  # (lanes, nbk)
    empty = xp.repeat(xp.sum(item.reshape(tiles, -1), axis=1) == 0, qb) \
        & (lane % qb == 0)
    first_blk = xp.arange(item.shape[1]) == 0
    item = item | (empty[:, None] & first_blk[None, :])
    # each lane's items up to and with a kv-block, and the items up to
    # and with a lane
    upto = xp.cumsum(item, axis=1, dtype=i32)
    return words, upto, xp.cumsum(upto[:, -1], dtype=i32), \
        run_lo | (run_hi << 8)


def _select_items(xp, words, upto, ends, run, lane_tables, *, q_rows,
                  max_items, before=0):
    """The second half: the items of `_select_runs` (over ANY whole
    tiles of its lanes: a call's, `before` the items of the lanes ahead
    of them) laid out as the kernel walks them.
    lane_tables (lanes, pages) each lane's page-table row. `max_items`
    None (numpy only): n = the list's own length. Dense comparisons and
    ROW gathers only: a gather of single words costs the chip some 10
    ns a word. -> (tile, blk, meta, pages (n, bp), masks (n, S), the
    items' count, the items that hold a chosen block)."""
    lanes, nbk, s_words = words.shape
    bp = lane_tables.shape[1] // nbk
    i32 = xp.int32
    ends = ends - before
    total = ends[-1]
    n = int(total) if max_items is None else max_items
    w = xp.arange(n, dtype=i32)
    alive = w < total
    wc = xp.minimum(w, total - 1)           # past the end: the last one
    # the lane whose run holds item w, and the item's rank in that run
    ahead = ends[None, :] <= wc[:, None]                    # (n, lanes)
    head = xp.sum(ahead, axis=1, dtype=i32)
    rank = wc - xp.max(xp.where(ahead, ends[None, :], 0), axis=1)
    # ... which is the run's (rank + 1)-th kv-block that is an item
    blk = xp.sum(xp.take(upto, head, axis=0) <= rank[:, None], axis=1,
                 dtype=i32)
    src = head * nbk + blk
    masks = xp.take(words.reshape(lanes * nbk, s_words), src, axis=0)
    real = xp.any(masks != 0, axis=1)
    pages = xp.where(
        real[:, None],
        xp.take(lane_tables.astype(i32).reshape(lanes * nbk, bp), src,
                axis=0), 0)
    at = head[:, None] == xp.arange(lanes, dtype=i32)[None, :]
    rows_of = xp.sum(xp.where(at, run[None, :], 0), axis=1, dtype=i32)
    tile = head // q_rows
    edge = xp.full(1, -1, i32)
    first = alive & (tile != xp.concatenate([edge, tile[:-1]]))
    last = alive & ((tile != xp.concatenate([tile[1:], edge]))
                    | (w == total - 1))
    meta = (rows_of | xp.where(first, _FIRST, 0) | xp.where(last, _LAST, 0)
            | xp.where(alive & real, _LIVE, 0))
    return (tile, blk, meta.astype(i32), pages, masks, total,
            xp.sum(alive & real, dtype=i32))


def _select_arrays(xp, blocks, chosen, rows, lane_slots, lane_tables, *,
                   block_pages, select_pages, q_rows, max_items):
    """The selection list's arrays over these lanes (whole tiles), in
    numpy or jax.numpy — ONE definition in two halves, as `_work_arrays`
    is the dense list's: `_select_runs`, then `_select_items`."""
    pp = lane_tables.shape[1]
    words, upto, ends, run = _select_runs(
        xp, blocks, chosen, rows, lane_slots, num_blocks=pp // select_pages,
        mask_words=block_pages // select_pages, q_rows=q_rows)
    tables = xp.concatenate([lane_tables, xp.zeros(
        (run.shape[0] - lane_tables.shape[0], pp), lane_tables.dtype)])
    return _select_items(xp, words, upto, ends, run, tables, q_rows=q_rows,
                         max_items=max_items)


def _as_work(arrays, lane_lens, max_items, q_rows, block_pages) -> WorkList:
    tile, blk, meta, pages, masks, total, _ = arrays
    lens = jnp.concatenate([
        lane_lens.astype(jnp.int32),
        jnp.ones(-lane_lens.shape[0] % q_rows, jnp.int32)])
    return WorkList(
        tile=tile, blk=blk, meta=meta, pages=pages.reshape(-1),
        lens=jnp.broadcast_to(lens[:, None], (lens.shape[0], 128)),
        count=jnp.clip(total, 1, max_items).astype(jnp.int32),
        q_rows=q_rows, block_pages=block_pages, masks=masks.reshape(-1))


def _check_select(block_pages, select_pages, pages_per_seq, q_rows):
    if (block_pages % select_pages or pages_per_seq % block_pages
            or q_rows > 32):
        raise ValueError(
            f"a selection's kv-block ({block_pages} pages) is whole "
            f"selection blocks ({select_pages}) and divides the table "
            f"({pages_per_seq}); a mask word holds {q_rows} rows' bits")


def build_select_list(blocks, chosen, rows, lane_slots, lane_tables,
                      lane_lens, *, block_pages: int, select_pages: int,
                      max_items: int, q_rows: int = Q_ROWS) -> WorkList:
    """The list of one key/value head's selection, on the device
    (jax.numpy), for ONE call's lanes (whole tiles): blocks, chosen (T,
    K) the selection blocks (of `select_pages` pages) each lane chose;
    rows (T,) bool the lanes that select at all (a serving step's live
    lanes at or past the selector's dense_len); lane_tables (T, pages)
    each lane's page-table row; lane_lens (T,) position + 1, the causal
    edge inside a row's own block. `block_pages` is whole selection
    blocks and divides the table; `max_items` the caller's proven bound
    (`max_select_items` / `select_call_tiles`). -> the WorkList with
    its `masks`; `count` is the grid's length (the sink items of tiles
    without a selection too)."""
    t, pp = lane_tables.shape
    _check_select(block_pages, select_pages, pp, q_rows)
    return _as_work(_select_arrays(
        jnp, blocks, chosen, rows, lane_slots, lane_tables,
        block_pages=block_pages, select_pages=select_pages, q_rows=q_rows,
        max_items=max_items + 1), lane_lens, max_items, q_rows, block_pages)


def build_select_lists(blocks, chosen, rows, lane_slots, lane_tables,
                       lane_lens, *, block_pages: int, select_pages: int,
                       call_lanes: int, max_items: int,
                       q_rows: int = Q_ROWS):
    """The lists of a layer's selection: blocks, chosen (T, G, K) every
    key/value head's, T whole calls of `call_lanes` lanes each; the
    other arguments `build_select_list`'s. The runs' mask words are made
    ONCE over all the lanes and heads (a tile's do not depend on the
    call it falls in), each call's items from its lanes' share. ->
    [call][head] (the WorkList, the same `build_select_list` gives a
    call's lanes; its items that hold a chosen block, int32: the
    others are a tile's sink item)."""
    t, pp = lane_tables.shape
    _check_select(block_pages, select_pages, pp, q_rows)
    if call_lanes % q_rows or t % call_lanes:
        raise ValueError(f"{t} lanes are no whole calls of {call_lanes}, "
                         f"or those no whole tiles of {q_rows}")
    words, upto, ends, run = jax.vmap(
        lambda b, c: _select_runs(
            jnp, b, c, rows, lane_slots, num_blocks=pp // select_pages,
            mask_words=block_pages // select_pages, q_rows=q_rows),
        in_axes=1)(blocks, chosen)                  # (G, T[, nbk[, S]])

    def of_call(j, lo):
        cut = slice(lo, lo + call_lanes)
        arrays = _select_items(
            jnp, words[j, cut], upto[j, cut], ends[j, cut], run[j, cut],
            lane_tables[cut], q_rows=q_rows, max_items=max_items + 1,
            before=ends[j, lo - 1] if lo else 0)
        return (_as_work(arrays, lane_lens[cut], max_items, q_rows,
                         block_pages), arrays[-1])

    return [[of_call(j, lo) for j in range(blocks.shape[1])]
            for lo in range(0, t, call_lanes)]


def select_counts(xp, blocks, chosen, rows, lane_slots, *, num_blocks: int,
                  mask_words: int, q_rows: int = Q_ROWS):
    """What a selection list over these lanes holds, without making it
    (the jnp twin's count, and a test's walk): (its items — the grid
    steps a call walks — and those of them that hold a chosen block)."""
    words, _, ends, _ = _select_runs(
        xp, blocks, chosen, rows, lane_slots, num_blocks=num_blocks,
        mask_words=mask_words, q_rows=q_rows)
    return (ends[-1],
            xp.sum(xp.any(words != 0, axis=-1), dtype=xp.int32))


# --------------------------------------------------------- Pallas kernel
# Everything inside the kernel is a 2-D array: Mosaic refuses to split a
# lane dimension (reshape (ps, H*D) -> (ps, H, D): "unsupported shape
# cast") and to batch a matmul over a non-leading axis, which is how a
# per-head dot over (bs, H, D) blocks has to be written. The per-head
# products go to the MXU one SLAB of the packed H*D axis at a time: a
# slab is W = 128 lanes (W = D where D > 128), G = W // D heads wide.
# The wrapper lays q out as q2 (tile, slab, G * q_rows, W): row
# g * q_rows + r of a slab holds row r's head g in ITS D lanes and 0 in
# the others, so ONE product with the slab of K contracts each head
# with itself alone,
#
#   s[g*QB + r, t] = sum_c q2[g*QB + r, c] k[t, c] = q[r, head g] . k[t, head g]
#
# and tokens land on the lanes: the softmax statistics are per row of
# s, kept lane-broadcast in scratch. The weighted sum p (G*QB, bs) @
# v (bs, W) gives head g's output in rows g*QB.. at lanes g*D.., which
# a select by lane folds to the (QB, W) slab of the accumulator. The
# per-(token, head) scales of quantized pages arrive tokens-on-sublanes
# and are turned onto the lanes by a product with the identity; they
# multiply s and p, so K/V are never dequantized. The slabs are a
# `fori_loop` inside the item, SLAB_UNROLL of them a trip (a slab's
# lanes are a dynamic slice at a multiple of W, which Mosaic takes;
# scratch and output keep the slab as a leading dimension): with all
# 16 slabs unrolled in Python, tracing and lowering the body cost every
# process 9 s before it could ask the compile cache (PERF.md section
# 6, PR 25).
#
# Precision: with bf16 q and bf16 / int8 / fp8 pages every operand of
# q.k is exact in bf16 and the MXU accumulates in f32; p is split into
# two bf16 halves (p_hi + p_lo, 16 bits of mantissa) stacked in one
# product with V. With f32 q or f32 pages the operands stay f32 at
# HIGHEST precision. Either way the result is as accurate as the output
# dtype can hold.
# Slabs in one trip of the item's slab loop. 4: within 3-5 % of the
# whole loop unrolled (0.73 against 0.70 ms for a decode-only call at
# the serving cell; 1 slab a trip: 0.97) at a quarter of its equations
SLAB_UNROLL = 4
# Rows of each head that the body of a one-lane item works on: one
# packed bf16 tile (two f32 tiles), the aligned stretch of the tile's
# rows that holds the lane's `group` query heads. A call gets that body
# where the whole-tile product has SHORT_MIN_ROWS rows or more (4 query
# heads a key/value head at Q_ROWS lanes: 128 rows for one lane's 4);
# below that the calls keep the one body and the program they had.
# The MXU takes its products in the order they are written, so a slab's
# two wait for each other through its softmax: the short body, whose
# tiles are small enough to hold, writes each stage for up to
# SHORT_ABREAST slabs side by side. us a one-lane item at Phi's
# geometry (10 slabs; whole tile 5.6): 1 abreast 4.2, 2 2.1, 5 1.1,
# 10 0.6 (tests_tpu/test_paged_short_tpu.py; PERF.md section 6, PR 40)
SHORT_ROWS = 16
SHORT_ABREAST = 16
SHORT_MIN_ROWS = 128
_MASK = -0.5 * float(jnp.finfo(jnp.float32).max)  # finite: m stays finite
_HIGHEST = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))                    # a @ b.T


def _slab_geometry(num_heads: int, head_dim: int) -> Tuple[int, int]:
    """(heads a slab, lanes a slab): as many whole heads as fit 128
    lanes and divide the head count."""
    g = max(1, min(num_heads, 128 // head_dim))
    while num_heads % g:
        g -= 1
    return g, g * head_dim


def _stack(pieces, dtype):
    """Page pieces (ps, W) stacked on the token (sublane) axis as one
    (bs, W) operand of `dtype`. Pieces that fill whole packed tiles of
    their own dtype are stacked as they lie; others go through f32."""
    native = pieces[0].dtype
    rows = 8 * 4 // jnp.dtype(native).itemsize       # sublanes a tile
    via = native if pieces[0].shape[0] % rows == 0 else jnp.float32
    if jnp.dtype(native).itemsize == 1:
        via = jnp.float32        # int8 / fp8 widen to f32 first
    x = [p.astype(via) for p in pieces]
    x = x[0] if len(x) == 1 else jnp.concatenate(x, axis=0)
    return x.astype(dtype)


def _by_head(x, q_rows, heads, head_dim):
    """(G * q_rows, W) with head g's rows stacked at g * q_rows ->
    (q_rows, W) taking lanes [g * D, (g + 1) * D) from head g's rows."""
    out = x[:q_rows]
    if heads > 1:
        lane = jax.lax.broadcasted_iota(jnp.int32, out.shape, 1)
        for g in range(1, heads):
            out = jnp.where(lane >= g * head_dim,
                            x[g * q_rows:(g + 1) * q_rows], out)
    return out


def _ragged_v2_kernel(tile_ref, blk_ref, meta_ref, pages_ref, *refs,
                      page_size, block_pages, q_rows, heads, head_dim,
                      slabs, scale, quantized, exact, group=1, window=0,
                      short=False, based=False, mask_words=0):
    """One work item: the rows [lo, hi) of a tile attend one kv-block
    of their sequence. Page refs arrive head-PACKED as (1, ps, H*D)
    blocks (plus (1, ps, H) scale blocks when quantized). The grid runs
    in order; m / l / acc carry a tile's online softmax from its first
    item to its last. The slabs are a loop inside the item (traced
    once: the body is not unrolled in Python). Grouped heads: the
    `group` query heads of a key/value head are `group` times the rows
    (row (g * q_rows + r) * group + j of a slab is row r's query head
    j of the slab's head g: a lane's rows lie together, and `lens_ref`
    holds a lane's length once a query head), so one product serves
    them all; under a `window` a row sees its last `window` positions.
    With `short` a live item whose run is ONE lane takes a second body:
    the same mathematics on the aligned SHORT_ROWS rows of each head
    that hold the lane's `group`, the tile's other rows untouched (as
    the whole-tile body leaves them: they see nothing of the item).
    `based`: a fifth scalar operand, the first row of the call's layer
    in the pages' arrays, comes before q2. `mask_words` S > 0 (a list
    made of a selection, `build_select_list`): one more scalar operand
    after it, S row-mask words an item — a row sees a key iff the bit
    of its tile row is set in the word of the key's selection block
    (the kv-block's S equal parts) AND the key lies under its length;
    a row no item shows anything comes out 0, not 0 / 0."""
    del tile_ref, pages_ref                  # read by the index maps
    refs = refs[1:] if based else refs
    if mask_words:
        masks_ref, *refs = refs
    q2_ref, lens_ref, *refs = refs
    per_page = 4 if quantized else 2
    n_kv = per_page * block_pages
    kv_refs = refs[:n_kv]
    if quantized:
        eye_ref = refs[n_kv]
        o_ref, m_ref, l_ref, acc_ref, ks_ref, vs_ref = refs[n_kv + 1:]
    else:
        o_ref, m_ref, l_ref, acc_ref = refs[n_kv:]
    g, d = heads, head_dim
    qb = group * q_rows         # rows of one head of a slab
    w_lanes = g * d
    bs = block_pages * page_size
    op_dtype = jnp.float32 if exact else jnp.bfloat16
    prec = _HIGHEST if exact else None

    w = pl.program_id(0)
    meta = meta_ref[w]

    def each_slab(*stages, abreast=0):
        """stage(slab, its lanes, what the stage before returned) for
        every slab, the stages in turn: a loop whose body holds
        SLAB_UNROLL slabs, independent of each other, so that their
        loads, matmuls and exponentials overlap. `abreast` > 0: that
        many slabs a trip, and a stage runs for all of them before the
        next one starts."""
        u = math.gcd(slabs, SLAB_UNROLL) if not abreast else max(
            n for n in range(1, abreast + 1) if slabs % n == 0)

        def lanes(slab):
            return pl.ds(pl.multiple_of(slab * w_lanes, w_lanes), w_lanes)

        def step(i, carry):
            side = u if abreast else 1      # slabs a stage runs for
            for j in range(0, u, side):
                at = [i * u + k for k in range(j, j + side)]
                at = [(slab, lanes(slab)) for slab in at]
                vals = [None] * side
                for stage in stages:
                    vals = [stage(slab, cols, v)
                            for (slab, cols), v in zip(at, vals)]
            return carry
        jax.lax.fori_loop(0, slabs // u, step, 0)

    @pl.when((meta & _FIRST) != 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _MASK)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def accumulate(lo, hi, start=None):
        """The item of the run at rows [lo, hi) of a head's qb, over
        the SHORT_ROWS rows from `start` of every head of a slab (None:
        all qb of them, each ref read whole)."""
        nr = qb if start is None else SHORT_ROWS

        def rows(i=0):
            return pl.ds(pl.multiple_of(i * qb + start, nr), nr)

        def load(ref, *lead, heads=g):
            """The item's rows of the `heads` row blocks of ref[lead]."""
            if start is None:
                return ref[lead]
            return jnp.concatenate([ref[(*lead, rows(i))]
                                    for i in range(heads)], axis=0)

        def store(ref, slab, x, heads=g):
            if start is None:
                ref[slab] = x
                return
            for i in range(heads):
                ref[slab, rows(i)] = x[i * nr:(i + 1) * nr]

        base = blk_ref[w] * bs
        # each row's visible length; 0 for the tile's rows outside the
        # run (they belong to other items), stacked once per head
        row = jax.lax.broadcasted_iota(jnp.int32, (nr, 128), 0)
        if start is not None:
            row = row + start
        vis = jnp.where((row >= lo) & (row < hi),
                        load(lens_ref, heads=1), 0)
        vis = jnp.concatenate([vis] * g, axis=0)[:, :1]
        pos = base + jax.lax.broadcasted_iota(jnp.int32, (g * nr, bs), 1)
        if mask_words:
            # a row's limit by selection block: its own length where the
            # bit of its tile row is set in the block's word, else 0
            tile_row = row >> (group.bit_length() - 1) \
                if group & (group - 1) == 0 else row // group
            bit = jnp.left_shift(1, tile_row)
            bit = jnp.concatenate([bit] * g, axis=0)[:, :1]
            each = bs // mask_words             # keys a selection block
            col = jax.lax.broadcasted_iota(jnp.int32, (g * nr, bs), 1)
            limits = [jnp.where((bit & masks_ref[w * mask_words + i]) != 0,
                                vis, 0) for i in range(mask_words)]
            vis = limits[-1]
            for i in reversed(range(mask_words - 1)):
                vis = jnp.where(col < (i + 1) * each, limits[i], vis)
        seen = pos < vis                                     # (G*nr, bs)
        if window:
            seen &= pos >= vis - window
        def scales_on_lanes(j, out_ref):
            """Page slot j's (bs, H) scale rows as (Hp, bs): a product
            with the identity at HIGHEST precision moves them exactly."""
            sc = _stack([kv_refs[per_page * i + j][0]
                         for i in range(block_pages)], jnp.float32)
            out_ref[...] = jax.lax.dot_general(
                eye_ref[...], sc, _NT, precision=_HIGHEST,
                preferred_element_type=jnp.float32)

        def head_rows(sc_ref, slab):
            """Rows slab * G + g of (Hp, bs), each over its rows."""
            return jnp.concatenate(
                [jnp.broadcast_to(sc_ref[pl.ds(slab * g + i, 1), :],
                                  (nr, bs)) for i in range(g)], axis=0)

        if quantized:
            scales_on_lanes(1, ks_ref)
            scales_on_lanes(3, vs_ref)

        def block(j, cols):
            return _stack([kv_refs[per_page * i + j][0, :, cols]
                           for i in range(block_pages)], op_dtype)

        def scores(slab, cols, _):
            s = jax.lax.dot_general(
                load(q2_ref, 0, slab), block(0, cols), _NT, precision=prec,
                preferred_element_type=jnp.float32)      # (G*nr, bs)
            if quantized:
                s = s * head_rows(ks_ref, slab)
            return jnp.where(seen, s * scale, _MASK)

        def softmax(slab, cols, s):
            m_prev = load(m_ref, slab)                   # (G*nr, W)
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=1, keepdims=True))
            # a row that sees nothing here has s == m_new == _MASK:
            # exp(0) = 1, so the mask zeroes p itself
            p = jnp.where(seen, jnp.exp(s - m_new[:, :1]), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            store(m_ref, slab, m_new)
            store(l_ref, slab, load(l_ref, slab) * alpha + jnp.sum(
                p, axis=1, keepdims=True))
            if quantized:
                p = p * head_rows(vs_ref, slab)
            return p, alpha

        def weigh(slab, cols, p_alpha):
            p, alpha = p_alpha
            v = block(2 if quantized else 1, cols)       # (bs, W)
            if exact:
                pv = jnp.dot(p, v, precision=_HIGHEST,
                             preferred_element_type=jnp.float32)
            else:
                p_hi = p.astype(jnp.bfloat16)
                p_lo = (p - p_hi.astype(jnp.float32)).astype(jnp.bfloat16)
                pv = jnp.dot(jnp.concatenate([p_hi, p_lo], axis=0), v,
                             preferred_element_type=jnp.float32)
                pv = pv[:g * nr] + pv[g * nr:]
            store(acc_ref, slab,
                  load(acc_ref, slab, heads=1) * _by_head(alpha, nr, g, d)
                  + _by_head(pv, nr, g, d), heads=1)

        each_slab(scores, softmax, weigh,
                  abreast=0 if start is None else SHORT_ABREAST)

    @pl.when((meta & _LIVE) != 0)
    def _accumulate():
        lo, hi = meta & 0xFF, (meta >> 8) & 0xFF
        if group > 1:           # the run's rows among a head's qb
            lo, hi = lo * group, hi * group
        if not short:
            accumulate(lo, hi)
            return
        one_lane = hi - lo == group

        @pl.when(one_lane)
        def _its_rows():
            accumulate(lo, hi, lo // SHORT_ROWS * SHORT_ROWS)

        @pl.when(jnp.logical_not(one_lane))
        def _whole_tile():
            accumulate(lo, hi)

    @pl.when((meta & _LAST) != 0)
    def _emit():
        def one_slab(slab, cols, _):
            acc = acc_ref[slab]
            l = _by_head(l_ref[slab], qb, g, d)
            if mask_words:      # a row with no bit anywhere: 0 / 0
                l = jnp.maximum(l, 1e-30)
            o_ref[0, slab] = (acc / l).astype(o_ref.dtype)

        each_slab(one_slab)


def _vmem_limit(block_bytes: int) -> int:
    """Scoped-VMEM request for a kernel whose pipelined blocks, scratch
    and live intermediates total `block_bytes`: twice that (Pallas
    double-buffers every block), never under Mosaic's own 16 MiB
    default, capped at half a v5e core's 128 MiB."""
    return int(min(max(2 * block_bytes, 16 * 2**20), 64 * 2**20))


# jitted on its own: the engine's layers make the same call 24 times,
# and tracing and lowering the kernel body is host time before the
# compile cache can even be asked — a nested jit pays it once
@functools.partial(jax.jit, static_argnames=("scale", "interpret",
                                             "window", "short",
                                             "out_dtype"))
def _ragged_v2_pallas(q, k_pages, v_pages, work: WorkList, scale,
                      interpret, k_scales=None, v_scales=None, window=0,
                      short=False, page_base=None, out_dtype=None):
    t, hq, d = q.shape
    npages, ps = k_pages.shape[:2]
    h = _kv_heads(k_pages, d)
    group = hq // h             # query heads a key/value head
    qb, bp = work.q_rows, work.block_pages
    qe = group * qb             # rows of one head of a slab
    n = work.tile.shape[0] - 1
    tiles = work.lens.shape[0] // qb
    quantized = k_scales is not None
    hd = h * d
    g, w_lanes = _slab_geometry(h, d)
    slabs = h // g
    # f32 anywhere among q and the pages: f32 operands at HIGHEST
    exact = not (q.dtype == jnp.bfloat16
                 and jnp.dtype(k_pages.dtype).itemsize <= 2)
    op_dtype = jnp.float32 if exact else jnp.bfloat16

    # head packing: pages stream as (ps, H*D) rows. On a standalone
    # array and on a packed pool's whole leaf (rows of all its layers,
    # `page_base` the call's first) this reshape is free; of an UNPACKED
    # pool the engine hands a layer's slice, and XLA copies the slab out
    # and lays it out anew for the call (PERF.md section 5)
    kp = k_pages.reshape(npages, ps, hd)
    vp = v_pages.reshape(npages, ps, hd)
    based = page_base is not None
    # a list made of a selection: its row-mask words an item
    mask_words = 0 if work.masks is None else work.masks.shape[0] // (n + 1)
    # q2[tile, slab, (g * qb + r) * group + j, g' * D + c] = q[tile * qb
    # + r, (slab * G + g) * group + j, c] where g' == g, else 0: a
    # lane's `group` rows lie together, so a one-lane item finds them
    # in one aligned stretch. One
    # group is written without the group's unit dimension: XLA lays the
    # two forms out differently around the call, and the cells of the
    # benchmark that run one group are held to the program they had
    # (`docqa-closed8` exits when it is served faster: PERF.md section 7)
    qp = jnp.pad(q, ((0, tiles * qb - t), (0, 0), (0, 0)))
    if group == 1:
        qp = qp.reshape(tiles, qb, slabs, g, d).transpose(0, 2, 3, 1, 4)
        q2 = (qp[:, :, :, :, None, :].astype(op_dtype)
              * jnp.eye(g, dtype=op_dtype)[None, None, :, None, :, None]
              ).reshape(tiles, slabs, g * qb, w_lanes)
    else:
        qp = qp.reshape(tiles, qb, slabs, g, group, d).transpose(
            0, 2, 3, 1, 4, 5)
        q2 = (qp[:, :, :, :, :, None, :].astype(op_dtype)
              * jnp.eye(g, dtype=op_dtype)[None, None, :, None, None, :,
                                           None]
              ).reshape(tiles, slabs, g * qe, w_lanes)

    def page_index(i):
        def imap(w, tile, blk, meta, pages, *rest):
            page = pages[w * bp + i]
            # with a base: the page's row among the rows of all layers
            return (rest[0][0] + page if based else page, 0, 0)
        return imap

    def tile_index(w, tile, *_):
        return (tile[w], 0)

    # a lane's length once a row of its group
    lens = work.lens if group == 1 else jnp.repeat(work.lens, group, axis=0)
    in_specs = [pl.BlockSpec((1, slabs, g * qe, w_lanes),
                             lambda w, tile, *_: (tile[w], 0, 0, 0)),
                pl.BlockSpec((qe, 128), tile_index)]
    args = [q2, lens]
    for i in range(bp):
        imap = page_index(i)
        in_specs.append(pl.BlockSpec((1, ps, hd), imap))
        args.append(kp)
        if quantized:
            in_specs.append(pl.BlockSpec((1, ps, h), imap))
            args.append(k_scales)
        in_specs.append(pl.BlockSpec((1, ps, hd), imap))
        args.append(vp)
        if quantized:
            in_specs.append(pl.BlockSpec((1, ps, h), imap))
            args.append(v_scales)
    hp = -(-h // 8) * 8
    if quantized:
        in_specs.append(pl.BlockSpec((hp, h), lambda w, *_: (0, 0)))
        args.append(jnp.eye(hp, h, dtype=jnp.float32))
    kern = functools.partial(
        _ragged_v2_kernel, page_size=ps, block_pages=bp, q_rows=qb,
        heads=g, head_dim=d, slabs=slabs, scale=scale,
        quantized=quantized, exact=exact, group=group, window=window,
        short=short, based=based, mask_words=mask_words)
    # the work list, and the layer's first row where the pages are rows
    # of many layers: a device scalar, so every layer of a leaf shares
    # this trace and one Mosaic kernel
    prefetch = (work.tile, work.blk, work.meta, work.pages) + (
        (jnp.asarray(page_base, jnp.int32).reshape(1),) if based else ()
    ) + ((work.masks,) if mask_words else ())
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        # the list's own length, a device scalar: a call walks its
        # items and not the bound's empty tail. The interpreter takes
        # no traced bound and walks the arrays whole; the entries past
        # `count` are not _LIVE, so both give the same result
        grid=(n if interpret else work.count,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, slabs, qe, w_lanes),
                               lambda w, tile, *_: (tile[w], 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((slabs, g * qe, w_lanes), jnp.float32),  # max
            pltpu.VMEM((slabs, g * qe, w_lanes), jnp.float32),  # sum
            pltpu.VMEM((slabs, qe, w_lanes), jnp.float32),  # accumulator
        ] + ([pltpu.VMEM((hp, bp * ps), jnp.float32)] * 2   # scales^T
             if quantized else []),
    )
    bs = bp * ps
    lanes = -(-h // 128) * 128      # a (.., h) f32 tile pads to 128 lanes
    op_size = jnp.dtype(op_dtype).itemsize
    block_bytes = (
        2 * bs * hd * jnp.dtype(k_pages.dtype).itemsize     # K + V pages
        + (2 * bs * lanes * 4 + 2 * hp * bs * 4 if quantized else 0)
        + slabs * g * qe * w_lanes * (op_size + 8)  # q2, max, sum
        + 2 * qe * hd * 4                           # acc, the output
        + 2 * bs * max(w_lanes, 128) * (4 + op_size)    # a slab of K, V
        + 8 * g * qe * max(bs, 128) * 4)            # s, p and their kin
    out = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tiles, slabs, qe, w_lanes),
                                       out_dtype or q.dtype),
        # the grid axis carries the online-softmax scratch from one
        # work item of a tile to the next: it must run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_limit(block_bytes)),
        interpret=interpret,
        # the device trace tells the two lists' calls apart by name
        name="paged_ragged_v2_window" if window else
        "paged_ragged_v2_select" if mask_words else "paged_ragged_v2",
    )(*prefetch, *args)
    # (tile, slab, (group, row), (head, dim)) -> (lane of the step,
    # query head, dim)
    if group == 1:
        return out.transpose(0, 2, 1, 3).reshape(tiles * qb, h, d)[:t]
    out = out.reshape(tiles, slabs, qb, group, g, d).transpose(
        0, 2, 1, 4, 3, 5)
    return out.reshape(tiles * qb, hq, d)[:t]


# ------------------------------------------------------------ entry point
PALLAS = "pallas"                    # compiled by Mosaic (TPU only)
PALLAS_INTERPRET = "pallas_interpret"  # the Pallas interpreter, any backend
JNP = "jnp"                          # the gather + XLA path


def resolve_paged_impl(use_pallas=None, interpret=False) -> str:
    """The one rule that picks a paged-attention implementation, so a
    caller (ServeEngine) can resolve it ONCE, report it, and pass the
    resolved booleans down:

      use_pallas=False  -> JNP               (asked for by argument)
      interpret=True    -> PALLAS_INTERPRET  (asked for by argument)
      use_pallas=None   -> PALLAS on a tpu backend, JNP elsewhere (the
                           CPU tests' path)
      use_pallas=True   -> PALLAS; off-TPU that is an error, not a
                           quiet jnp run

    On a tpu backend nothing but an argument reaches the interpreter
    or the jnp path."""
    if use_pallas is False:
        return JNP
    if interpret:
        return PALLAS_INTERPRET
    on_tpu = jax.default_backend() == "tpu"
    if use_pallas is None and not on_tpu:
        return JNP
    if not on_tpu:
        raise RuntimeError(
            f"use_pallas=True needs a tpu backend to compile for (this "
            f"one is {jax.default_backend()!r}); pass interpret=True to "
            f"run the kernel through the Pallas interpreter")
    return PALLAS


def paged_attention_ragged_v2(q, k_pages, v_pages, page_tables,
                              lane_slots, lane_lens, *, k_scales=None,
                              v_scales=None, scale=None, block_kv=None,
                              work=None, use_pallas=None,
                              interpret=False, window=0, page_base=None,
                              out_dtype=None):
    """Ragged batched attention through page tables — kernel v2.

    GROUPED HEADS: q may have `group` times the pages' heads; query
    head j reads key/value head j // group. `window` > 0: a lane sees
    only its last `window` positions (a `work` list given with it must
    have been built with the same window).

    q (T, H, D) — one query token per LANE, where lanes mix
    prompt-chunk tokens from any number of sequences with single decode
    tokens (a decode step is one lane per sequence); k_pages/v_pages
    (num_pages, page_size, H, D); page_tables (max_seqs, pages_per_seq)
    int32 physical page ids (0 = sink/padding); lane_slots (T,) int32
    selects each lane's page-table row (lanes of the same sequence
    share a row); lane_lens (T,) int32 the lane's visible tokens —
    position + 1 for a prefill token at `position`, so causality inside
    a chunk is exact even though the whole chunk's K/V is scattered
    before attention runs. Every lane_lens entry must be >= 1: a
    zero-length lane has every score masked, which NaNs the softmax of
    the jnp path and leaves garbage in the kernel's — callers with
    empty lanes clamp them to 1 and aim their page table at the sink
    (serve/engine.py does exactly this). Returns (T, H, D).

      k_scales/v_scales — (num_pages, page_size, H) f32 per-page scale
        arrays for int8 K/V pages (None = unquantized pages; the two
        must be both present or both absent).
      block_kv — KV tokens per work item (None = the autotune-by-shape
        table via choose_block_kv; rounded to whole pages).
      page_base — ROWS OF MANY LAYERS: k_pages/v_pages (rows, page_size,
        H * D) head-packed (the scales (rows, page_size, H)), the pages
        of several layers one after another as a packed `KVPool` stores
        them, and `page_base` () int32 the row of THIS layer's page 0:
        page p of the tables is row page_base + p. The kernel fetches
        its blocks at those rows of the whole array, so a caller hands
        over the pool's leaf where it lies, not a slice of it that XLA
        would first copy out; the base is a device scalar (traced, not
        static), so every layer of a leaf shares one trace of the
        kernel. None: the arrays hold one layer's pages, in either
        form.
      out_dtype — the result's dtype where it is not q's: the kernel's
        f32 accumulator over its f32 sum, rounded once to this (the jnp
        path's result is cast).
      work — the step's WorkList (`build_work_list` over these very
        lane arrays, made once for all the calls that share them: its
        kv-block shape is the one used); None builds one here, with
        the grid bound that holds for any lane arrays (and takes the
        lanes in several calls where one list would not fit SMEM).

    The Pallas kernel agrees with the jnp path to f32 rounding (it
    sums in a different order). use_pallas/interpret pick the implementation by
    resolve_paged_impl.
    """
    if (k_scales is None) != (v_scales is None):
        raise ValueError("k_scales and v_scales must be given together")
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    impl = resolve_paged_impl(use_pallas, interpret)
    if impl == JNP:
        o = _ragged_jnp(q, k_pages, v_pages, page_tables, lane_slots,
                        lane_lens, scale, k_scales=k_scales,
                        v_scales=v_scales, window=window,
                        page_base=page_base)
        return o if out_dtype is None else o.astype(out_dtype)
    ps = k_pages.shape[1]
    heads = _kv_heads(k_pages, q.shape[2])
    if work is None:
        if block_kv is None:
            block_kv = choose_block_kv(
                ps, page_tables.shape[1], heads, q.shape[2],
                jnp.dtype(k_pages.dtype).itemsize)
        bp = max(1, min(int(block_kv) // ps, page_tables.shape[1]))
        # the list lives in SMEM: with no bound from the caller, as
        # many tiles of lanes a call as its budget holds
        per_tile = max_work_items(Q_ROWS, page_tables.shape[1], bp) \
            * (3 + bp)
        step = max(1, SMEM_LIST_WORDS // per_tile) * Q_ROWS
        if q.shape[0] > step:
            return jnp.concatenate([
                paged_attention_ragged_v2(
                    q[a:a + step], k_pages, v_pages, page_tables,
                    lane_slots[a:a + step], lane_lens[a:a + step],
                    k_scales=k_scales, v_scales=v_scales, scale=scale,
                    block_kv=block_kv, use_pallas=use_pallas,
                    interpret=interpret, window=window,
                    page_base=page_base, out_dtype=out_dtype)
                for a in range(0, q.shape[0], step)], axis=0)
        work = build_work_list(page_tables, lane_slots, lane_lens,
                               page_size=ps, block_pages=bp,
                               window=window)
    return _ragged_v2_pallas(
        q, k_pages, v_pages, work, scale, impl == PALLAS_INTERPRET,
        k_scales=k_scales, v_scales=v_scales, window=int(window),
        short=has_short_body(q.shape[1] // heads, work.q_rows),
        page_base=page_base, out_dtype=out_dtype)
