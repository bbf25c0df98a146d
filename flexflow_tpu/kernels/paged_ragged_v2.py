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

  * ONE FLATTENED GRID over (lane, kv-block) work items: grid
    (T * num_kv_blocks,), item w -> lane w // nb, kv-block w % nb. A
    kv-block covers `block_kv_pages` pages — several page DMAs land per
    grid step (one BlockSpec per page slot, so Mosaic pipelines them),
    and the per-lane step count drops pages_per_seq / block_kv_pages x.
  * RAGGED SKIPPING: a work item whose kv-block starts past its lane's
    visible length is DEAD — `pl.when` skips its entire accumulation
    (v1 computed and masked it), and its page index maps clamp to the
    lane's last live block so no new DMA is issued for dead tail items.
  * HEAD PACKING for small head_dim: page blocks stream as
    (page_size, H*D) rows — the layout is already contiguous in HBM, so
    this is a free reshape that fills 128-lane VMEM tiles where
    (page_size, H, D) tiling padded D up to 128 — and STAY packed: the
    per-head reductions are matmuls with a 0/1 segment matrix (see the
    kernel section), because Mosaic cannot split a lane dimension.
  * TUNABLE KV-BLOCK SHAPES: `block_kv` (tokens per work item; FFConfig
    serve_attn_block_kv / --serve-attn-block-kv) with an
    autotune-by-shape table supplying defaults — sized so each step's
    K+V DMA traffic amortizes issue overhead without exceeding a VMEM
    budget. Measured entries can be registered (tools/flash_sweep.py
    style) and override the analytic pick.
  * QUANTIZED KV PAGES: int8 K/V pages ride with per-page scale arrays
    (one f32 scale per head per in-page slot — see serve/kv_cache.py
    for why scales are per-slot, not per-whole-page); the kernel DMAs
    the int8 block + its scale rows and applies the scales to the
    scores and the probabilities (algebraically the dequantized
    product) inside the online-softmax accumulation. bf16 pages need
    no scales (values upcast exactly like v1's bf16 handling).

Numerics contract: the jnp fallback is BIT-IDENTICAL to v1's
(`flash_attention._paged_decode_jnp`) on fp32 — same gather, same
dot_general dims, same single-pass softmax — so every existing
bit-equality oracle (full-prefill per lane, one-lane == decode) holds
verbatim under v2. The Pallas kernel is the same online softmax summed
in another order, with every product in f32, so it agrees with the jnp
path to f32 rounding on every page format (2e-6 in the interpreter,
tests/test_kv_quant.py; on the chip tests_tpu/test_serve_tpu.py and
chip_smoke.py state their tolerances); the QUANTIZATION error itself is
gated by the bounded-error + greedy-parity tests (tests/test_kv_quant.py).
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

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
# Analytic targets for choose_block_kv: each work item should move at
# least DMA_TARGET_BYTES of K+V so the per-step DMA issue cost is
# amortized, while the resident K/V (+ scale) blocks stay under
# VMEM_BUDGET_BYTES (Pallas double-buffers them, hence the /2).
DMA_TARGET_BYTES = 32 * 1024
VMEM_BUDGET_BYTES = 512 * 1024

# (page_size, num_heads, head_dim, kv_itemsize, pages_per_seq) ->
# block_kv tokens. Seeded analytically on first use; measured sweeps
# (register_block_kv) override — the "autotune-by-shape table".
_BLOCK_KV_TABLE: Dict[Tuple[int, int, int, int, int], int] = {}


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
    whole-page multiple whose K+V DMA reaches DMA_TARGET_BYTES, capped
    by the VMEM budget and the table width. Always a multiple of
    page_size and >= one page."""
    key = (page_size, num_heads, head_dim, kv_itemsize, pages_per_seq)
    got = _BLOCK_KV_TABLE.get(key)
    if got is not None:
        return got
    per_tok = 2 * num_heads * head_dim * kv_itemsize  # K + V
    if kv_itemsize == 1:  # quantized (int8/fp8) pages also stream
        per_tok += 2 * num_heads * 4  # their f32 scale rows
    want = max(1, -(-DMA_TARGET_BYTES // (per_tok * page_size)))
    cap = max(1, (VMEM_BUDGET_BYTES // 2) // (per_tok * page_size))
    ppb = min(max(1, want), cap, pages_per_seq)
    block = ppb * page_size
    _BLOCK_KV_TABLE[key] = block
    return block


def ragged_dispatch_passes(num_lanes: int, pages_per_seq: int,
                           block_kv_pages: int) -> Dict[str, int]:
    """Grid-step accounting for the serve bench: the v1 kernel runs one
    grid step per (lane, page); v2 runs one per (lane, kv-block)."""
    nb = -(-pages_per_seq // max(1, block_kv_pages))
    return {"v1": num_lanes * pages_per_seq, "v2": num_lanes * nb}


# ------------------------------------------------------------ jnp paths
def _ragged_jnp(q, k_pages, v_pages, page_tables, lane_slots, lane_lens,
                scale, k_scales=None, v_scales=None):
    """Vectorized fallback over the flattened ragged layout.

    Gathers each lane's pages (int8 gathers move 1/4 the bytes of f32),
    dequantizes, and runs EXACTLY v1's math — same dot_general dims,
    same masked single-pass softmax, same divide-after-matmul — so fp32
    outputs are bit-identical to `flash_attention._paged_decode_jnp`
    (the oracle every serve parity test is built on)."""
    b, h, d = q.shape
    ps = k_pages.shape[1]
    lane_tables = jnp.take(page_tables, lane_slots, axis=0)  # (T, pp)
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
    s = jax.lax.dot_general(
        q, k, (((2,), (3,)), ((0, 1), (0, 2))),
        preferred_element_type=jnp.float32) * scale     # (T, H, pp*ps)
    pos = jax.lax.broadcasted_iota(jnp.int32, (b, 1, pp * ps), 2)
    s = jnp.where(pos < lane_lens[:, None, None], s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jax.lax.dot_general(
        p, v.astype(jnp.float32), (((2,), (1,)), ((0, 1), (0, 2))),
        preferred_element_type=jnp.float32)
    return (o / l).astype(q.dtype)


# --------------------------------------------------------- Pallas kernel
# Everything inside the kernel is a 2-D array with the packed H*D axis
# on the 128-lane dimension: Mosaic refuses to split a lane dimension
# (reshape (ps, H*D) -> (ps, H, D): "unsupported shape cast") and to
# batch a matmul over a non-leading axis, which is how a per-head dot
# over (bs, H, D) blocks has to be written. The per-head reductions
# are instead matmuls with a 0/1 SEGMENT matrix seg (H*D, H),
# seg[j, h] = (j // D == h):
#
#   scores   s[t, h]  = sum_d q[h, d] k[t, h, d] = ((k * q_row) @ seg)[t, h]
#   weighted o[h*D+d] = sum_t p[t, h] v[t, h, d] = sum_t ((p @ seg^T) * v)[t, h*D+d]
#
# Tokens sit on sublanes and heads on lanes, so the per-(token, head)
# scales of quantized pages multiply s and p directly and K/V are never
# dequantized. The segment matmuls run on f32 operands at HIGHEST
# precision: the kernel's result is f32-accurate for every page format.
_MASK = -0.5 * float(jnp.finfo(jnp.float32).max)  # finite: exp(_MASK - m)
#                                 is exactly 0 and never inf - inf = NaN
_HIGHEST = jax.lax.Precision.HIGHEST


def _seg_dot(a, b):
    return jnp.dot(a, b, precision=_HIGHEST,
                   preferred_element_type=jnp.float32)


def _ragged_v2_kernel(pt_ref, ls_ref, ll_ref, q_ref, seg_ref, segt_ref,
                      *refs, page_size, num_blocks, block_pages, scale,
                      quantized):
    """Flattened-grid kernel body. Grid (T * num_blocks,); work item
    w covers kv positions [blk * block_pages * ps, ...) of lane
    w // num_blocks. Page refs arrive head-PACKED as (1, ps, H*D)
    blocks (plus (1, ps, H) scale blocks when quantized); dead items
    (block start past the lane's visible length) skip their whole
    accumulation."""
    per_page = 4 if quantized else 2
    kv_refs = refs[:per_page * block_pages]
    o_ref, m_ref, l_ref, acc_ref = refs[per_page * block_pages:]

    w = pl.program_id(0)
    t = w // num_blocks
    blk = w % num_blocks
    length = ll_ref[t]

    @pl.when(blk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _MASK)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    base = blk * block_pages * page_size

    def rows(j):
        """Page slot j's blocks of this work item, stacked on the
        token (sublane) axis: (block_pages * ps, ...)."""
        parts = [kv_refs[per_page * i + j][0] for i in range(block_pages)]
        return parts[0] if block_pages == 1 else jnp.concatenate(parts, 0)

    # dead item: this block starts at or past the lane's visible
    # length (lane_lens >= 1, so block 0 is always live) — skip the
    # entire accumulation. v1 computed the full masked block here.
    @pl.when(base < length)
    def _accumulate():
        q = q_ref[0].astype(jnp.float32)                  # (1, H*D)
        k = rows(0).astype(jnp.float32)                   # (bs, H*D)
        v = rows(2 if quantized else 1).astype(jnp.float32)
        s = _seg_dot(k * q, seg_ref[...])                 # (bs, H)
        if quantized:
            s = s * rows(1)                               # k scales
        s = s * scale
        pos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        s = jnp.where(pos < length, s, _MASK)
        m_prev = m_ref[...]                               # (1, H)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)                            # masked -> 0
        alpha = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=0,
                                                  keepdims=True)
        if quantized:
            p = p * rows(3)                               # v scales
        # ONE pass over seg^T expands both p and the accumulator's
        # rescale factor from per-head to per-(head, dim) lanes
        bs = p.shape[0]
        x = _seg_dot(
            jnp.concatenate([p, jnp.broadcast_to(alpha, (8, alpha.shape[1]))],
                            axis=0), segt_ref[...])       # (bs + 8, H*D)
        acc_ref[...] = acc_ref[...] * x[bs:bs + 1] + jnp.sum(
            x[:bs] * v, axis=0, keepdims=True)

    @pl.when(blk == num_blocks - 1)
    def _emit():
        l = l_ref[...]
        lx = _seg_dot(jnp.broadcast_to(l, (8, l.shape[1])), segt_ref[...])
        o_ref[0] = (acc_ref[...] / lx[0:1]).astype(o_ref.dtype)


def _vmem_limit(block_bytes: int) -> int:
    """Scoped-VMEM request for a kernel whose pipelined blocks, scratch
    and live intermediates total `block_bytes`: twice that (Pallas
    double-buffers every block), never under Mosaic's own 16 MiB
    default, capped at half a v5e core's 128 MiB."""
    return int(min(max(2 * block_bytes, 16 * 2**20), 64 * 2**20))


def _ragged_v2_pallas(q, k_pages, v_pages, page_tables, lane_slots,
                      lane_lens, scale, block_kv_pages, interpret,
                      k_scales=None, v_scales=None):
    t, h, d = q.shape
    npages, ps = k_pages.shape[0], k_pages.shape[1]
    pp = page_tables.shape[1]
    bp = max(1, min(int(block_kv_pages), pp))
    nb = -(-pp // bp)
    quantized = k_scales is not None
    hd = h * d

    # head packing: pages stream as (ps, H*D) rows and q / out as
    # (1, H*D) rows — contiguous in HBM, so the reshapes are free
    kp = k_pages.reshape(npages, ps, hd)
    vp = v_pages.reshape(npages, ps, hd)
    seg = (jnp.arange(hd, dtype=jnp.int32)[:, None] // d
           == jnp.arange(h, dtype=jnp.int32)[None, :]).astype(jnp.float32)

    def lane_of(w):
        """Work item -> lane, CLAMPED to the last lane. Mosaic's
        pipeline also evaluates the index maps for the step after the
        grid's last one (to prefetch a block it then never uses), and
        an index map that reads the prefetched scalars at lane T reads
        SMEM past their end — on a v5e lane_slots[T] happens to be
        lane_lens[0], and a 2048-token lane made page_tables[2048, .]
        a bad_smem_address core halt. Every SMEM read in an index map
        must be in range for ANY w."""
        return jnp.minimum(w // nb, t - 1)

    def page_index(i):
        """Index map for page slot i of each work item: the physical
        page at table column blk*bp + i of the item's lane, CLAMPED to
        the lane's last live column — dead tail items re-select a page
        already resident, so they issue no new DMA (their compute is
        pl.when-skipped anyway)."""
        def imap(w, pt, ls, ll):
            tt = lane_of(w)
            col = (w % nb) * bp + i
            # clamp into both the table and the lane's live range so
            # dead items never demand a fresh (sink) page DMA
            live_last = jnp.maximum((ll[tt] - 1) // ps, 0)
            col = jnp.minimum(jnp.minimum(col, pp - 1), live_last)
            return (pt[ls[tt], col], 0, 0)
        return imap

    def lane_index(w, pt, ls, ll):
        return (lane_of(w), 0, 0)

    def whole(w, pt, ls, ll):
        return (0, 0)

    in_specs = [pl.BlockSpec((1, 1, hd), lane_index),
                pl.BlockSpec((hd, h), whole),
                pl.BlockSpec((h, hd), whole)]
    args = [q.reshape(t, 1, hd), seg, seg.T]
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
    kern = functools.partial(
        _ragged_v2_kernel, page_size=ps, num_blocks=nb, block_pages=bp,
        scale=scale, quantized=quantized)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # page_tables, lane_slots, lane_lens
        grid=(t * nb,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, hd), lane_index),
        scratch_shapes=[
            pltpu.VMEM((1, h), jnp.float32),    # running max
            pltpu.VMEM((1, h), jnp.float32),    # running sum
            pltpu.VMEM((1, hd), jnp.float32),   # output accumulator
        ],
    )
    bs = bp * ps
    lanes = -(-h // 128) * 128      # a (.., h) f32 tile pads to 128 lanes
    block_bytes = (
        2 * bs * hd * jnp.dtype(k_pages.dtype).itemsize     # K + V pages
        + (2 * bs * lanes * 4 if quantized else 0)          # their scales
        + hd * lanes * 4 + max(h, 8) * hd * 4               # seg, seg^T
        + 5 * (bs + 8) * hd * 4)    # f32 K, V, k*q, p@seg^T, its product
    out = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, 1, hd), q.dtype),
        # the grid axis carries the online-softmax scratch from one
        # work item of a lane to the next: it must run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_limit(block_bytes)),
        interpret=interpret,
        name="paged_ragged_v2",
    )(page_tables, lane_slots, lane_lens, *args)
    return out.reshape(t, h, d)


def kv_read_bytes(lane_lens, lane_slots, page_tables, *, page_size: int,
                  num_heads: int, head_dim: int, kv_itemsize: int,
                  block_kv_pages: int = 1, quantized: bool = False) -> int:
    """Bytes of K and V pages (and their scale rows on a quantized
    pool) that ONE call of the kernel above fetches from HBM, for the
    whole step's lanes (numpy; host side, no device work).

    It mirrors `page_index`: page slot i of work item (lane t, block
    blk) selects table column min(blk * bp + i, pp - 1, (len_t - 1) //
    ps) of the lane's row, and Pallas's pipeline fetches a block only
    when its index differs from the previous grid step's. So a lane
    fetches each of its live columns once per slot — lanes of one chunk
    each re-read their sequence's pages — dead tail items fetch
    nothing, and a lane whose first page is the one the lane before it
    ended on (the run of inactive lanes on the sink page) fetches
    nothing for it either. Heads are counted whole: a tensor-parallel
    step fetches the same bytes summed over its chips."""
    ll = np.asarray(lane_lens, np.int64)
    rows = np.asarray(page_tables)[np.asarray(lane_slots, np.int64)]
    pp = rows.shape[1]
    bp = max(1, min(int(block_kv_pages), pp))
    nb = -(-pp // bp)
    last = np.minimum(np.maximum((ll - 1) // page_size, 0), pp - 1)
    lanes = np.arange(len(ll))
    fetches = 0
    for i in range(bp):
        # columns blk * bp + i below the clamp are all distinct; the
        # clamped ones (if any) repeat one column, `last`
        below = np.clip(-(-(last - i) // bp), 0, nb)
        fetches += int(np.sum(below + (below < nb)))
        first = rows[lanes, np.minimum(i, last)]
        end = rows[lanes, np.minimum((nb - 1) * bp + i, last)]
        fetches -= int(np.sum(first[1:] == end[:-1]))
    per_page = 2 * page_size * num_heads * head_dim * kv_itemsize
    if quantized:
        per_page += 2 * page_size * num_heads * 4       # f32 scale rows
    return fetches * per_page


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
                              use_pallas=None, interpret=False):
    """Ragged batched attention through page tables — kernel v2.

    Same contract as flash_attention.paged_attention_ragged (q (T,H,D),
    one query token per lane; page 0 = sink; every lane_lens >= 1) plus:

      k_scales/v_scales — (num_pages, page_size, H) f32 per-page scale
        arrays for int8 K/V pages (None = unquantized pages; the two
        must be both present or both absent).
      block_kv — KV tokens per flattened work item (None = the
        autotune-by-shape table via choose_block_kv; rounded to whole
        pages).

    fp32 outputs are bit-identical to v1 on the jnp path (same math);
    the Pallas kernel agrees with it to f32 rounding (it sums in a
    different order). use_pallas/interpret pick the implementation by
    resolve_paged_impl.
    """
    if (k_scales is None) != (v_scales is None):
        raise ValueError("k_scales and v_scales must be given together")
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    impl = resolve_paged_impl(use_pallas, interpret)
    if impl == JNP:
        return _ragged_jnp(q, k_pages, v_pages, page_tables, lane_slots,
                           lane_lens, scale, k_scales=k_scales,
                           v_scales=v_scales)
    ps = k_pages.shape[1]
    if block_kv is None:
        block_kv = choose_block_kv(
            ps, page_tables.shape[1], q.shape[1], q.shape[2],
            jnp.dtype(k_pages.dtype).itemsize)
    return _ragged_v2_pallas(
        q, k_pages, v_pages, page_tables, lane_slots, lane_lens,
        scale, max(1, int(block_kv) // ps), impl == PALLAS_INTERPRET,
        k_scales=k_scales, v_scales=v_scales)
