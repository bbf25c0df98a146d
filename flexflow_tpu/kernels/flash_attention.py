"""Flash attention (Pallas, TPU): the training attention core.

Replaces the reference's single cuDNN fused-MHA call
(src/ops/attention.cu:245 cudnnMultiHeadAttnForward) with an online-softmax
blocked kernel that never materializes the (Lq, Lk) score matrix in HBM.

Forward is a Pallas kernel (grid over (batch, head slabs, q-blocks), inner
loops over k-blocks with online max/sum rescaling). Backward is two
Pallas kernels (dq over q-blocks; dk/dv over k-blocks) that recompute
probabilities from the saved logsumexp — exact gradients with no saved or
materialized probability tensor.

All MXU dots run in the input dtype (bf16 on TPU) with float32
accumulation (`preferred_element_type`); softmax statistics stay float32.
Casting to f32 *before* the dot would push the matmuls off the MXU's
native bf16 path and cost ~4x.

Layout contract: the kernels read and write the projections' OWN layout.
q, k, v leave `einsum("bse,ehd->bshd")` contiguous as (batch, seq,
heads*head_dim) and the output feeds `einsum("bshd,hde->bse")` the same
way, so `flash_attention_bshd` only reshapes (a bitcast): nothing is
transposed in HBM, forward or backward. The lane axis is cut into SLABS
of whole heads (`paged_ragged_v2._slab_geometry`, the serving kernel's
rule): two 64-wide heads, four 32-wide, or one head of a 128-lane
multiple. A grid step takes one slab's (block_q, W) rows against that
slab's (seq_k, W) K and V, resident for all q-blocks of the slab. The
heads of a slab go through the MXU together as ONE block-diagonal
product — `[q_a|0 ; 0|q_b]` against a W-lane K block gives both heads'
scores stacked on rows at full depth, with no lane shuffles — and the
softmax statistics are per (head, row). The logsumexp and `delta` live
as (batch, slabs, heads-a-slab, seq) with the sequence on lanes, so
they take their own size in HBM and VMEM, not 128 lanes a number. A
head shape whose slab does not fill whole 128-lane tiles (an odd count
of 64-wide heads, a head_dim that neither divides nor is a multiple of
128) is zero-padded on the lane axis alone, which is exact.

Set `interpret=True` to run the same kernels through the Pallas
interpreter on CPU — used by tests/test_flash_attention.py on the forced
CPU platform.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from .paged_ragged_v2 import _by_head, _slab_geometry, _vmem_limit

MIN_BLOCK = 128      # one lane tile: the floor of every block size
# the analytic pick's ceilings (choose_flash_blocks): the stacked f32
# score tile of a step is heads-a-slab x block_q x block_k numbers
MAX_BLOCK_Q = 512
MAX_BLOCK_K = 512


def _dot_t(a, b):
    """a (m, d) . b^T (d, n) -> (m, n), contracting the last dims."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot(a, b):
    return jax.lax.dot(a, b, preferred_element_type=jnp.float32)


def _fold_scale(q, scale):
    """(q', s_scale): the 1/sqrt(d) of the scores folded into q where
    that is EXACT (a power of two, as at d = 64: the product's bits are
    the unfolded product's), else left to a multiply of the f32
    scores."""
    if math.frexp(scale)[0] == 0.5:
        return (q * scale).astype(q.dtype), None
    return q, scale


def _blockdiag(x, g, d):
    """x (rows, g*d) -> (g*rows, g*d): head i's rows stacked at i*rows
    with every lane outside head i's own d zeroed. ONE full-depth
    product of this against a (n, g*d) block yields each head's own
    product, stacked on rows."""
    if g == 1:
        return x
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    zero = jnp.zeros_like(x)
    return jnp.concatenate(
        [jnp.where((lane >= i * d) & (lane < (i + 1) * d), x, zero)
         for i in range(g)], axis=0)


def _cols_to_rows(col, rows, g):
    """(g*rows, 1) per-(head, row) numbers -> (g, rows), rows on lanes:
    one f32 tile transpose a q-block, not one a k-step."""
    seg = 128 // g
    x = _by_head(jnp.broadcast_to(col, (g * rows, 128)), rows, g, seg)
    xt = x.T                                              # (128, rows)
    return jnp.concatenate([xt[i * seg:i * seg + 1] for i in range(g)],
                           axis=0)


def _rows_to_cols(row, rows, g):
    """(g, rows) -> (g*rows, 1): the way back."""
    seg = 128 // g
    x = jnp.concatenate([jnp.broadcast_to(row[i:i + 1], (seg, rows))
                         for i in range(g)], axis=0)      # (128, rows)
    xt = x.T                                              # (rows, 128)
    return jnp.concatenate([xt[:, i * seg:i * seg + 1] for i in range(g)],
                           axis=0)


def _visible(q0, k0, block_q, block_k, g, transposed=False):
    """Where query q0+i sees key k0+j (top-left aligned: j <= i), for g
    heads' query rows stacked: (g*block_q, block_k), or its transpose
    (block_k, g*block_q) with the heads side by side on lanes."""
    shape = (block_k, g * block_q) if transposed else (g * block_q, block_k)
    qax = 1 if transposed else 0
    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, qax) % block_q
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - qax)
    return qpos >= kpos


def _k_spans(causal, qi, block_q, block_k, num_kb):
    """(plain, masked) spans of k-blocks for q-block qi: causal, [0,
    full) lie wholly on or below the diagonal (no mask), [full, end)
    cross it (masked) and the rest lie wholly above and are skipped;
    else every block is plain."""
    if not causal:
        return (0, num_kb), None
    full = jnp.minimum(num_kb, (qi * block_q) // block_k)
    end = jnp.minimum(num_kb, ((qi + 1) * block_q + block_k - 1) // block_k)
    return (0, full), (full, end)


def _loop_blocks(step, carry, plain, masked):
    """step(False) over the plain span of blocks, step(True) over the
    span that crosses the diagonal: those blocks alone carry the
    mask."""
    carry = jax.lax.fori_loop(*plain, step(False), carry)
    if masked is not None:
        carry = jax.lax.fori_loop(*masked, step(True), carry)
    return carry


def _compiler_params(sq, sk, w, g, block_q, block_k, dtype):
    """No grid step of the flash kernels carries state to the next
    (each writes its own output block); the resident whole-sequence
    operands and the stacked score tiles need more scoped VMEM than
    Mosaic's 16 MiB default from about 4k tokens on."""
    need = _flash_resident_bytes(sq, sk, w, jnp.dtype(dtype).itemsize) \
        + 8 * g * block_q * block_k * 4
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel"),
        vmem_limit_bytes=_vmem_limit(need + 4 * 2**20))


# ---------------------------------------------------------------- forward
def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                      block_q, block_k, seq_k, scale, causal, heads,
                      head_dim):
    qi = pl.program_id(2)
    g, d = heads, head_dim
    # (g*bq, W), native dtype: the dots ride the MXU's bf16 path
    q2, s_scale = _fold_scale(_blockdiag(q_ref[...], g, d), scale)
    rows, w = q2.shape

    def step(masked):
        def body(j, carry):
            m, l, acc = carry
            ks = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
            v = v_ref[ks, :]
            s = _dot_t(q2, k_ref[ks, :])                  # f32 accumulate
            if s_scale:
                s = s * s_scale
            if masked:
                s = jnp.where(_visible(qi * block_q, j * block_k, block_q,
                                       block_k, g), s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=1, keepdims=True)
            return m_new, l_new, acc * alpha + _dot(p.astype(v.dtype), v)
        return body

    m, l, acc = _loop_blocks(
        step, (jnp.full((rows, 1), -jnp.inf, jnp.float32),
               jnp.zeros((rows, 1), jnp.float32),
               jnp.zeros((rows, w), jnp.float32)),
        *_k_spans(causal, qi, block_q, block_k, seq_k // block_k))
    o_ref[...] = _by_head(acc / l, block_q, g, d).astype(o_ref.dtype)
    lse_ref[...] = _cols_to_rows(m + jnp.log(l), block_q, g)


def _specs(g, w, block, seq):
    """BlockSpecs over (b, s, H*D) arrays and the (b, slabs, g, s) row
    statistics for grid (b, slab p, block i): one block of the gridded
    sequence, the other sequence whole (resident for every i of p)."""
    blk = pl.BlockSpec((None, block, w), lambda b, p, i: (b, i, p))
    whole = pl.BlockSpec((None, seq, w), lambda b, p, i: (b, 0, p))
    blk_row = pl.BlockSpec((None, None, g, block),
                           lambda b, p, i: (b, p, 0, i))
    return blk, whole, blk_row


def _kernel_kw(q, heads, causal, scale, block_q, block_k):
    """(heads a slab, lanes a slab, the kernels' static arguments) of
    a call over q (b, s, H*D)."""
    hd = q.shape[2]
    g, w = _slab_geometry(heads, hd // heads)
    return g, w, dict(block_q=block_q, block_k=block_k, scale=scale,
                      causal=causal, heads=g, head_dim=hd // heads)


# jitted on their own: a model's layers make the same three calls, and
# tracing and lowering a kernel body is host time before the compile
# cache can even be asked — a nested jit pays it once (as
# paged_ragged_v2._ragged_v2_pallas does)
@functools.partial(jax.jit, static_argnames=(
    "heads", "causal", "scale", "block_q", "block_k", "interpret"))
def flash_fwd(q, k, v, *, heads, causal, scale, block_q, block_k,
              interpret):
    """q (b, sq, H*D), k, v (b, sk, H*D) -> o (b, sq, H*D) and the
    logsumexp (b, slabs, g, sq) f32."""
    b, sq, hd = q.shape
    sk = k.shape[1]
    g, w, kw = _kernel_kw(q, heads, causal, scale, block_q, block_k)
    blk, whole, blk_row = _specs(g, w, block_q, sk)
    return pl.pallas_call(
        functools.partial(_flash_fwd_kernel, seq_k=sk, **kw),
        grid=(b, hd // w, sq // block_q),
        in_specs=[blk, whole, whole],
        out_specs=[blk, blk_row],
        out_shape=[
            jax.ShapeDtypeStruct((b, sq, hd), q.dtype),
            jax.ShapeDtypeStruct((b, hd // w, g, sq), jnp.float32),
        ],
        compiler_params=_compiler_params(sq, sk, w, g, block_q, block_k,
                                         q.dtype),
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)


# --------------------------------------------------------------- backward
def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, *, block_q, block_k, seq_k, scale, causal,
                         heads, head_dim):
    qi = pl.program_id(2)
    g, d = heads, head_dim
    q2, s_scale = _fold_scale(_blockdiag(q_ref[...], g, d), scale)
    do2 = _blockdiag(do_ref[...], g, d)    # (g*bq, W)
    lse = _rows_to_cols(lse_ref[...], block_q, g)      # (g*bq, 1) f32
    delta = _rows_to_cols(delta_ref[...], block_q, g)

    def step(masked):
        def body(j, acc):
            ks = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
            k = k_ref[ks, :]
            s = _dot_t(q2, k)
            if s_scale:
                s = s * s_scale
            if masked:
                s = jnp.where(_visible(qi * block_q, j * block_k, block_q,
                                       block_k, g), s, -jnp.inf)
            p = jnp.exp(s - lse)         # masked -inf exp to exactly 0
            dp = _dot_t(do2, v_ref[ks, :])
            # dS's own 1/sqrt(d) waits for the f32 sum (below)
            return acc + _dot((p * (dp - delta)).astype(k.dtype), k)
        return body

    acc = _loop_blocks(
        step, jnp.zeros(q2.shape, jnp.float32),
        *_k_spans(causal, qi, block_q, block_k, seq_k // block_k))
    dq_ref[...] = (_by_head(acc, block_q, g, d) * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, block_q, block_k, seq_q, scale,
                          causal, heads, head_dim):
    """Works on the TRANSPOSED scores (block_k, g*block_q): the row
    statistics broadcast over sublanes as they are stored, and all four
    products are plain (m, k) x (k, n) or (m, k) x (n, k)^T matmuls."""
    kj = pl.program_id(2)
    g, d = heads, head_dim
    k = k_ref[...]  # (bk, W)
    v = v_ref[...]
    num_qb = seq_q // block_q

    def step(masked):
        def body(i, carry):
            dk, dv = carry
            qs = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
            q2 = _blockdiag(q_ref[qs, :], g, d)        # (g*bq, W)
            qf, s_scale = _fold_scale(q2, scale)
            do2 = _blockdiag(do_ref[qs, :], g, d)
            row = lambda ref: jnp.concatenate(  # noqa: E731
                [ref[h:h + 1, qs] for h in range(g)], axis=1)
            st = _dot_t(k, qf)                         # (bk, g*bq)
            if s_scale:
                st = st * s_scale
            if masked:
                st = jnp.where(_visible(i * block_q, kj * block_k, block_q,
                                        block_k, g, transposed=True),
                               st, -jnp.inf)
            pt = jnp.exp(st - row(lse_ref))
            dv = dv + _dot(pt.astype(do2.dtype), do2)
            dpt = _dot_t(v, do2)
            dst = (pt * (dpt - row(delta_ref))).astype(q2.dtype)
            return dk + _dot(dst, q2), dv
        return body

    plain, masked = (0, num_qb), None
    if causal:
        # q-blocks wholly left of this k-block see none of it; those
        # that cross the diagonal carry the mask; the rest see it whole
        start = jnp.minimum(num_qb, (kj * block_k) // block_q)
        full = jnp.minimum(
            num_qb, ((kj + 1) * block_k - 1 + block_q - 1) // block_q)
        plain, masked = (full, num_qb), (start, full)
    dk, dv = _loop_blocks(step, (jnp.zeros(k.shape, jnp.float32),
                                 jnp.zeros(v.shape, jnp.float32)),
                          plain, masked)
    dk_ref[...] = (dk * scale).astype(dk_ref.dtype)  # dS's 1/sqrt(d)
    dv_ref[...] = dv.astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "heads", "causal", "scale", "block_q", "block_k", "interpret"))
def flash_bwd_dq(q, k, v, do, lse, delta, *, heads, causal, scale, block_q,
                 block_k, interpret):
    b, sq, hd = q.shape
    sk = k.shape[1]
    g, w, kw = _kernel_kw(q, heads, causal, scale, block_q, block_k)
    blk, whole, blk_row = _specs(g, w, block_q, sk)
    return pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, seq_k=sk, **kw),
        grid=(b, hd // w, sq // block_q),
        in_specs=[blk, whole, whole, blk, blk_row, blk_row],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct((b, sq, hd), q.dtype),
        compiler_params=_compiler_params(sq, sk, w, g, block_q, block_k,
                                         q.dtype),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse, delta)


@functools.partial(jax.jit, static_argnames=(
    "heads", "causal", "scale", "block_q", "block_k", "interpret"))
def flash_bwd_dkv(q, k, v, do, lse, delta, *, heads, causal, scale, block_q,
                  block_k, interpret):
    b, sq, hd = q.shape
    sk = k.shape[1]
    g, w, kw = _kernel_kw(q, heads, causal, scale, block_q, block_k)
    blk, whole, _ = _specs(g, w, block_k, sq)
    whole_row = pl.BlockSpec((None, None, g, sq),
                             lambda b, p, j: (b, p, 0, 0))
    return pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, seq_q=sq, **kw),
        grid=(b, hd // w, sk // block_k),
        in_specs=[whole, blk, blk, whole, whole_row, whole_row],
        out_specs=[blk, blk],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        compiler_params=_compiler_params(sq, sk, w, g, block_q, block_k,
                                         q.dtype),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, lse, delta)


# ---------------------------------------------------------- custom VJP
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, heads, causal, scale, blocks, interpret):
    return _flash_fwd(q, k, v, heads, causal, scale, blocks, interpret)[0]


def _flash_fwd(q, k, v, heads, causal, scale, blocks, interpret):
    o, lse = flash_fwd(q, k, v, heads=heads, causal=causal, scale=scale,
                       block_q=blocks[0], block_k=blocks[1],
                       interpret=interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(heads, causal, scale, blocks, interpret, res, do):
    q, k, v, o, lse = res
    b, sq, hd = q.shape
    # delta_i = rowsum(do * o) per head: cheap elementwise, fused by
    # XLA, laid out like the logsumexp (sequence on lanes)
    delta = jnp.sum((do.astype(jnp.float32) * o.astype(jnp.float32))
                    .reshape(b, sq, heads, hd // heads), axis=-1)
    delta = jnp.swapaxes(delta, 1, 2).reshape(lse.shape)
    kw = dict(heads=heads, causal=causal, scale=scale, interpret=interpret)
    dq = flash_bwd_dq(q, k, v, do, lse, delta, block_q=blocks[0],
                      block_k=blocks[1], **kw)
    dk, dv = flash_bwd_dkv(q, k, v, do, lse, delta, block_q=blocks[2],
                           block_k=blocks[3], **kw)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


# ------------------------------------------------- blocks, gate, dispatch
# (sq, sk, heads a slab, itemsize) -> (block_q, block_k) of the forward
# and dQ kernels, (block_q, block_k) of the dK/dV kernel. Measured
# entries come first; every other shape gets the analytic pick.
# register_flash_blocks overrides either — the "autotune-by-shape
# table", as paged_ragged_v2's choose_block_kv keeps for the serving
# kernel.
_BLOCK_TABLE: Dict[Tuple[int, int, int, int], Tuple[int, int, int, int]] = {
    # OPT-1.3B's training attention, 2048 tokens, two 64-wide bf16 heads
    # a slab (benchmark/configs/opt-1.3b-l8.json): us a call at (block_q,
    # block_k), forward | dQ | dK/dV (the sweep above): 128 x 128 1205 |
    # 996 | 1181, 256 x 256 645 | 534 | 568, 256 x 512 493 | 477 | 582,
    # 512 x 512 513 | 460 | 543, 1024 x 512 597 | 531 | 613, 1024 x 1024
    # 562 | 514 | 624
    (2048, 2048, 2, 2): (512, 512, 512, 512),
}


def register_flash_blocks(sq: int, sk: int, slab_heads: int, itemsize: int,
                          blocks: Tuple[int, int, int, int]) -> None:
    """Pin measured block shapes for a call shape."""
    _BLOCK_TABLE[(sq, sk, slab_heads, itemsize)] = tuple(map(int, blocks))


def _largest_block(seq: int, cap: int) -> int:
    """The largest power-of-two multiple of MIN_BLOCK that divides seq,
    at most cap."""
    blk = MIN_BLOCK
    while blk * 2 <= cap and seq % (blk * 2) == 0:
        blk *= 2
    return blk


def choose_flash_blocks(sq: int, sk: int, slab_heads: int = 1,
                        itemsize: int = 2) -> Tuple[int, int, int, int]:
    """(block_q, block_k) of forward / dQ and (block_q, block_k) of
    dK/dV for a call shape: the table's entry if one is registered,
    else the largest blocks up to MAX_BLOCK_Q x MAX_BLOCK_K that divide
    the sequences (128 x 128 is the floor: a larger block is fewer loop
    steps and fewer rescales of the accumulator a score)."""
    got = _BLOCK_TABLE.get((sq, sk, slab_heads, itemsize))
    if got is not None:
        return got
    bq = _largest_block(sq, MAX_BLOCK_Q)
    bk = _largest_block(sk, MAX_BLOCK_K)
    return bq, bk, bq, bk


def _lane_pad(h: int, d: int) -> Tuple[int, int]:
    """(heads, head_dim) the kernels run for a call's (h, d): its own
    when its slab fills whole 128-lane tiles, else the nearest that
    does — zero heads up to a whole slab where head_dim divides 128,
    zero lanes a head up to a multiple of 128 where it does not."""
    if _slab_geometry(h, d)[1] % 128 == 0:
        return h, d
    if 128 % d == 0:
        per = 128 // d
        return -(-h // per) * per, d
    return h, -(-d // 128) * 128


# The f32 scores of one call, in bytes, above which the kernels beat the
# XLA path: under it XLA keeps the whole (b, h, sq, sk) score tensor in
# the core's 128 MiB of VMEM and never pays HBM for it; over it the
# scores cross HBM about nine times a layer, forward and backward. From
# the chip sweep of PR 31 (`tools/flash_sweep.py` on a TPU v5 lite,
# 2026-09-28, `evidence/flash_sweep_tpu.json`: bf16, causal, forward +
# backward, microseconds a layer, flash | XLA): XLA wins at 33 MiB
# ((1, 32, 512, 512, 64): 179 | 74; (2, 16, 512, 512, 128): 204 | 91)
# and at 48 MiB ((1, 3, 2048, 2048, 64): 201 | 123); flash wins at 64
# MiB ((1, 64, 512, 512, 32): 317 | 481), at 128 MiB ((1, 32, 1024,
# 1024, 64): 537 | 940; (2, 16, 1024, 1024, 128): 604 | 1022) and from
# there on by more ((1, 32, 2048, 2048, 64): 1543 | 4958; (1, 32, 4096,
# 4096, 64): 4941 | 28976), at head sizes 32, 64 and 128 alike.
FLASH_MIN_SCORE_BYTES = 56 * 2**20


def flash_profitable(b: int, h: int, sq: int, sk: int, d: int) -> bool:
    """The measured auto-dispatch gate over the shapes OF THE CALL (per
    shard under a mesh), shared by every flash call site
    (ops/attention.py and the all-to-all SP lowering,
    parallel/ulysses.py) so a re-tune propagates everywhere."""
    del d       # the sweep found no head size that moves the line
    return b * h * sq * sk * 4 >= FLASH_MIN_SCORE_BYTES


# the most one slab's resident operands may take: the kernels ask for
# twice this (double buffering) and a v5e core has 128 MiB of VMEM
_FLASH_VMEM_BYTES = 24 * 2**20


def _flash_resident_bytes(sq: int, sk: int, w: int, itemsize: int) -> int:
    """VMEM one grid step of the largest of the three kernels keeps
    resident. Forward and dQ hold a slab's WHOLE K and V; dK/dV holds
    its whole Q and dO plus the f32 logsumexp and delta rows (up to 8
    sublanes of sq lanes each: their own size, not 128 lanes a row)."""
    kv = 2 * sk * w * itemsize
    q_do = 2 * sq * w * itemsize + 2 * 8 * sq * 4
    return max(kv, q_do)


def flash_unsupported(sq: int, sk: int, d: int, itemsize: int = 2,
                      block_q: Optional[int] = None,
                      block_k: Optional[int] = None):
    """Why flash_attention_bshd cannot take these shapes, or None when
    it can — the predicate the auto dispatch asks BEFORE choosing the
    kernel (a kernel that is chosen and then raises, raises)."""
    block_q = block_q or MIN_BLOCK
    block_k = block_k or MIN_BLOCK
    if (sq % block_q or sk % block_k or block_q % MIN_BLOCK
            or block_k % MIN_BLOCK):
        return (f"seq ({sq},{sk}) not divisible by block "
                f"({block_q},{block_k}), a multiple of {MIN_BLOCK}")
    if d > 256:
        return "head_dim > 256 unsupported"
    w = max(128, _lane_pad(1, d)[1])
    need = _flash_resident_bytes(sq, sk, w, itemsize)
    if need > _FLASH_VMEM_BYTES:
        return (f"one slab's resident operands ({need / 2**20:.1f} MiB at "
                f"seq ({sq},{sk})) exceed the kernel's "
                f"{_FLASH_VMEM_BYTES / 2**20:.0f} MiB VMEM budget")
    return None


def resolve_flash(use_flash, b: int, h: int, sq: int, sk: int, d: int,
                  itemsize: int = 2) -> bool:
    """The attention op's tri-state resolved to one decision: True =
    run the Pallas kernel (and let it raise if it cannot), False = the
    XLA path. use_flash True forces, False forbids; None is the auto
    rule — a tpu backend, a shape the kernel takes, and the measured
    flash_profitable gate. The shapes are those of the CALL: a caller
    under a mesh passes its per-shard shapes. Shared by
    ops/attention.py and the all-to-all SP lowering
    (parallel/ulysses.py)."""
    if use_flash is not None:
        return bool(use_flash)
    return (jax.default_backend() == "tpu"
            and flash_unsupported(sq, sk, d, itemsize) is None
            and flash_profitable(b, h, sq, sk, d))


def flash_attention_bshd(q, k, v, *, causal=False, block_q=None,
                         block_k=None, interpret=False):
    """softmax(QK^T/sqrt(d))V for (b, s, h, d) tensors via Pallas, read
    and written as the (b, s, h*d) arrays they are (module docstring).

    Raises on unsupported shapes/platform: callers ask resolve_flash /
    flash_unsupported first, they do not catch. block_q / block_k pin
    all three kernels' blocks (the sweep's and the tests' handle);
    None takes choose_flash_blocks' pick for the shape.
    """
    if not interpret and jax.default_backend() != "tpu":
        raise NotImplementedError(
            f"pallas flash attention compiles for a tpu backend (this "
            f"one is {jax.default_backend()!r}); pass interpret=True to "
            f"run it through the Pallas interpreter")
    b, sq, h, d = q.shape
    sk = k.shape[1]
    itemsize = jnp.dtype(q.dtype).itemsize
    hp, dp = _lane_pad(h, d)
    blocks = choose_flash_blocks(sq, sk, _slab_geometry(hp, dp)[0], itemsize)
    if block_q or block_k:
        blocks = (block_q or blocks[0], block_k or blocks[1]) * 2
    for bq, bk in (blocks[:2], blocks[2:]):
        reason = flash_unsupported(sq, sk, d, itemsize, bq, bk)
        if reason:
            raise NotImplementedError(f"pallas flash attention: {reason}")

    def packed(x):
        # zero heads / zero lanes are exact: a zero head's output and
        # gradients are sliced off, zero d-columns add nothing to q.k^T
        if (hp, dp) != (h, d):
            x = jnp.pad(x, ((0, 0), (0, 0), (0, hp - h), (0, dp - d)))
        return x.reshape(x.shape[0], x.shape[1], hp * dp)

    # scale uses the unpadded head_dim
    o = _flash(packed(q), packed(k), packed(v), hp, causal,
               1.0 / math.sqrt(d), blocks, interpret)
    return o.reshape(b, sq, hp, dp)[:, :, :h, :d]
