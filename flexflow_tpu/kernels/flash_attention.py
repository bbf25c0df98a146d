"""Flash attention (Pallas, TPU).

Replaces the reference's single cuDNN fused-MHA call
(src/ops/attention.cu:245 cudnnMultiHeadAttnForward) with an online-softmax
blocked kernel that never materializes the (Lq, Lk) score matrix in HBM.

Forward is a Pallas kernel (grid over (batch*heads, q-blocks), inner
fori_loop over k-blocks with online max/sum rescaling). Backward is two
Pallas kernels (dq over q-blocks; dk/dv over k-blocks) that recompute
probabilities from the saved logsumexp — exact gradients with no saved or
materialized probability tensor.

All MXU dots run in the input dtype (bf16 on TPU) with float32
accumulation (`preferred_element_type`); softmax statistics stay float32.
Casting to f32 *before* the dot would push the matmuls off the MXU's
native bf16 path and cost ~4x.

Layout contract: (batch, seq, heads, head_dim) in/out, matching
ops/attention.py. head_dim is zero-padded to a multiple of 128 lanes
(padding is exact: zero d-columns contribute nothing to q.k^T, and padded
v columns are sliced off the output).

Set `interpret=True` to run the same kernels through the Pallas
interpreter on CPU — used by tests/test_flash_attention.py on the forced
CPU platform.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from .paged_ragged_v2 import _vmem_limit

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


def _dot_t(a, b):
    """a (m, d) . b^T (d, n) -> (m, n), contracting the last dims."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_tt(a, b):
    """a^T (k, m) . b (k, n) -> (m, n), contracting the first dims."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _causal_mask(s, q0, k0, block_q, block_k):
    """Mask scores s (block_q, block_k) where q0+i < k0+j (top-left aligned)."""
    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    return jnp.where(qpos >= kpos, s, -jnp.inf)


def _compiler_params(sq, sk, d, dtype):
    """No grid step of the flash kernels carries state to the next
    (each writes its own output block), and the resident whole-head
    operands need more scoped VMEM than Mosaic's 16 MiB default from
    about 4k tokens on."""
    need = _flash_resident_bytes(sq, sk, d, jnp.dtype(dtype).itemsize)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        vmem_limit_bytes=_vmem_limit(need + 4 * 2**20))


# ---------------------------------------------------------------- forward
def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                      block_q, block_k, seq_k, scale, causal):
    qi = pl.program_id(1)
    q = q_ref[:]  # (block_q, d), native dtype — bf16 dots ride the MXU
    d = q.shape[-1]
    m0 = jnp.full((block_q,), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc0 = jnp.zeros((block_q, d), jnp.float32)

    num_kb = seq_k // block_k
    if causal:
        # blocks strictly above the diagonal contribute nothing
        num_kb = jnp.minimum(num_kb,
                             ((qi + 1) * block_q + block_k - 1) // block_k)

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(j * block_k, block_k), :]
        v = v_ref[pl.ds(j * block_k, block_k), :]
        s = _dot_t(q, k) * scale  # f32 accumulate
        if causal:
            s = _causal_mask(s, qi * block_q, j * block_k, block_q, block_k)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1)
        acc_new = acc * alpha[:, None] + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, num_kb, body, (m0, l0, acc0))
    o_ref[:] = (acc / l[:, None]).astype(o_ref.dtype)
    lse_ref[:] = (m + jnp.log(l))[:, None]


def _fwd_pallas(q, k, v, *, causal, scale, block_q, block_k, interpret):
    """q,k,v: (bh, s, d_padded) -> o (bh, sq, d_padded), lse (bh, sq, 1)."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    kern = functools.partial(
        _flash_fwd_kernel, block_q=block_q, block_k=block_k, seq_k=sk,
        scale=scale, causal=causal)
    grid = (bh, sq // block_q)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, sk, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, sk, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
        ],
        compiler_params=_compiler_params(sq, sk, d, q.dtype),
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)


# --------------------------------------------------------------- backward
def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, *, block_q, block_k, seq_k, scale, causal):
    qi = pl.program_id(1)
    q = q_ref[:]          # (block_q, d)
    do = do_ref[:]        # (block_q, d)
    lse = lse_ref[:]      # (block_q, 1) f32
    delta = delta_ref[:]  # (block_q, 1) f32
    d = q.shape[-1]
    acc0 = jnp.zeros((block_q, d), jnp.float32)

    num_kb = seq_k // block_k
    if causal:
        num_kb = jnp.minimum(num_kb,
                             ((qi + 1) * block_q + block_k - 1) // block_k)

    def body(j, acc):
        k = k_ref[pl.ds(j * block_k, block_k), :]
        v = v_ref[pl.ds(j * block_k, block_k), :]
        s = _dot_t(q, k) * scale
        if causal:
            s = _causal_mask(s, qi * block_q, j * block_k, block_q, block_k)
        p = jnp.exp(s - lse)         # masked -inf rows exp to exactly 0
        dp = _dot_t(do, v)           # (block_q, block_k) f32
        ds = (p * (dp - delta) * scale).astype(k.dtype)
        return acc + jax.lax.dot(ds, k, preferred_element_type=jnp.float32)

    acc = jax.lax.fori_loop(0, num_kb, body, acc0)
    dq_ref[:] = acc.astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, block_q, block_k, seq_q, scale,
                          causal):
    kj = pl.program_id(1)
    k = k_ref[:]  # (block_k, d)
    v = v_ref[:]
    d = k.shape[-1]
    dk0 = jnp.zeros((block_k, d), jnp.float32)
    dv0 = jnp.zeros((block_k, d), jnp.float32)

    num_qb = seq_q // block_q
    start_qb = 0
    if causal:
        # q blocks strictly left of this k block see none of it
        start_qb = (kj * block_k) // block_q

    def body(i, carry):
        dk, dv = carry
        q = q_ref[pl.ds(i * block_q, block_q), :]
        do = do_ref[pl.ds(i * block_q, block_q), :]
        lse = lse_ref[pl.ds(i * block_q, block_q), :]
        delta = delta_ref[pl.ds(i * block_q, block_q), :]
        s = _dot_t(q, k) * scale
        if causal:
            s = _causal_mask(s, i * block_q, kj * block_k, block_q, block_k)
        p = jnp.exp(s - lse)
        dv = dv + _dot_tt(p.astype(do.dtype), do)
        dp = _dot_t(do, v)
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dk = dk + _dot_tt(ds, q)
        return dk, dv

    dk, dv = jax.lax.fori_loop(start_qb, num_qb, body, (dk0, dv0))
    dk_ref[:] = dk.astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


def _bwd_pallas(q, k, v, o, lse, do, *, causal, scale, block_q, block_k,
                interpret):
    bh, sq, d = q.shape
    sk = k.shape[1]
    # delta_i = rowsum(do * o): cheap elementwise, fused by XLA
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)  # (bh, sq, 1)

    blk_q = lambda b, i: (b, i, 0)  # noqa: E731
    full = lambda b, i: (b, 0, 0)  # noqa: E731

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, block_q=block_q,
                          block_k=block_k, seq_k=sk, scale=scale,
                          causal=causal),
        grid=(bh, sq // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), blk_q),
            pl.BlockSpec((None, sk, d), full),
            pl.BlockSpec((None, sk, d), full),
            pl.BlockSpec((None, block_q, d), blk_q),
            pl.BlockSpec((None, block_q, 1), blk_q),
            pl.BlockSpec((None, block_q, 1), blk_q),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), blk_q),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        compiler_params=_compiler_params(sq, sk, d, q.dtype),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse, delta)

    blk_k = lambda b, j: (b, j, 0)  # noqa: E731
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, block_q=block_q,
                          block_k=block_k, seq_q=sq, scale=scale,
                          causal=causal),
        grid=(bh, sk // block_k),
        in_specs=[
            pl.BlockSpec((None, sq, d), full),
            pl.BlockSpec((None, block_k, d), blk_k),
            pl.BlockSpec((None, block_k, d), blk_k),
            pl.BlockSpec((None, sq, d), full),
            pl.BlockSpec((None, sq, 1), full),
            pl.BlockSpec((None, sq, 1), full),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, d), blk_k),
            pl.BlockSpec((None, block_k, d), blk_k),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        compiler_params=_compiler_params(sq, sk, d, q.dtype),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------- custom VJP
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, scale, block_q, block_k, interpret):
    o, _ = _fwd_pallas(q, k, v, causal=causal, scale=scale,
                       block_q=block_q, block_k=block_k, interpret=interpret)
    return o


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    o, lse = _fwd_pallas(q, k, v, causal=causal, scale=scale,
                         block_q=block_q, block_k=block_k,
                         interpret=interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = _bwd_pallas(q, k, v, o, lse, do, causal=causal, scale=scale,
                             block_q=block_q, block_k=block_k,
                             interpret=interpret)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_profitable(b: int, h: int, sq: int, sk: int, d: int) -> bool:
    """The measured auto-dispatch gate, shared by every flash call site
    (unsharded ops/attention.py and the all-to-all SP lowering,
    parallel/ulysses.py) so a re-tune propagates everywhere. Constants
    from the v5e b8/h8 2026-07 sweep (tests_tpu/test_flash_tpu.py): at
    d=64 the 128-lane padding doubles the kernel's dot FLOPs and XLA
    ties or wins; at d=128 flash wins from s>=1024; at any d flash wins
    once the materialized (b,h,sq,sk) score tensor stresses HBM."""
    score_bytes = b * h * sq * sk * 6  # f32 logits + bf16 probs
    return (d % 128 == 0 and sk >= 1024) or score_bytes > 2**31


# the most one head's resident operands may take: the kernels ask for
# twice this (double buffering) and a v5e core has 128 MiB of VMEM
_FLASH_VMEM_BYTES = 24 * 2**20


def _flash_resident_bytes(sq: int, sk: int, d_pad: int, itemsize: int) -> int:
    """VMEM one grid step of the largest of the three kernels keeps
    resident. Forward and dQ hold a head's WHOLE K and V; dK/dV holds
    its whole Q and dO plus the (sq, 1) f32 logsumexp and delta
    columns, which pad to 128 lanes."""
    kv = 2 * sk * d_pad * itemsize
    q_do = 2 * sq * d_pad * itemsize + 2 * sq * 128 * 4
    return max(kv, q_do)


def flash_unsupported(sq: int, sk: int, d: int, itemsize: int = 2,
                      block_q: int = DEFAULT_BLOCK_Q,
                      block_k: int = DEFAULT_BLOCK_K):
    """Why flash_attention_bshd cannot take these shapes, or None when
    it can — the predicate the auto dispatch asks BEFORE choosing the
    kernel (a kernel that is chosen and then raises, raises)."""
    if sq % block_q != 0 or sk % block_k != 0:
        return f"seq ({sq},{sk}) not divisible by block ({block_q},{block_k})"
    if d > 256:
        return "head_dim > 256 unsupported"
    d_pad = max(128, -(-d // 128) * 128)
    need = _flash_resident_bytes(sq, sk, d_pad, itemsize)
    if need > _FLASH_VMEM_BYTES:
        return (f"one head's resident operands ({need / 2**20:.1f} MiB at "
                f"seq ({sq},{sk})) exceed the kernel's "
                f"{_FLASH_VMEM_BYTES / 2**20:.0f} MiB VMEM budget")
    return None


def resolve_flash(use_flash, b: int, h: int, sq: int, sk: int, d: int,
                  itemsize: int = 2) -> bool:
    """The attention op's tri-state resolved to one decision: True =
    run the Pallas kernel (and let it raise if it cannot), False = the
    XLA path. use_flash True forces, False forbids; None is the auto
    rule — a tpu backend, a shape the kernel takes, and the measured
    flash_profitable gate. Shared by ops/attention.py and the
    all-to-all SP lowering (parallel/ulysses.py)."""
    if use_flash is not None:
        return bool(use_flash)
    return (jax.default_backend() == "tpu"
            and flash_unsupported(sq, sk, d, itemsize) is None
            and flash_profitable(b, h, sq, sk, d))


def flash_attention_bshd(q, k, v, *, causal=False,
                         block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                         interpret=False, pad_lanes=True):
    """softmax(QK^T/sqrt(d))V for (b, s, h, d) tensors via Pallas.

    Raises on unsupported shapes/platform: callers ask resolve_flash /
    flash_unsupported first, they do not catch.

    pad_lanes=True zero-pads head_dim up to a 128-lane multiple (always
    safe). pad_lanes=False hands Mosaic the raw head_dim (still a
    multiple of 8): halves the kernel's HBM traffic and dot FLOPs for
    d=64, at the cost of relying on Mosaic's sub-128 lane handling.
    """
    if not interpret and jax.default_backend() != "tpu":
        raise NotImplementedError(
            f"pallas flash attention compiles for a tpu backend (this "
            f"one is {jax.default_backend()!r}); pass interpret=True to "
            f"run it through the Pallas interpreter")
    b, sq, h, d = q.shape
    sk = k.shape[1]
    reason = flash_unsupported(sq, sk, d, jnp.dtype(q.dtype).itemsize,
                               block_q, block_k)
    if reason:
        raise NotImplementedError(f"pallas flash attention: {reason}")

    # scale uses the unpadded head_dim
    scale = 1.0 / math.sqrt(d)
    if pad_lanes or d % 8 != 0:
        d_pad = max(128, ((d + 127) // 128) * 128)
    else:
        d_pad = d

    def to_bhd(x, s):
        x = jnp.swapaxes(x, 1, 2).reshape(b * h, s, d)
        if d_pad != d:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, d_pad - d)))
        return x

    o = _flash(to_bhd(q, sq), to_bhd(k, sk), to_bhd(v, sk),
               causal, scale, block_q, block_k, interpret)
    o = o[..., :d].reshape(b, h, sq, d)
    return jnp.swapaxes(o, 1, 2)
