"""Multi-timestep LSTM recurrence (Pallas, TPU).

Replaces the `lax.scan` recurrence of ops/rnn.py (the analog of the
reference's cuDNN RNN, nmt/lstm.cu) for the sequence loop ONLY — the
time-batched input GEMM (x @ wx) stays outside in XLA where it already
saturates the MXU.

Why a kernel: under scan, XLA re-reads the recurrent weight `wh`
(H, 4H — 16 MB f32 at NMT's H=1024) from HBM every timestep, so the
recurrence is wh-bandwidth-bound: T=40 steps stream 640 MB for 21 GFLOP
of math. Here the grid iterates over time with `wh` mapped to a
CONSTANT block index — Mosaic keeps the block resident in VMEM across
grid steps (no recopy on unchanged index) — and the (B, H) h/c carry
lives in VMEM scratch, cutting HBM traffic per step to the xg slice in
and the y/c slices out.

Backward is a second time-reversed kernel that RECOMPUTES the gates
from the stashed per-step h/c states (flash-attention-style recompute:
one extra (B,H)x(H,4H) GEMM per step instead of stashing (T, B, 4H)
activations), accumulating dwh in an f32 VMEM scratch and carrying
dh/dc across steps. Gate layout matches ops/rnn.py: [i, f, g, o].

Layout contract: xg (T, B, 4H) = x@wx + b precomputed; returns
ys (T, B, H) and cs (T, B, H). B % 8 == 0 and H % 128 == 0 required
(unsupported shapes raise — the LSTM op's default path IS the scan,
and force-mode must fail loudly rather than silently degrade).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

# what the resident recurrent weight and its gradient may take of a
# core's 128 MiB of VMEM (a v5e's; the time axis carries h/c in scratch)
_VMEM_BUDGET = 96 * 2**20


def _resident_bytes(B: int, H: int, itemsize: int, backward: bool) -> int:
    """VMEM one grid step keeps: wh single-buffered (constant block
    index), the backward's f32 dwh output block and accumulator, the
    double-buffered per-step (B, 4H) / (B, H) slices, and room for the
    f32 gate temporaries."""
    wh = H * 4 * H * itemsize
    if not backward:
        return (wh + B * 4 * H * (2 * itemsize + 8)
                + B * H * (8 * itemsize + 24))
    return (wh + 2 * H * 4 * H * 4 + B * 4 * H * (4 * itemsize + 16)
            + B * H * (8 * itemsize + 40))


def _compiler_params(B, H, dtype, backward):
    need = _resident_bytes(B, H, jnp.dtype(dtype).itemsize, backward)
    # the grid axis is TIME: h/c (and dwh) carry from step to step
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary",),
        vmem_limit_bytes=int(min(max(need + 16 * 2**20, 16 * 2**20),
                                 _VMEM_BUDGET + 16 * 2**20)))


def _prec(dtype):
    """f32 operands dot at HIGHEST so the kernel and the XLA scan agree
    to f32 accuracy (DEFAULT lets Mosaic and XLA pick different bf16
    pass counts on the MXU); bf16 operands stay DEFAULT — single-pass
    native, and precision would only slow them down."""
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


def _gates(lin, h):
    """lin (B, 4H) f32 logits -> activated i, f, g, o, each (B, H)."""
    hdim = h
    i = jax.nn.sigmoid(lin[:, :hdim])
    f = jax.nn.sigmoid(lin[:, hdim:2 * hdim])
    g = jnp.tanh(lin[:, 2 * hdim:3 * hdim])
    o = jax.nn.sigmoid(lin[:, 3 * hdim:])
    return i, f, g, o


# ---------------------------------------------------------------- forward
def _fwd_kernel(xg_ref, wh_ref, h0_ref, c0_ref, ys_ref, cs_ref,
                h_scr, c_scr, *, hdim):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        h_scr[:] = h0_ref[:].astype(jnp.float32)
        c_scr[:] = c0_ref[:].astype(jnp.float32)

    h_prev = h_scr[:]
    lin = xg_ref[:].astype(jnp.float32) + jax.lax.dot(
        h_prev.astype(wh_ref.dtype), wh_ref[:],
        precision=_prec(wh_ref.dtype),
        preferred_element_type=jnp.float32)
    i, f, g, o = _gates(lin, hdim)
    c = f * c_scr[:] + i * g
    h = o * jnp.tanh(c)
    h_scr[:] = h
    c_scr[:] = c
    ys_ref[:] = h.astype(ys_ref.dtype)
    cs_ref[:] = c.astype(cs_ref.dtype)


def _fwd_pallas(xg, wh, h0, c0, *, interpret):
    T, B, four_h = xg.shape
    H = four_h // 4
    kern = functools.partial(_fwd_kernel, hdim=H)
    scratch = [
        pltpu.VMEM((B, H), jnp.float32),
        pltpu.VMEM((B, H), jnp.float32),
    ]
    return pl.pallas_call(
        kern,
        grid=(T,),
        in_specs=[
            pl.BlockSpec((None, B, four_h), lambda t: (t, 0, 0)),
            pl.BlockSpec((H, four_h), lambda t: (0, 0),   # resident
                         pipeline_mode=pl.Buffered(1)),
            pl.BlockSpec((B, H), lambda t: (0, 0)),
            pl.BlockSpec((B, H), lambda t: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, B, H), lambda t: (t, 0, 0)),
            pl.BlockSpec((None, B, H), lambda t: (t, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, B, H), xg.dtype),
            jax.ShapeDtypeStruct((T, B, H), jnp.float32),
        ],
        scratch_shapes=scratch,
        compiler_params=_compiler_params(B, H, wh.dtype, False),
        interpret=interpret,
        name="lstm_fwd",
    )(xg, wh, h0, c0)


# --------------------------------------------------------------- backward
def _bwd_kernel(xg_ref, wh_ref, hprev_ref, cprev_ref, cs_ref, dys_ref,
                dxg_ref, dwh_ref, dh0_ref, dc0_ref,
                dh_scr, dc_scr, dwh_scr, *, hdim, T):
    step = pl.program_id(0)  # 0..T-1, walking time T-1..0 via index maps
    t_is_last = step == T - 1  # i.e. time step 0

    @pl.when(step == 0)
    def _init():
        dh_scr[:] = jnp.zeros_like(dh_scr)
        dc_scr[:] = jnp.zeros_like(dc_scr)
        dwh_scr[:] = jnp.zeros_like(dwh_scr)

    h_prev = hprev_ref[:].astype(jnp.float32)
    lin = xg_ref[:].astype(jnp.float32) + jax.lax.dot(
        h_prev.astype(wh_ref.dtype), wh_ref[:],
        precision=_prec(wh_ref.dtype),
        preferred_element_type=jnp.float32)
    i, f, g, o = _gates(lin, hdim)
    c = cs_ref[:].astype(jnp.float32)
    c_prev = cprev_ref[:].astype(jnp.float32)
    tanh_c = jnp.tanh(c)

    dh = dys_ref[:].astype(jnp.float32) + dh_scr[:]
    dc = dh * o * (1.0 - tanh_c * tanh_c) + dc_scr[:]
    do = dh * tanh_c
    di = dc * g
    dg = dc * i
    df = dc * c_prev
    dlin = jnp.concatenate([
        di * i * (1.0 - i),
        df * f * (1.0 - f),
        dg * (1.0 - g * g),
        do * o * (1.0 - o),
    ], axis=1)  # (B, 4H)

    dxg_ref[:] = dlin.astype(dxg_ref.dtype)
    dwh_scr[:] += jax.lax.dot_general(
        h_prev.astype(wh_ref.dtype), dlin.astype(wh_ref.dtype),
        (((0,), (0,)), ((), ())), precision=_prec(wh_ref.dtype),
        preferred_element_type=jnp.float32)
    dh_scr[:] = jax.lax.dot_general(
        dlin.astype(wh_ref.dtype), wh_ref[:],
        (((1,), (1,)), ((), ())), precision=_prec(wh_ref.dtype),
        preferred_element_type=jnp.float32)
    dc_scr[:] = dc * f

    @pl.when(t_is_last)
    def _finish():
        dwh_ref[:] = dwh_scr[:].astype(dwh_ref.dtype)
        dh0_ref[:] = dh_scr[:].astype(dh0_ref.dtype)
        dc0_ref[:] = dc_scr[:].astype(dc0_ref.dtype)


def _bwd_pallas(xg, wh, h0, c0, ys, cs, dys, *, interpret):
    T, B, four_h = xg.shape
    H = four_h // 4
    # previous-step states, host-assembled so the kernel needs no
    # negative block indices: hs_prev[t] = h_{t-1} (h0 at t=0)
    hs_prev = jnp.concatenate([h0[None].astype(ys.dtype), ys[:-1]], axis=0)
    cs_prev = jnp.concatenate([c0[None].astype(cs.dtype), cs[:-1]], axis=0)

    rev = lambda t: (T - 1 - t, 0, 0)  # noqa: E731
    const2 = lambda t: (0, 0)  # noqa: E731
    kern = functools.partial(_bwd_kernel, hdim=H, T=T)
    scratch = [
        pltpu.VMEM((B, H), jnp.float32),
        pltpu.VMEM((B, H), jnp.float32),
        pltpu.VMEM((H, four_h), jnp.float32),
    ]
    dxg, dwh, dh0, dc0 = pl.pallas_call(
        kern,
        grid=(T,),
        in_specs=[
            pl.BlockSpec((None, B, four_h), rev),
            pl.BlockSpec((H, four_h), const2,   # resident
                         pipeline_mode=pl.Buffered(1)),
            pl.BlockSpec((None, B, H), rev),    # hs_prev
            pl.BlockSpec((None, B, H), rev),    # cs_prev
            pl.BlockSpec((None, B, H), rev),    # cs
            pl.BlockSpec((None, B, H), rev),    # dys
        ],
        out_specs=[
            pl.BlockSpec((None, B, four_h), rev),
            pl.BlockSpec((H, four_h), const2,
                         pipeline_mode=pl.Buffered(1)),
            pl.BlockSpec((B, H), const2),
            pl.BlockSpec((B, H), const2),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, B, four_h), xg.dtype),
            jax.ShapeDtypeStruct((H, four_h), jnp.float32),
            jax.ShapeDtypeStruct((B, H), jnp.float32),
            jax.ShapeDtypeStruct((B, H), jnp.float32),
        ],
        scratch_shapes=scratch,
        compiler_params=_compiler_params(B, H, wh.dtype, True),
        interpret=interpret,
        name="lstm_bwd",
    )(xg, wh, hs_prev, cs_prev, cs, dys)
    return dxg, dwh, dh0, dc0


# ---------------------------------------------------------- custom VJP
@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _lstm_seq(xg, wh, h0, c0, interpret):
    ys, _ = _fwd_pallas(xg, wh, h0, c0, interpret=interpret)
    return ys


def _lstm_seq_fwd(xg, wh, h0, c0, interpret):
    ys, cs = _fwd_pallas(xg, wh, h0, c0, interpret=interpret)
    return ys, (xg, wh, h0, c0, ys, cs)


def _lstm_seq_bwd(interpret, res, dys):
    xg, wh, h0, c0, ys, cs = res
    dxg, dwh, dh0, dc0 = _bwd_pallas(xg, wh, h0, c0, ys, cs, dys,
                                     interpret=interpret)
    return (dxg, dwh.astype(wh.dtype), dh0.astype(h0.dtype),
            dc0.astype(c0.dtype))


_lstm_seq.defvjp(_lstm_seq_fwd, _lstm_seq_bwd)


def scan_reference(xg, wh, h0, c0):
    """Executable specification of the recurrence: the exact lax.scan
    the kernel replaces (ops/rnn.py cell with f32 carries). Both test
    suites validate the kernel against THIS single definition."""
    def cell(carry, xg_t):
        h_prev, c_prev = carry
        lin = xg_t.astype(jnp.float32) + jnp.dot(
            h_prev.astype(wh.dtype), wh,
            precision=_prec(wh.dtype),
            preferred_element_type=jnp.float32)
        i, f, g, o = jnp.split(lin, 4, axis=-1)
        c = jax.nn.sigmoid(f) * c_prev + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), h.astype(xg.dtype)

    (_, _), ys = jax.lax.scan(
        cell, (h0.astype(jnp.float32), c0.astype(jnp.float32)), xg)
    return ys


def lstm_sequence(xg, wh, h0, c0, *, interpret=False):
    """Run the LSTM recurrence over time via the Pallas kernel.

    xg (T, B, 4H) precomputed input gates (x@wx + b); wh (H, 4H);
    h0/c0 (B, H). Returns ys (T, B, H). Raises on unsupported
    shapes/platform — deliberate for the force-mode caller
    (LSTM use_pallas=True): an explicitly requested but unusable
    kernel must fail loudly, not silently degrade; the DEFAULT LSTM
    path is the scan."""
    if not interpret and jax.default_backend() != "tpu":
        raise NotImplementedError(
            f"pallas lstm compiles for a tpu backend (this one is "
            f"{jax.default_backend()!r}); pass interpret=True to run it "
            f"through the Pallas interpreter")
    T, B, four_h = xg.shape
    H = four_h // 4
    if B % 8 != 0 or H % 128 != 0:
        raise NotImplementedError(
            f"pallas lstm needs B%8==0 and H%128==0, got B={B} H={H}")
    need = _resident_bytes(B, H, jnp.dtype(wh.dtype).itemsize, True)
    if need > _VMEM_BUDGET:
        raise NotImplementedError(
            f"pallas lstm keeps wh and its gradient in VMEM: B={B} H={H} "
            f"{jnp.dtype(wh.dtype).name} needs {need / 2**20:.0f} MiB, "
            f"over the {_VMEM_BUDGET / 2**20:.0f} MiB budget")
    return _lstm_seq(xg, wh, h0, c0, interpret)
