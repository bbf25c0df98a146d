"""The serving step's selective-scan recurrence (Pallas, TPU).

Replaces `ops/ssm.py::segmented_scan` — a `lax.scan` over the step's
lanes whose every trip round-trips the carried state and the slots'
slab through HBM (2.8 ms a layer at Phi-4-mini-flash's served shape;
PERF.md section 6, PR 32) — in the state-space body of
serve/mixers.py. That scan stays as this kernel's jnp twin (as
`_ragged_jnp` is the paged kernel's): the tests hold the two together.

Why a kernel: the recurrence is elementwise in `d_inner`, so the grid
runs over BLOCKS of `d_inner`, each independent and exact. A grid step
holds in VMEM its column of EVERY slot's state, of `dt`, `u` and `y`,
and all of `b`, `c`; the lanes' slots, positions, run starts and
write-back slots are scalars in SMEM. One loop walks the step's LIVE
lanes (a prefix of the lanes: `_pack` fills them from 0 up), eight a
trip, the carried state in registers: only `decay * s + inc` is carried
from lane to lane, so the exponentials and outer products of a trip's
later lanes overlap the chain. HBM traffic is the block's own: the
layer's states in and out once, `dt`, `u` in, `y` out.

The slab (state_layers, slots + 1, d_state, d_inner) f32 is aliased
in to out and the layer is chosen by the block index (a scalar operand:
one trace, one Mosaic kernel for all of a model's layers), so the step
copies neither the slab nor a layer's row of it. Layout contract:
lanes % 8 == 0, d_inner % 128 == 0, d_state % 8 == 0 (`supported`) and
an f32 slab; everything is f32, as in the twin.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_ragged_v2 import _vmem_limit

F32 = jnp.float32
TRIP = 8          # lanes a trip of the loop: one f32 sublane tile
# the widest block of d_inner a grid step takes. On a v5e at Phi's
# served shape (576 lanes, 65 x 16 x 5120 f32; tests_tpu/
# test_ssm_scan_tpu.py prints the sweep; PERF.md section 6, PR 33) a
# layer's scan took, at blocks of 256 | 512 | 640 | 1280, 0.283 | 0.213
# | 0.202 | 0.206 ms with 552 live lanes and 0.112 ms at every block
# with 40 (the layer's states in and out, 42 MB, and `dt`, `u`, `y`, 35
# MB, bound it there); the XLA loop took 2.12 ms at either
MAX_BLOCK = 640


VMEM_CAP = 64 * 2**20       # what `_vmem_limit` grants at most


def supported(lanes: int, d_state: int, d_inner: int, rows: int = 0) -> bool:
    """Whether the kernel takes this shape (else the jnp twin runs):
    whole tiles, and — where the caller says how many slot `rows` the
    slab has — a grid step's blocks (its column of EVERY row's state,
    in and out) within the VMEM a call may ask for, so that it is
    `Geometry.scan_impl` that falls to the twin and not Mosaic that
    refuses the compile."""
    if lanes % TRIP or d_inner % 128 or d_state % 8:
        return False
    return not rows or 2 * (block_bytes(
        lanes, rows, d_state, choose_block(d_inner)) + 2 * 2**20) <= VMEM_CAP


def choose_block(d_inner: int) -> int:
    """The widest multiple of 128 that divides d_inner, at most
    MAX_BLOCK."""
    return max(w for w in range(128, min(d_inner, MAX_BLOCK) + 1, 128)
               if d_inner % w == 0)


def block_bytes(lanes: int, rows: int, d_state: int, block: int) -> int:
    """What one grid step holds in VMEM: the block's column of every
    slot row's state in and out, of u, dt and y, of A_log and D, and
    the lanes' b | c tiles."""
    return 4 * block * (2 * rows * d_state + 3 * lanes + d_state + 1) \
        + 4 * (lanes // TRIP) * d_state * 128


def _scan_kernel(slots_ref, pos_ref, starts_ref, wslots_ref, meta_ref,
                 alog_ref, d_ref, u_ref, dt_ref, bc_ref, state_in_ref,
                 y_ref, state_ref):
    n, blk = alog_ref.shape
    a_neg = -jnp.exp(alog_ref[...])                       # (N, blk)
    d_skip = d_ref[...]                                   # (1, blk)
    # the block's column of every slot's state: worked on in place
    state_ref[...] = state_in_ref[...]
    # dead lanes' rows (a suffix) are zeros
    y_ref[...] = jnp.zeros(y_ref.shape, F32)
    live = meta_ref[0]

    def trip(i, s):
        t0 = pl.multiple_of(i * TRIP, TRIP)
        u8 = u_ref[pl.ds(t0, TRIP), :]                    # (8, blk)
        dt8 = dt_ref[pl.ds(t0, TRIP), :]
        du8 = dt8 * u8
        bc = bc_ref[i]                                    # (N, 16)
        for k in range(TRIP):
            t = t0 + k
            # a run's first lane takes its slot's state (zeros where
            # the sequence starts here); the others carry
            stored = state_ref[slots_ref[t]]              # (N, blk)
            s0 = jnp.where(pos_ref[t] > 0, stored, 0.0)
            s = jnp.where(starts_ref[t] != 0, s0, s)
            decay = jnp.exp(dt8[k:k + 1, :] * a_neg)
            s = decay * s + du8[k:k + 1, :] * bc[:, k:k + 1]
            y = jnp.sum(s * bc[:, TRIP + k:TRIP + k + 1], axis=0,
                        keepdims=True) + d_skip * u8[k:k + 1, :]
            y_ref[pl.ds(t, 1), :] = jnp.where(t < live, y, 0.0)
            # its slot where the lane is a run's last live one, else
            # the sink row
            state_ref[wslots_ref[t]] = s
        return s

    jax.lax.fori_loop(0, (live + TRIP - 1) // TRIP, trip,
                      jnp.zeros((n, blk), F32))


# jitted on its own, the layer an operand: a model's layers make the
# same call, and tracing and lowering the kernel body is host time
# before the compile cache can even be asked — a nested jit pays it once
@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _scan_pallas(a_log, d_skip, u, dt, b, c, state, lane_slots, positions,
                 starts, wslots, meta, *, block, interpret):
    t, d_inner = u.shape
    _, rows, n, _ = state.shape
    # (trip, N, 16): a trip's eight columns of b, then of c, d_state on
    # the sublanes as in the state
    bc = jnp.concatenate([b.reshape(t // TRIP, TRIP, n),
                          c.reshape(t // TRIP, TRIP, n)],
                         axis=1).transpose(0, 2, 1)
    col = lambda i, *_: (0, i)
    # meta = (live lanes, layer): the layer's row of the slab by index
    row = lambda i, slots, pos, starts, wslots, meta: (meta[1], 0, 0, i)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(d_inner // block,),
        in_specs=[
            pl.BlockSpec((n, block), col),                        # A_log
            pl.BlockSpec((1, block), col),                        # D
            pl.BlockSpec((t, block), col),                        # u
            pl.BlockSpec((t, block), col),                        # dt
            pl.BlockSpec((t // TRIP, n, 2 * TRIP),
                         lambda i, *_: (0, 0, 0)),                # b | c
            pl.BlockSpec((None, rows, n, block), row),            # state
        ],
        out_specs=[
            pl.BlockSpec((t, block), col),                        # y
            pl.BlockSpec((None, rows, n, block), row),
        ],
    )
    y, state = pl.pallas_call(
        _scan_kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((t, d_inner), F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands count the scalar-prefetch arrays: the slab in place
        input_output_aliases={10: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_vmem_limit(
                block_bytes(t, rows, n, block) + 2 * 2**20)),
        interpret=interpret,
        name="ssm_scan",
    )(lane_slots, positions, starts, wslots, meta,
      a_log, d_skip, u, dt, bc, state)
    return y, state


def ssm_scan(p, u, dt, b, c, state, layer, lane_slots, positions,
             starts, wslots, live, *, block=None, interpret=False):
    """`ops/ssm.py::segmented_scan` over layer `layer` of the slab
    `state` (state_layers, slots + 1, N, d_inner) f32, for the first
    `live` lanes (a scalar; the lanes past them are dead). u, dt
    (T, d_inner), b, c (T, N) f32 -> (y (T, d_inner) f32, dead rows
    zero; the slab, the layer's written slots updated in place). The
    sink row's content is not the twin's (the twin walks the dead lanes
    too); nothing reads it."""
    d_inner = u.shape[1]
    i32 = jnp.int32
    return _scan_pallas(
        p["A_log"].astype(F32), p["D"].astype(F32).reshape(1, d_inner),
        u, dt, b, c, state, lane_slots.astype(i32), positions.astype(i32),
        starts.astype(i32), wslots.astype(i32),
        jnp.stack([jnp.asarray(live, i32), jnp.asarray(layer, i32)]),
        block=int(block or choose_block(d_inner)), interpret=interpret)
