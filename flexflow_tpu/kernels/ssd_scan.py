"""Mamba-2's one-lane runs of a serving step (Pallas, TPU).

The lane form of `ops/ssd.py::segmented` — `ops/ssd.py::lane_pass`, a
loop whose every trip takes a slot's state through HBM several times —
for the SSD body of serve/mixers.py; `lane_pass` there stays, whole, as
this kernel's jnp twin: the tests hold the two together. Neither of the
kernels that were here fits: kernels/ssm_scan.py keeps its column of
EVERY slot's state in VMEM (rows x N x block f32, twice: 25 MB a
128-wide block at Falcon-H1's 97 rows of N 256) and pays an exp an
element where Mamba-2 has one a head; kernels/gated_delta_scan.py
computes the delta rule's correction and holds a head's key in ONE tile
row of at most 128.

What the kernel walks is a list of SEGMENTS (`ops/gated_delta.py::
lane_plan`, the delta kernel's): consecutive lanes of one run that go
lane by lane. One invocation, no grid: the slab, the lanes' rows and y
stay in HBM and the kernel moves what a segment needs itself. A
segment's state (N, H P) f32 — 4 MiB at Falcon-H1's 32 heads of 128 x
256 — comes into one of three VMEM buffers, is worked on there by every
lane of the segment, and goes back to the run's slot: in once, out once.
The next segment's state is fetched and the last one's written while
this one's lanes are worked, so a one-lane run costs its 2 x 4 MiB of
HBM traffic and little else. A lane's v rows (H x P) and its B and C
(2 G N values, as rows of 128 in one (128, 128) tile: B's groups, then
C's, N / 128 rows a group) are fetched a lane ahead; the tile's
TRANSPOSE hands every 128 state rows their B and C as a column, the
state dimension on the sublanes as in the state; exp(la) is a scalar a
head in SMEM. Per lane and head j of group g, in f32 and in the twin's
order:
  S_j <- exp(la_j) S_j + B_g v_j^T;   y_j = S_j^T C_g.
Shapes taken (`supported`): N and P multiples of 128, B's and C's rows
in one tile, three states in VMEM, the decays in SMEM.

The slab (layers, slots + 1, N, H P) f32 is aliased in to out and the
layer is a scalar operand (one trace, one Mosaic kernel for all of a
model's layers); `y` is aliased too, so a second call adds its lanes to
the first's. Contract: a slot holds at most ONE run a step (the PROOF in
serve/mixers.py::geometry), so a segment's state may be fetched while
the segments before it are still being written.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
TILE = 128        # the transposed tile, and the rows of a state block
BUFFERS = 3       # states in VMEM: one coming in, one worked, one going out
VMEM_LIMIT = 32 * 2**20     # the call's scoped VMEM: the states take 3/4 at most
# what one call may hold of SMEM (1 MiB a v5e core) in exp(la), words
SMEM_WORDS = 128 * 1024


def _rows8(n: int) -> int:
    return -(-n // 8) * 8


def supported(lanes: int, heads: int, head_dim: int, groups: int,
              d_state: int) -> bool:
    """Whether the kernel takes this shape (else the jnp twin runs): a
    head's (N, P) block of the state is whole (128, 128) tiles, B's and
    C's rows fit ONE tile, the three states fit in VMEM and the decays
    in SMEM."""
    return (d_state % TILE == 0 and head_dim % TILE == 0
            and heads % groups == 0
            and _rows8(2 * groups * d_state // TILE) <= TILE
            and 4 * BUFFERS * d_state * heads * head_dim * 4
            <= 3 * VMEM_LIMIT
            and lanes * heads <= SMEM_WORDS)


def _kernel(first_ref, len_ref, src_ref, dst_ref, meta_ref, decay_ref,
            bc_hbm, v_hbm, y_in, state_in, y_hbm, state_hbm,
            sbuf, bcbuf, vbuf, ybuf, sem_load, sem_store, sem_in, sem_out,
            *, heads, groups):
    del y_in, state_in                  # aliased: the outputs are they
    rows = bc_hbm.shape[1]              # B's and C's rows of the tile
    halves = sbuf.shape[1] // TILE      # blocks of 128 state rows
    p = sbuf.shape[2] // heads
    bound = first_ref.shape[0] - 1
    n, layer = meta_ref[0], meta_ref[1]

    def load(r, b):
        return pltpu.make_async_copy(
            state_hbm.at[layer, src_ref[r]], sbuf.at[b], sem_load.at[b])

    def store(r, b):
        return pltpu.make_async_copy(
            sbuf.at[b], state_hbm.at[layer, dst_ref[r]], sem_store.at[b])

    def fetch(t, b):
        return [pltpu.make_async_copy(bc_hbm.at[t],
                                      bcbuf.at[b, pl.ds(0, rows)],
                                      sem_in.at[b]),
                pltpu.make_async_copy(v_hbm.at[t], vbuf.at[b], sem_in.at[b])]

    def put(t, b):
        return pltpu.make_async_copy(ybuf.at[b], y_hbm.at[t], sem_out.at[b])

    @pl.when(n > 0)
    def _():
        for c in fetch(first_ref[0], 0):
            c.start()

        @pl.when(src_ref[0] >= 0)
        def _():
            load(0, 0).start()

    def a_lane(t, b, tb):
        """Lane t on the state in sbuf[b], its rows in bcbuf[tb], vbuf[tb]."""
        cols = bcbuf[tb].T                                # (TILE, TILE)
        for h in range(heads):
            g = h // (heads // groups)
            lanes = pl.ds(h * p, p)
            v = vbuf[tb, h:h + 1, :]                      # (1, P)
            decay = decay_ref[t * heads + h]
            y = None
            for r in range(halves):
                at = pl.ds(r * TILE, TILE)
                nb = g * halves + r
                nc = (groups + g) * halves + r
                s = sbuf[b, at, lanes] * decay + cols[:, nb:nb + 1] * v
                sbuf[b, at, lanes] = s
                part = jnp.sum(cols[:, nc:nc + 1] * s, axis=0, keepdims=True)
                y = part if y is None else y + part
            ybuf[tb, h:h + 1, :] = y

    def a_segment(r, done):
        b = r % BUFFERS
        ahead = jnp.minimum(r + 1, bound)
        more = r + 1 < n

        @pl.when(src_ref[r] >= 0)
        def _():
            load(r, b).wait()

        @pl.when(src_ref[r] < 0)                 # the sequence starts here
        def _():
            sbuf[b] = jnp.zeros(sbuf.shape[1:], F32)

        # the buffer the next segment takes is the one before last's
        @pl.when(r >= 2)
        def _():
            store(r - 2, (r + 1) % BUFFERS).wait()

        @pl.when(more & (src_ref[ahead] >= 0))
        def _():
            load(ahead, (r + 1) % BUFFERS).start()

        def lane(j, done):
            t = first_ref[r] + j
            tb = done % 2
            for c in fetch(t, tb):
                c.wait()
            last = j + 1 == len_ref[r]

            @pl.when(~last | more)
            def _():
                for c in fetch(jnp.where(last, first_ref[ahead], t + 1),
                               1 - tb):
                    c.start()

            @pl.when(done >= 2)
            def _():
                put(t, tb).wait()

            a_lane(t, b, tb)
            put(t, tb).start()
            return done + 1

        done = jax.lax.fori_loop(0, len_ref[r], lane, done)
        store(r, b).start()
        return done

    done = jax.lax.fori_loop(0, n, a_segment, jnp.int32(0))

    for back in (1, 2):
        @pl.when(n >= back)
        def _():
            store(n - back, (n - back) % BUFFERS).wait()

        @pl.when(done >= back)
        def _():
            put(0, (done - back) % 2).wait()


# jitted on its own, the layer an operand: a model's layers make the
# same calls, and tracing and lowering the kernel body is host time
# before the compile cache can even be asked — a nested jit pays it once
@functools.partial(jax.jit, static_argnames=("interpret", "groups"))
def _lane_pass(bc, v, decay, y, state, first, length, src, dst, meta, *,
               interpret, groups):
    heads = decay.shape[1]
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6, grid=(1,),
        in_specs=[anywhere] * 4, out_specs=[anywhere] * 2,
        scratch_shapes=[
            pltpu.VMEM((BUFFERS,) + state.shape[2:], F32),
            pltpu.VMEM((2, TILE, TILE), F32),
            pltpu.VMEM((2,) + v.shape[1:], F32),
            pltpu.VMEM((2,) + v.shape[1:], F32),
            pltpu.SemaphoreType.DMA((BUFFERS,)),
            pltpu.SemaphoreType.DMA((BUFFERS,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ])
    return pl.pallas_call(
        functools.partial(_kernel, heads=heads, groups=groups),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(y.shape, F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands count the scalar-prefetch arrays: y and the slab in
        # place
        input_output_aliases={8: 0, 9: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="ssd_scan",
    )(first, length, src, dst, meta, decay.reshape(-1), bc, v, y, state)


def _tile_bc(b, c):
    """B, C (T, G, N) -> (T, rows, 128): B's groups then C's, N / 128
    rows a group — the same bytes — the rows up to a multiple of 8 with
    zeros (none at Falcon-H1's 2 groups of 256)."""
    t = b.shape[0]
    bc = jnp.concatenate([b, c], axis=1).reshape(t, -1, TILE)
    return jnp.pad(bc, ((0, 0), (0, -bc.shape[1] % 8), (0, 0)))


def _rows(a):
    """v or y (T, H, P), the heads up to a multiple of 8 with zeros
    (none at whole sublane tiles of heads)."""
    return jnp.pad(a, ((0, 0), (0, -a.shape[1] % 8), (0, 0)))


def lane_pass(v, b, c, la, y, state, layer, segments, *, interpret=False):
    """`ops/ssd.py::lane_pass` on this kernel: the lanes of `segments`
    on layer `layer` of the slab `state` (layers, slots + 1, N, H P)
    f32. v, y (T, H, P), b, c (T, G, N), la (T, H), f32 -> (y, the
    segments' lanes' rows written; the slab, each segment's slot updated
    in place)."""
    heads = v.shape[1]
    i32 = jnp.int32
    meta = jnp.stack([jnp.asarray(segments.count, i32),
                      jnp.asarray(layer, i32)])
    y, state = _lane_pass(
        _tile_bc(b, c), _rows(v), jnp.exp(la), _rows(y), state,
        segments.first, segments.length, segments.src, segments.dst, meta,
        interpret=interpret, groups=b.shape[1])
    return y[:, :heads], state
