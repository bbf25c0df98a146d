"""The gated delta rule's one-lane runs of a serving step (Pallas, TPU).

Replaces the lane form of `ops/gated_delta.py::segmented` — a
`fori_loop` of four fusions and a dynamic-update-slice whose every trip
takes a slot's 2 MiB state through HBM five times, inside a scan over
blocks of lanes that reads a state and writes the sink for every block,
live or not (20 us a lane and 293 us a layer at Qwen3-Next's served
shape against 6.4 and 74 here; tests_tpu/test_gated_delta_tpu.py,
PERF.md section 6, PR 51) — in the delta body of serve/mixers.py.
`segmented` stays, whole, as this kernel's jnp twin: the tests hold the
two together.

What the kernel walks is a list of SEGMENTS (`ops/gated_delta.py::
lane_plan`): consecutive lanes of one run that go lane by lane. One
invocation, no grid: the slab, q, k, v and o stay in HBM and the kernel
moves what a segment needs itself. A segment's state (Hv * Dk, Dv) f32
comes into one of three VMEM buffers, is worked on there by every lane
of the segment, and goes back to the run's slot: in once, out once. The
next segment's state is fetched and the last one's written while this
one's lanes are worked, so a one-lane run costs its 2 x 2 MiB of HBM
traffic and little else. A lane's q, k and v rows (Hv x Dk each) are
fetched a lane ahead; k and q share one (128, 128) tile — k's heads
from sublane 0, q's from the next multiple of 8 after them, each row's
first Dk lanes (Mosaic slices no memory off its (8, 128) tiling, so
where Hv or Dk is off it `lane_pass` hands the kernel q and k with zero
rows and lanes up to it: what HBM's own tiling holds of them anyway) —
whose TRANSPOSE hands every head its key and query as a column (the
transposed tile's first Dk rows), the key dimension on the sublanes as
in the state; exp(g) and beta are scalars in SMEM.
Per lane and value head, in f32 and in the twin's order:
  S <- exp(g) S;  u = beta (v - S^T k);  S <- S + k u^T;  o = S^T q.
Where the state holds its heads in PAIRS on the lanes
(ops/gated_delta.state_pack: Dv 192, Olmo-Hybrid's), v and o are read
as (T, Hv / 2, 2 Dv) — the same bytes — and a pair is worked as one
tile of 2 Dv lanes: its two heads' key, query, exp(g) and beta are
SELECTED by lane (the first Dv, the last Dv), everything else is the
line above. Shapes taken (`supported`): Dk any multiple of 8 up to 128,
any head count whose k and q rows fit the tile, Dv a multiple of 128 or
two heads side by side one.

The slab (delta layers, slots + 1) + gd.state_shape f32 is aliased in to
out and the layer is a scalar operand (one trace, one Mosaic kernel for
all of a model's layers); `o` is aliased too, so a second call adds its
lanes to the first's. Contract: a slot holds at most ONE run a step
(the PROOF in serve/mixers.py::geometry), so a segment's state may be
fetched while the segments before it are still being written.

`gated_delta_scan` is the whole recurrence over a step's lanes on this
kernel: the lanes that come before a chunk-form block of their run,
then the chunk-form blocks (the twin's `_chunk`, a block's state passed
through the run's slot; a loop of as many trips as there are such
blocks), then the lanes that follow one — a run is lanes, blocks,
lanes, in that order, so the one kernel is called on either side of
the blocks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ops import gated_delta as gd

F32 = jnp.float32
TILE = 128        # rows of the tile that is transposed: k's heads, then q's
BUFFERS = 3       # states in VMEM: one coming in, one worked, one going out
VMEM_LIMIT = 32 * 2**20     # the call's scoped VMEM: the states take 3/4 at most
# what one call may hold of SMEM (1 MiB a v5e core) in exp(g) and beta,
# words: the paged kernel's list takes as much of its own call
SMEM_WORDS = 128 * 1024


def _q_row(heads: int) -> int:
    """The tile's sublane q's heads start at: the next multiple of 8
    after k's."""
    return -(-heads // 8) * 8


def supported(lanes: int, heads: int, dk: int, dv: int) -> bool:
    """Whether the kernel takes this shape (else the jnp twin runs):
    the key dimension lies on whole sublane tiles inside the tile, k's
    and q's heads fit ONE tile, the state's rows fill whole lane tiles
    as `gd.state_pack` lays them (a head's Dv, or a pair's 2 Dv), the
    three states fit in VMEM and the gates in SMEM."""
    return (dk % 8 == 0 and 0 < dk <= TILE
            and (gd.state_pack(heads, dv) * dv) % gd.LANE_TILE == 0
            and _q_row(heads) + heads <= TILE
            and 4 * BUFFERS * heads * dk * dv * 4 <= 3 * VMEM_LIMIT
            and 2 * lanes * heads <= SMEM_WORDS)


def _kernel(first_ref, len_ref, src_ref, dst_ref, meta_ref, decay_ref,
            beta_ref, q_hbm, k_hbm, v_hbm, o_in, state_in, o_hbm, state_hbm,
            sbuf, kq, vbuf, obuf, sem_load, sem_store, sem_in, sem_out, *,
            heads, pack):
    del o_in, state_in                  # aliased: the outputs are they
    q_row = k_hbm.shape[1]              # k's rows of the tile, then q's
    width = vbuf.shape[2]               # a state row's lanes: pack heads'
    dk = sbuf.shape[1] * pack // heads
    bound = first_ref.shape[0] - 1
    n, layer = meta_ref[0], meta_ref[1]

    def load(r, b):
        return pltpu.make_async_copy(
            state_hbm.at[layer, src_ref[r]], sbuf.at[b], sem_load.at[b])

    def store(r, b):
        return pltpu.make_async_copy(
            sbuf.at[b], state_hbm.at[layer, dst_ref[r]], sem_store.at[b])

    def fetch(t, b):
        return [pltpu.make_async_copy(k_hbm.at[t], kq.at[b, pl.ds(0, q_row)],
                                      sem_in.at[b]),
                pltpu.make_async_copy(q_hbm.at[t],
                                      kq.at[b, pl.ds(q_row, q_row)],
                                      sem_in.at[b]),
                pltpu.make_async_copy(v_hbm.at[t], vbuf.at[b], sem_in.at[b])]

    def put(t, b):
        return pltpu.make_async_copy(obuf.at[b], o_hbm.at[t], sem_out.at[b])

    @pl.when(n > 0)
    def _():
        for c in fetch(first_ref[0], 0):
            c.start()

        @pl.when(src_ref[0] >= 0)
        def _():
            load(0, 0).start()

    def a_lane(t, b, tb):
        """Lane t on the state in sbuf[b], its rows in kq[tb], vbuf[tb]."""
        cols = kq[tb].T                                   # (TILE, TILE)
        if dk != TILE:
            cols = cols[:dk]                              # (Dk, TILE)
        if pack > 1:
            first = jax.lax.broadcasted_iota(
                jnp.int32, (1, width), 1) < width // pack

        def by_lane(of_head, h):
            """What head h's lanes of the row take: the head's own, or
            of a pair each half its head's."""
            if pack == 1:
                return of_head(h)
            return jnp.where(first, of_head(h), of_head(h + 1))

        for h in range(0, heads, pack):
            n = h // pack
            rows = pl.ds(n * dk, dk)
            kc = by_lane(lambda j: cols[:, j:j + 1], h)
            qc = by_lane(lambda j: cols[:, q_row + j:q_row + j + 1], h)
            s = sbuf[b, rows, :] * by_lane(
                lambda j: decay_ref[t * heads + j], h)
            u = by_lane(lambda j: beta_ref[t * heads + j], h) * (
                vbuf[tb, n:n + 1, :] - jnp.sum(kc * s, axis=0, keepdims=True))
            s = s + kc * u
            sbuf[b, rows, :] = s
            obuf[tb, n:n + 1, :] = jnp.sum(qc * s, axis=0, keepdims=True)

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
@functools.partial(jax.jit, static_argnames=("interpret", "pack"))
def _lane_pass(q, k, v, decay, beta, o, state, first, length, src, dst,
               meta, *, interpret, pack=1):
    heads = decay.shape[1]
    # v and o by state row (`_by_row`): a head, or a pair side by side
    groups, width = v.shape[1:]
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7, grid=(1,),
        in_specs=[anywhere] * 5, out_specs=[anywhere] * 2,
        scratch_shapes=[
            pltpu.VMEM((BUFFERS,) + state.shape[2:], F32),
            pltpu.VMEM((2, TILE, TILE), F32),
            pltpu.VMEM((2, groups, width), F32),
            pltpu.VMEM((2, groups, width), F32),
            pltpu.SemaphoreType.DMA((BUFFERS,)),
            pltpu.SemaphoreType.DMA((BUFFERS,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ])
    return pl.pallas_call(
        functools.partial(_kernel, heads=heads, pack=pack),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(o.shape, F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands count the scalar-prefetch arrays: o and the slab in
        # place
        input_output_aliases={10: 0, 11: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="gated_delta_scan",
    )(first, length, src, dst, meta, decay.reshape(-1), beta.reshape(-1),
      q, k, v, o, state)


def _tile_qk(a):
    """q or k (N, H, Dk) as the kernel's tile takes its rows: H up to a
    multiple of 8, Dk up to the tile's lanes, zeros (nothing where they
    are whole: Qwen3-Next's 32 heads of 128)."""
    _, heads, dk = a.shape
    rows = _q_row(heads)
    if (rows, dk) == (heads, TILE):
        return a
    return jnp.pad(a, ((0, 0), (0, rows - heads), (0, TILE - dk)))


def _by_row(a):
    """v or o (N, H, Dv) by STATE ROW, as the kernel reads and writes
    them: (N, H / pack, pack Dv) — a pair's two heads side by side, the
    same bytes — the row groups up to a multiple of 8 with zeros
    (nothing at pack 1 and whole sublane tiles of heads)."""
    n, heads, dv = a.shape
    pack = gd.state_pack(heads, dv)
    groups = heads // pack
    if pack > 1:
        a = a.reshape(n, groups, pack * dv)
    if groups % 8:
        a = jnp.pad(a, ((0, 0), (0, -groups % 8), (0, 0)))
    return a


def _by_head(a, heads: int, dv: int):
    """`_by_row`'s inverse."""
    groups = heads // gd.state_pack(heads, dv)
    if groups % 8:
        a = a[:, :groups]
    return a.reshape(a.shape[0], heads, dv)


def _pass(q, k, v, decay, beta, o, state, layer, segments, pack,
          interpret):
    """`lane_pass` on q and k as `_tile_qk`, v and o as `_by_row` make
    them, `pack` heads side by side a state row."""
    i32 = jnp.int32
    meta = jnp.stack([jnp.asarray(segments.count, i32),
                      jnp.asarray(layer, i32)])
    return _lane_pass(q, k, v, decay, beta, o, state, segments.first,
                      segments.length, segments.src, segments.dst, meta,
                      interpret=interpret, pack=pack)


def lane_pass(q, k, v, decay, beta, o, state, layer, segments, *,
              interpret=False):
    """The lanes of `segments` (ops/gated_delta.Segments) through the
    rule, on layer `layer` of the slab `state`: q, k (T, H, Dk), v, o
    (T, H, Dv), decay = exp(g), beta (T, H), f32 -> (o, the segments'
    lanes' rows written; the slab, each segment's slot updated in
    place)."""
    _, heads, dv = v.shape
    o, state = _pass(_tile_qk(q), _tile_qk(k), _by_row(v), decay, beta,
                     _by_row(o), state, layer, segments,
                     gd.state_pack(heads, dv), interpret)
    return _by_head(o, heads, dv), state


def gated_delta_scan(q, k, v, g, beta, state, layer, lane_slots,
                     positions, plan, *, interpret=False):
    """`ops/gated_delta.py::segmented` over layer `layer` of the slab
    `state` (delta layers, slots + 1) + gd.state_shape f32, the lanes as
    `plan` (ops/gated_delta.lane_plan) sorts them. q, k (T, H, Dk), v
    (T, H, Dv), g, beta (T, H), f32 -> (o (T, H, Dv) f32; the slab, the
    runs' slots updated in place). The sink row is not written: the
    lanes that end no run write nothing."""
    t, h, _ = q.shape
    dv = v.shape[-1]
    # whole blocks of lanes for the chunk form (none at a width that is
    # a multiple of its block, as the served ones are)
    pad = -t % gd.CHUNK
    if pad:
        q, k, v, g, beta, lane_slots, positions = (
            jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
            for a in (q, k, v, g, beta, lane_slots, positions))
    decay = jnp.exp(g)
    # the kernel's view of the rows, made once for its two calls; `o`
    # stays in it from the first call to the last
    tiled = _tile_qk(q), _tile_qk(k), _by_row(v), decay, beta
    o = _by_row(jnp.zeros((t + pad, h, dv), F32))
    pack = gd.state_pack(h, dv)
    o, state = _pass(*tiled, o, state, layer, plan.before, pack, interpret)
    o, state = gd.chunk_blocks(q, k, v, g, beta, o, state, layer,
                               lane_slots, positions, plan,
                               o_rows=None if o.shape[1:] == (h, dv)
                               else _by_row)
    o, state = _pass(*tiled, o, state, layer, plan.after, pack, interpret)
    return _by_head(o, h, dv)[:t], state
