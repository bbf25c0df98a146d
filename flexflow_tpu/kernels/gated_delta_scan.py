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
fetched a lane ahead; k and q share one (128, Dk) tile whose TRANSPOSE
hands every head its key and query as a column, the key dimension on
the sublanes as in the state; exp(g) and beta are scalars in SMEM.
Per lane and value head, in f32 and in the twin's order:
  S <- exp(g) S;  u = beta (v - S^T k);  S <- S + k u^T;  o = S^T q.

The slab (delta layers, slots + 1, Hv * Dk, Dv) f32 is aliased in to
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


def supported(lanes: int, heads: int, dk: int, dv: int) -> bool:
    """Whether the kernel takes this shape (else the jnp twin runs):
    the key dimension is the tile's, k's and q's heads fill whole
    sublane tiles of ONE tile, the value dimension fills the lanes, the
    three states fit in VMEM and the gates in SMEM."""
    return (dk == TILE and dv % 128 == 0 and heads % 8 == 0
            and 2 * heads <= TILE
            and 4 * BUFFERS * heads * dk * dv * 4 <= 3 * VMEM_LIMIT
            and 2 * lanes * heads <= SMEM_WORDS)


def _kernel(first_ref, len_ref, src_ref, dst_ref, meta_ref, decay_ref,
            beta_ref, q_hbm, k_hbm, v_hbm, o_in, state_in, o_hbm, state_hbm,
            sbuf, kq, vbuf, obuf, sem_load, sem_store, sem_in, sem_out):
    del o_in, state_in                  # aliased: the outputs are they
    heads, dv = vbuf.shape[1:]
    dk = kq.shape[2]
    bound = first_ref.shape[0] - 1
    n, layer = meta_ref[0], meta_ref[1]

    def load(r, b):
        return pltpu.make_async_copy(
            state_hbm.at[layer, src_ref[r]], sbuf.at[b], sem_load.at[b])

    def store(r, b):
        return pltpu.make_async_copy(
            sbuf.at[b], state_hbm.at[layer, dst_ref[r]], sem_store.at[b])

    def fetch(t, b):
        return [pltpu.make_async_copy(k_hbm.at[t], kq.at[b, pl.ds(0, heads)],
                                      sem_in.at[b]),
                pltpu.make_async_copy(q_hbm.at[t],
                                      kq.at[b, pl.ds(heads, heads)],
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
        cols = kq[tb].T                                   # (Dk, TILE)
        for h in range(heads):
            rows = pl.ds(h * dk, dk)
            kc, qc = cols[:, h:h + 1], cols[:, heads + h:heads + h + 1]
            s = sbuf[b, rows, :] * decay_ref[t * heads + h]
            u = beta_ref[t * heads + h] * (
                vbuf[tb, h:h + 1, :] - jnp.sum(kc * s, axis=0, keepdims=True))
            s = s + kc * u
            sbuf[b, rows, :] = s
            obuf[tb, h:h + 1, :] = jnp.sum(qc * s, axis=0, keepdims=True)

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
@functools.partial(jax.jit, static_argnames=("interpret",))
def _lane_pass(q, k, v, decay, beta, o, state, first, length, src, dst,
               meta, *, interpret):
    _, heads, dk = q.shape
    dv = v.shape[-1]
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7, grid=(1,),
        in_specs=[anywhere] * 5, out_specs=[anywhere] * 2,
        scratch_shapes=[
            pltpu.VMEM((BUFFERS, heads * dk, dv), F32),
            pltpu.VMEM((2, TILE, dk), F32),
            pltpu.VMEM((2, heads, dv), F32),
            pltpu.VMEM((2, heads, dv), F32),
            pltpu.SemaphoreType.DMA((BUFFERS,)),
            pltpu.SemaphoreType.DMA((BUFFERS,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ])
    return pl.pallas_call(
        _kernel, grid_spec=grid_spec,
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


def lane_pass(q, k, v, decay, beta, o, state, layer, segments, *,
              interpret=False):
    """The lanes of `segments` (ops/gated_delta.Segments) through the
    rule, on layer `layer` of the slab `state`: q, k (T, H, Dk), v, o
    (T, H, Dv), decay = exp(g), beta (T, H), f32 -> (o, the segments'
    lanes' rows written; the slab, each segment's slot updated in
    place)."""
    i32 = jnp.int32
    meta = jnp.stack([jnp.asarray(segments.count, i32),
                      jnp.asarray(layer, i32)])
    return _lane_pass(q, k, v, decay, beta, o, state, segments.first,
                      segments.length, segments.src, segments.dst, meta,
                      interpret=interpret)


def gated_delta_scan(q, k, v, g, beta, state, layer, lane_slots,
                     positions, plan, *, interpret=False):
    """`ops/gated_delta.py::segmented` over layer `layer` of the slab
    `state` (delta layers, slots + 1, H * Dk, Dv) f32, the lanes as
    `plan` (ops/gated_delta.lane_plan) sorts them. q, k (T, H, Dk), v
    (T, H, Dv), g, beta (T, H), f32 -> (o (T, H, Dv) f32; the slab, the
    runs' slots updated in place). The sink row is not written: the
    lanes that end no run write nothing."""
    t, h, _ = q.shape
    # whole blocks of lanes for the chunk form (none at a width that is
    # a multiple of its block, as the served ones are)
    pad = -t % gd.CHUNK
    if pad:
        q, k, v, g, beta, lane_slots, positions = (
            jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
            for a in (q, k, v, g, beta, lane_slots, positions))
    decay = jnp.exp(g)
    o = jnp.zeros((t + pad, h, v.shape[-1]), F32)
    o, state = lane_pass(q, k, v, decay, beta, o, state, layer, plan.before,
                         interpret=interpret)
    o, state = gd.chunk_blocks(q, k, v, g, beta, o, state, layer,
                               lane_slots, positions, plan)
    o, state = lane_pass(q, k, v, decay, beta, o, state, layer, plan.after,
                         interpret=interpret)
    return o[:t], state
