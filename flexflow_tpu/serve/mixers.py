"""What a mixer KIND is to the serving step — the one owner.

A description (serve/arch.py) names each layer's mixer kind; the engine
(serve/engine.py) runs a step that names none. What a kind means to the
step is here, side by side: the step's GEOMETRY, made once at engine
build (`geometry`); the LANES the layers share, on the device
(`step_lanes` -> `Lanes`); the layer BODIES, plain functions traced
inline in the step under the scopes a profiler trace is read by
(`BODIES`; docs/observability.md "Device scopes"); and the host's
COUNTS of the same step (`step_counts`), which `attn_grid_live_share.*`,
`attn_row_fill.*`, `attn_hbm_share.*` and `ssm_scan_hbm_share.phi`
read. A rule both sides need is ONE function over `xp` (`walked`,
kv_cache.ring_tables), so the device's list and the host's count of it
cannot drift apart.

The arrows go one way: engine -> mixers -> {arch (the kinds' names, the
description's projections), kv_cache, sparse_paged, the kernels, the
ops}; nothing here imports the engine or the scheduler. What a new kind
touches: docs/serving.md "What a new architecture touches".
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import gated_delta_scan, ssd_scan, ssm_scan
from ..kernels.paged_ragged_v2 import (JNP, PALLAS_INTERPRET, Q_ROWS,
                                       WorkList, build_work_list,
                                       kv_page_bytes, max_work_items,
                                       paged_attention_ragged_v2,
                                       window_block_bound, work_items)
from ..ops import gated_delta, linear_attention, short_conv, ssd, ssm
from .arch import (ATTN, CONV, CROSS, DELTA, FULL, GMU, LINEAR, SPARSE,
                   SSD_ATTN, SSM, WINDOW)
from .kv_cache import KVCacheConfig, ring_tables
from .sparse_paged import (LANE_TILE, STRAY_TILE, main_slots,
                           paged_sparse_attention, selection_geometry,
                           stray_batches, stride_keys)

# the step's fixed shape against its live work: StepEvents attributes
# and `dispatch` span arguments of every model
LIVE_COUNTS = ("grid_steps", "live_steps", "short_steps", "live_rows",
               "lanes", "emitters", "paged_calls", "paged_calls_in_place")
# what a step's selection did, on a model that selects its context
# (a SPARSE layer), counted where the lanes are made; the last two: the
# stretches of lanes the scores take a layer, and those of them whose
# live lanes all share the stretch's one fetch of compressed keys
# (sparse_paged.main_slots)
SELECT_COUNTS = ("sparse_lanes", "blocks_selected", "blocks_visible",
                 "selector_bytes", "score_tiles", "score_shared_tiles")
# ... and what only the DEVICE can count, because the selection is made
# there: the grid steps the selection's paged calls walk, summed over
# key/value heads and sparse layers, the selection blocks those items
# fetch, and the K and V bytes that is. Fetched with the step's tokens,
# so they are a landed step's (StepEvents, the `emit` span)
SELECT_LANDED_COUNTS = ("select_items", "select_block_fetches",
                        "selected_kv_bytes")
# ... where a sequence holds state or a ring besides pages
HYBRID_COUNTS = ("state_bytes", "window_kv_bytes", "full_kv_bytes")
# ... where a layer runs the gated delta rule: the live lanes that go
# lane by lane and the blocks of lanes that take the chunk form
# (ops/gated_delta.block_forms), a layer
DELTA_COUNTS = ("delta_lanes", "delta_chunk_blocks")
# ... where a layer runs Mamba-2 heads: the same two of its recurrence
# (one rule, one plan: ops/ssd.py takes the delta rule's)
SSD_COUNTS = ("ssd_lanes", "ssd_chunk_blocks")
# ... where a layer is a gated short convolution: the live lanes that
# pass through those layers, a layer
CONV_COUNTS = ("conv_lanes",)
# what StepEvents takes of every step and the span does not (it has
# the first three under older names)
EVENT_COUNTS = ("kv_bytes_read", "attn_items", "attn_rows", "ssm_runs",
                "lanes_past_window")
STEP_COUNTS = EVENT_COUNTS + LIVE_COUNTS + HYBRID_COUNTS + SELECT_COUNTS \
    + SELECT_LANDED_COUNTS + DELTA_COUNTS + SSD_COUNTS + CONV_COUNTS


# ------------------------------------------------------------- geometry
@dataclasses.dataclass(frozen=True, eq=False)
class Geometry:
    """One engine's step as its mixer kinds shape it (`geometry` says
    how each field is made)."""
    arch: Any                   # the serve/arch.py Description
    cfg: KVCacheConfig
    width: int                  # the step's lanes
    attn_impl: str              # "pallas" | "pallas_interpret" | "jnp"
    block_kv: int               # the paged kernel's kv-block, positions
    block_pages: int            # the same in pages
    scan_impl: Optional[str]    # the state-space scan; None: no state
    delta_impl: Optional[str]   # the delta rule's lanes; None: no such layer
    dense_pages: int            # columns a selecting model walks (0: all)
    attn_max_items: int         # grid bound of a call on the full list
    window_max_items: int       # ... on the window layers' list (0: none)
    attn_calls: Tuple[int, int]     # calls on (the full, the window) list
    rings: Optional[np.ndarray]     # the rings' page table, for the host
    # the keys of `step_counts` that the `dispatch` span takes beside
    # StepEvents: LIVE_COUNTS, and the other two where they apply
    counted: Tuple[str, ...]
    # how the delta layers' slab holds a state (ops/gated_delta.
    # state_layout: the layout's name, a state's rows, its resident
    # bytes) or the Mamba-2 layers' theirs (`ssd_state_*`) or the short
    # convolutions' tails theirs (`conv_tail_*`); {}: no such layer
    delta_state: dict = dataclasses.field(default_factory=dict)
    # a layer runs Mamba-2 heads: its lanes take the delta rule's plan
    ssd: bool = False
    # the layers that are gated short convolutions (CONV)
    conv_layers: int = 0
    # a selecting model's selected blocks through the paged kernel
    # (sparse_paged.selection_geometry): the kv-block of those calls in
    # pages, the lanes a call takes of the step's (the static cut that
    # keeps a call's list in the kernel's SMEM budget) and a call's
    # bound on its list; 0: no SPARSE layer
    select_block_pages: int = 0
    select_call_lanes: int = 0
    select_max_items: int = 0

    @property
    def attn_kw(self) -> dict:
        return {"use_pallas": self.attn_impl != JNP,
                "interpret": self.attn_impl == PALLAS_INTERPRET}


def attn_calls(arch) -> Tuple[int, int]:
    """The paged calls a step makes, from the layers' kinds: one on the
    full pages' list an ATTN, FULL, CROSS or SSD_ATTN layer (a cross
    layer reads the full layer's pages), a call a key/value head a SPARSE layer
    (each head's pages are a pool layer of their own), one on the
    window layers' list a WINDOW layer; a CONV layer makes none."""
    kinds = [arch.mixer(i) for i in range(arch.num_layers)]
    full = sum(kinds.count(k) for k in (ATTN, FULL, CROSS, SSD_ATTN)) \
        + arch.kv_heads * kinds.count(SPARSE)
    return full, kinds.count(WINDOW)


def paged_calls(g: "Geometry") -> dict:
    """`paged_calls`, the paged calls a step makes, and
    `paged_calls_in_place`, those of them that read their pool's leaf
    where it lies: all of them on a head-packed pool, whose rows are
    what the kernel streams; none on an unpacked one, where XLA copies
    the layer's K and V slab out of the pool for every call
    (KVPool.layer; ROADMAP S2 counts what is left by the difference)."""
    calls = sum(g.attn_calls)
    return {"paged_calls": calls,
            "paged_calls_in_place": calls if g.cfg.packed_heads else 0}


def geometry(arch, cfg: KVCacheConfig, *, width: int, attn_impl: str,
             block_kv: int) -> Geometry:
    """The step's geometry for a description and its pool, at `width`
    lanes, the paged kernel as resolved and at its kv-block."""
    kinds = [arch.mixer(i) for i in range(arch.num_layers)]
    hyb = cfg.hybrid
    # the state-space layers' scan runs where the paged kernel runs
    # (kernels/ssm_scan.py, under `attn_impl`) wherever that kernel
    # takes the step's shape, else as its jnp twin
    # (ops/ssm.segmented_scan); a linear-attention layer's matrix state
    # has the twin alone (ops/linear_attention.segmented_lightning).
    # The delta rule's lanes resolve the same way under a name of their
    # own, `delta_impl`: kernels/gated_delta_scan.py where it takes the
    # step's shape, else its twin (ops/gated_delta.segmented)
    scan_impl = delta_impl = None
    delta_state = {}
    if hyb is not None and hyb.state_layers:
        scan_impl = attn_impl if SSM in kinds and ssm_scan.supported(
            width, *hyb.state_shape, rows=cfg.max_seqs + 1) else JNP
    if DELTA in kinds:
        d = arch.delta
        delta_impl = attn_impl if gated_delta_scan.supported(
            width, d.value_heads, d.key_dim, d.value_dim) else JNP
        delta_state = gated_delta.state_layout(
            d.value_heads, d.key_dim, d.value_dim)
    if SSD_ATTN in kinds:
        # Mamba-2's lanes resolve as the state-space scan does, under
        # `scan_impl`: kernels/ssd_scan.py where it takes the step's
        # shape, else its twin (ops/ssd.lane_pass)
        d = arch.ssd.dims
        scan_impl = attn_impl if ssd_scan.supported(width, *d) else JNP
        delta_state = {"ssd_state_layout": "state_rows_by_head_lanes",
                       "ssd_state_shape": d.state_shape,
                       "ssd_state_slot_bytes":
                           4 * d.state_shape[0] * d.state_shape[1]}
    if CONV in kinds:
        # the slots are tails alone: what a slot's row of the slab is
        delta_state = {"conv_tail_layout": "layers_by_slots_by_flat_rows",
                       "conv_tail_shape": (hyb.tail_layers,
                                           cfg.max_seqs + 1,
                                           hyb.tail_shape[0]
                                           * hyb.tail_shape[1]),
                       "conv_tail_slot_bytes": hyb.tail_bytes}
    block_pages = max(1, block_kv // cfg.page_size)
    # a model that SELECTS its context (arch.dense_len) walks pages in
    # the paged kernel only for its lanes under dense_len: the list is
    # built over the table's first `dense_pages` columns, and the grid
    # is bounded by them, not by the positions served
    dense_pages = min(cfg.pages_per_seq,
                      -(-arch.dense_len // cfg.page_size))
    # the paged kernel's grid: the most work items a plan can make
    # (kernels/paged_ragged_v2.max_work_items). PROOF of the slot
    # changes: ServeSession._pack lays a plan's chunks one after
    # another, each chunk (with its draft tokens) in consecutive lanes
    # of ONE slot, then the inactive lanes on slot 0; a plan holds at
    # most one chunk per running request (Scheduler.schedule: one per
    # entry of `running`, one per admission, each with a slot of its
    # own), so at most max_seqs chunks; the slot changes from a lane to
    # the next only where a chunk ends: at most max_seqs times.
    # `step_counts` checks every plan against the bound and raises
    # (tests/test_paged_work_list.py drives a busy session at it).
    attn_max_items = max_work_items(
        width, dense_pages or cfg.pages_per_seq, block_pages, Q_ROWS,
        slot_changes=cfg.max_seqs)
    # a window layer's list is built apart (its items start at the
    # window's first block); its grid is bounded by the window
    window_max_items = max_work_items(
        width, cfg.pages_per_seq, block_pages, Q_ROWS,
        slot_changes=cfg.max_seqs,
        window_blocks=window_block_bound(
            arch.window, block_pages * cfg.page_size)
    ) if arch.window else 0
    # the selected blocks' calls: the kernel's kv-block in whole
    # selection blocks, the lanes in as many calls as keep a call's list
    # in the kernel's SMEM budget. The slot changes inside a call's
    # lanes are at most the step's (the proof above)
    select = selection_geometry(
        arch.sparse, cfg.page_size, cfg.pages_per_seq, block_pages, width,
        slot_changes=cfg.max_seqs) if dense_pages else (0, 0, 0)
    counted = LIVE_COUNTS + (HYBRID_COUNTS if hyb is not None else ()) \
        + (SELECT_COUNTS if dense_pages else ()) \
        + (DELTA_COUNTS if DELTA in kinds else ()) \
        + (SSD_COUNTS if SSD_ATTN in kinds else ()) \
        + (CONV_COUNTS if CONV in kinds else ())
    return Geometry(
        arch=arch, cfg=cfg, width=width, attn_impl=attn_impl,
        block_kv=block_kv, block_pages=block_pages, scan_impl=scan_impl,
        delta_impl=delta_impl,
        dense_pages=dense_pages, attn_max_items=attn_max_items,
        window_max_items=window_max_items, attn_calls=attn_calls(arch),
        rings=ring_tables(cfg) if cfg.ring_pages else None,
        counted=counted, delta_state=delta_state, ssd=SSD_ATTN in kinds,
        conv_layers=kinds.count(CONV),
        select_block_pages=select[0], select_call_lanes=select[1],
        select_max_items=select[2])


def walked(g: Geometry, page_tables, positions, lane_lens, xp=np):
    """-> (page tables, lane lengths) as the calls on the full pages'
    list walk them: every lane's pages — or, where the model selects
    its context, the tables' first `dense_pages` columns and a length
    of 1 for a lane past the selector's dense_len. numpy where the host
    counts the list, jax.numpy where the step builds it."""
    if not g.dense_pages:
        return page_tables, lane_lens
    return (page_tables[:, :g.dense_pages],
            xp.where(positions < g.arch.dense_len, lane_lens, 1))


# ---------------------------------------------------------------- lanes
class Lanes(NamedTuple):
    """What a step's layers share, on the device: the lane arrays the
    program was handed (ServeEngine._mixed_impl), then what `step_lanes`
    makes of them once for all the layers."""
    positions: Any
    write_pages: Any
    write_offs: Any
    page_tables: Any
    lane_slots: Any
    lane_lens: Any
    # the full pages' work list (None: the jnp attention reads the lane
    # arrays) and the tables and lengths it is built over (`walked`)
    work: Optional[WorkList]
    walked_tables: Any
    walked_lens: Any
    # the live lanes as an expert layer routes them (inactive lanes aim
    # their K/V at the sink page 0); None without an expert layer
    ffn_live: Any = None
    # the same where a sequence holds state or a ring; None: pages alone
    live: Any = None
    # the RUNS (consecutive lanes of one sequence at consecutive
    # positions: a chunk, a decode lane), each lane's offset in its
    # run, the slot a lane's state is written back to (its own where it
    # is its run's last live lane, else the slabs' sink row) and the
    # lanes up to the last live one (_pack fills them from 0 up, so:
    # the live lanes), the trips of the scan kernel; None: no state
    starts: Any = None
    offsets: Any = None
    wslots: Any = None
    live_lanes: Any = None
    # for each slot the lane whose inputs replace its convolution tail,
    # -1 where none does (ops/ssm.run_tail_lanes); None: no layer holds
    # a tail. Where the slots hold tails ALONE (HybridSpec.tail_layers)
    # `starts`, `offsets` and `wslots` are made for them and
    # `live_lanes` is not
    tail_lanes: Any = None
    # the rings' page table, the ring page each lane writes and the
    # window layers' work list; None: no window layer
    rings: Any = None
    ring_pages: Any = None
    window_work: Optional[WorkList] = None
    # the delta rule's lanes as its kernel walks them (ops/gated_delta.
    # lane_plan), and Mamba-2's as either of its lane passes does; None:
    # no such layer, or the delta rule's twin sorts its own
    delta_plan: Optional[gated_delta.LanePlan] = None


def step_lanes(g: Geometry, positions, write_pages, write_offs,
               page_tables, lane_slots, lane_lens) -> Lanes:
    """The step's `Lanes` from the lane arrays it was handed."""
    scope = jax.named_scope
    c = g.cfg
    tables, lens = walked(g, page_tables, positions, lane_lens, jnp)
    # the paged kernel's work list: from the lane arrays, once for all
    # the layers
    work = None
    if g.attn_impl != JNP:
        with scope("work_list"):
            work = build_work_list(
                tables, lane_slots, lens, page_size=c.page_size,
                block_pages=g.block_pages, max_items=g.attn_max_items)
    lanes = Lanes(positions, write_pages, write_offs, page_tables,
                  lane_slots, lane_lens, work, tables, lens,
                  ffn_live=write_pages != 0 if g.arch.experts else None)
    if c.hybrid is None:
        return lanes
    with scope("work_list"):
        live = write_pages != 0
        lane = jnp.arange(1, live.shape[0] + 1, dtype=jnp.int32)
        # runs are the scans' and the tails': nothing of them without a
        # layer that holds a state or a tail
        state = c.hybrid.state_layers > 0
        runs = state or c.hybrid.tail_layers > 0
        starts = ssm.run_starts(lane_slots, positions) if runs else None
        # rings are the window layers' alone: no table, write addresses
        # or work list of them without one
        ringed = c.hybrid.window_layers > 0
        if ringed:
            rings = ring_tables(c, jnp)
            page = positions // c.page_size
        made = {"live": live}
        if runs:
            made.update(
                starts=starts, offsets=ssm.run_offsets(starts),
                wslots=ssm.run_write_slots(starts, live, lane_slots,
                                           c.max_seqs))
            if state:       # the scans' trips; the tails alone need none
                made["live_lanes"] = jnp.max(jnp.where(live, lane, 0))
            if c.hybrid.tails:
                made["tail_lanes"] = ssm.run_tail_lanes(
                    made["wslots"], c.max_seqs)
            if g.delta_impl not in (None, JNP) or g.ssd:
                made["delta_plan"] = gated_delta.lane_plan(
                    lane_slots, positions, live, starts, made["live_lanes"])
        if ringed:
            made.update(
                rings=rings,
                ring_pages=jnp.where(live, rings[lane_slots, page], 0))
            if g.attn_impl != JNP:
                made["window_work"] = build_work_list(
                    rings, lane_slots, lane_lens, page_size=c.page_size,
                    block_pages=g.block_pages,
                    max_items=g.window_max_items, window=g.arch.window)
    return lanes._replace(**made)


# --------------------------------------------------------------- bodies
# A body is the mixer of layer `i` over the step's lanes:
#   body(g, params, i, x, h, lanes, pool, memory, lora, tp_axis)
#     -> (x, pool, memory)
# `x` the residual stream and `h` its norm (the engine's `ln`); `pool`
# flows through (the K/V written before any lane attends); `memory` is
# what the description's memory layer hands the gated memory units
# after it, carried by the engine's layer loop; `lora` the lanes'
# adapter rows of this layer and their scales, (dict, (T,)) or None;
# `tp_axis` the serve mesh's axis inside shard_map. The last two reach
# the kinds whose descriptions serve them (arch.refused). Returns x
# after the mixer and its residual or, in a parallel block
# (arch.parallel_block), the mixer's branch alone. A body whose device
# work only the device can count returns those counts as a fourth value
# (the SPARSE body: what its selection's calls walked).

def _paged(g, q, kv, layer, tables, lanes, lens, work, window=0,
           heads=None):
    """One call of the ragged paged kernel on a pool layer's pages, for
    all of q's heads or for `heads`, a slice of them."""
    k_pages, v_pages, k_scales, v_scales, page_base = kv.layer(layer)
    return paged_attention_ragged_v2(
        q if heads is None else q[:, heads], k_pages, v_pages, tables,
        lanes.lane_slots, lens,
        k_scales=k_scales, v_scales=v_scales,
        scale=float(g.arch.attn_scale), block_kv=g.block_kv, work=work,
        window=window, page_base=page_base, **g.attn_kw)


def _attention(g, params, i, x, h, lanes, pool, memory, lora=None,
               tp_axis=None):
    """An attention layer: `qkv` (the description's projections at the
    lanes' positions), `kv_write` (KVPool.write: quantize and scatter),
    `attn` (the ragged paged kernel over a work list), `diff_norm`
    where the attention is differential (arch.differential), `attn_gate`
    where `qkv` bore an output gate (arch.output_gate), `attn_out`. The
    kind says which pages it writes and reads: ATTN layer i of the one
    pool; WINDOW its own layer of the rings, under
    the window's list; FULL (and the attention half of SSD_ATTN) its own
    of a hybrid pool's paged layers; CROSS the first of those, writing
    nothing."""
    scope = jax.named_scope
    arch = g.arch
    kind = arch.mixer(i)
    la, ad_s = lora if lora is not None else (None, None)
    with scope("qkv"):
        q, k, v, *gate = arch.qkv(
            params, i, h, lanes.positions, lora=None if la is None else
            (la["a_qkv"], la["b_qkv"], ad_s))             # (T, H[/t], D)
    write_pages, page_tables = lanes.write_pages, lanes.page_tables
    work, window = lanes.work, 0
    if kind == ATTN:
        kv, layer = pool, i
    elif kind == WINDOW:
        kv, layer = pool.window, arch.window_layers.index(i)
        write_pages, page_tables = lanes.ring_pages, lanes.rings
        work, window = lanes.window_work, arch.window
    elif kind in (FULL, SSD_ATTN):
        kv, layer = pool.full, arch.full_layers.index(i)
    else:
        kv, layer = pool.full, 0
    if kind != CROSS:
        with scope("kv_write"):
            kv = kv.write(layer, write_pages, lanes.write_offs, k, v)
        if kind == WINDOW:
            pool = dataclasses.replace(pool, window=kv)
        elif kind in (FULL, SSD_ATTN):
            pool = dataclasses.replace(pool, full=kv)
        else:
            pool = kv
    with scope("attn"):
        o = _paged(g, q, kv, layer, page_tables, lanes, lanes.lane_lens,
                   work, window)
    if arch.differential:
        with scope("diff_norm"):
            o = arch.diff_norm(params, i, o)
    if arch.output_gate:
        with scope("attn_gate"):
            o = arch.attn_gate(o, *gate)
    with scope("attn_out"):
        x = arch.attn_out(
            params, i, o, x, psum_axis=tp_axis,
            lora=None if la is None else (la["a_wo"], la["b_wo"], ad_s))
    return x, pool, memory


def _state_space(g, params, i, x, h, lanes, pool, memory, lora=None,
                 tp_axis=None):
    """A state-space layer: `ssm_proj` (the in, x, dt and out
    projections), `ssm_conv` (the convolution over a run and its slot's
    tail), `ssm_scan` (the recurrence from each run's slot state, the
    gate, the state's write-back). The convolution, the scan and the
    gate run in f32. The description's memory layer returns its scan
    output as `memory`."""
    scope = jax.named_scope
    arch = g.arch
    j = arch.ssm_layers.index(i)
    p = params[f"layer{i}_ssm"]
    slots, positions = lanes.lane_slots, lanes.positions
    with scope("ssm_proj"):
        u, z = arch.ssm_in(params, i, h)                  # (T, d_inner)
    with scope("ssm_conv"):
        u, tail = ssm.segmented_conv(
            p, u, pool.tail[j], slots, positions, lanes.offsets,
            lanes.tail_lanes)
        u = jax.nn.silu(u)
    with scope("ssm_proj"):
        dt, b, c = arch.ssm_scan_inputs(params, i, u)
    with scope("ssm_scan"):
        # the kernel keeps an f32 slab in place; a slab of another
        # dtype (no configuration's) keeps the twin's rounding at
        # every lane
        if g.scan_impl != JNP and pool.state.dtype == jnp.float32:
            y, state = ssm_scan.ssm_scan(
                p, u, dt, b, c, pool.state, j, slots, positions,
                lanes.starts, lanes.wslots, lanes.live_lanes,
                interpret=g.scan_impl == PALLAS_INTERPRET)
        else:
            y, state = ssm.segmented_scan(
                p, u, dt, b, c, pool.state[j], slots, positions,
                lanes.starts, lanes.wslots)
            state = pool.state.at[j].set(state)
        gated = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
        pool = dataclasses.replace(
            pool, state=state, tail=pool.tail.at[j].set(tail))
        if i == arch.memory_layer:
            memory = y.astype(x.dtype)
    with scope("ssm_proj"):
        x = arch.ssm_out(params, i, gated, x)
    return x, pool, memory


def _gated_memory(g, params, i, x, h, lanes, pool, memory, lora=None,
                  tp_axis=None):
    """A gated memory unit, `gmu`: the memory layer's scan output,
    gated by this layer's input."""
    with jax.named_scope("gmu"):
        return g.arch.gmu(params, i, h, memory, x), pool, memory


def _linear(g, params, i, x, h, lanes, pool, memory, lora=None,
            tp_axis=None):
    """A lightning linear-attention layer: `linear_proj` (the
    projections, the QK-norm and rotation; the output norm, gate and
    projection), `linear_scan` (the recurrence from each run's slot
    state and the state's write-back, ops/linear_attention.
    segmented_lightning, in f32; a slab of another dtype — no
    configuration's — is read and rounded back at the step's edge)."""
    scope = jax.named_scope
    arch = g.arch
    j = arch.linear_layers.index(i)
    with scope("linear_proj"):
        q, k, v = arch.linear_qkv(params, i, h, lanes.positions)
    with scope("linear_scan"):
        o, state = linear_attention.segmented_lightning(
            q, k, v, arch.decays[i], pool.state[j].astype(jnp.float32),
            lanes.lane_slots, lanes.positions, lanes.live, lanes.starts,
            lanes.offsets)
        # the write-back below updates the slab IN PLACE and `o` reads
        # the layer's OLD state too: held together, so that every reader
        # of the old state is done before the write. The barrier stands
        # through the compiler's scheduler and rematerialization. Without
        # it, short of memory at the published size, the compiler
        # REMATERIALIZED the slice of the old state for o's product
        # after the in-place write (the last lightning layer's): a
        # sequence's second chunk left that layer 0.073 from the f32
        # reference where 0.012 entered it, in whichever build of the
        # step the schedule fell that way (PERF.md section 6, PR 57)
        o, state = jax.lax.optimization_barrier((o, state))
        pool = dataclasses.replace(pool, state=pool.state.at[j].set(
            state.astype(pool.state.dtype)))
    with scope("linear_proj"):
        x = arch.linear_out(params, i, o, h, x)
    return x, pool, memory


def _delta(g, params, i, x, h, lanes, pool, memory, lora=None,
           tp_axis=None):
    """A gated-delta-rule layer: `delta_proj` (the q, k, v, z, b and a
    projections and the gates; the output norm, gate and projection),
    `delta_conv` (the convolution over a run and its slot's tail, silu,
    the L2 norms), `delta_scan` (the rule from each run's slot state and
    the state's write-back, in f32 and in place in the pool's slab:
    kernels/gated_delta_scan.py for the lanes that go lane by lane, or
    its twin ops/gated_delta.segmented)."""
    scope = jax.named_scope
    arch = g.arch
    j = arch.delta_layers.index(i)
    with scope("delta_proj"):
        u, z, beta, gl = arch.delta_in(params, i, h)
    with scope("delta_conv"):
        u, tail = ssm.segmented_conv(
            params[f"layer{i}_delta"], u, pool.tail[j], lanes.lane_slots,
            lanes.positions, lanes.offsets, lanes.tail_lanes)
        q, k, v = arch.delta_heads(jax.nn.silu(u))
    with scope("delta_scan"):
        # the kernel keeps an f32 slab in place; a slab of another
        # dtype (no configuration's) keeps the twin
        if lanes.delta_plan is not None and pool.state.dtype == jnp.float32:
            o, state = gated_delta_scan.gated_delta_scan(
                q, k, v, gl, beta, pool.state, j, lanes.lane_slots,
                lanes.positions, lanes.delta_plan,
                interpret=g.delta_impl == PALLAS_INTERPRET)
        else:
            o, state = gated_delta.segmented(
                q, k, v, gl, beta, pool.state, lanes.lane_slots,
                lanes.positions, lanes.live, lanes.starts, lanes.wslots,
                lanes.live_lanes, layer=j)
        pool = dataclasses.replace(
            pool, state=state, tail=pool.tail.at[j].set(tail))
    with scope("delta_proj"):
        x = arch.delta_out(params, i, o, z, x)
    return x, pool, memory


def _short_conv(g, params, i, x, h, lanes, pool, memory, lora=None,
                tp_axis=None):
    """A gated short convolution (ops/short_conv.py): `conv_proj` (the
    in-projection to B | C | z), `short_conv` (the gate B * z, the taps
    over a run and its slot's tail, the tail's write-back by runs, the
    second gate; the taps in f32), `conv_out` (the out-projection and
    the residual). The layer's whole cache is its slot's tail: no state,
    no page."""
    scope = jax.named_scope
    arch = g.arch
    j = arch.conv_layers.index(i)
    p = params[f"layer{i}_conv"]
    with scope("conv_proj"):
        b, c, z = short_conv.project(p, h)                # (T, E) each
    with scope("short_conv"):
        y, tail = short_conv.segmented(
            p, b, c, z, pool.tail[j], lanes.lane_slots, lanes.positions,
            lanes.offsets, lanes.tail_lanes)
        pool = dataclasses.replace(pool, tail=pool.tail.at[j].set(tail))
    with scope("conv_out"):
        return x + short_conv.out_project(p, y), pool, memory


def _ssd(g, params, i, h, lanes, pool):
    """Layer `i`'s Mamba-2 heads over `h` -> (their BRANCH alone, the
    pool): `ssm_proj` (the in-projection and its multipliers; the gated
    per-group norm and the out-projection), `ssm_conv` (the convolution
    over a run and its slot's tail, silu), `ssm_scan` (the recurrence
    from each run's slot state and the state's write-back, in f32 and in
    place in the pool's slab: kernels/ssd_scan.py for the lanes that go
    lane by lane, or its twin ops/ssd.lane_pass; whole blocks of one run
    as products)."""
    scope = jax.named_scope
    arch = g.arch
    j = arch.ssd_layers.index(i)
    with scope("ssm_proj"):
        # held as it is computed: at the published size (13.6 GiB of
        # arguments) XLA's rematerialization otherwise recomputes the
        # whole in-projection for each of its seven readers — z, dt and
        # the convolution's shifted rows — 2.0 ms a layer for 0.3
        # (PERF.md section 6, PR 56)
        z, u, dt = jax.lax.optimization_barrier(arch.ssd_in(params, i, h))
    with scope("ssm_conv"):
        u, tail = ssm.segmented_conv(
            params[f"layer{i}_ssm"], u, pool.tail[j], lanes.lane_slots,
            lanes.positions, lanes.offsets, lanes.tail_lanes)
        u = jax.nn.silu(u)
    with scope("ssm_scan"):
        forms, skip = arch.ssd_scan_inputs(params, i, u, dt)
        # the kernel keeps an f32 slab in place; a slab of another
        # dtype (no configuration's) keeps the twin
        lane_pass = functools.partial(
            ssd_scan.lane_pass, interpret=g.scan_impl == PALLAS_INTERPRET
        ) if g.scan_impl != JNP and pool.state.dtype == jnp.float32 \
            else ssd.lane_pass
        y, state = ssd.segmented(
            *forms, pool.state, j, lanes.lane_slots, lanes.positions,
            lanes.delta_plan, lane_pass=lane_pass)
        y = y + skip
        pool = dataclasses.replace(
            pool, state=state, tail=pool.tail.at[j].set(tail))
    with scope("ssm_proj"):
        return arch.ssd_out(params, i, y, z), pool


def _ssd_and_attention(g, params, i, x, h, lanes, pool, memory, lora=None,
                       tp_axis=None):
    """TWO sequence mixers off the layer's one norm (Falcon-H1): the
    Mamba-2 heads (`_ssd`), then the attention layer's scopes
    (`_attention`) on the layer's own pages, both of `h`; each branch
    comes back ALONE and `residual` adds them to x once."""
    s, pool = _ssd(g, params, i, h, lanes, pool)
    a, pool, memory = _attention(g, params, i, x, h, lanes, pool, memory,
                                 lora, tp_axis)
    with jax.named_scope("residual"):
        return x + (s + a), pool, memory


def _sparse(g, params, i, x, h, lanes, pool, memory, lora=None,
            tp_axis=None):
    """A block-sparse attention layer: `qkv`, `kv_write`,
    `sparse_compress` (the compressed key of every stride a lane's
    token completes, from the pages just written, to the selector's row
    of that lane's page), `attn` (the lanes under the selector's
    dense_len: the paged kernel over the table's first dense_len
    positions, `lanes.work` its list, a call a key/value head: each
    head's pages are a pool layer of their own,
    KVCacheConfig.head_layers), then for the lanes past it
    `sparse_score` (each lane against its sequence's compressed keys,
    fetched once a stretch of lanes for its main sequence, a copy a
    lane for the stray lanes of another),
    `sparse_select` (block scores, forced blocks, top-k) and
    `sparse_attn` (the selected blocks through the paged kernel again,
    from a list made of the selection: a masked call a key/value head
    and `select_call_lanes` lanes of `geometry`; the per-lane
    gathers under the jnp attention), `attn_out` (the gate and the
    output projection). -> (x, pool, memory, what the selection's calls
    walked: sparse_paged.paged_sparse_attention)."""
    scope = jax.named_scope
    arch = g.arch
    sc = arch.sparse
    positions, slots = lanes.positions, lanes.lane_slots
    # a pool layer a key/value head
    layers = g.cfg.head_layers(arch.sparse_layers.index(i))
    with scope("qkv"):
        q, k, v = arch.sparse_qkv(params, i, h)
    kv = pool.full
    with scope("kv_write"):
        for head, layer in enumerate(layers):
            kv = kv.write(layer, lanes.write_pages, lanes.write_offs,
                          k[:, head:head + 1], v[:, head:head + 1])
    with scope("sparse_compress"):
        tables = jnp.take(lanes.page_tables, slots, axis=0)
        for layer in layers:
            rows, done = stride_keys(kv, layer, tables, positions, sc)
            kv = kv.write_selector(
                layer, jnp.where(done, lanes.write_pages, 0), rows)
    pool = dataclasses.replace(pool, full=kv)
    with scope("attn"):
        each = arch.num_heads // arch.kv_heads
        o_dense = jnp.concatenate([
            _paged(g, q, kv, layer, lanes.walked_tables, lanes,
                   lanes.walked_lens, lanes.work,
                   heads=slice(head * each, (head + 1) * each))
            for head, layer in enumerate(layers)], axis=1)
    o, walked = paged_sparse_attention(
        q, kv, layers, lanes.page_tables, slots, positions, lanes.live, sc,
        impl=g.attn_impl, block_pages=g.select_block_pages,
        call_lanes=g.select_call_lanes, max_items=g.select_max_items)
    with scope("sparse_attn"):
        o = jnp.where((positions < sc.dense_len)[:, None, None],
                      o_dense, o)
    with scope("attn_out"):
        x = arch.sparse_out(params, i, o, h, x)
    return x, pool, memory, walked


def select_landed(g: Geometry, walked) -> dict:
    """SELECT_LANDED_COUNTS of a step from what its SPARSE layers'
    calls walked, (2,) summed over them on the device (`_sparse`):
    their grid steps, the selection blocks their items fetch, and the
    bytes of K and V those are — what the device moves of the selected
    context, whoever reads it."""
    items, fetches = (int(n) for n in walked)
    return {"select_items": items, "select_block_fetches": fetches,
            "selected_kv_bytes": fetches * 2 * g.arch.sparse.block_size
            * g.arch.kv_head_dim * g.cfg.kv_itemsize}


BODIES = {ATTN: _attention, WINDOW: _attention, FULL: _attention,
          CROSS: _attention, SSM: _state_space, GMU: _gated_memory,
          LINEAR: _linear, SPARSE: _sparse, DELTA: _delta,
          SSD_ATTN: _ssd_and_attention, CONV: _short_conv}


# --------------------------------------------------------------- counts
def step_counts(g: Geometry, page_tables, positions, lane_slots,
                lane_lens, *, live_lanes: int, runs: int, head_rows: int,
                emitters: int) -> dict:
    """What the step's mixers will do for these packed lanes, counted
    on the host where the lanes are made (numpy; `page_tables` the
    cache's, the lane arrays (width,), the first `live_lanes` of them
    live, in `runs` chunks): `work_items` of ONE call on the full
    pages' list (`items`, `rows`, `total`, ...), `kv_bytes_read` what
    all the paged calls fetch, LIVE_COUNTS over all of the calls (the
    head's `head_rows` and `emitters` are the caller's), and of
    STEP_COUNTS what the model has. Raises for a plan
    whose items pass a grid's bound: it would lose work (the PROOF in
    `geometry`)."""
    c, arch = g.cfg, g.arch
    ps = c.page_size
    group = arch.num_heads // arch.kv_heads  # query heads a K/V head
    full_calls, window_calls = g.attn_calls

    def listed(what, lens, tables, bound, window=0):
        """One call's `work_items` on a list, checked against its
        grid's bound."""
        items = work_items(
            lens, lane_slots, tables, page_size=ps,
            block_kv_pages=g.block_pages, max_items=bound,
            live_lanes=live_lanes, window=window, group=group)
        if items["total"] > items["grid"]:
            raise RuntimeError(
                f"the plan makes {items['total']} {what} work items, "
                f"the kernel's grid holds {items['grid']}")
        return items

    tables, lens = walked(g, page_tables, positions, lane_lens)
    work = listed("attention", lens, tables, g.attn_max_items)
    work.update(attn_items=work["items"], attn_rows=work["rows"],
                ssm_runs=0, lanes_past_window=0)
    lists = [(full_calls, work)]            # (calls that walk it, list)
    # what ONE call fetches of a page: a pool layer's heads
    page_bytes = kv_page_bytes(ps, c.layer_heads, arch.kv_head_dim,
                               c.kv_itemsize, c.quantized)
    full = full_calls * work["page_fetches"] * page_bytes
    ringed = 0
    if c.hybrid is not None:
        # a model of several mixer kinds: the full layer's pages are
        # fetched by its own call and by every cross layer's; the
        # window layers' calls walk the rings under the window's list;
        # a scan reads and writes one state and one tail a RUN
        if g.rings is not None:
            ring = listed("window", lane_lens, g.rings,
                          g.window_max_items, arch.window)
            lists.append((window_calls, ring))
            ringed = window_calls * ring["page_fetches"] * page_bytes
        work.update(
            full_kv_bytes=full, window_kv_bytes=ringed,
            lanes_past_window=int(
                (lane_lens[:live_lanes] > arch.window).sum())
            if arch.window else 0,
            ssm_runs=runs if c.hybrid.state_layers
            or c.hybrid.tail_layers else 0,
            state_bytes=2 * runs * c.hybrid.state_bytes)
    if g.conv_layers:
        work["conv_lanes"] = live_lanes * g.conv_layers
    if g.delta_impl is not None or g.ssd:
        # which form each block of lanes takes, by the rule the step
        # itself follows (the same function over numpy)
        live = np.arange(g.width) < live_lanes
        as_chunk, count, _ = gated_delta.block_forms(
            ssm.run_starts(lane_slots, positions, np), live, live_lanes, np)
        by_lane, by_block = SSD_COUNTS if g.ssd else DELTA_COUNTS
        work.update({by_lane: int(count[~as_chunk].sum()),
                     by_block: int(as_chunk.sum())})
    work["kv_bytes_read"] = full + ringed
    if g.dense_pages:
        # what the selection does for the live lanes past dense_len,
        # counted where the lanes are made: a lane at position t sees
        # t // block + 1 blocks and selects min(topk, that) of them a
        # key/value head, whatever the scores say. What the device
        # MOVES for the scores: of a table's compressed keys every
        # stretch fetches one copy (its main sequence's) and the stray
        # lanes a copy each, a stretch of them a trip, by the rule the
        # step itself follows (the same functions over numpy). What it
        # moves of the selected blocks depends on WHICH were chosen:
        # the device counts that (`select_landed`)
        sc = arch.sparse
        past = positions[:live_lanes]
        past = past[past >= sc.dense_len]
        visible = past // sc.block_size + 1
        heads = arch.kv_heads * len(arch.sparse_layers)
        # the proof of the selection lists' bounds: the runs a call's
        # lanes hold are its tiles and the slot changes among them
        changes = int((lane_slots[1:] != lane_slots[:-1]).sum())
        if changes > c.max_seqs:
            raise RuntimeError(
                f"the plan changes slot {changes} times; the selection's "
                f"lists are bounded for {c.max_seqs}")
        _, stray = main_slots(
            lane_slots, np.arange(g.width) < live_lanes, np)
        tiles = -(-g.width // LANE_TILE)
        mixed = len(np.unique(np.flatnonzero(stray) // LANE_TILE))
        copies = tiles + STRAY_TILE * int(stray_batches(stray, np))
        work.update(
            sparse_lanes=len(past),
            blocks_visible=int(visible.sum()) * heads,
            blocks_selected=int(np.minimum(visible, sc.topk).sum())
            * heads,
            selector_bytes=copies * heads * c.pages_per_seq
            * c.selector_dim * int(c.selector_dtype.itemsize),
            score_tiles=tiles, score_shared_tiles=tiles - mixed)
    # what the calls walk against the step's live work (LIVE_COUNTS): a
    # call's grid is its list's own length, the live lanes' items and
    # one a tile of the inactive ones
    work.update(
        grid_steps=sum(n * w["total"] for n, w in lists),
        live_steps=sum(n * w["items"] for n, w in lists),
        short_steps=sum(n * w["short_items"] for n, w in lists),
        live_rows=sum(n * w["rows"] for n, w in lists),
        lanes=head_rows, emitters=emitters, **paged_calls(g))
    return work
