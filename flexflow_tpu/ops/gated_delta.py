"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464; the
`linear_attention` layers of Qwen3-Next, `model_type` qwen3_next, and of
Olmo-Hybrid, `model_type` olmo_hybrid): a matrix state a value head that
every token DECAYS, then CORRECTS along its key before it adds to it.

Per token t of a sequence, h (E), Hk key heads of Dk, Hv = r Hk value
heads of Dv:
  [q | k | v | z] = h W_qkvz, laid out a KEY head at a time as
      [q Dk | k Dk | v r Dv | z r Dv];  [b | a] = h W_ba, [b r | a r]
      a key head;
  [q | k | v] (all heads' q, then k, then v: 2 Hk Dk + Hv Dv channels)
      through a causal depthwise convolution of `d_conv` taps, no bias,
      then silu;
  q, k L2-normalised over their Dk dims (x / sqrt(sum x^2 + 1e-6)),
      each key head serving its r value heads, q <- q / sqrt(Dk);
  beta = beta_scale sigmoid(b),  g = -exp(A_log) * softplus(a + dt_bias)
      (a value head, f32; g <= 0; beta_scale 1, Qwen3-Next's — or 2
      where the layer ALLOWS NEGATIVE EIGENVALUES, Olmo-Hybrid's
      `linear_allow_neg_eigval`: I - beta k k^T then has the eigenvalue
      1 - beta in (-1, 1) along k and a state can flip sign along a key;
      every form below takes beta as data);
  S <- exp(g_t) S;  S <- S + k_t (beta_t (v_t - S^T k_t))^T;
  o_t = S^T q_t                            (S is Dk x Dv a head, f32);
  out = (RMSNorm_Dv(o; w) * silu(z)) W_o   (the norm's scale is w, not
      1 + w).
The state, the recurrence, the convolution and the norm run in f32
whatever the activation dtype.

Three forms of the recurrence, the same numbers up to f32 rounding:
`recurrent` (a token a trip: the definition), `chunked` (whole sequences
from a zero state, CHUNK tokens a trip, the WY form: with G the running
sum of g inside the chunk and D_ij = exp(G_i - G_j) for i >= j,
  T = (I + tril(beta K K^T * D, -1))^-1,  W = T (beta K * exp(G)),
  U = T (beta V),  V' = U - W S,
  O = (Q * exp(G)) S + tril(Q K^T * D) V',
  S <- exp(G_C) S + (K * exp(G_C - G))^T V';
the graph op's forward) and `segmented` (the LANES of a serving step:
runs of consecutive lanes of one sequence, each resuming from its
slot's state, serve/mixers.py; on the chip its lane form is the kernel
kernels/gated_delta_scan.py, whose twin it is, and `lane_plan` /
`chunk_blocks` below are that kernel's order of work). Every decay is
the exp of a non-positive sum of g, never a quotient of two of them.

The state is laid out (Hv * Dk, Dv): a value head's Dk x Dv matrix after
another's, the value dimension on the lanes — the layout a batched
product over heads and a token's rank-one update both take as it lies
(with the key dimension leading, XLA re-laid the whole slab of every
layer out for each block of lanes: PERF.md section 6, PR 49). Where Dv
is no multiple of the 128 lanes but two heads side by side are (192:
an f32 row of 192 tiles to 256 in HBM, a third more to hold and to
move), the heads lie in PAIRS on the lanes, (Hv / 2 * Dk, 2 Dv): head
2p's matrix in a row's first Dv lanes, head 2p + 1's in its last.
`state_pack` is the one rule, `state_shape` the slab's rows by it;
serve/kv_cache.HybridSpec.state_shape, `segmented`, `chunk_blocks` and
the kernel all ask them.
"""

from __future__ import annotations

import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ..op import CHANNEL_IN, CHANNEL_OUT, SAMPLE, SEQ, Op, OpContext, \
    WeightSpec, register_op
from .ssm import causal_conv

F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
CHUNK = 64          # tokens a trip of the chunk form; a power of two
# the fewest live lanes of ONE run for which a block of the serving step
# takes the chunk form: under it the lanes go one after another. Set
# (PR 49) where XLA's loop cost a lane what the chunk form cost a
# sixteenth of a block. On the chip that is the twin's price alone now:
# in the kernel (kernels/gated_delta_scan.py, PR 51) a run's lanes cost
# 2.4 us each behind 20 us a call, a chunk-form block 171 us, so the
# two cross near a WHOLE block (evidence/gated_delta_tpu.json: one run
# of 64 lanes 166 us lane by lane). PR 51 leaves the threshold where it
# was; moving it is ROADMAP S14 (d-chunk)
CHUNK_MIN_LANES = 16
L2_EPS = 1e-6


# ------------------------------------------------------------ the layer
def project(p, h, key_heads: int, ratio: int, dk: int, dv: int):
    """h (..., E) -> (u (..., 2 Hk Dk + Hv Dv) the convolution's raw
    input in its channel order [q | k | v], z (..., Hv, Dv), b, a
    (..., Hv)), all in h's dtype."""
    lead = h.shape[:-1]
    mm = lambda w: jnp.dot(h, p[w].astype(h.dtype),
                           preferred_element_type=F32).astype(h.dtype)
    qkvz = mm("w_qkvz").reshape(lead + (key_heads, -1))
    q, k, v, z = jnp.split(
        qkvz, [dk, 2 * dk, 2 * dk + ratio * dv], axis=-1)
    ba = mm("w_ba").reshape(lead + (key_heads, 2 * ratio))
    flat = lambda a: a.reshape(lead + (-1,))
    u = jnp.concatenate([flat(q), flat(k), flat(v)], axis=-1)
    hv = key_heads * ratio
    return (u, z.reshape(lead + (hv, dv)),
            ba[..., :ratio].reshape(lead + (hv,)),
            ba[..., ratio:].reshape(lead + (hv,)))


def gates(p, b, a, beta_scale: float = 1.0):
    """-> (beta, g) (..., Hv) f32; `beta_scale` static, 1.0 or 2.0 (the
    layer allows negative eigenvalues)."""
    beta = jax.nn.sigmoid(b.astype(F32))
    if beta_scale != 1.0:
        beta = beta * beta_scale
    g = -jnp.exp(p["A_log"].astype(F32)) * jax.nn.softplus(
        a.astype(F32) + p["dt_bias"].astype(F32))
    return beta, g


def split_heads(u, key_heads: int, ratio: int, dk: int, dv: int):
    """u (..., channels) f32 after the convolution and silu -> q, k
    (..., Hv, Dk) L2-normalised, q over sqrt(Dk), a key head repeated
    for its `ratio` value heads, and v (..., Hv, Dv), f32."""
    lead = u.shape[:-1]
    q, k, v = jnp.split(u, [key_heads * dk, 2 * key_heads * dk], axis=-1)

    def unit(x):
        x = x.reshape(lead + (key_heads, dk))
        x = x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                              + L2_EPS)
        return jnp.repeat(x, ratio, axis=-2)

    return (unit(q) * (1.0 / math.sqrt(dk)), unit(k),
            v.reshape(lead + (key_heads * ratio, dv)))


def gate_and_project(p, o, z, eps: float):
    """o (..., Hv, Dv) f32, z (..., Hv, Dv) -> (RMSNorm_Dv(o; w) *
    silu(z)) W_o in z's dtype."""
    var = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
    y = o * jax.lax.rsqrt(var + eps) * p["o_norm"].astype(F32)
    y = (y * jax.nn.silu(z.astype(F32))).astype(z.dtype)
    y = y.reshape(y.shape[:-2] + (-1,))
    return jnp.dot(y, p["wo"].astype(z.dtype),
                   preferred_element_type=F32).astype(z.dtype)


# ------------------------------------------------- the state's layout
LANE_TILE = 128     # an HBM row's f32 lanes: a narrower last dim is padded


def state_pack(heads: int, dv: int) -> int:
    """How many value heads lie side by side on a state row's lanes: 1
    (a head after another, the rows (Hv * Dk, Dv)) wherever Dv fills
    whole lane tiles or pairs would not either; 2 where two heads do and
    one does not."""
    return 2 if dv % LANE_TILE and (2 * dv) % LANE_TILE == 0 \
        and heads % 2 == 0 else 1


def state_shape(heads: int, dk: int, dv: int) -> tuple:
    """A sequence's state of one layer as the slab holds it."""
    pack = state_pack(heads, dv)
    return (heads // pack * dk, pack * dv)


STATE_LAYOUTS = {1: "heads", 2: "head_pairs"}


def state_layout(heads: int, dk: int, dv: int) -> dict:
    """What an engine's record says of the slab: the layout's name, a
    state's rows and what ONE state of one layer holds of HBM, its rows
    up to 8 and its lanes up to 128 (the logical bytes where the layout
    pads nothing)."""
    rows, lanes = state_shape(heads, dk, dv)
    return {"delta_state_layout": STATE_LAYOUTS[state_pack(heads, dv)],
            "delta_state_shape": (rows, lanes),
            "delta_state_slot_bytes":
                4 * (-(-rows // 8) * 8) * (-(-lanes // LANE_TILE)
                                          * LANE_TILE)}


def _slab_view(whole, h: int, dk: int, dv: int):
    """The slab (layers, slots + 1) + state_shape with a state's rows
    split by head — a view where a head's rows are whole (pack 1). A
    pair's two heads share their rows' LANES: splitting those is no view
    of the slab but a copy of all of it (an f32 row of 192 tiles to
    256), so at pack 2 the slab stays as it lies and ONE state is
    re-laid where it is read or written (`_heads_of`, `_rows_of`)."""
    if state_pack(h, dv) == 1:
        return whole.reshape(whole.shape[:2] + (h, dk, dv))
    return whole


def _heads_of(rows, h: int, dk: int, dv: int):
    """One state of `_slab_view`'s slab -> (H, Dk, Dv)."""
    if rows.ndim == 3:
        return rows
    pack = state_pack(h, dv)
    return jnp.moveaxis(rows.reshape(h // pack, dk, pack, dv), 2, 1
                        ).reshape(h, dk, dv)


def _rows_of(s, like):
    """(H, Dk, Dv) -> one state as `_slab_view`'s slab `like` holds it."""
    if like.ndim == 5:
        return s
    h, dk, dv = s.shape
    pack = state_pack(h, dv)
    return jnp.moveaxis(s.reshape(h // pack, pack, dk, dv), 1, 2
                        ).reshape(like.shape[2:])


# ------------------------------------------------------ the recurrence
def _token(s, q, k, v, g, beta):
    """One token on a state s (H, Dk, Dv): q, k (H, Dk), v (H, Dv), g,
    beta (H,), all f32 -> (s', o (H, Dv))."""
    kc = k[:, :, None]                                     # (H, Dk, 1)
    s = s * jnp.exp(g)[:, None, None]
    u = beta[:, None] * (v - jnp.sum(kc * s, axis=1))
    s = s + kc * u[:, None, :]
    return s, jnp.sum(q[:, :, None] * s, axis=1)


def recurrent(q, k, v, g, beta, state=None):
    """The definition, a token a trip: q, k (S, H, Dk), v (S, H, Dv),
    g, beta (S, H) -> (o (S, H, Dv) f32, the state after (H, Dk, Dv)),
    from `state` (None: zeros)."""
    s0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), F32) \
        if state is None else state

    def step(s, x):
        return _token(s, *(a.astype(F32) for a in x))

    s, o = jax.lax.scan(step, s0, (q, k, v, g, beta))
    return o, s


def _unit_lower_inverse(m):
    """(..., C, C) unit lower triangular, C a power of two -> its
    inverse, by halves: [[A, 0], [B, D]]^-1 = [[A^-1, 0], [-D^-1 B A^-1,
    D^-1]] from blocks of one up (block forward substitution: as stable
    as a row at a time, in 2 log2(C) small products)."""
    c = m.shape[-1]
    lead = m.shape[:-2]
    inv = jnp.broadcast_to(jnp.eye(c, dtype=m.dtype), m.shape)
    s = 1
    while s < c:
        n = c // (2 * s)

        def diagonal(x):                   # (..., n, 2s, 2s)
            x = x.reshape(lead + (n, 2 * s, n, 2 * s))
            return jnp.moveaxis(
                jnp.diagonal(x, axis1=-4, axis2=-2), -1, -3)

        mb, ib = diagonal(m), diagonal(inv)
        low = -jnp.einsum("...ab,...bc,...cd->...ad", ib[..., s:, s:],
                          mb[..., s:, :s], ib[..., :s, :s], precision=_HI)
        ib = ib.at[..., s:, :s].set(low)
        inv = jnp.einsum("...nab,nm->...namb", ib,
                         jnp.eye(n, dtype=m.dtype)).reshape(m.shape)
        s *= 2
    return inv


def _chunk(s, q, k, v, g, beta):
    """CHUNK (or fewer) tokens of ONE sequence on a state s (H, Dk, Dv),
    the WY form: q, k (C, H, Dk), v (C, H, Dv), g, beta (C, H), f32 ->
    (s', o (C, H, Dv)). A token with beta 0 and g 0 changes nothing."""
    c = q.shape[0]
    cum = jnp.cumsum(g, axis=0)                              # (C, H) <= 0
    i = jnp.arange(c)
    low = (i[:, None] >= i[None, :])[None]                   # (1, C, C)
    diff = cum.T[:, :, None] - cum.T[:, None, :]             # (H, C, C)
    decay = jnp.where(low, jnp.exp(jnp.where(low, diff, 0.0)), 0.0)
    kk = jnp.einsum("ihd,jhd->hij", k, k, precision=_HI)
    a = jnp.where(i[:, None] > i[None, :], 1.0, 0.0)[None] * decay * kk \
        * beta.T[:, :, None]
    t = _unit_lower_inverse(a + jnp.eye(c, dtype=F32))
    d_in = jnp.exp(cum)                                      # (C, H)
    w = jnp.einsum("hij,jhd->ihd", t, k * (beta * d_in)[:, :, None],
                   precision=_HI)
    u = jnp.einsum("hij,jhd->ihd", t, v * beta[:, :, None], precision=_HI)
    vn = u - jnp.einsum("ihk,hkv->ihv", w, s, precision=_HI)
    qk = jnp.einsum("ihd,jhd->hij", q, k, precision=_HI) * decay
    o = jnp.einsum("ihk,hkv->ihv", q * d_in[:, :, None], s, precision=_HI) \
        + jnp.einsum("hij,jhv->ihv", qk, vn, precision=_HI)
    d_out = jnp.exp(cum[-1][None] - cum)                     # (C, H)
    s = jnp.exp(cum[-1])[:, None, None] * s + jnp.einsum(
        "jhk,jhv->hkv", k * d_out[:, :, None], vn, precision=_HI)
    return s, o


def chunked(q, k, v, g, beta, chunk: int = CHUNK):
    """Whole sequences from a zero state, `chunk` tokens a trip: q, k
    (B, S, H, Dk), v (B, S, H, Dv), g, beta (B, S, H) -> o (B, S, H, Dv)
    f32, equal to the recurrence."""
    b, s, h, dk = q.shape
    pad = -s % chunk
    n = (s + pad) // chunk

    def blocks(a):
        # padding tokens: beta 0 and g 0, they change nothing
        a = jnp.pad(a.astype(F32), ((0, 0), (0, pad)) + ((0, 0),) * (
            a.ndim - 2))
        return a.reshape((b, n, chunk) + a.shape[2:]).swapaxes(0, 1)

    def trip(state, x):
        return jax.vmap(_chunk)(state, *x)

    s0 = jnp.zeros((b, h, dk, v.shape[-1]), F32)
    _, o = jax.lax.scan(trip, s0, tuple(map(blocks, (q, k, v, g, beta))))
    return o.swapaxes(0, 1).reshape(b, s + pad, h, -1)[:, :s]


def block_forms(starts, live, live_lanes, xp=jnp, block: int = CHUNK):
    """Which form each BLOCK of `block` lanes takes: `starts`, `live`
    (T,) bool, `live_lanes` how many lanes from lane 0 up hold a token
    -> (as_chunk (n,) bool, count (n,) the block's live lanes, among
    (n, block) bool which lanes those are). A block whose live lanes
    are CHUNK_MIN_LANES or more of ONE run takes the chunk form; any
    other block's live lanes go lane by lane. numpy where the host
    counts the forms (serve/mixers.step_counts), jax.numpy where the
    step takes them."""
    t = starts.shape[0]
    pad = -t % block
    n = (t + pad) // block
    begins = xp.pad(starts, (0, pad), constant_values=True).reshape(n, block)
    alive = xp.pad(live, (0, pad), constant_values=False).reshape(n, block)
    lane = xp.arange(block)[None]
    count = xp.clip(live_lanes - xp.arange(n) * block, 0, block)
    among = (lane < count[:, None]) & alive
    one_run = ~xp.any(begins & among & (lane > 0), axis=1)
    return one_run & (count >= CHUNK_MIN_LANES), count, among


class Segments(NamedTuple):
    """Consecutive lanes of one run that go lane by lane, the first
    `count` entries of (T,) int32 arrays: a segment's first lane, its
    lanes, the slot its state comes from (-1: from zero, the sequence
    starts there) and the slot its state goes back to."""
    first: Any
    length: Any
    src: Any
    dst: Any
    count: Any


class LanePlan(NamedTuple):
    """A step's lanes as kernels/gated_delta_scan.py walks them: the
    first `chunks` entries of `chunk_ids` (n,) are the blocks that take
    the chunk form, in order, `among` (n, block) every block's live
    lanes; the lanes that go lane by lane are the segments `before` any
    chunk-form block of their run and `after` one."""
    chunk_ids: Any
    chunks: Any
    among: Any
    before: Segments
    after: Segments


def _front(flag):
    """(N,) bool -> (N,) int32: the indices where `flag`, in order,
    from entry 0 up (no scatter: a compare of every entry with every
    index), zeros behind them."""
    at = jnp.arange(flag.shape[0], dtype=jnp.int32)
    nth = jnp.cumsum(flag.astype(jnp.int32)) - 1
    hit = flag[None, :] & (nth[None, :] == at[:, None])
    return jnp.sum(jnp.where(hit, at[None, :], 0), axis=1)


def _segments(mask, starts, lane_slots, positions) -> Segments:
    """The lanes of `mask` (T,) as `Segments`: one where a run starts
    or the lane before is not of the mask."""
    no = jnp.zeros((1,), bool)
    begin = mask & (starts | ~jnp.concatenate([no, mask[:-1]]))
    end = mask & jnp.concatenate([begin[1:] | ~mask[1:], ~no])
    first, last = _front(begin), _front(end)
    slot = lane_slots[first].astype(jnp.int32)
    return Segments(first, last - first + 1,
                    jnp.where(positions[first] > 0, slot, -1), slot,
                    jnp.sum(begin.astype(jnp.int32)))


def lane_plan(lane_slots, positions, live, starts, live_lanes,
              block: int = CHUNK) -> LanePlan:
    """`segmented`'s order of work, made once a step for all the
    layers. A run is at most lanes (in a block it shares, or too few
    for the chunk form), then whole chunk-form blocks, then lanes (a
    tail under CHUNK_MIN_LANES, or one that shares its block): the
    lanes AFTER a chunk-form block of their own run are those of a
    block whose predecessor took the chunk form, up to the block's
    first run start."""
    t = starts.shape[0]
    as_chunk, _, among = block_forms(starts, live, live_lanes, block=block)
    lane = jnp.arange(t, dtype=jnp.int32)
    blk = lane // block
    lane_form = (lane < live_lanes) & ~as_chunk[blk]
    begun = jnp.cumsum(starts.astype(jnp.int32))
    base = jnp.concatenate([jnp.zeros((1,), jnp.int32), begun])[blk * block]
    no = jnp.zeros((1,), bool)
    after = lane_form & jnp.concatenate([no, as_chunk[:-1]])[blk] \
        & (begun == base)
    return LanePlan(
        _front(as_chunk), jnp.sum(as_chunk.astype(jnp.int32)), among,
        _segments(lane_form & ~after, starts, lane_slots, positions),
        _segments(after, starts, lane_slots, positions))


def chunk_blocks(q, k, v, g, beta, o, state, layer, lane_slots, positions,
                 plan: LanePlan, block: int = CHUNK, o_rows=None):
    """The chunk-form blocks of `plan`, one after another — a loop of
    as many trips as the step has such blocks, none where it has none —
    on layer `layer` of the slab `state` (layers, slots + 1) +
    `state_shape`: a block's run resumes from its slot's state (from zero where
    the sequence starts at the block's first lane) and leaves its state
    there, where the next block, or the lanes after, take it up; the
    block's rows of `o` (n * block, H, Dv) are written — through `o_rows`
    where `o` is held otherwise (the kernel's view of it). q, k, v, g,
    beta: n * block rows. -> (o, the slab)."""
    _, h, dk = q.shape
    dv = v.shape[-1]
    slab = _slab_view(state, h, dk, dv)
    heads = lambda rows: _heads_of(rows, h, dk, dv)

    def a_block(i, carry):
        slab, o = carry
        b = plan.chunk_ids[i]
        first = b * block
        rows = lambda a: jax.lax.dynamic_slice_in_dim(a, first, block)
        m = plan.among[b][:, None]
        slot = lane_slots[first]
        s = jnp.where(positions[first] > 0, heads(slab[layer, slot]), 0.0)
        s, ob = _chunk(s, rows(q), rows(k), rows(v),
                       jnp.where(m, rows(g), 0.0),
                       jnp.where(m, rows(beta), 0.0))
        return (slab.at[layer, slot].set(_rows_of(s, slab)),
                jax.lax.dynamic_update_slice_in_dim(
                    o, ob if o_rows is None else o_rows(ob), first, 0))

    slab, o = jax.lax.fori_loop(0, plan.chunks, a_block, (slab, o))
    return o, slab.reshape(state.shape)


def segmented(q, k, v, g, beta, state, lane_slots, positions, live,
              starts, wslots, live_lanes, layer=None, block: int = CHUNK):
    """The recurrence over the step's lanes. q, k (T, H, Dk), v (T, H,
    Dv), g, beta (T, H), f32; state (slots + 1,) + `state_shape` f32,
    every slot's matrix state (the last row the write sink) — or, with
    `layer`, the slab of all the layers' (layers, slots + 1) +
    `state_shape`, of which this call reads and writes row `layer` in
    place;
    `live` (T,) the
    lanes that hold a token, `live_lanes` how many from lane 0 up hold
    one; `starts` / `wslots` the runs (ops/ssm.run_starts /
    run_write_slots). A run resumes from its slot's state — from zero
    where the sequence starts inside it — and leaves the state after its
    last live lane in its slot.

    The lanes go by in BLOCKS of `block`, one after another, the state
    of the run that crosses a block's edge carried. A block whose live
    lanes are CHUNK_MIN_LANES or more of ONE run takes the chunk form on
    that run's one state; any other block's live lanes go a lane at a
    time, each run on its own slot's state; a block with no live lane
    does nothing. So a step touches a state once a run (or once a block
    of a long run): the work grows with the lanes and the runs, never
    with lanes x slots. -> (o (T, H, Dv) f32, state)."""
    t, h, dk = q.shape
    dv = v.shape[-1]
    pad = -t % block
    n = (t + pad) // block
    whole = state if layer is not None else state[None]
    at = layer or 0
    sink = whole.shape[1] - 1
    slab = _slab_view(whole, h, dk, dv)
    heads = lambda rows: _heads_of(rows, h, dk, dv)

    def blocks(a, fill=0):
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1),
                    constant_values=fill)
        return a.reshape((n, block) + a.shape[1:])

    def resume(slab, s, start, slot, pos):
        """The state a lane works on: its slot's where the lane starts a
        run (zeros where the sequence starts there), else the carried."""
        own = jnp.where(pos > 0, heads(slab[at, slot]), 0.0)
        return jnp.where(start, own, s)

    def trip(carry, x):
        s, slab = carry
        as_chunk, count, among, qb, kb, vb, gb, bb, slots, pos, begins, \
            wb = x

        def a_lane(j, c):
            s, slab, o = c
            s = resume(slab, s, begins[j], slots[j], pos[j])
            s, oj = _token(s, qb[j], kb[j], vb[j], gb[j], bb[j])
            return (s, slab.at[at, wb[j]].set(_rows_of(s, slab)),
                    jax.lax.dynamic_update_index_in_dim(o, oj, j, 0))

        s, slab, o_lanes = jax.lax.fori_loop(
            0, jnp.where(as_chunk, 0, count), a_lane,
            (s, slab, jnp.zeros((block, h, dv), F32)))

        def the_chunk(s_in):
            m = among[:, None]
            return _chunk(s_in, qb, kb, vb, jnp.where(m, gb, 0.0),
                          jnp.where(m, bb, 0.0))

        s_in = resume(slab, s, begins[0], slots[0], pos[0])
        s_out, o_chunk = jax.lax.cond(
            as_chunk, the_chunk,
            lambda s_in: (s_in, jnp.zeros((block, h, dv), F32)), s_in)
        s = jnp.where(as_chunk, s_out, s)
        last = jnp.maximum(count - 1, 0)
        slab = slab.at[at, jnp.where(as_chunk, wb[last], sink)].set(
            _rows_of(s, slab))
        return (s, slab), jnp.where(as_chunk, o_chunk, o_lanes)

    xs = block_forms(starts, live, live_lanes, block=block) + (
        blocks(q), blocks(k), blocks(v), blocks(g), blocks(beta),
        blocks(lane_slots), blocks(positions), blocks(starts, True),
        blocks(wslots, sink))
    (_, slab), o = jax.lax.scan(
        trip, (jnp.zeros((h, dk, dv), F32), slab), xs)
    whole = slab.reshape(whole.shape)
    return (o.reshape(n * block, h, dv)[:t],
            whole if layer is not None else whole[0])


# ----------------------------------------------------------------- the op
def a_log_init(key, shape, dtype=F32):
    """A = exp(A_log) uniform in (0, 16): the layer's published start."""
    return jnp.log(jax.random.uniform(key, shape, F32, 1e-3, 16.0)
                   ).astype(dtype)


def make_dt_bias_init(dt_min: float, dt_max: float):
    """softplus^-1 of a step drawn log-uniformly in [dt_min, dt_max]."""
    def init(key, shape, dtype=F32):
        dt = jnp.exp(jax.random.uniform(key, shape, F32)
                     * (math.log(dt_max) - math.log(dt_min))
                     + math.log(dt_min))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return init


@register_op
class GatedDeltaNet(Op):
    """x (B, S, E) -> out (B, S, E): the whole mixer (projections,
    convolution, gates, the delta rule, output norm, gate and
    projection). `dt_range`: the steps dt_bias starts at (softplus^-1,
    log-uniform); `norm_init` (lo, hi): the output norm's scale starts
    uniform in it; `allow_neg_eigval`: beta = 2 sigmoid(b) (the module's
    docstring)."""

    op_type = "gated_delta_net"

    def __init__(self, model, name, inputs, key_heads: int,
                 value_heads: int, key_dim: int, value_dim: int,
                 d_conv: int = 4, eps: float = 1e-6,
                 dt_range=(1e-3, 1e-1), norm_init=(1.0, 1.0),
                 kernel_initializer="glorot",
                 allow_neg_eigval: bool = False):
        super().__init__(model, name, inputs)
        self.embed_dim = int(inputs[0].shape[-1])
        self.key_heads, self.value_heads = int(key_heads), int(value_heads)
        if self.value_heads % self.key_heads:
            raise ValueError(f"{name}: {value_heads} value heads do not "
                             f"divide over {key_heads} key heads")
        self.ratio = self.value_heads // self.key_heads
        self.key_dim, self.value_dim = int(key_dim), int(value_dim)
        self.d_conv, self.eps = int(d_conv), float(eps)
        self.dt_range = tuple(map(float, dt_range))
        self.norm_init = tuple(norm_init)
        self.kernel_initializer = kernel_initializer
        self.allow_neg_eigval = bool(allow_neg_eigval)
        self.beta_scale = 2.0 if self.allow_neg_eigval else 1.0
        self.attrs = {"key_heads": self.key_heads,
                      "value_heads": self.value_heads,
                      "key_dim": self.key_dim, "value_dim": self.value_dim,
                      "d_conv": self.d_conv}
        if self.allow_neg_eigval:
            self.attrs["allow_neg_eigval"] = True

    @property
    def channels(self) -> int:
        """What the convolution runs over: all heads' q, k and v."""
        return 2 * self.key_heads * self.key_dim \
            + self.value_heads * self.value_dim

    @property
    def shape_args(self) -> tuple:
        return (self.key_heads, self.ratio, self.key_dim, self.value_dim)

    @property
    def state_shape(self) -> tuple:
        """A sequence's state as a serving slab holds it."""
        return state_shape(self.value_heads, self.key_dim, self.value_dim)

    def output_shapes(self):
        return [tuple(self.inputs[0].shape)]

    def output_dtypes(self):
        return [self.inputs[0].dtype]

    def weight_specs(self):
        from ..core.initializers import range_init
        e, hv = self.embed_dim, self.value_heads
        inner = hv * self.value_dim
        init = self.kernel_initializer
        mat = lambda i, o: WeightSpec((i, o), initializer=init,
                                      axes=(CHANNEL_IN, CHANNEL_OUT))
        return {
            "w_qkvz": mat(e, self.channels + inner),
            "w_ba": mat(e, 2 * hv),
            # glorot over the taps whatever the matrices start at
            "conv_w": WeightSpec((self.d_conv, self.channels),
                                 fan_in=self.d_conv, fan_out=self.d_conv),
            "A_log": WeightSpec((hv,), custom_init=a_log_init),
            "dt_bias": WeightSpec((hv,), custom_init=make_dt_bias_init(
                *self.dt_range)),
            "o_norm": WeightSpec((self.value_dim,),
                                 custom_init=range_init(self.norm_init)),
            "wo": mat(inner, e),
        }

    def forward(self, params, xs, ctx: OpContext):
        (x,) = xs
        u, z, b, a = project(params, x, *self.shape_args)
        u = jax.nn.silu(causal_conv(params, u))
        q, k, v = split_heads(u, *self.shape_args)
        beta, g = gates(params, b, a, self.beta_scale)
        o = chunked(q, k, v, g, beta)
        return [gate_and_project(params, o, z, self.eps)]

    def output_axes(self):
        return [(SAMPLE, SEQ, None)]

    def input_axes(self):
        return [(SAMPLE, SEQ, None)]

    def flops(self) -> float:
        n_tok = 1
        for s in self.inputs[0].shape[:-1]:
            n_tok *= s
        e, hv = self.embed_dim, self.value_heads
        inner = hv * self.value_dim
        proj = 2.0 * e * (self.channels + 2 * inner + 2 * hv)
        rule = 6.0 * hv * self.key_dim * self.value_dim
        return n_tok * (proj + 2.0 * self.d_conv * self.channels + rule)
