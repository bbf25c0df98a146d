"""The Mamba-2 mixer (state-space duality, SSD; arXiv:2405.21060) as
Falcon-H1 (`model_type` falcon_h1) lays it out, and the forms of its
recurrence.

Per token t of a sequence, h (E) the block's normed input, H heads of P
channels (H P = d_ssm), G groups of N state rows, K = H / G heads a
group (head j reads group j // K):
  p = ((h * in_multiplier) W_in) * m      W_in: E -> 2 d_ssm + 2 G N + H,
      no bias, columns [z d_ssm | x d_ssm | B G N | C G N | dt H];
      m = multipliers[0..4] spread over those five column groups
  [x | B | C] = silu(conv_causal([x | B | C])): a depthwise convolution
      over the last `d_conv` positions (zeros before the sequence), WITH
      bias, over all d_ssm + 2 G N channels
  dt_j = softplus(dt_j + dt_bias_j);  a_j = exp(-exp(A_log_j) * dt_j)
      ONE scalar a head a token (Mamba-1's is one a channel and state row)
  S_j <- a_j S_j + (dt_j x_j) (outer) B_g        S_j: P x N, f32
  y_j = S_j C_g + D_j x_j
  y = RMSNorm_per_group(y * silu(z); w_y): the gate FIRST
      (`norm_before_gate` false), the statistics over a GROUP's d_ssm / G
      channels, not all of them
  out = (y W_out) * out_multiplier               W_out: d_ssm -> E
The state, the recurrence, the convolution, the gate and the norm run in
f32 whatever the activation dtype. The multipliers (Falcon-H1's muP
scalars) stand where they are written: scalars on activations, `m` on
the in-projection's OUTPUT; none is folded into a matrix.

Three forms of the recurrence, the same numbers up to f32 rounding, all
over v = dt x, the log decay la = -exp(A_log) dt <= 0, B and C (the D
term is the caller's):
`recurrent` (a token a trip: the definition); `chunked` (whole sequences
from a zero state, CHUNK tokens a trip, as products: with cum the
running sum of la inside the block and L_ij = exp(cum_i - cum_j), i >= j,
  Y = (C B^T * L) V + (C S) * exp(cum),
  S <- exp(cum_last) S + B^T (V * exp(cum_last - cum));
the graph op's forward) and `segmented` (the LANES of a serving step:
runs of consecutive lanes of one sequence, each resuming from its slot's
state, serve/mixers.py — the lanes that go lane by lane through
`lane_pass` below or, on the chip, its kernel kernels/ssd_scan.py, whole
blocks of one run through the chunk form; which is which is
ops/gated_delta.block_forms' rule and `lane_plan`'s order of work, the
delta rule's). Every decay is the exp of a non-positive sum of la, never
a quotient of two. CHUNK is this program's block (64, the delta rule's:
one plan serves both); the published kernel's `mamba_chunk_size` 128 is
that kernel's block, not part of the mathematics.

A sequence's state of one layer is laid out (N, H P): the state rows on
the sublanes, head j's P channels in lanes j P .. (j + 1) P — a head's
(N, P) tile is whole (8, 128) tiles at P = 128, B and C are columns over
it and x, y rows (serve/kv_cache.HybridSpec.state_shape, the kernel and
every form here ask `state_shape`).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..op import CHANNEL_IN, CHANNEL_OUT, SAMPLE, SEQ, Op, OpContext, \
    WeightSpec, register_op
from .gated_delta import CHUNK, LanePlan, Segments, make_dt_bias_init
from .ssm import causal_conv

F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


class Dims(NamedTuple):
    """A Mamba-2 mixer's shape: H heads of P channels, G groups of N
    state rows."""
    heads: int
    head_dim: int
    groups: int
    d_state: int

    @property
    def d_ssm(self) -> int:
        return self.heads * self.head_dim

    @property
    def bc(self) -> int:
        """B's (and C's) width: every group's state rows."""
        return self.groups * self.d_state

    @property
    def channels(self) -> int:
        """What the convolution runs over: x, B and C."""
        return self.d_ssm + 2 * self.bc

    @property
    def in_width(self) -> int:
        return 2 * self.d_ssm + 2 * self.bc + self.heads

    @property
    def state_shape(self) -> tuple:
        """A sequence's state of one layer as the slab holds it."""
        return (self.d_state, self.d_ssm)


def mup_vector(d: Dims, multipliers) -> np.ndarray:
    """`multipliers` (5,) spread over the in-projection's columns
    [z | x | B | C | dt] -> (in_width,) f32."""
    widths = (d.d_ssm, d.d_ssm, d.bc, d.bc, d.heads)
    return np.concatenate([np.full((w,), m, np.float32)
                           for w, m in zip(widths, multipliers)])


# ------------------------------------------------------------ the layer
def project(p, h, d: Dims, in_multiplier: float, multipliers):
    """h (..., E) -> (z (..., d_ssm), the convolution's raw input
    [x | B | C] (..., channels), dt (..., H)), in h's dtype: ((h *
    in_multiplier) W_in) * m."""
    if in_multiplier != 1.0:
        h = h * in_multiplier
    y = jnp.dot(h, p["w_in"].astype(h.dtype),
                preferred_element_type=F32).astype(h.dtype)
    if any(m != 1.0 for m in multipliers):
        y = y * jnp.asarray(mup_vector(d, multipliers), h.dtype)
    return jnp.split(y, [d.d_ssm, d.d_ssm + d.channels], axis=-1)


def gates(p, dt):
    """dt (..., H) raw -> (the step softplus(dt + dt_bias), the log
    decay la = -exp(A_log) * step <= 0), f32."""
    step = jax.nn.softplus(dt.astype(F32) + p["dt_bias"].astype(F32))
    return step, -jnp.exp(p["A_log"].astype(F32)) * step


def split_heads(u, d: Dims):
    """u (..., channels) f32 after the convolution and silu -> x (...,
    H, P), B, C (..., G, N)."""
    lead = u.shape[:-1]
    x, b, c = jnp.split(u, [d.d_ssm, d.d_ssm + d.bc], axis=-1)
    return (x.reshape(lead + (d.heads, d.head_dim)),
            b.reshape(lead + (d.groups, d.d_state)),
            c.reshape(lead + (d.groups, d.d_state)))


def scan_inputs(p, u, dt, d: Dims):
    """The convolution's output after silu and the raw dt -> what every
    form of the recurrence takes (v = step * x, B, C, la) and the D
    term D x the caller adds to its output, f32."""
    x, b, c = split_heads(u, d)
    step, la = gates(p, dt)
    return (x * step[..., None], b, c, la), \
        x * p["D"].astype(F32)[:, None]


def gate_and_project(p, y, z, d: Dims, eps: float, out_multiplier: float):
    """y (..., H, P) f32 (the D term in), z (..., d_ssm) -> (RMSNorm over
    each GROUP's channels of y * silu(z)) W_out * out_multiplier, in z's
    dtype."""
    lead = z.shape[:-1]
    g = y.reshape(lead + (d.d_ssm,)) * jax.nn.silu(z.astype(F32))
    g = g.reshape(lead + (d.groups, d.d_ssm // d.groups))
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                          + eps)
    g = (g.reshape(lead + (d.d_ssm,)) * p["norm"].astype(F32)
         ).astype(z.dtype)
    out = jnp.dot(g, p["w_out"].astype(z.dtype),
                  preferred_element_type=F32).astype(z.dtype)
    return out * out_multiplier if out_multiplier != 1.0 else out


# ------------------------------------------------------ the recurrence
def _by_group(a, groups: int):
    """(..., H, P) -> (..., G, K, P): a group's heads together."""
    return a.reshape(a.shape[:-2] + (groups, -1, a.shape[-1]))


def _token(s, v, b, c, la):
    """One token on a state s (N, H, P): v (H, P), b, c (G, N), la (H,),
    f32 -> (s', y (H, P))."""
    n, h, p = s.shape
    g = b.shape[0]
    s = s * jnp.exp(la)[None, :, None]
    s = _by_group(s, g) + b.T[:, :, None, None] * _by_group(v, g)[None]
    y = jnp.sum(s * c.T[:, :, None, None], axis=0)
    return s.reshape(n, h, p), y.reshape(h, p)


def recurrent(v, b, c, la, state=None):
    """The definition, a token a trip: v (S, H, P), b, c (S, G, N), la
    (S, H) -> (y (S, H, P) f32, the state after (N, H, P)), from `state`
    (None: zeros)."""
    s0 = jnp.zeros((b.shape[-1],) + v.shape[1:], F32) \
        if state is None else state

    def step(s, x):
        return _token(s, *(a.astype(F32) for a in x))

    s, y = jax.lax.scan(step, s0, (v, b, c, la))
    return y, s


def _chunk(s, v, b, c, la):
    """CHUNK (or fewer) tokens of ONE sequence on a state s (N, H, P),
    as products: v (C, H, P), b, c (C, G, N), la (C, H), f32 -> (s', y
    (C, H, P)). A token with v 0 and la 0 changes nothing."""
    n, h, p = s.shape
    g = b.shape[1]
    cum = jnp.cumsum(la, axis=0)                             # (C, H) <= 0
    i = jnp.arange(v.shape[0])
    low = (i[:, None] >= i[None, :])[None]                   # (1, C, C)
    diff = cum.T[:, :, None] - cum.T[:, None, :]             # (H, C, C)
    decay = jnp.where(low, jnp.exp(jnp.where(low, diff, 0.0)), 0.0)
    cb = jnp.einsum("ign,jgn->gij", c, b, precision=_HI)     # (G, C, C)
    y = jnp.einsum("hij,jhp->ihp",
                   jnp.repeat(cb, h // g, axis=0) * decay, v, precision=_HI)
    sg = _by_group(s, g)                                     # (N, G, K, P)
    y = y + (jnp.einsum("ign,ngkp->igkp", c, sg, precision=_HI)
             * _by_group(jnp.exp(cum)[:, :, None], g)).reshape(y.shape)
    d_out = jnp.exp(cum[-1][None] - cum)                     # (C, H)
    sg = _by_group(jnp.exp(cum[-1])[:, None], g)[None] * sg + jnp.einsum(
        "jgn,jgkp->ngkp", b, _by_group(v * d_out[:, :, None], g),
        precision=_HI)
    return sg.reshape(n, h, p), y


def chunked(v, b, c, la, chunk: int = CHUNK):
    """Whole sequences from a zero state, `chunk` tokens a trip: v (B, S,
    H, P), b, c (B, S, G, N), la (B, S, H) -> y (B, S, H, P) f32, equal
    to the recurrence."""
    bs, s, h, p = v.shape
    pad = -s % chunk
    n = (s + pad) // chunk

    def blocks(a):
        # padding tokens: v 0 and la 0, they change nothing
        a = jnp.pad(a.astype(F32), ((0, 0), (0, pad)) + ((0, 0),) * (
            a.ndim - 2))
        return a.reshape((bs, n, chunk) + a.shape[2:]).swapaxes(0, 1)

    def trip(state, x):
        return jax.vmap(_chunk)(state, *x)

    s0 = jnp.zeros((bs, b.shape[-1], h, p), F32)
    _, y = jax.lax.scan(trip, s0, tuple(map(blocks, (v, b, c, la))))
    return y.swapaxes(0, 1).reshape(bs, s + pad, h, p)[:, :s]


def lane_pass(v, b, c, la, y, state, layer, seg: Segments):
    """The lanes of `seg` (ops/gated_delta.Segments) a lane at a time,
    on layer `layer` of the slab `state` (layers, slots + 1, N, H P): a
    segment's state comes from its source slot (from zero where the
    sequence starts there), every lane of it is worked, and it goes back
    to its destination slot — in once, out once. v, y (T, H, P), b, c
    (T, G, N), la (T, H), f32 -> (y, the segments' lanes' rows written;
    the slab). The jnp twin of kernels/ssd_scan.lane_pass."""
    heads = v.shape[1:]
    flat = state.shape[2:]

    def a_segment(r, carry):
        y, state = carry
        src = seg.src[r]
        s = jnp.where(src >= 0, state[layer, jnp.maximum(src, 0)], 0.0
                      ).reshape(flat[:1] + heads)

        def a_lane(j, carry):
            s, y = carry
            t = seg.first[r] + j
            s, yt = _token(s, v[t], b[t], c[t], la[t])
            return s, jax.lax.dynamic_update_index_in_dim(y, yt, t, 0)

        s, y = jax.lax.fori_loop(0, seg.length[r], a_lane, (s, y))
        return y, state.at[layer, seg.dst[r]].set(s.reshape(flat))

    return jax.lax.fori_loop(0, seg.count, a_segment, (y, state))


def chunk_blocks(v, b, c, la, y, state, layer, lane_slots, positions,
                 plan: LanePlan, block: int = CHUNK):
    """The chunk-form blocks of `plan`, one after another — a loop of as
    many trips as the step has such blocks — on layer `layer` of the
    slab: a block's run resumes from its slot's state (from zero where
    the sequence starts at the block's first lane) and leaves its state
    there, where the next block, or the lanes after, take it up; the
    block's rows of `y` are written. -> (y, the slab)."""
    heads = v.shape[1:]
    flat = state.shape[2:]

    def a_block(i, carry):
        state, y = carry
        blk = plan.chunk_ids[i]
        first = blk * block
        rows = lambda a: jax.lax.dynamic_slice_in_dim(a, first, block)
        m = plan.among[blk][:, None]
        slot = lane_slots[first]
        s = jnp.where(positions[first] > 0, state[layer, slot], 0.0)
        s, yb = _chunk(s.reshape(flat[:1] + heads),
                       jnp.where(m[:, :, None], rows(v), 0.0), rows(b),
                       rows(c), jnp.where(m, rows(la), 0.0))
        return (state.at[layer, slot].set(s.reshape(flat)),
                jax.lax.dynamic_update_slice_in_dim(
                    y, jnp.where(m[:, :, None], yb, 0.0), first, 0))

    state, y = jax.lax.fori_loop(0, plan.chunks, a_block, (state, y))
    return y, state


def segmented(v, b, c, la, state, layer, lane_slots, positions,
              plan: LanePlan, lane_pass=lane_pass, block: int = CHUNK):
    """The recurrence over the step's lanes, on layer `layer` of the
    slab `state` (layers, slots + 1, N, H P) f32 in place, the lanes as
    `plan` (ops/gated_delta.lane_plan over the step's runs) sorts them:
    a run is lanes, whole chunk-form blocks, lanes, in that order, so
    `lane_pass` (the twin above, or the kernel's) is called on either
    side of the blocks. A run resumes from its slot's state — from zero
    where the sequence starts inside it — and leaves the state after its
    last live lane in its slot; a dead lane's row of y is zero and the
    slab's sink row is not written. v (T, H, P), b, c (T, G, N), la (T,
    H), f32 -> (y (T, H, P) f32, the slab)."""
    t = v.shape[0]
    pad = -t % block
    if pad:     # whole blocks for the chunk form (none at the served widths)
        v, b, c, la, lane_slots, positions = (
            jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
            for a in (v, b, c, la, lane_slots, positions))
    y = jnp.zeros(v.shape, F32)
    y, state = lane_pass(v, b, c, la, y, state, layer, plan.before)
    y, state = chunk_blocks(v, b, c, la, y, state, layer, lane_slots,
                            positions, plan, block)
    y, state = lane_pass(v, b, c, la, y, state, layer, plan.after)
    return y[:t], state


# ----------------------------------------------------------------- the op
def make_a_log_init(lo: float, hi: float):
    """A = exp(A_log) uniform in [lo, hi] (Mamba-2's `A_init_range`)."""
    def init(key, shape, dtype=F32):
        return jnp.log(jax.random.uniform(key, shape, F32, lo, hi)
                       ).astype(dtype)
    return init


@register_op
class Mamba2Mixer(Op):
    """x (B, S, E) -> out (B, S, E): the whole mixer of the module's
    docstring (projection and its multipliers, convolution, the SSD
    recurrence, the gated per-group norm, the output projection and its
    multiplier). `dt_range`: the steps dt_bias starts at (softplus^-1,
    log-uniform); `a_range`: where A starts (uniform); `norm_init` (lo,
    hi): the gated norm's scale starts uniform in it;
    `kernel_initializer` / `out_initializer`: W_in's and W_out's."""

    op_type = "mamba2_mixer"

    def __init__(self, model, name, inputs, heads: int, head_dim: int,
                 groups: int, d_state: int, d_conv: int = 4,
                 eps: float = 1e-5, in_multiplier: float = 1.0,
                 multipliers=(1.0,) * 5, out_multiplier: float = 1.0,
                 dt_range=(1e-3, 1e-1), a_range=(1.0, 16.0),
                 norm_init=(1.0, 1.0), kernel_initializer="glorot",
                 out_initializer=None):
        super().__init__(model, name, inputs)
        self.embed_dim = int(inputs[0].shape[-1])
        if int(heads) % int(groups):
            raise ValueError(f"{name}: {heads} heads do not divide over "
                             f"{groups} groups")
        self.dims = Dims(int(heads), int(head_dim), int(groups),
                         int(d_state))
        self.d_conv, self.eps = int(d_conv), float(eps)
        self.in_multiplier = float(in_multiplier)
        self.multipliers = tuple(map(float, multipliers))
        if len(self.multipliers) != 5:
            raise ValueError(f"{name}: five multipliers, one a column "
                             f"group [z | x | B | C | dt]")
        self.out_multiplier = float(out_multiplier)
        self.dt_range = tuple(map(float, dt_range))
        self.a_range = tuple(map(float, a_range))
        self.norm_init = tuple(norm_init)
        self.kernel_initializer = kernel_initializer
        self.out_initializer = out_initializer or kernel_initializer
        self.attrs = {"heads": self.dims.heads,
                      "head_dim": self.dims.head_dim,
                      "groups": self.dims.groups,
                      "d_state": self.dims.d_state, "d_conv": self.d_conv}

    def output_shapes(self):
        return [tuple(self.inputs[0].shape)]

    def output_dtypes(self):
        return [self.inputs[0].dtype]

    def weight_specs(self):
        from ..core.initializers import range_init
        e, d = self.embed_dim, self.dims
        return {
            "w_in": WeightSpec((e, d.in_width),
                               initializer=self.kernel_initializer,
                               axes=(CHANNEL_IN, CHANNEL_OUT)),
            # glorot over the taps whatever the matrices start at
            "conv_w": WeightSpec((self.d_conv, d.channels),
                                 fan_in=self.d_conv, fan_out=self.d_conv),
            "conv_b": WeightSpec((d.channels,), initializer="zeros"),
            "A_log": WeightSpec((d.heads,),
                                custom_init=make_a_log_init(*self.a_range)),
            "D": WeightSpec((d.heads,), initializer="ones"),
            "dt_bias": WeightSpec((d.heads,), custom_init=make_dt_bias_init(
                *self.dt_range)),
            "norm": WeightSpec((d.d_ssm,),
                               custom_init=range_init(self.norm_init)),
            "w_out": WeightSpec((d.d_ssm, e),
                                initializer=self.out_initializer,
                                axes=(CHANNEL_IN, CHANNEL_OUT)),
        }

    def forward(self, params, xs, ctx: OpContext):
        (x,) = xs
        d = self.dims
        z, u, dt = project(params, x, d, self.in_multiplier,
                           self.multipliers)
        u = jax.nn.silu(causal_conv(params, u))
        forms, skip = scan_inputs(params, u, dt, d)
        y = chunked(*forms) + skip
        return [gate_and_project(params, y, z, d, self.eps,
                                 self.out_multiplier)]

    def output_axes(self):
        return [(SAMPLE, SEQ, None)]

    def input_axes(self):
        return [(SAMPLE, SEQ, None)]

    def flops(self) -> float:
        n_tok = 1
        for s in self.inputs[0].shape[:-1]:
            n_tok *= s
        e, d = self.embed_dim, self.dims
        proj = 2.0 * e * (d.in_width + d.d_ssm)
        rule = 6.0 * d.d_state * d.d_ssm
        return n_tok * (proj + 2.0 * self.d_conv * d.channels + rule)
