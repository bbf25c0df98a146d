"""Lightning linear attention (Lightning Attention-2, arXiv:2401.04658;
the layer of MiniMax-01, arXiv:2501.08313, and of MiniCPM-SALA's
`lightning-attn` mixers): a matrix state a head in place of a cache.

Per token t of a sequence, h (E), H heads of D:
  q = rope(RMSNorm_D(h W_q)), k = rope(RMSNorm_D(h W_k)), v = h W_v
      (the norm over each head's D dims with a learned (D,) scale, the
      rotation half-split at the token's absolute position);
  S_t = lam_h S_{t-1} + k_t^T v_t   (D x D a head, f32);
  o_t = q_t S_t / sqrt(D);
  out = (RMSNorm_E(concat o) * sigmoid(h W_gate)) W_o.
`lam_h = exp(-g_h)`, `g_h = 2^(-8 (h + 1) / H) * (1 - l / (L - 1) +
1e-5)` for layer l of L PUBLISHED layers (`decay_rates`): a constant a
head, no data dependence. The state, the recurrence and both norms run
in f32 whatever the activation dtype.

Three forms of the recurrence, all the same numbers up to f32 rounding:
`lightning_recurrent` (one token after another: the definition),
`lightning_chunked` (whole sequences from a zero state, C tokens a
trip: O = ((Q K^T) * D) V + (Q * d) S_in, S_out = lam^C S_in +
(K * d')^T V with D_ij = lam^(i - j), i >= j: the graph op's forward)
and `segmented_lightning` (the LANES of a serving step — runs of
consecutive lanes of one sequence, each resuming from its slot's stored
state, serve/engine.py). Every decay is computed as exp of a
non-positive exponent, never as a quotient of two powers, so no form
overflows however long the run.

The state is laid out (D, H * D): row = the key dimension, the heads'
value dimensions side by side on the lanes.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..op import CHANNEL_IN, CHANNEL_OUT, SAMPLE, SEQ, Op, OpContext, \
    WeightSpec, register_op
from .common import rms_norm, rotary

F32 = jnp.float32
# products with an f32 operand (the state, a decayed key) keep f32's
# mantissa: a TPU's default would round them to bf16 on the way in
_HI = jax.lax.Precision.HIGHEST
# the serving step's products: one operand of each is exact in bf16 (a
# served q, k or v), so three bf16 passes keep 16 bits of the other's
# mantissa — 2^-17 a product beside the activations' own 2^-9 — at half
# the six passes' cost (no effect on a CPU, whose products are f32)
_STEP = jax.lax.Precision.HIGH
CHUNK = 64          # tokens a trip of the graph op's chunk form


def decay_rates(num_heads: int, layer_index: int,
                published_layers: int) -> jnp.ndarray:
    """(H,) f32 g_h > 0, lam_h = exp(-g_h): Lightning Attention-2's
    slopes 2^(-8 (h + 1) / H) times MiniMax-01's layer factor
    1 - l / (L - 1) + 1e-5, l the layer's PUBLISHED index."""
    h = jnp.arange(1, num_heads + 1, dtype=F32)
    slope = jnp.exp2(-8.0 * h / num_heads)
    factor = 1.0 - layer_index / max(1, published_layers - 1) + 1e-5
    return slope * jnp.asarray(factor, F32)


def project_qkv(p, h, positions, theta: float, eps: float):
    """h (..., E), positions (...) -> q, k (normed, rotated), v, each
    (..., H, D) in h's dtype."""
    q, k, v = (jnp.einsum("...e,ehd->...hd", h, p[w].astype(h.dtype))
               for w in ("wq", "wk", "wv"))
    q = rotary(rms_norm(q, p["q_norm"], eps), positions, theta)
    k = rotary(rms_norm(k, p["k_norm"], eps), positions, theta)
    return q, k, v


def gate_and_project(p, o, h, eps: float):
    """o (..., H, D) f32 (already over sqrt(D)), h (..., E) the layer's
    input -> (RMSNorm_E(o) * sigmoid(h W_gate)) W_o, in h's dtype."""
    flat = o.reshape(o.shape[:-2] + (-1,))
    normed = rms_norm(flat, p["o_norm"], eps)
    gate = jnp.dot(h, p["w_gate"].astype(h.dtype),
                   preferred_element_type=F32)
    y = (normed * jax.nn.sigmoid(gate)).astype(h.dtype)
    return jnp.dot(y, p["wo"].astype(h.dtype),
                   preferred_element_type=F32).astype(h.dtype)


def lightning_recurrent(q, k, v, g):
    """The definition, a token a trip: q, k, v (S, H, D), g (H,) ->
    o (S, H, D) f32 = q_t S_t / sqrt(D), from a zero state."""
    d = q.shape[-1]
    lam = jnp.exp(-g)[:, None, None]

    def step(s, x):
        qt, kt, vt = (a.astype(F32) for a in x)
        s = lam * s + kt[:, :, None] * vt[:, None, :]
        return s, jnp.einsum("hk,hkv->hv", qt, s, precision=_HI)

    s0 = jnp.zeros((q.shape[1], d, d), F32)
    return jax.lax.scan(step, s0, (q, k, v))[1] / math.sqrt(d)


def _decay_matrix(g, n: int):
    """(H, n, n) f32: lam_h^(i - j) for i >= j, else 0."""
    i = jnp.arange(n)
    diff = (i[:, None] - i[None, :]).astype(F32)
    d = jnp.exp(-g[:, None, None] * jnp.maximum(diff, 0.0))
    return jnp.where(diff >= 0, d, 0.0)


def lightning_chunked(q, k, v, g, chunk: int = CHUNK):
    """Whole sequences from a zero state, `chunk` tokens a trip: q, k,
    v (B, S, H, D) -> o (B, S, H, D) f32, equal to the recurrence."""
    b, s, h, d = q.shape
    c = min(chunk, s)
    pad = -s % c
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
    n = (s + pad) // c
    blocks = lambda a: a.astype(F32).reshape(b, n, c, h, d).swapaxes(0, 1)
    dm = _decay_matrix(g, c)                               # (H, c, c)
    i = jnp.arange(c, dtype=F32)
    d_in = jnp.exp(-g[None, :] * (i[:, None] + 1.0))        # (c, H)
    d_out = jnp.exp(-g[None, :] * (c - 1.0 - i[:, None]))   # (c, H)
    d_all = jnp.exp(-g * c)                                 # (H,)

    def trip(state, x):
        qc, kc, vc = x                                      # (B, c, H, D)
        a = jnp.einsum("bihd,bjhd->bhij", qc, kc, precision=_HI) * dm
        o = jnp.einsum("bhij,bjhd->bihd", a, vc, precision=_HI)
        o = o + jnp.einsum("bihk,bhkv->bihv", qc * d_in[None, :, :, None],
                           state, precision=_HI)
        state = d_all[None, :, None, None] * state + jnp.einsum(
            "bjhk,bjhv->bhkv", kc * d_out[None, :, :, None], vc,
            precision=_HI)
        return state, o

    s0 = jnp.zeros((b, h, d, d), F32)
    _, o = jax.lax.scan(trip, s0, (blocks(q), blocks(k), blocks(v)))
    o = o.swapaxes(0, 1).reshape(b, s + pad, h, d)[:, :s]
    return o / math.sqrt(d)


def segmented_lightning(q, k, v, g, state, lane_slots, positions, live,
                        starts, offsets):
    """The recurrence over the step's lanes. q, k, v (T, H, D); g (H,);
    state (slots + 1, D, H * D) f32, every slot's matrix state (the last
    row the slabs' write sink, which no lane names); `live` (T,) the
    lanes that hold a token; `starts` / `offsets` the runs
    (ops/ssm.run_starts / run_offsets). A run resumes from its slot's
    state — from zero where the sequence starts inside it — and leaves
    the state after its last live lane. A slot has at most one run a
    step (the scheduler's one chunk a sequence), so the step is three
    products over ALL slots at once: the runs' own lanes among
    themselves under the decay matrix, every lane against its slot's
    state, and every slot's state moved on by its run's keys and
    values. -> (o (T, H, D) f32 = q_t S_t / sqrt(D), state)."""
    t, h, d = q.shape
    n_slots = state.shape[0]
    kf, vf = k.astype(F32), v.astype(F32)
    lane = jnp.arange(t)
    run = jnp.cumsum(starts.astype(jnp.int32))
    diff = (lane[:, None] - lane[None, :]).astype(F32)
    same = (run[:, None] == run[None, :]) & (diff >= 0) & live[None, :]
    dm = jnp.where(same[None], jnp.exp(
        -g[:, None, None] * jnp.maximum(diff, 0.0)[None]), 0.0)
    # q . k in the operands' own dtype: exact products of bf16 values
    a = jnp.einsum("ihd,jhd->hij", q, k, precision=_STEP,
                   preferred_element_type=F32) * dm
    o = jnp.einsum("hij,jhd->ihd", a, vf, precision=_STEP)
    # every lane against its slot's state as it stood before the step
    mine = (lane_slots[:, None] == jnp.arange(n_slots)[None, :])  # (T, S)
    fresh = positions - offsets == 0       # the sequence starts in the run
    s_in = state.reshape(n_slots, d, h, d)
    w_in = jnp.exp(-g[None, :] * (offsets[:, None] + 1.0).astype(F32))
    w_in = jnp.where(fresh[:, None], 0.0, w_in)               # (T, H)
    q_by_slot = jnp.where(mine[:, :, None, None], q[:, None], 0)
    o = o + w_in[:, :, None] * jnp.einsum(
        "tshk,skhv->thv", q_by_slot, s_in, precision=_STEP,
        preferred_element_type=F32)
    # every slot's state after its run: n live lanes of it this step
    alive = mine & live[:, None]
    n = jnp.sum(alive, axis=0).astype(F32)                    # (S,)
    n_lane = jnp.take(n, lane_slots)
    w_out = jnp.exp(-g[None, :] * jnp.maximum(
        n_lane[:, None] - 1.0 - offsets[:, None].astype(F32), 0.0))
    k_by_slot = jnp.where(alive[:, :, None, None],
                          (kf * w_out[:, :, None])[:, None], 0.0)
    add = jnp.einsum("tshk,thv->skhv", k_by_slot, vf, precision=_STEP)
    # a slot whose sequence starts in this step's run forgets what the
    # slot held (a re-admitted sequence starts from zero)
    restart = jnp.any(alive & fresh[:, None], axis=0)         # (S,)
    keep = jnp.where(restart[:, None], 0.0,
                     jnp.exp(-g[None, :] * n[:, None]))       # (S, H)
    new = keep[:, None, :, None] * s_in + add
    return o / math.sqrt(d), new.reshape(state.shape)


def _norm_ones(key, shape, dtype=F32):
    return jnp.ones(shape, dtype)


@register_op
class LightningAttention(Op):
    """x (B, S, E), positions (B, S) -> out (B, S, E): the whole mixer
    (projections, QK-norm, rotation, recurrence, output norm, gate,
    output projection). `layer_index` / `published_layers` set the
    heads' decays."""

    op_type = "lightning_attention"

    def __init__(self, model, name, inputs, num_heads: int, head_dim: int,
                 layer_index: int = 0, published_layers: int = 1,
                 rotary_theta: float = 10000.0, eps: float = 1e-6,
                 kernel_initializer: str = "glorot"):
        super().__init__(model, name, inputs)
        self.embed_dim = int(inputs[0].shape[-1])
        self.num_heads, self.head_dim = int(num_heads), int(head_dim)
        self.layer_index = int(layer_index)
        self.published_layers = int(published_layers)
        self.rotary_theta, self.eps = float(rotary_theta), float(eps)
        self.kernel_initializer = kernel_initializer
        self.attrs = {"num_heads": self.num_heads,
                      "head_dim": self.head_dim,
                      "layer_index": self.layer_index,
                      "published_layers": self.published_layers}

    def decay(self):
        return decay_rates(self.num_heads, self.layer_index,
                           self.published_layers)

    def output_shapes(self):
        return [tuple(self.inputs[0].shape)]

    def output_dtypes(self):
        return [self.inputs[0].dtype]

    def weight_specs(self):
        e, h, d = self.embed_dim, self.num_heads, self.head_dim
        init = self.kernel_initializer
        proj = lambda: WeightSpec((e, h, d), initializer=init, fan_in=e,
                                  fan_out=h * d)
        return {
            "wq": proj(), "wk": proj(), "wv": proj(),
            "q_norm": WeightSpec((d,), initializer="ones"),
            "k_norm": WeightSpec((d,), initializer="ones"),
            "o_norm": WeightSpec((h * d,), initializer="ones"),
            "w_gate": WeightSpec((e, h * d), initializer=init,
                                 axes=(CHANNEL_IN, CHANNEL_OUT)),
            "wo": WeightSpec((h * d, e), initializer=init,
                             axes=(CHANNEL_IN, CHANNEL_OUT)),
        }

    def forward(self, params, xs, ctx: OpContext):
        x, positions = xs
        q, k, v = project_qkv(params, x, positions, self.rotary_theta,
                              self.eps)
        o = lightning_chunked(q, k, v, self.decay())
        return [gate_and_project(params, o, x, self.eps)]

    def output_axes(self):
        return [(SAMPLE, SEQ, None)]

    def input_axes(self):
        return [(SAMPLE, SEQ, None), (SAMPLE, SEQ)]

    def flops(self) -> float:
        n_tok = 1
        for s in self.inputs[0].shape[:-1]:
            n_tok *= s
        e, hd, d = self.embed_dim, self.num_heads * self.head_dim, \
            self.head_dim
        return n_tok * (2.0 * 5 * e * hd + 4.0 * hd * d)
