"""Block-sparse attention over a learned selection of the context
(InfLLM-v2, arXiv:2509.24663; MiniCPM4, arXiv:2506.07900; MiniCPM-SALA's
`minicpm4` mixers): H query heads on G key/value heads, no position
signal.

Per token t, h (E):
  q = RMSNorm_D(h W_q), k = RMSNorm_D(h W_k), v = h W_v; query head j
  reads key/value head j // (H / G).
  1. compressed keys Kc_{g,j} = mean(k_{g, stride j .. stride j +
     kernel - 1}), once position stride j + kernel - 1 exists;
  2. p_{j} = softmax_j(q . Kc_j / sqrt(D)) over the j visible at t;
  3. P_{g} = the sum of p over the group's heads; block score B_{g,b} =
     the maximum of P over the strides that overlap block b (a max-pool
     of block/stride + 1, stride block/stride, one stride of padding);
  4. the selection: `init_blocks` first blocks and the `window_size /
     block_size` blocks up to the query's own score +inf; the `topk`
     highest-scoring blocks at or before the query's own;
  5. o = softmax over the tokens s <= t of the selected blocks (q . k /
     sqrt(D)) v;
  6. a query at t < `dense_len` attends every s <= t instead (by QUERY
     POSITION, so the answer cannot depend on how a prompt was cut);
  7. out = (o * sigmoid(h W_gate)) W_o.
The selector has no weights of its own. Its softmax is exact (the
published kernels approximate its normaliser from a coarser pooling).

`select_blocks` is the one definition of steps 3-4; `sparse_attention`
runs a whole sequence (the graph op's forward: a dense product under the
selection's mask). Plain arrays only: the serving step's form, through
pages and the stored compressed keys, is serve/sparse_paged.py, built
from `mean_keys`, `group_probs` and `select_blocks` here.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from ..core.initializers import make_constant
from ..op import CHANNEL_IN, CHANNEL_OUT, SAMPLE, SEQ, Op, OpContext, \
    WeightSpec, register_op
from .common import rms_norm

F32 = jnp.float32
_NEG = -0.5 * float(jnp.finfo(jnp.float32).max)   # finite: no NaN rows


@dataclasses.dataclass(frozen=True)
class SparseConfig:
    """The selector's sizes (MiniCPM4's published `sparse_config`)."""
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    topk: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    dense_len: int = 8192

    def __post_init__(self):
        if (self.kernel_size != 2 * self.kernel_stride
                or self.block_size % self.kernel_stride
                or self.window_size % self.block_size
                or self.dense_len % self.block_size
                or self.dense_len < self.kernel_size):
            raise ValueError(
                f"selector sizes {self}: a compressed key spans two "
                f"strides, and blocks, the window and dense_len are whole "
                f"strides / blocks")

    @property
    def block_strides(self) -> int:
        return self.block_size // self.kernel_stride

    @property
    def local_blocks(self) -> int:
        return self.window_size // self.block_size


def project_qkv(p, h, eps: float):
    """h (..., E) -> q (..., H, D), k, v (..., G, D); q and k normed
    over each head's D dims."""
    q, k, v = (jnp.einsum("...e,ehd->...hd", h, p[w].astype(h.dtype))
               for w in ("wq", "wk", "wv"))
    return rms_norm(q, p["q_norm"], eps), rms_norm(k, p["k_norm"], eps), v


def gate_and_project(p, o, h):
    """o (..., H, D), h (..., E) the layer's input -> (o * sigmoid(h
    W_gate)) W_o, in h's dtype."""
    flat = o.reshape(o.shape[:-2] + (-1,)).astype(F32)
    gate = jnp.dot(h, p["w_gate"].astype(h.dtype),
                   preferred_element_type=F32)
    y = (flat * jax.nn.sigmoid(gate)).astype(h.dtype)
    return jnp.dot(y, p["wo"].astype(h.dtype),
                   preferred_element_type=F32).astype(h.dtype)


def mean_keys(rows):
    """(..., kernel, G * D) keys -> their mean (..., G * D), summed in
    f32 and rounded to the keys' dtype: ONE definition of a compressed
    key's arithmetic, for the graph op and the serving step's write."""
    return jnp.mean(rows.astype(F32), axis=-2).astype(rows.dtype)


def compress_keys(k, sc: SparseConfig):
    """k (S, G, D) -> Kc (S // stride, G, D): row j the mean of keys
    stride j .. stride j + kernel - 1 (zeros stand past the sequence's
    end: such a row is visible to no query)."""
    s, g, d = k.shape
    st = sc.kernel_stride
    n = s // st
    kp = jnp.pad(k[:n * st].reshape(n, st, g * d), ((0, 1), (0, 0), (0, 0)))
    both = jnp.concatenate([kp[:-1], kp[1:]], axis=1)     # (n, 2 st, GD)
    return mean_keys(both).reshape(n, g, d)


def stride_scores(q, kc):
    """q (R, H, D), kc (J, G, D) one sequence's compressed keys -> the
    strides' scores (R, G, H / G, J) f32, q . Kc / sqrt(D)."""
    r, h, d = q.shape
    g = kc.shape[-2]
    return jnp.einsum("rgid,jgd->rgij", q.reshape(r, g, h // g, d), kc,
                      preferred_element_type=F32) / math.sqrt(d)


def group_probs(scores, positions, sc: SparseConfig):
    """Steps 2-3's first half: scores (R, G, I, J) of each row's heads
    against the strides of its own sequence, positions (R,) -> P (R, G,
    J) f32, the group-summed softmax over the strides visible at the
    row's position (0 on the others)."""
    j = scores.shape[-1]
    seen = (jnp.arange(j) * sc.kernel_stride + sc.kernel_size - 1
            )[None, :] <= positions[:, None]                  # (R, J)
    seen = seen[:, None, None, :]
    s = jnp.where(seen, scores, _NEG)
    p = jnp.where(seen, jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)),
                  0.0)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    return jnp.sum(p, axis=2)


def select_blocks(probs, positions, sc: SparseConfig):
    """Steps 3-4: probs (R, G, J) with J a whole number of blocks,
    positions (R,) -> (blocks (R, G, K) int32, chosen (R, G, K) bool),
    K = min(topk, blocks): the row's selected blocks, `chosen` False on
    the entries a row with fewer visible blocks than K does not use."""
    r, g, j = probs.shape
    bs = sc.block_strides
    nb = j // bs
    # the strides that overlap block b: bs b - 1 .. bs b + bs - 1 (a
    # max-pool of bs + 1, stride bs, one stride of padding), as bs + 1
    # strided views side by side
    low = jnp.pad(probs, ((0, 0), (0, 0), (1, 0)),
                  constant_values=-jnp.inf)
    score = functools.reduce(jnp.maximum, (
        low[..., o:o + j:bs] for o in range(bs + 1)))         # (R, G, nb)
    b = jnp.arange(nb)[None, :]
    own = (positions // sc.block_size)[:, None]               # (R, 1)
    forced = (b < sc.init_blocks) | (b > own - sc.local_blocks)
    score = jnp.where(forced[:, None, :], jnp.inf, score)
    score = jnp.where((b <= own)[:, None, :], score, -jnp.inf)
    top, blocks = jax.lax.top_k(score, min(sc.topk, nb))
    return blocks.astype(jnp.int32), top > -jnp.inf


def selection_mask(blocks, chosen, num_blocks: int):
    """(R, G, K) selected blocks -> (R, G, num_blocks) bool."""
    hit = (blocks[..., None] == jnp.arange(num_blocks)) & chosen[..., None]
    return jnp.any(hit, axis=-2)


def sparse_attention(q, k, v, sc: SparseConfig):
    """One whole sequence: q (S, H, D), k, v (S, G, D) -> o (S, H, D)
    in q's dtype. A dense product under the mask the selection makes:
    for the graph's sequence lengths, not for a long context."""
    s, h, d = q.shape
    g = k.shape[1]
    pad = -s % sc.block_size
    positions = jnp.arange(s)
    kc = compress_keys(jnp.pad(k, ((0, pad), (0, 0), (0, 0))), sc)
    probs = group_probs(stride_scores(q, kc), positions, sc)
    blocks, chosen = select_blocks(probs, positions, sc)
    nb = (s + pad) // sc.block_size
    allowed = selection_mask(blocks, chosen, nb)              # (S, G, nb)
    allowed |= (positions < sc.dense_len)[:, None, None]
    key = jnp.arange(s)
    seen = jnp.take(allowed, key // sc.block_size, axis=2) \
        & (key[None, :] <= positions[:, None])[:, None, :]    # (S, G, S)
    qg = q.reshape(s, g, h // g, d)
    a = jnp.einsum("tgid,sgd->tgis", qg, k,
                   preferred_element_type=F32) / math.sqrt(d)
    a = jnp.where(seen[:, :, None, :], a, -jnp.inf)
    p = jax.nn.softmax(a, axis=-1)
    o = jnp.einsum("tgis,sgd->tgid", p, v.astype(F32))
    return o.reshape(s, h, d).astype(q.dtype)


@register_op
class SparseAttention(Op):
    """x (B, S, E) -> out (B, S, E): the whole mixer (projections,
    QK-norm, selection, attention, gate, output projection)."""

    op_type = "sparse_attention"

    def __init__(self, model, name, inputs, num_heads: int,
                 num_kv_heads: int, head_dim: int,
                 sparse: SparseConfig = SparseConfig(), eps: float = 1e-6,
                 kernel_initializer: str = "glorot",
                 qk_norm_init: float = 1.0):
        super().__init__(model, name, inputs)
        self.embed_dim = int(inputs[0].shape[-1])
        self.num_heads, self.num_kv_heads = int(num_heads), int(num_kv_heads)
        self.head_dim = int(head_dim)
        if self.num_heads % self.num_kv_heads:
            raise ValueError("query heads are whole groups of a "
                             "key/value head")
        self.sparse = sparse
        self.eps = float(eps)
        self.kernel_initializer = kernel_initializer
        # what the QK-norm's scales start at: s on both gives the
        # attention's logits a deviation of s * s over random keys (1:
        # nearly flat over thousands of keys; a trained model's is
        # peaked)
        self.qk_norm_init = float(qk_norm_init)
        self.attrs = {"num_heads": self.num_heads,
                      "num_kv_heads": self.num_kv_heads,
                      "head_dim": self.head_dim,
                      **dataclasses.asdict(sparse)}

    def output_shapes(self):
        return [tuple(self.inputs[0].shape)]

    def output_dtypes(self):
        return [self.inputs[0].dtype]

    def weight_specs(self):
        e, h, g, d = (self.embed_dim, self.num_heads, self.num_kv_heads,
                      self.head_dim)
        init = self.kernel_initializer
        proj = lambda n: WeightSpec((e, n, d), initializer=init, fan_in=e,
                                    fan_out=n * d)
        return {
            "wq": proj(h), "wk": proj(g), "wv": proj(g),
            "q_norm": WeightSpec((d,), custom_init=make_constant(
                self.qk_norm_init)),
            "k_norm": WeightSpec((d,), custom_init=make_constant(
                self.qk_norm_init)),
            "w_gate": WeightSpec((e, h * d), initializer=init,
                                 axes=(CHANNEL_IN, CHANNEL_OUT)),
            "wo": WeightSpec((h * d, e), initializer=init,
                             axes=(CHANNEL_IN, CHANNEL_OUT)),
        }

    def forward(self, params, xs, ctx: OpContext):
        (x,) = xs
        q, k, v = project_qkv(params, x, self.eps)
        o = jax.vmap(lambda a, b, c: sparse_attention(a, b, c, self.sparse)
                     )(q, k, v)
        return [gate_and_project(params, o, x)]

    def output_axes(self):
        return [(SAMPLE, SEQ, None)]

    def input_axes(self):
        return [(SAMPLE, SEQ, None)]

    def flops(self) -> float:
        n_tok = 1
        for s in self.inputs[0].shape[:-1]:
            n_tok *= s
        e, h, g, d = (self.embed_dim, self.num_heads, self.num_kv_heads,
                      self.head_dim)
        seen = min(self.inputs[0].shape[-2],
                   self.sparse.topk * self.sparse.block_size)
        return n_tok * (2.0 * e * d * (3 * h + 2 * g) + 4.0 * h * d * seen)
