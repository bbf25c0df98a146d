"""The plain reference of MiniCPM-SALA's language model (configuration
`minicpm-sala-1chip-l16`; openbmb/MiniCPM-SALA, `model_type`
minicpm_sala).

Straightforward `jax.numpy` in float32 under
`default_matmul_precision("highest")`: no kernel, no cache, no pages,
no state slots, no batching across requests. It reads the SYSTEM's
parameter arrays (a dict of op name -> weight name -> array, bf16 as
served) and nothing else of the program. A long sequence is walked in
blocks of rows (a 50k-token score matrix is 6 GB a block of 1024), the
weights upcast where they are used.

For token rows x (S, E), x_0 = scale_emb * Emb[token]; layer i, of kind
mixer_types[layers_kept[i]] and PUBLISHED index l = layers_kept[i]:

  h = RMSNorm(x; w1_i);  x = x + r * mixer(h)
  h = RMSNorm(x; w2_i);  x = x + r * ((silu(h Wg) * (h Wu)) Wd)
  r = scale_depth / sqrt(published depth);  RMSNorm(x; w) =
  x * rsqrt(mean(x^2) + eps) * w

`lightning-attn` (H heads of D): q = rope(RMSNorm_D(h Wq)), k =
  rope(RMSNorm_D(h Wk)) (the norm over each head's D with a (D,) scale;
  half-split pairs (x[j], x[j + D/2]), angle pos * theta^(-2j / D)),
  v = h Wv; S_t = lam S_{t-1} + k_t^T v_t, o_t = q_t S_t / sqrt(D), lam =
  exp(-2^(-8 (head + 1) / H) * (1 - l / (L - 1) + 1e-5)); out =
  (RMSNorm_E(concat o) * sigmoid(h Wgate)) Wo. Computed a block of rows
  at a time from the carried S (the same sums, grouped).
`minicpm4` (H query heads on G key/value heads of D, no position
  signal): q = RMSNorm_D(h Wq), k = RMSNorm_D(h Wk), v = h Wv. A query
  at t < dense_len attends every s <= t. Otherwise: compressed keys
  Kc_j = mean(k[stride j .. stride j + kernel - 1]); p = softmax over
  the j with stride j + kernel - 1 <= t of q . Kc_j / sqrt(D); P = the
  sum of p over the group's heads; block score B_b = max of P over the
  strides block_strides b - 1 .. block_strides b + block_strides - 1;
  the first `init_blocks` blocks and the `window_size / block_size`
  blocks up to t's own score +inf; the `topk` highest-scoring blocks
  <= t's own are selected (ties to the lower block); softmax over the
  tokens s <= t of the selected blocks. out = (o * sigmoid(h Wgate)) Wo.
  The selector's OPERANDS (q, k and the compressed keys) are rounded to
  `selector_dtype` where one is given (the configuration states bf16
  activations and bf16 compressed keys: its `assumed`), the products
  summed in f32; None: f32 operands.

After the last layer RMSNorm(x; w_final) * (dim_model_base / hidden);
logits = . W_head.

DEPARTURES from the published code, each an `assumed` entry of the
configuration: the selector's softmax is exact (the published kernels
approximate its normaliser from a second, coarser pooling); dense_len
switches by QUERY POSITION (the published code by the length of one
forward call); the decays are Lightning Attention-2's slopes with
MiniMax-01's layer factor (the config names none); no feature map on q
and k besides the norm and the rotation.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

MINICPM4 = "minicpm4"
ROWS = 1024             # rows a block of the projections and the scan
SCORE_FLOATS = 1 << 26  # floats of one block's attention scores


def _f32(a):
    return a.astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * _f32(w)


def _blocks(s: int, want: int) -> int:
    """The largest block of at most `want` rows that divides s."""
    b = min(want, s)
    while s % b:
        b -= 1
    return b


def _by_rows(fn, x, rows: int):
    """fn over x (S, ...) a block of `rows` rows at a time."""
    s = x.shape[0]
    out = jax.lax.map(fn, x.reshape((s // rows, rows) + x.shape[1:]))
    return out.reshape((s,) + out.shape[2:])


def _rotate(x, pos, theta):
    """x (R, H, D) at positions pos (R,): half-split pairs."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = _f32(pos)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _ffn(p, x, w_norm, eps, r):
    def block(xb):
        h = _rms(xb, w_norm, eps)
        g, u = jnp.split(h @ _f32(p["w_gu"]), 2, axis=-1)
        return xb + r * ((jax.nn.silu(g) * u) @ _f32(p["w_down"]))
    return _by_rows(block, x, _blocks(x.shape[0], ROWS))


def _lightning(p, x, w_norm, eps, r, theta, layer, layers):
    """x (S, E) -> x + r * the lightning mixer of RMSNorm(x)."""
    s = x.shape[0]
    heads, d = p["wq"].shape[1:]
    c = _blocks(s, 256)
    slope = 2.0 ** (-8.0 * jnp.arange(1, heads + 1, dtype=jnp.float32)
                    / heads)
    g = slope * (1.0 - layer / max(1, layers - 1) + 1e-5)     # (H,)
    i = jnp.arange(c, dtype=jnp.float32)
    gap = i[:, None] - i[None, :]
    decay = jnp.where(gap >= 0, jnp.exp(
        -g[:, None, None] * jnp.maximum(gap, 0.0)), 0.0)      # (H, c, c)

    def block(state, xs):
        xb, pos = xs
        h = _rms(xb, w_norm, eps)
        q, k, v = (jnp.einsum("se,ehd->shd", h, _f32(p[w]))
                   for w in ("wq", "wk", "wv"))
        q = _rotate(_rms(q, p["q_norm"], eps), pos, theta)
        k = _rotate(_rms(k, p["k_norm"], eps), pos, theta)
        a = jnp.einsum("ihd,jhd->hij", q, k) * decay
        o = jnp.einsum("hij,jhd->ihd", a, v)
        o = o + jnp.exp(-g[None, :] * (i[:, None] + 1.0))[:, :, None] \
            * jnp.einsum("ihk,hkv->ihv", q, state)
        left = jnp.exp(-g[None, :] * (c - 1.0 - i[:, None]))  # (c, H)
        state = jnp.exp(-g * c)[:, None, None] * state + jnp.einsum(
            "jhk,jhv->hkv", k * left[:, :, None], v)
        o = (o / math.sqrt(d)).reshape(c, heads * d)
        y = _rms(o, p["o_norm"], eps) * jax.nn.sigmoid(h @ _f32(p["w_gate"]))
        return state, xb + r * (y @ _f32(p["wo"]))

    pos = jnp.arange(s).reshape(s // c, c)
    _, out = jax.lax.scan(block, jnp.zeros((heads, d, d), jnp.float32),
                          (x.reshape(s // c, c, -1), pos))
    return out.reshape(x.shape)


def _selected(q, kc, pos, sp, groups):
    """q (R, H, D), kc (J, G, D) the selector's operands, pos (R,) ->
    (R, G, J / block_strides) bool: the blocks each row selects."""
    r, heads, d = q.shape
    j = kc.shape[0]
    bs = sp["block_size"] // sp["kernel_stride"]
    nb = j // bs
    sc = jnp.einsum("rgid,jgd->rgij", q.reshape(r, groups, -1, d), kc,
                    preferred_element_type=jnp.float32) / math.sqrt(d)
    last = jnp.arange(j) * sp["kernel_stride"] + sp["kernel_size"] - 1
    seen = (last[None, :] <= pos[:, None])[:, None, None, :]
    prob = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
    prob = jnp.sum(jnp.where(seen, prob, 0.0), axis=2)          # (R, G, J)
    # the strides that overlap block b: bs b - 1 .. bs b + bs - 1
    padded = jnp.pad(prob, ((0, 0), (0, 0), (1, 0)),
                     constant_values=-jnp.inf)
    score = jnp.max(jnp.stack(
        [padded[..., o:o + bs * nb:bs] for o in range(bs + 1)]), axis=0)
    b = jnp.arange(nb)[None, :]
    own = (pos // sp["block_size"])[:, None]
    local = sp["window_size"] // sp["block_size"]
    forced = (b < sp["init_blocks"]) | (b > own - local)
    score = jnp.where(forced[:, None, :], jnp.inf, score)
    score = jnp.where((b <= own)[:, None, :], score, -jnp.inf)
    # rank of every block among the row's scores, ties to the lower
    # block; the topk best that the row can see at all
    order = jnp.argsort(-score, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return (rank < sp["topk"]) & (score > -jnp.inf)


def _sparse(p, x, w_norm, eps, r, sp, selector_dtype):
    """x (S, E) -> x + r * the block-sparse mixer of RMSNorm(x)."""
    s = x.shape[0]
    heads, d = p["wq"].shape[1:]
    groups = p["wk"].shape[1]
    rows = _blocks(s, ROWS)

    def keys(xb):
        h = _rms(xb, w_norm, eps)
        k = _rms(jnp.einsum("se,egd->sgd", h, _f32(p["wk"])),
                 p["k_norm"], eps)
        return k, jnp.einsum("se,egd->sgd", h, _f32(p["wv"]))

    k, v = jax.lax.map(keys, x.reshape(s // rows, rows, -1))
    k, v = k.reshape(s, groups, d), v.reshape(s, groups, d)
    cast = (lambda a: a) if selector_dtype is None \
        else (lambda a: a.astype(selector_dtype))
    st = sp["kernel_stride"]
    ks = jnp.concatenate([_f32(cast(k)), jnp.zeros((st, groups, d))]
                         ).reshape(s // st + 1, st, groups, d)
    kc = cast(jnp.mean(jnp.concatenate([ks[:-1], ks[1:]], axis=1),
                       axis=1))                               # (J, G, D)
    qb = _blocks(s, max(8, SCORE_FLOATS // (heads * s)))
    key = jnp.arange(s)

    def block(xs):
        xb, pos = xs
        h = _rms(xb, w_norm, eps)
        q = _rms(jnp.einsum("se,ehd->shd", h, _f32(p["wq"])),
                 p["q_norm"], eps)
        chosen = _selected(cast(q), kc, pos, sp, groups)     # (R, G, nb)
        chosen |= (pos < sp["dense_len"])[:, None, None]
        seen = jnp.take(chosen, key // sp["block_size"], axis=2) \
            & (key[None, :] <= pos[:, None])[:, None, :]      # (R, G, S)
        a = jnp.einsum("rgid,sgd->rgis", q.reshape(qb, groups, -1, d), k
                       ) / math.sqrt(d)
        a = jax.nn.softmax(jnp.where(seen[:, :, None, :], a, -jnp.inf),
                           axis=-1)
        o = jnp.einsum("rgis,sgd->rgid", a, v).reshape(qb, heads * d)
        y = o * jax.nn.sigmoid(h @ _f32(p["w_gate"]))
        return xb + r * (y @ _f32(p["wo"]))

    out = jax.lax.map(block, (x.reshape(s // qb, qb, -1),
                              key.reshape(s // qb, qb)))
    return out.reshape(x.shape)


def hidden_states(params, tokens, mixer_types, layers_kept, sparse,
                  theta: float, eps: float, scale_emb: float,
                  scale_depth: float, selector_dtype=None):
    """(S,) int32 tokens -> (S, E) float32 after the final norm (before
    the head's 1 / (hidden / dim_model_base))."""
    layers = len(mixer_types)
    r = scale_depth / math.sqrt(layers)
    x = _f32(jnp.take(params["tok_embed"]["kernel"], tokens, axis=0)) \
        * scale_emb
    for i, pub in enumerate(layers_kept):
        w1 = params[f"layer{i}_norm1"]["scale"]
        if mixer_types[pub] == MINICPM4:
            x = _sparse(params[f"layer{i}_sparse"], x, w1, eps, r,
                        sparse, selector_dtype)
        else:
            x = _lightning(params[f"layer{i}_linear"], x, w1, eps, r,
                           theta, pub, layers)
        x = _ffn(params[f"layer{i}_ffn"], x,
                 params[f"layer{i}_norm2"]["scale"], eps, r)
    return _rms(x, params["final_norm"]["scale"], eps)


def logits_at(params, tokens, rows, mixer_types, layers_kept, sparse,
              theta: float, eps: float, scale_emb: float,
              scale_depth: float, head_scale: float, selector_dtype=None):
    """Logits (len(rows), V) of one sequence (1, S) at positions
    `rows`. `sparse`: the selector's sizes, a dict; `head_scale` =
    dim_model_base / hidden."""
    with jax.default_matmul_precision("highest"):
        h = jnp.take(hidden_states(
            params, tokens[0], tuple(mixer_types), tuple(layers_kept),
            dict(sparse), theta, eps, scale_emb, scale_depth,
            selector_dtype), rows, axis=0)
        return (h * head_scale) @ _f32(params["lm_head"]["kernel"])
