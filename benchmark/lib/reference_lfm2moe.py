"""The plain reference of LFM2-MoE's language model (configuration
`lfm2-24b-a2b-1chip-l10`; LiquidAI/LFM2-24B-A2B, `model_type` lfm2_moe).

Straightforward `jax.numpy` in float32 under
`default_matmul_precision("highest")`: no kernel, no cache, no pages, no
tails, no segmented form, no dispatch, no batching across requests. The
convolution is three shifted products over the whole sequence; attention
a dense masked softmax, one head and one block of query rows at a time;
every expert is computed for the tokens that chose it by a plain loop
over the experts. It imports nothing of the program and reads the
weights in the PUBLISHED layout: one in-projection matrix with columns
[B | C | z], separate q, k, v and o, `w1` / `w3` / `w2` an expert
(lib/lfm2moe_cell.published_params makes that layout from the system's
arrays). Weights are upcast where they are used.

`params`: {"embed" (V, E), "embedding_norm" (E,), "layers": [a dict a
layer]}; a layer: "operator_norm", "ffn_norm" (E,), then of a CONV layer
"in_proj" (E, 3 E), "conv" (taps, E), "out_proj" (E, E); of an ATTN
layer "q_proj" (E, H D), "k_proj", "v_proj" (E, Hk D), "o_proj" (H D,
E), "q_layernorm", "k_layernorm" (D,); then of a DENSE layer "w1", "w3"
(E, F), "w2" (F, E); of an expert layer "router" (E, n), "expert_bias"
(n,), "w1", "w3" (n, E, F), "w2" (n, F, E).

For token rows x (S, E), N(x; w) = w * x / sqrt(mean(x^2) + eps):

  x_0   = embed[token]
  layer:  h = N(x; w_op)
   CONV   [B | C | z] = h W_in                  thirds in THIS order
          u_t = B_t * z_t
          c_t = w[0] u_{t-2} + w[1] u_{t-1} + w[2] u_t     zeros before the
                                                sequence, no bias, NO activation
          m_t = (C_t * c_t) W_out
   ATTN   q = h W_q, k = h W_k, v = h W_v
          q = N(q_head; w_qn), k = N(k_head; w_kn)   over EACH head's D dims,
                                                BEFORE the rotation
          q, k rotated half-split over all D dims (pairs (j, j + D / 2),
              angle pos * theta^(-2j / D))
          o = causal softmax(q k^T / sqrt(D)) v, H / Hk query heads a key-value head
          m = o W_o
   x  = x + m
   h2 = N(x; w_ffn)
   DENSE  f = (silu(h2 W1) * (h2 W3)) W2
   MOE    s   = sigmoid(h2 W_r)
          sel = the k largest of (s + b)        the bias chooses, never weighs
          g_e = s_e / (sum_{e in sel} s_e + 1e-6)
          f   = sum_{e in sel} g_e (silu(h2 W1_e) * (h2 W3_e)) W2_e
   x  = x + f
  logits = N(x; w_final) embed^T                the head is the table (tied)

The keyword arguments after `eps` are NOT this model's: each replaces
one line of the above, for the controls that show the line matters
(lib/lfm2moe_cell, check_lfm2moe_logits.py, tests/test_lfm2_moe.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Q_BLOCK = 1024          # query rows whose scores are held at once
HEAD_BLOCKS = 8         # blocks of the table's rows upcast one at a time


def _f32(a):
    return a.astype(jnp.float32)


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(w)


def _rotate(x, theta):
    """x (S, D) at positions 0 .. S - 1, half-split over all D dims."""
    s, d = x.shape
    half = d // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[:, :half], x[:, half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=1)


def _short_conv(p, h, swap_gates: bool = False, silu_after: bool = False):
    s = h.shape[0]
    b, c, z = jnp.split(h @ _f32(p["in_proj"]), 3, axis=1)
    if swap_gates:
        b, c = c, b
    u = b * z
    w = _f32(p["conv"])                                 # (taps, E)
    taps = w.shape[0]
    padded = jnp.pad(u, ((taps - 1, 0), (0, 0)))
    conv = sum(padded[j:j + s] * w[j] for j in range(taps))
    if silu_after:
        conv = jax.nn.silu(conv)
    return (c * conv) @ _f32(p["out_proj"])


def _attention(p, h, heads: int, kv_heads: int, theta: float, eps: float,
               whole_norm: bool = False):
    s = h.shape[0]
    q = h @ _f32(p["q_proj"])                               # (S, H D)
    k = h @ _f32(p["k_proj"])                               # (S, Hk D)
    v = h @ _f32(p["v_proj"])
    d = q.shape[1] // heads
    by_head = lambda a, n: a.reshape(s, n, d)
    q, k, v = by_head(q, heads), by_head(k, kv_heads), by_head(v, kv_heads)
    wq, wk = _f32(p["q_layernorm"]), _f32(p["k_layernorm"])
    if whole_norm:
        # NOT this model's: the statistics over the whole projection
        stat = lambda a: jax.lax.rsqrt(
            jnp.mean(a * a, axis=(1, 2), keepdims=True) + eps)
        q, k = q * stat(q) * wq, k * stat(k) * wk
    else:
        q, k = _norm(q, wq, eps), _norm(k, wk, eps)
    q, k, v = (a.swapaxes(0, 1) for a in (q, k, v))
    q, k = (jax.vmap(lambda a: _rotate(a, theta))(a) for a in (q, k))
    qb = min(Q_BLOCK, s)
    blocks = -(-s // qb)
    rows = jnp.arange(blocks * qb).reshape(blocks, qb)
    keys = jnp.arange(s)
    wo = _f32(p["o_proj"]).reshape(heads, d, -1)
    each = heads // kv_heads

    def add(acc, j):
        """Query head j's part of the output projection (S, E)."""
        q_j = jnp.pad(q[j], ((0, blocks * qb - s), (0, 0)))
        k_j, v_j = k[j // each], v[j // each]

        def block(r):
            sc = (q_j[r] @ k_j.T) / jnp.sqrt(jnp.float32(d))
            return jax.nn.softmax(jnp.where(
                r[:, None] >= keys[None, :], sc, -jnp.inf), axis=-1) @ v_j

        o = jax.lax.map(block, rows).reshape(-1, d)[:s]
        return acc + o @ wo[j], None

    out, _ = jax.lax.scan(add, jnp.zeros_like(h), jnp.arange(heads))
    return out


def _dense(p, x):
    """A block of rows at a time: S x 11,776 is held for no more."""
    s = x.shape[0]
    rb = min(Q_BLOCK, s)
    blocks = -(-s // rb)
    w1, w3, w2 = (_f32(p[w]) for w in ("w1", "w3", "w2"))
    y = jax.lax.map(
        lambda r: (jax.nn.silu(r @ w1) * (r @ w3)) @ w2,
        jnp.pad(x, ((0, blocks * rb - s), (0, 0))).reshape(blocks, rb, -1))
    return y.reshape(blocks * rb, -1)[:s]


def route(p, h2, k: int, bias: str = "select", score: str = "sigmoid",
          renorm: bool = True):
    """-> (weights (S, n) f32, 0 for the experts a token did not
    choose; the chosen ids (S, k)). `bias`: "select" (this model's: the
    bias chooses and never weighs), "weights" (it weighs too) or "none";
    `score` "softmax" and `renorm` False are NOT this model's either."""
    logits = h2 @ _f32(p["router"])
    s = jax.nn.sigmoid(logits) if score == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    b = _f32(p["expert_bias"]) if bias != "none" else 0.0
    _, sel = jax.lax.top_k(s + b, k)
    chosen = jnp.take_along_axis(s + b if bias == "weights" else s, sel,
                                 axis=1)
    if renorm:
        chosen = chosen / (jnp.sum(chosen, axis=1, keepdims=True) + 1e-6)
    n = logits.shape[1]
    weights = jnp.sum(jax.nn.one_hot(sel, n, dtype=jnp.float32)
                      * chosen[..., None], axis=1)
    return weights, sel


def _experts(p, h2, k: int, **route_kw):
    """A plain loop over the experts: each computes every row and is
    weighed by 0 where the token did not choose it (the same sum, no
    dispatch)."""
    weights, _ = route(p, h2, k, **route_kw)

    def add(acc, e):
        w1, w3, w2 = (_f32(p[w][e]) for w in ("w1", "w3", "w2"))
        y = (jax.nn.silu(h2 @ w1) * (h2 @ w3)) @ w2
        return acc + y * weights[:, e][:, None], None

    out, _ = jax.lax.scan(add, jnp.zeros_like(h2),
                          jnp.arange(weights.shape[1]))
    return out


def hidden_states(params, tokens, heads: int, kv_heads: int,
                  experts_per_token: int, theta: float = 1e6,
                  eps: float = 1e-5, swap_gates: bool = False,
                  silu_after: bool = False, whole_norm: bool = False,
                  bias: str = "select", score: str = "sigmoid",
                  renorm: bool = True, dense_as_experts=None,
                  sizes: bool = False):
    """(S,) int32 tokens -> (S, E) float32 after the final norm; with
    `sizes`, also the root mean square of the stream and of the two
    branches at every layer (layers, 3): x, m, f. `dense_as_experts`
    (NOT this model's): a layer's parameters to run in place of each
    leading dense feed-forward."""
    x = _f32(jnp.take(params["embed"], tokens, axis=0))
    rms = lambda a: jnp.sqrt(jnp.mean(a * a))
    seen = []
    for p in params["layers"]:
        h = _norm(x, p["operator_norm"], eps)
        if "in_proj" in p:
            m = _short_conv(p, h, swap_gates, silu_after)
        else:
            m = _attention(p, h, heads, kv_heads, theta, eps, whole_norm)
        x0 = x
        x = x + m
        h2 = _norm(x, p["ffn_norm"], eps)
        if "router" in p:
            f = _experts(p, h2, experts_per_token, bias=bias, score=score,
                         renorm=renorm)
        elif dense_as_experts is not None:
            f = _experts(dense_as_experts, h2, experts_per_token)
        else:
            f = _dense(p, h2)
        x = x + f
        seen.append(jnp.stack([rms(x0), rms(m), rms(f)]))
    out = _norm(x, params["embedding_norm"], eps)
    return (out, jnp.stack(seen)) if sizes else out


def logits_at(params, tokens, rows, **kw):
    """Logits (len(rows), V) of one sequence (1, S) at positions `rows`.
    The table is upcast HEAD_BLOCKS blocks of rows at a time."""
    with jax.default_matmul_precision("highest"):
        h = jnp.take(hidden_states(params, tokens[0], **kw), rows, axis=0)
        table = params["embed"]
        v = table.shape[0]
        nb = HEAD_BLOCKS if v % HEAD_BLOCKS == 0 else 1
        vb = v // nb
        out = jax.lax.map(
            lambda j: h @ _f32(jax.lax.dynamic_slice_in_dim(
                table, j * vb, vb, axis=0)).T, jnp.arange(nb))  # (nb, R, vb)
        return out.swapaxes(0, 1).reshape(h.shape[0], v)
