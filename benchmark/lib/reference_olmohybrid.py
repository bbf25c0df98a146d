"""The plain reference of Olmo-Hybrid's language model (configuration
`olmo-hybrid-7b-1chip-l16`; allenai/Olmo-Hybrid-7B, `model_type`
olmo_hybrid).

Straightforward `jax.numpy` in float32 under
`default_matmul_precision("highest")`: no kernel, no cache, no pages, no
state slots, no chunks, no batching across requests. The delta rule is
the RECURRENCE, a token at a time; attention a dense masked softmax, one
head and one block of query rows at a time. It imports nothing of the
program and reads the weights in the PUBLISHED layout, one matrix a
projection (lib/olmohybrid_cell.published_params makes that layout from
the system's arrays: the program's delta layer holds q, k, v and the
gate in ONE matrix laid out a head at a time, a permutation of these
columns). Weights are upcast where they are used.

`params`: {"embed" (V, E), "final_norm" (E,), "lm_head" (E, V),
"layers": [a dict a layer]}. Every layer has "post_attention_norm",
"post_feedforward_norm" (E,) and "gate_proj", "up_proj" (E, F),
"down_proj" (F, E). A `full_attention` layer: "q_proj", "k_proj",
"v_proj" (E, H D), "q_norm", "k_norm" (H D,), "o_proj" (H D, E). A
`linear_attention` layer: "q_proj", "k_proj" (E, H Dk), "v_proj",
"g_proj" (E, H Dv), "b_proj", "a_proj" (E, H), "conv" (taps, 2 H Dk +
H Dv) over [q | k | v], "A_log", "dt_bias" (H,), "o_norm" (Dv,),
"o_proj" (H Dv, E).

For token rows x (S, E), x_0 = embed[token]:

  N(x; w) = w * x / sqrt(mean(x^2) + eps)          PLAIN (not 1 + w)
  x = x + N(mixer_i(x); w_i^a);  x = x + N(mlp(x); w_i^f)    POST-norm:
      mixer and mlp read the stream itself
  mlp(x) = (silu(x W_g) * (x W_u)) W_d

`full_attention` (H heads of D = E / H): q = N(x W_q; w_q), k = N(x W_k;
  w_k), each over ALL H D dims of the projection; v = x W_v; `theta`
  None: no rotation, else half-split over a head's D dims (pairs (j,
  j + D / 2), angle pos * theta^(-2j / D)); token t sees 0 .. t; softmax
  scale D^-0.5; out = concat(o) W_o.
`linear_attention` (H heads, key Dk, value Dv): q = x W_q, k = x W_k,
  v = x W_v, z = x W_g, b = x W_b, a = x W_a; c = silu(conv([q | k |
  v])), causal, depthwise, zeros before the sequence, no bias; q, k <-
  x / sqrt(sum x^2 + 1e-6) over Dk, q <- q / sqrt(Dk);
  beta = 2 sigmoid(b)  (`linear_allow_neg_eigval`: `beta_scale` 2),
  g = -exp(A_log) * softplus(a + dt_bias);
  S <- exp(g_t) S;  S <- S + k_t (beta_t (v_t - S^T k_t))^T;
  o_t = S^T q_t;  out = (N_Dv(o; w_o) * silu(z)) W_o.

After the last layer N(x; w_final); logits = . W_head.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

Q_BLOCK = 1024          # query rows whose scores are held at once
L2_EPS = 1e-6


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


def _attention(p, x, heads: int, theta, eps: float):
    s = x.shape[0]
    q = _norm(x @ _f32(p["q_proj"]), p["q_norm"], eps)      # (S, H D)
    k = _norm(x @ _f32(p["k_proj"]), p["k_norm"], eps)
    v = x @ _f32(p["v_proj"])
    d = q.shape[1] // heads
    by_head = lambda a: a.reshape(s, heads, d).swapaxes(0, 1)  # (H, S, D)
    q, k, v = by_head(q), by_head(k), by_head(v)
    if theta is not None:
        q, k = (jax.vmap(lambda a: _rotate(a, theta))(a) for a in (q, k))
    qb = min(Q_BLOCK, s)
    blocks = -(-s // qb)
    rows = jnp.arange(blocks * qb).reshape(blocks, qb)
    keys = jnp.arange(s)
    wo = _f32(p["o_proj"]).reshape(heads, d, -1)

    def add(acc, j):
        """Head j's part of the output projection (S, E)."""
        q_j = jnp.pad(q[j], ((0, blocks * qb - s), (0, 0)))
        k_j, v_j = k[j], v[j]

        def block(r):
            sc = (q_j[r] @ k_j.T) / jnp.sqrt(jnp.float32(d))
            return jax.nn.softmax(jnp.where(
                r[:, None] >= keys[None, :], sc, -jnp.inf), axis=-1) @ v_j

        o = jax.lax.map(block, rows).reshape(-1, d)[:s]
        return acc + o @ wo[j], None

    out, _ = jax.lax.scan(add, jnp.zeros_like(x), jnp.arange(heads))
    return out


def _delta(p, x, heads: int, eps: float, beta_scale: float):
    """The gated delta rule's layer, the recurrence a token at a time."""
    s = x.shape[0]
    dv = p["o_norm"].shape[0]
    q, k, v = (x @ _f32(p[w]) for w in ("q_proj", "k_proj", "v_proj"))
    dk = q.shape[1] // heads
    z = (x @ _f32(p["g_proj"])).reshape(s, heads, dv)
    b, a = x @ _f32(p["b_proj"]), x @ _f32(p["a_proj"])     # (S, H)
    # the convolution over [all q | all k | all v]
    c = jnp.concatenate([q, k, v], axis=1)
    w = _f32(p["conv"])                             # (taps, channels)
    taps = w.shape[0]
    padded = jnp.pad(c, ((taps - 1, 0), (0, 0)))
    c = jax.nn.silu(sum(padded[j:j + s] * w[j] for j in range(taps)))
    n = heads * dk
    unit = lambda u: u / jnp.sqrt(jnp.sum(u * u, axis=-1, keepdims=True)
                                  + L2_EPS)
    q = unit(c[:, :n].reshape(s, heads, dk)) / math.sqrt(dk)
    k = unit(c[:, n:2 * n].reshape(s, heads, dk))
    v = c[:, 2 * n:].reshape(s, heads, dv)
    beta = beta_scale * jax.nn.sigmoid(b)
    g = -jnp.exp(_f32(p["A_log"])) * jax.nn.softplus(a + _f32(p["dt_bias"]))

    def token(state, t):
        """state (H, Dk, Dv)."""
        q_t, k_t, v_t, g_t, beta_t = t
        state = state * jnp.exp(g_t)[:, None, None]
        seen = jnp.einsum("hkv,hk->hv", state, k_t)
        state = state + k_t[:, :, None] * (
            beta_t[:, None] * (v_t - seen))[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((heads, dk, dv), jnp.float32),
                        (q, k, v, g, beta))
    o = _norm(o, p["o_norm"], eps)
    return (o * jax.nn.silu(z)).reshape(s, heads * dv) @ _f32(p["o_proj"])


def _mlp(p, x):
    """A block of rows at a time: S x 11,008 is held for no more."""
    s = x.shape[0]
    rb = min(4 * Q_BLOCK, s)
    blocks = -(-s // rb)
    g, u, d = (_f32(p[w]) for w in ("gate_proj", "up_proj", "down_proj"))
    y = jax.lax.map(lambda r: (jax.nn.silu(r @ g) * (r @ u)) @ d,
                    jnp.pad(x, ((0, blocks * rb - s), (0, 0))
                            ).reshape(blocks, rb, -1))
    return y.reshape(blocks * rb, -1)[:s]


def hidden_states(params, tokens, layer_types, heads: int,
                  linear_heads: int, theta=None, eps: float = 1e-6,
                  beta_scale: float = 2.0):
    """(S,) int32 tokens -> (S, E) float32 after the final norm."""
    x = _f32(jnp.take(params["embed"], tokens, axis=0))
    for kind, p in zip(layer_types, params["layers"]):
        if kind == "full_attention":
            m = _attention(p, x, heads, theta, eps)
        elif kind == "linear_attention":
            m = _delta(p, x, linear_heads, eps, beta_scale)
        else:
            raise ValueError(f"layer type {kind!r}")
        x = x + _norm(m, p["post_attention_norm"], eps)
        x = x + _norm(_mlp(p, x), p["post_feedforward_norm"], eps)
    return _norm(x, params["final_norm"], eps)


def logits_at(params, tokens, rows, layer_types, heads: int,
              linear_heads: int, theta=None, eps: float = 1e-6,
              beta_scale: float = 2.0):
    """Logits (len(rows), V) of one sequence (1, S) at positions
    `rows`."""
    with jax.default_matmul_precision("highest"):
        h = jnp.take(hidden_states(
            params, tokens[0], tuple(layer_types), heads, linear_heads,
            theta, eps, beta_scale), rows, axis=0)
        return h @ _f32(params["lm_head"])
