"""The plain reference of Command A+'s language model (configuration
`command-a-plus-1chip-ep8`; CohereLabs/command-a-plus-05-2026,
`model_type` cohere2_moe), as ONE SHARE of an expert-parallel
deployment holds it.

Straightforward `jax.numpy` in float32 under
`default_matmul_precision("highest")`: no kernel, no cache, no sort, no
batching across requests; attention a dense masked softmax, one query
head and one block of query rows at a time (a 17k-token score matrix
is 1.2 GB a head); every token through every HELD expert behind a dense
0/1 mask. It reads the SYSTEM's parameter arrays (a dict of op name ->
weight name -> array, bf16 as served) and nothing else of the program.
Weights are upcast where they are used: one layer's attention, then ONE
expert at a time.

For token rows x (S, E), layer i of kind layer_types[i]:

  h  = LN(x; w_i)        LN(x; w) = (x - mean(x)) * rsqrt(var(x) + eps) * w
                         no bias; ONE norm a layer (the parallel block)
  q  = h Wq (H heads of D);  k = h Wk, v = h Wv (Hk heads of D); no bias
  sliding_attention: q, k rotated at the token's absolute position over
                     ALL D dims, interleaved pairs (x[2j], x[2j+1]),
                     angle pos * theta^(-2j / D); token t sees keys
                     t - W + 1 .. t (W keys with its own)
  full_attention:    no rotation (no position signal); t sees 0 .. t
  query head j reads key/value head j // (H / Hk); softmax scale D^-0.5
  a  = concat(o) Wo
  s  = sigmoid(h Wr) in R^N (N the router's width, ALL the experts);
       the k largest s and their experts; p_j = s_j / sum of the k;
       h and Wr are the router's OPERANDS, rounded to `router_dtype`
       where one is given (the configuration states bf16 activations:
       its `assumed`), the products summed in f32, the logits never
       rounded, sigmoid and top-k in f32
  E(h; g, u, d) = (silu(h g) * (h u)) d
  f  = sum_{j held here} p_j E_j(h)  +  (1 / M) sum_{m=1..M} S_m(h)
       `held` (first, count): the experts first .. first + count - 1,
       whose weights are wg[0 .. count - 1]; what the absent experts
       would have added is LEFT OUT (the deployment's other chips hold
       them); the M shared experts lie side by side in sg, su (E, M F)
       and sd (M F, E)
  x' = x + a + f

After the last layer LN(x; w_final); logits = . Emb^T * logit_scale
over this chip's slice of the vocabulary (the token table as it is
held).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

SLIDING = "sliding_attention"
Q_BLOCK = 1024          # query rows whose scores are held at once


def _f32(a):
    return a.astype(jnp.float32)


def _ln(x, w, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * _f32(w)


def _rotate(x, theta):
    """x (S, D): interleaved pairs rotated at positions 0 .. S - 1."""
    s, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    even, odd = x[:, 0::2], x[:, 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(s, d)


def _attention(p, h, window: int, theta: float):
    """-> the layer's attention output (S, E). `window` 0: full, no
    rotation."""
    s = h.shape[0]
    wq, wo = p["wq"], p["wo"]                   # (E, H, D), (H, D, E)
    n_q, d = wq.shape[1:]
    k = jnp.einsum("se,ehd->hsd", h, _f32(p["wk"]))     # (Hk, S, D)
    v = jnp.einsum("se,ehd->hsd", h, _f32(p["wv"]))
    if window:
        k = jax.vmap(lambda a: _rotate(a, theta))(k)
    group = n_q // k.shape[0]
    qb = min(Q_BLOCK, s)
    blocks = -(-s // qb)
    rows = jnp.arange(blocks * qb).reshape(blocks, qb)
    keys = jnp.arange(s)

    def head(j):
        """Query head j -> its part of the output projection (S, E)."""
        q = h @ _f32(wq[:, j])                          # (S, D)
        if window:
            q = _rotate(q, theta)
        k_j, v_j = k[j // group], v[j // group]
        q = jnp.pad(q, ((0, blocks * qb - s), (0, 0)))

        def block(r):
            sc = (q[r] @ k_j.T) / jnp.sqrt(jnp.float32(d))
            mask = r[:, None] >= keys[None, :]
            if window:
                mask &= r[:, None] - keys[None, :] < window
            return jax.nn.softmax(jnp.where(mask, sc, -jnp.inf),
                                  axis=-1) @ v_j

        o = jax.lax.map(block, rows).reshape(-1, d)[:s]
        return o @ _f32(wo[j])

    def add(acc, j):
        return acc + head(j), None

    out, _ = jax.lax.scan(add, jnp.zeros_like(h), jnp.arange(n_q))
    return out


def _gated(h, g, u, d):
    return (jax.nn.silu(h @ _f32(g)) * (h @ _f32(u))) @ _f32(d)


def _experts(p, h, experts_per_token: int, held, shared: int,
             router_dtype=None):
    """-> f (S, E): the held experts' part of the routed sum and the
    shared experts' mean. `router_dtype`: the router's two operands
    rounded to it (the logits still accumulate in f32 and are never
    rounded); None: f32 operands."""
    if router_dtype is None:
        logits = h @ _f32(p["gate"])
    else:
        logits = jnp.dot(h.astype(router_dtype),
                         p["gate"].astype(router_dtype),
                         preferred_element_type=jnp.float32)
    s = jax.nn.sigmoid(logits)                          # (S, N)
    top, ids = jax.lax.top_k(s, experts_per_token)
    weights = top / jnp.sum(top, axis=-1, keepdims=True)
    first, count = held

    def add(acc, e):
        # the 0/1 mask of the tokens that chose expert first + e, times
        # their weight for it
        w = jnp.sum(jnp.where(ids == first + e, weights, 0.0), axis=-1)
        y = _gated(h, p["wg"][e], p["wu"][e], p["wd"][e])
        return acc + w[:, None] * y, None

    f, _ = jax.lax.scan(add, jnp.zeros_like(h), jnp.arange(count))
    if shared:
        width = p["sg"].shape[1] // shared
        for m in range(shared):
            cols = slice(m * width, (m + 1) * width)
            f = f + _gated(h, p["sg"][:, cols], p["su"][:, cols],
                           p["sd"][cols]) / shared
    return f


def hidden_states(params, tokens, layer_types, window: int, theta: float,
                  experts_per_token: int, held, shared: int,
                  ln_eps: float = 1e-5, router_dtype=None):
    """(S,) int32 tokens -> (S, E) float32 after the final norm."""
    x = _f32(jnp.take(params["tok_embed"]["kernel"], tokens, axis=0))
    for i, kind in enumerate(layer_types):
        h = _ln(x, params[f"layer{i}_ln"]["scale"], ln_eps)
        a = _attention(params[f"layer{i}_attn"], h,
                       window if kind == SLIDING else 0, theta)
        f = _experts(params[f"layer{i}_moe"], h, experts_per_token, held,
                     shared, router_dtype)
        x = x + a + f
    return _ln(x, params["final_ln"]["scale"], ln_eps)


def logits_at(params, tokens, rows, layer_types, window: int, theta: float,
              experts_per_token: int, held, shared: int,
              ln_eps: float = 1e-5, logit_scale: float = 1.0,
              router_dtype=None):
    """Logits (len(rows), V) of one sequence (1, S) at positions
    `rows`, over the token table as it is held."""
    with jax.default_matmul_precision("highest"):
        h = jnp.take(hidden_states(
            params, tokens[0], tuple(layer_types), window, theta,
            experts_per_token, tuple(held), shared, ln_eps, router_dtype),
            rows, axis=0)
        return h @ _f32(params["tok_embed"]["kernel"]).T * logit_scale
