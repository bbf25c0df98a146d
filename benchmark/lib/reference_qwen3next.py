"""The plain reference of Qwen3-Next's language model (configuration
`qwen3-next-80b-a3b-1chip-ep4-l8`; Qwen/Qwen3-Next-80B-A3B-Instruct,
`model_type` qwen3_next), as ONE SHARE of an expert-parallel deployment
holds it.

Straightforward `jax.numpy` in float32 under
`default_matmul_precision("highest")`: no kernel, no cache, no pages, no
state slots, no chunks, no sort, no batching across requests. The delta
rule is the RECURRENCE, a token at a time; attention a dense masked
softmax, one query head and one block of query rows at a time; every
token through every HELD expert behind a dense 0/1 mask, one expert at
a time. It reads the SYSTEM's parameter arrays (a dict of op name ->
weight name -> array, bf16 as served) and nothing else of the program.
Weights are upcast where they are used.

For token rows x (S, E), x_0 = Emb[token]; layer i is `full_attention`
where (i + 1) % full_attention_interval == 0, else `linear_attention`:

  N0(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)     zero-centred
  h = N0(x; w1_i);  x = x + mixer_i(h)
  h = N0(x; w2_i);  x = x + moe_i(h)

`full_attention` (H query heads on G key/value heads of D): h Wq is
  (H, 2 D), a head's first D its query, its last D its gate;
  q = N0_D(q; wq_norm), k = N0_D(h Wk; wk_norm), v = h Wv; dims
  0 .. R - 1 of q and k rotated half-split (pairs (j, j + R / 2), angle
  pos * theta^(-2j / R)), dims R .. D - 1 as they are; token t sees
  0 .. t; softmax scale D^-0.5; query head j reads key/value head
  j // (H / G); out = (concat(o) * sigmoid(gate)) Wo.
`linear_attention` (Hk key heads of Dk, Hv = r Hk value heads of Dv):
  [q | k | v | z] = h W_qkvz, a KEY head at a time as
  [q Dk | k Dk | v r Dv | z r Dv]; [b | a] = h W_ba, [b r | a r] a key
  head; c = silu(conv([q | k | v])): all heads' q, then k, then v, a
  causal depthwise convolution of `taps` taps, zeros before the
  sequence, no bias; q, k <- x / sqrt(sum x^2 + 1e-6) over Dk, key head
  j serving value heads r j .. r j + r - 1, q <- q / sqrt(Dk);
  beta = sigmoid(b), g = -exp(A_log) * softplus(a + dt_bias);
  S <- exp(g_t) S;  S <- S + k_t (beta_t (v_t - S^T k_t))^T;
  o_t = S^T q_t;  out = (o / sqrt(mean_Dv(o^2) + eps) * w_o * silu(z)) Wo
  (this norm's scale is w_o, NOT 1 + w_o).
moe: p = softmax(h Wr) over ALL N experts (h and Wr the router's
  OPERANDS, rounded to `router_dtype` where one is given — the
  configuration states bf16 activations: its `assumed` — the products
  summed in f32, the logits never rounded, softmax and top-k in f32);
  the k largest p, renormalised over the k;
  E(h; g, u, d) = (silu(h g) * (h u)) d;
  moe(h) = sum_{j held here} p_j E_j(h) + sigmoid(h w_sg) * E_shared(h);
  `held` (first, count): what the absent experts would have added is
  LEFT OUT (the deployment's other chips hold them).

After the last layer N0(x; w_final); logits = . W_head over this chip's
slice of the vocabulary (the head as it is held).

The multi-token-prediction module of the checkpoint is not part of
config.json and is not computed.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

Q_BLOCK = 1024          # query rows whose scores are held at once
L2_EPS = 1e-6


def _f32(a):
    return a.astype(jnp.float32)


def _norm0(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * (1.0 + _f32(w))


def _rotate(x, theta, rotary_dim):
    """x (S, D) at positions 0 .. S - 1: the first `rotary_dim` dims
    rotated half-split among themselves."""
    s = x.shape[0]
    half = rotary_dim // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0
                    / rotary_dim)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[:, :half], x[:, half:rotary_dim]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[:, rotary_dim:]], axis=1)


def _attention(p, h, theta: float, rotary_dim: int, eps: float):
    s = h.shape[0]
    wq, wo = p["wq"], p["wo"]                   # (E, H, 2D), (H, D, E)
    n_q = wq.shape[1]
    d = wo.shape[1]
    k = jnp.einsum("se,ehd->hsd", h, _f32(p["wk"]))         # (G, S, D)
    v = jnp.einsum("se,ehd->hsd", h, _f32(p["wv"]))
    k = jax.vmap(lambda a: _rotate(_norm0(a, p["k_norm"], eps), theta,
                                   rotary_dim))(k)
    group = n_q // k.shape[0]
    qb = min(Q_BLOCK, s)
    blocks = -(-s // qb)
    rows = jnp.arange(blocks * qb).reshape(blocks, qb)
    keys = jnp.arange(s)

    def head(j):
        """Query head j -> its part of the output projection (S, E)."""
        qg = h @ _f32(wq[:, j])                             # (S, 2D)
        q = _rotate(_norm0(qg[:, :d], p["q_norm"], eps), theta, rotary_dim)
        k_j, v_j = k[j // group], v[j // group]
        q = jnp.pad(q, ((0, blocks * qb - s), (0, 0)))

        def block(r):
            sc = (q[r] @ k_j.T) / jnp.sqrt(jnp.float32(d))
            return jax.nn.softmax(jnp.where(
                r[:, None] >= keys[None, :], sc, -jnp.inf), axis=-1) @ v_j

        o = jax.lax.map(block, rows).reshape(-1, d)[:s]
        return (o * jax.nn.sigmoid(qg[:, d:])) @ _f32(wo[j])

    def add(acc, j):
        return acc + head(j), None

    out, _ = jax.lax.scan(add, jnp.zeros_like(h), jnp.arange(n_q))
    return out


def _delta(p, h, key_heads: int, ratio: int, eps: float):
    """The gated delta rule's layer, the recurrence a token at a time."""
    s = h.shape[0]
    hv = key_heads * ratio
    dv = p["o_norm"].shape[0]
    width = p["w_qkvz"].shape[1] // key_heads       # 2 Dk + 2 r Dv
    dk = (width - 2 * ratio * dv) // 2
    qkvz = (h @ _f32(p["w_qkvz"])).reshape(s, key_heads, width)
    ba = (h @ _f32(p["w_ba"])).reshape(s, key_heads, 2 * ratio)
    q, k = qkvz[:, :, :dk], qkvz[:, :, dk:2 * dk]
    v = qkvz[:, :, 2 * dk:2 * dk + ratio * dv]
    z = qkvz[:, :, 2 * dk + ratio * dv:].reshape(s, hv, dv)
    b = ba[:, :, :ratio].reshape(s, hv)
    a = ba[:, :, ratio:].reshape(s, hv)
    # the convolution over [all q | all k | all v]
    c = jnp.concatenate([q.reshape(s, -1), k.reshape(s, -1),
                         v.reshape(s, -1)], axis=1)
    w = _f32(p["conv_w"])                           # (taps, channels)
    taps = w.shape[0]
    padded = jnp.pad(c, ((taps - 1, 0), (0, 0)))
    c = jax.nn.silu(sum(padded[j:j + s] * w[j] for j in range(taps)))
    n = key_heads * dk
    unit = lambda x: x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                                  + L2_EPS)
    q = jnp.repeat(unit(c[:, :n].reshape(s, key_heads, dk)), ratio, axis=1) \
        / math.sqrt(dk)
    k = jnp.repeat(unit(c[:, n:2 * n].reshape(s, key_heads, dk)), ratio,
                   axis=1)
    v = c[:, 2 * n:].reshape(s, hv, dv)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(_f32(p["A_log"])) * jax.nn.softplus(a + _f32(p["dt_bias"]))

    def token(state, x):
        """state (Hv, Dk, Dv)."""
        q_t, k_t, v_t, g_t, beta_t = x
        state = state * jnp.exp(g_t)[:, None, None]
        seen = jnp.einsum("hkv,hk->hv", state, k_t)
        state = state + k_t[:, :, None] * (
            beta_t[:, None] * (v_t - seen))[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((hv, dk, dv), jnp.float32),
                        (q, k, v, g, beta))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) \
        * _f32(p["o_norm"])
    return (o * jax.nn.silu(z)).reshape(s, hv * dv) @ _f32(p["wo"])


def _gated(h, g, u, d):
    return (jax.nn.silu(h @ _f32(g)) * (h @ _f32(u))) @ _f32(d)


def _experts(p, h, experts_per_token: int, held, router_dtype=None,
             shared: bool = True):
    """-> the held experts' part of the routed sum (`held` (first,
    count); weights wg[0 .. count - 1]) and, with `shared`, the gated
    shared expert's term (every share computes it alike: a sum over
    shares counts it once)."""
    if router_dtype is None:
        logits = h @ _f32(p["gate"])
    else:
        logits = jnp.dot(h.astype(router_dtype),
                         p["gate"].astype(router_dtype),
                         preferred_element_type=jnp.float32)
    top, ids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                             experts_per_token)
    weights = top / jnp.sum(top, axis=-1, keepdims=True)
    first, count = held

    def add(acc, e):
        w = jnp.sum(jnp.where(ids == first + e, weights, 0.0), axis=-1)
        y = _gated(h, p["wg"][e], p["wu"][e], p["wd"][e])
        return acc + w[:, None] * y, None

    f, _ = jax.lax.scan(add, jnp.zeros_like(h), jnp.arange(count))
    if shared:
        f = f + jax.nn.sigmoid(h @ _f32(p["sgate"])) * _gated(
            h, p["sg"], p["su"], p["sd"])
    return f


def hidden_states(params, tokens, num_layers: int, interval: int,
                  theta: float, rotary_dim: int, key_heads: int,
                  ratio: int, experts_per_token: int, held,
                  eps: float = 1e-6, router_dtype=None):
    """(S,) int32 tokens -> (S, E) float32 after the final norm."""
    x = _f32(jnp.take(params["tok_embed"]["kernel"], tokens, axis=0))
    for i in range(num_layers):
        h = _norm0(x, params[f"layer{i}_norm1"]["scale"], eps)
        if (i + 1) % interval == 0:
            x = x + _attention(params[f"layer{i}_attn"], h, theta,
                               rotary_dim, eps)
        else:
            x = x + _delta(params[f"layer{i}_delta"], h, key_heads, ratio,
                           eps)
        h = _norm0(x, params[f"layer{i}_norm2"]["scale"], eps)
        x = x + _experts(params[f"layer{i}_moe"], h, experts_per_token,
                         held, router_dtype)
    return _norm0(x, params["final_norm"]["scale"], eps)


def logits_at(params, tokens, rows, num_layers: int, interval: int,
              theta: float, rotary_dim: int, key_heads: int, ratio: int,
              experts_per_token: int, held, eps: float = 1e-6,
              router_dtype=None):
    """Logits (len(rows), V) of one sequence (1, S) at positions
    `rows`, over the head as it is held."""
    with jax.default_matmul_precision("highest"):
        h = jnp.take(hidden_states(
            params, tokens[0], num_layers, interval, theta, rotary_dim,
            key_heads, ratio, experts_per_token, tuple(held), eps,
            router_dtype), rows, axis=0)
        return h @ _f32(params["lm_head"]["kernel"])
