"""The plain reference of Phi-4-mini-flash-reasoning (configuration
`phi-4-mini-flash-1chip`; microsoft/Phi-4-mini-flash-reasoning,
`model_type` phi4flash; arXiv:2507.06607).

Straightforward `jax.numpy` in float32 under
`default_matmul_precision("highest")`: no kernel, no cache, no batching
across requests, the scan a `lax.scan` over tokens, attention a dense
masked softmax. It reads the SYSTEM's parameter arrays (a dict of op
name -> weight name -> array, bf16 as served) and nothing else of the
program. Weights are upcast where they are used, one layer at a time.

Layer i (0-based) of L, F = L // 2 + 1 (17 of 32), x (S, E):

  h = LN(x; ln1);  x = x + mixer_i(h);  h = LN(x; ln2)
  (g, u) = split(h W_gu);  x = x + (silu(g) * u) W_down

  mixer: i even, i < F -> ssm;  i odd, i < F -> window attention;
         i = F -> full attention;  i even, i > F -> gmu;
         i odd, i > F -> cross attention (layer F's k, v; W_q, W_o only)

  ssm:  (u, z) = split(h W_in);  u = silu(conv(u)), causal depthwise
        over the last 4 positions, with bias;  (r, B, C) = split(u W_x);
        dt = softplus(r W_dt + b_dt);  A = -exp(A_log);
        s_t = exp(dt_t A) * s_{t-1} + (dt_t u_t) B_t^T;
        y_t = s_t C_t + D * u_t;  out = (y * silu(z)) W_out.
        Layer F - 1 hands y (before the gate) on as the MEMORY m.
  gmu:  out = (silu(h W_1) * m) W_2
  attention (differential; H query heads, Hk key/value heads of D):
        q1, q2 = even, odd query heads;  k1, k2, v1, v2 likewise;
        query head j of a half reads key/value head j // (H / Hk) of
        the matching half. With P1 = softmax(q1 k1^T / sqrt(D)) and
        P2 = softmax(q2 k2^T / sqrt(D)) under the layer's mask, the
        FOUR products  A11 = P1 v1, A12 = P1 v2, A21 = P2 v1,
        A22 = P2 v2  give
        out = RMSNorm_2D([A11 - lam A21 ; A12 - lam A22]) * (1 - lam0),
        flattened (H/2 x 2D), then W_o (+ b_o);
        lam0 = 0.8 - 0.6 exp(-0.3 i);
        lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0.
        Masks: full and cross causal; window lets t see t-W+1 .. t.

Then LN(x; final_ln) and logits = . E^T with the token table E. No
positional encoding. LN has scale and bias.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _f32(a):
    return a.astype(jnp.float32)


def _ln(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * _f32(p["scale"]) \
        + _f32(p["bias"])


def _ssm(p, h):
    """-> (the mixer's output (S, E), the scan's output y (S, d_inner))."""
    d_state = p["A_log"].shape[0]
    dt_rank = p["w_dt"].shape[0]
    u, z = jnp.split(h @ _f32(p["w_in"]), 2, axis=-1)
    w = _f32(p["conv_w"])                               # (4, d_inner)
    k = w.shape[0]
    s = u.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, u.shape[1])), u])
    u = _f32(p["conv_b"]) + sum(padded[j:j + s] * w[j] for j in range(k))
    u = jax.nn.silu(u)
    r, b, c = jnp.split(u @ _f32(p["w_x"]), [dt_rank, dt_rank + d_state],
                        axis=-1)
    dt = jax.nn.softplus(r @ _f32(p["w_dt"]) + _f32(p["b_dt"]))
    a = -jnp.exp(_f32(p["A_log"]))                      # (N, d_inner)
    d_skip = _f32(p["D"])

    def step(state, x):
        dt_t, u_t, b_t, c_t = x
        state = jnp.exp(dt_t[None, :] * a) * state \
            + (dt_t * u_t)[None, :] * b_t[:, None]
        return state, jnp.sum(state * c_t[:, None], axis=0) + d_skip * u_t

    _, y = jax.lax.scan(step, jnp.zeros_like(a), (dt, u, b, c))
    return (y * jax.nn.silu(z)) @ _f32(p["w_out"]), y


def _proj(p, h, name):
    return jnp.einsum("se,ehd->shd", h, _f32(p["w" + name])) \
        + _f32(p["b" + name])


def _attention(p, h, layer: int, window: int, eps: float, kv=None):
    """-> (the layer's output (S, E), its (k, v))."""
    q = _proj(p, h, "q")                                 # (S, H, D)
    k, v = kv if kv is not None else (_proj(p, h, "k"), _proj(p, h, "v"))
    s, n_q, d = q.shape
    g = n_q // k.shape[1]
    pos = jnp.arange(s)
    mask = pos[:, None] >= pos[None, :]
    if window:
        mask &= pos[:, None] - pos[None, :] < window
    lam0 = 0.8 - 0.6 * math.exp(-0.3 * layer)
    lam = jnp.exp(jnp.sum(_f32(p["lq1"]) * _f32(p["lk1"]))) \
        - jnp.exp(jnp.sum(_f32(p["lq2"]) * _f32(p["lk2"]))) + lam0

    def probs(q_j, k_j):
        sc = (q_j @ k_j.T) / math.sqrt(d)
        return jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)

    def head(j):
        """Query head j of each half -> its (2D,) rows of the output."""
        m = j // g
        p1 = probs(q[:, 2 * j], k[:, 2 * m])
        p2 = probs(q[:, 2 * j + 1], k[:, 2 * m + 1])
        v1, v2 = v[:, 2 * m], v[:, 2 * m + 1]
        a11, a12, a21, a22 = p1 @ v1, p1 @ v2, p2 @ v1, p2 @ v2
        return jnp.concatenate([a11 - lam * a21, a12 - lam * a22], axis=-1)

    o = jax.lax.map(head, jnp.arange(n_q // 2))          # (H/2, S, 2D)
    var = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
    o = o * jax.lax.rsqrt(var + eps) * _f32(p["subln"]) * (1.0 - lam0)
    return jnp.einsum("hsd,hde->se", o, _f32(p["wo"])) + _f32(p["bo"]), \
        (k, v)


def hidden_states(params, tokens, num_layers: int, window: int,
                  ln_eps: float = 1e-5):
    """(S,) int32 tokens -> (S, E) float32 after the final norm."""
    full = num_layers // 2 + 1
    x = _f32(jnp.take(params["tok_embed"]["kernel"], tokens, axis=0))
    memory = kv = None
    for i in range(num_layers):
        h = _ln(x, params[f"layer{i}_ln1"], ln_eps)
        if i < full and i % 2 == 0:
            y, m = _ssm(params[f"layer{i}_ssm"], h)
            if i == full - 1:
                memory = m
        elif i > full and i % 2 == 0:
            g = params[f"layer{i}_gmu"]
            y = (jax.nn.silu(h @ _f32(g["w1"])) * memory) @ _f32(g["w2"])
        else:
            y, got = _attention(
                params[f"layer{i}_attn"], h, i,
                window if i < full else 0, ln_eps,
                kv if i > full else None)
            if i == full:
                kv = got
        x = x + y
        h = _ln(x, params[f"layer{i}_ln2"], ln_eps)
        f = params[f"layer{i}_ffn"]
        gate, up = jnp.split(h @ _f32(f["w_gu"]), 2, axis=-1)
        x = x + (jax.nn.silu(gate) * up) @ _f32(f["w_down"])
    return _ln(x, params["final_ln"], ln_eps)


def logits_at(params, tokens, rows, num_layers: int, window: int,
              ln_eps: float = 1e-5):
    """Logits (len(rows), V) of one sequence (1, S) at positions `rows`,
    the tied head a block of the table at a time."""
    with jax.default_matmul_precision("highest"):
        h = jnp.take(hidden_states(params, tokens[0], num_layers, window,
                                   ln_eps), rows, axis=0)
        table = params["tok_embed"]["kernel"]
        v = table.shape[0]
        blocks = 16 if v % 16 == 0 and v >= 4096 else 1
        out = jax.lax.map(lambda e: h @ _f32(e).T,
                          table.reshape(blocks, v // blocks, -1))
        return jnp.moveaxis(out, 0, 1).reshape(h.shape[0], v)
