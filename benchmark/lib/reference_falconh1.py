"""The plain reference of Falcon-H1's language model (configuration
`falcon-h1-34b-1chip-l6`; tiiuae/Falcon-H1-34B-Instruct, `model_type`
falcon_h1).

Straightforward `jax.numpy` in float32 under
`default_matmul_precision("highest")`: no kernel, no cache, no pages, no
state slots, no chunk form, no batching across requests. The Mamba-2
heads are the RECURRENCE, a token at a time; attention a dense masked
softmax, one head and one block of query rows at a time. It imports
nothing of the program and reads the weights in the PUBLISHED layout:
one in-projection matrix with columns [z | x | B | C | dt], separate q,
k, v and o, separate gate, up and down (lib/falconh1_cell.published_params
makes that layout from the system's arrays). Every multiplier is applied
where it stands, UNFOLDED. Weights are upcast where they are used.

`params`: {"embed" (V, E), "final_norm" (E,), "lm_head" (E, V),
"layers": [a dict a layer]}; a layer: "input_norm", "pre_ff_norm" (E,),
"in_proj" (E, 2 d_ssm + 2 G N + H), "conv" (taps, d_ssm + 2 G N),
"conv_bias" (d_ssm + 2 G N,), "A_log", "D", "dt_bias" (H,), "ssm_norm"
(d_ssm,), "out_proj" (d_ssm, E), "q_proj" (E, Hq D), "k_proj", "v_proj"
(E, Hk D), "o_proj" (Hq D, E), "gate_proj", "up_proj" (E, F),
"down_proj" (F, E).

`mult`: the configuration's scalars by their published keys —
embedding_multiplier, lm_head_multiplier, ssm_in_multiplier,
ssm_multipliers (5), ssm_out_multiplier, attention_in_multiplier,
attention_out_multiplier, key_multiplier, mlp_multipliers (2).

For token rows x (S, E), N(x; w) = w * x / sqrt(mean(x^2) + eps):

  x_0   = embed[token] * embedding_multiplier
  layer:  h = N(x; w_in)
   SSM    p   = ((h * ssm_in_multiplier) W_in) * m
          m   = ssm_multipliers[0..4] over the column groups [z | x | B | C | dt]
          [x|B|C] = silu(conv_causal([x|B|C]) + bias), zeros before the sequence
          x: H heads of P;  B, C: G groups of N;  head j reads group j // (H / G)
          dt_j = softplus(dt_j + dt_bias_j);  a_j = exp(-exp(A_log_j) * dt_j)
          S_j <- a_j S_j + dt_j * x_j (outer) B_g;   y_j = S_j C_g + D_j x_j
          y   = N_per_group(y * silu(z); w_y): statistics over a group's d_ssm / G
          s   = (y W_out) * ssm_out_multiplier
   ATTN   u   = h * attention_in_multiplier
          q = u W_q, k = (u W_k) * key_multiplier, v = u W_v
          q, k rotated half-split over all D dims (pairs (j, j + D / 2),
              angle pos * theta^(-2j / D))
          o   = causal softmax(q k^T / sqrt(D)) v, Hq / Hk query heads a key-value head
          a   = (o W_o) * attention_out_multiplier
          x   = x + s + a                       both branches read the SAME h
   FFN    h2  = N(x; w_ff)
          f   = ((silu((h2 W_gate) * mlp_multipliers[0]) * (h2 W_up)) W_down) * mlp_multipliers[1]
          x   = x + f
  logits = (N(x; w_final) W_head) * lm_head_multiplier
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Q_BLOCK = 1024          # query rows whose scores are held at once
HEAD_BLOCKS = 8         # blocks of the head's columns upcast one at a time


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


def _attention(p, h, heads: int, kv_heads: int, theta: float, mult: dict):
    s = h.shape[0]
    u = h * mult["attention_in_multiplier"]
    q = u @ _f32(p["q_proj"])                               # (S, Hq D)
    k = (u @ _f32(p["k_proj"])) * mult["key_multiplier"]    # (S, Hk D)
    v = u @ _f32(p["v_proj"])
    d = q.shape[1] // heads
    by_head = lambda a, n: a.reshape(s, n, d).swapaxes(0, 1)
    q, k, v = by_head(q, heads), by_head(k, kv_heads), by_head(v, kv_heads)
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
    return out * mult["attention_out_multiplier"]


def _mamba2(p, h, heads: int, groups: int, d_state: int, eps: float,
            mult: dict, group_of=None, whole_norm: bool = False):
    """The Mamba-2 heads, the recurrence a token at a time. `group_of`
    (H,) and `whole_norm`: NOT this model's (every head on one group;
    the gated norm over all channels at once) — what lib/falconh1_cell's
    controls read the reference under to show they matter."""
    s = h.shape[0]
    d_ssm = p["ssm_norm"].shape[0]
    hp = d_ssm // heads
    bc = groups * d_state
    m = mult["ssm_multipliers"]
    proj = (h * mult["ssm_in_multiplier"]) @ _f32(p["in_proj"])
    z = proj[:, :d_ssm] * m[0]
    xbc = jnp.concatenate([
        proj[:, d_ssm:2 * d_ssm] * m[1],
        proj[:, 2 * d_ssm:2 * d_ssm + bc] * m[2],
        proj[:, 2 * d_ssm + bc:2 * d_ssm + 2 * bc] * m[3]], axis=1)
    dt = proj[:, 2 * d_ssm + 2 * bc:] * m[4]                # (S, H)
    w = _f32(p["conv"])                             # (taps, channels)
    taps = w.shape[0]
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[j:j + s] * w[j] for j in range(taps))
                      + _f32(p["conv_bias"]))
    x = xbc[:, :d_ssm].reshape(s, heads, hp)
    b = xbc[:, d_ssm:d_ssm + bc].reshape(s, groups, d_state)
    c = xbc[:, d_ssm + bc:].reshape(s, groups, d_state)
    group = jnp.arange(heads) // (heads // groups) if group_of is None \
        else jnp.asarray(group_of)
    b, c = b[:, group], c[:, group]                         # (S, H, N)
    dt = jax.nn.softplus(dt + _f32(p["dt_bias"]))
    a = jnp.exp(-jnp.exp(_f32(p["A_log"])) * dt)            # (S, H)

    def token(state, t):
        """state (H, P, N)."""
        x_t, b_t, c_t, a_t, dt_t = t
        state = state * a_t[:, None, None] \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    _, y = jax.lax.scan(token, jnp.zeros((heads, hp, d_state), jnp.float32),
                        (x, b, c, a, dt))
    y = (y + _f32(p["D"])[:, None] * x).reshape(s, d_ssm) * jax.nn.silu(z)
    per = d_ssm if whole_norm else d_ssm // groups
    y = y.reshape(s, -1, per)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    y = y.reshape(s, d_ssm) * _f32(p["ssm_norm"])
    return (y @ _f32(p["out_proj"])) * mult["ssm_out_multiplier"]


def _mlp(p, x, mult: dict):
    """A block of rows at a time: S x 21,504 is held for no more."""
    s = x.shape[0]
    rb = min(Q_BLOCK, s)
    blocks = -(-s // rb)
    g, u, d = (_f32(p[w]) for w in ("gate_proj", "up_proj", "down_proj"))
    m0, m1 = mult["mlp_multipliers"]
    y = jax.lax.map(
        lambda r: ((jax.nn.silu((r @ g) * m0) * (r @ u)) @ d) * m1,
        jnp.pad(x, ((0, blocks * rb - s), (0, 0))).reshape(blocks, rb, -1))
    return y.reshape(blocks * rb, -1)[:s]


def hidden_states(params, tokens, mult: dict, heads: int, kv_heads: int,
                  ssm_heads: int, groups: int, d_state: int,
                  theta: float = 1e11, eps: float = 1e-5, group_of=None,
                  whole_norm: bool = False, sizes: bool = False):
    """(S,) int32 tokens -> (S, E) float32 after the final norm; with
    `sizes`, also the root mean square of the stream and of each branch
    at every layer (layers, 4): x, s, a, f."""
    x = _f32(jnp.take(params["embed"], tokens, axis=0)) \
        * mult["embedding_multiplier"]
    rms = lambda a: jnp.sqrt(jnp.mean(a * a))
    seen = []
    for p in params["layers"]:
        h = _norm(x, p["input_norm"], eps)
        s = _mamba2(p, h, ssm_heads, groups, d_state, eps, mult, group_of,
                    whole_norm)
        a = _attention(p, h, heads, kv_heads, theta, mult)
        x0 = x
        x = x + s + a
        f = _mlp(p, _norm(x, p["pre_ff_norm"], eps), mult)
        x = x + f
        seen.append(jnp.stack([rms(x0), rms(s), rms(a), rms(f)]))
    out = _norm(x, params["final_norm"], eps)
    return (out, jnp.stack(seen)) if sizes else out


def logits_at(params, tokens, rows, mult: dict, **kw):
    """Logits (len(rows), V) of one sequence (1, S) at positions `rows`.
    The head is upcast HEAD_BLOCKS blocks of columns at a time: whole in
    f32 it is 5.3 GB, which does not fit beside an engine's weights."""
    with jax.default_matmul_precision("highest"):
        h = jnp.take(hidden_states(params, tokens[0], mult, **kw), rows,
                     axis=0)
        head = params["lm_head"]
        v = head.shape[1]
        nb = HEAD_BLOCKS if v % HEAD_BLOCKS == 0 else 1
        vb = v // nb
        out = jax.lax.map(
            lambda j: h @ _f32(jax.lax.dynamic_slice_in_dim(
                head, j * vb, vb, axis=1)), jnp.arange(nb))   # (nb, R, vb)
        return out.swapaxes(0, 1).reshape(h.shape[0], v) \
            * mult["lm_head_multiplier"]
