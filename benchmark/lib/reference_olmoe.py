"""The plain reference of the OLMoE decoder LM (configuration
`olmoe-1b-7b-1chip`; allenai/OLMoE-1B-7B-0125-Instruct, `model_type`
olmoe).

Straightforward `jax.numpy` in float32 under
`default_matmul_precision("highest")`: no kernel, no cache, no sort, no
grouped matmul, no batching across requests. It reads the SYSTEM's
parameter arrays (a dict of op name -> weight name -> array, bf16 as
served) and nothing else of the program. Weights are upcast where they
are used: one layer's attention at a time, one EXPERT at a time (an f32
copy of 8 layers is 13.4 GB and would not fit beside the engine).

The layer, x (tokens, E), H heads of D:

  h = rms(x; norm1)
  q = rms(h Wq; q_norm)   k = rms(h Wk; k_norm)   v = h Wv
      the norm runs over the WHOLE E-wide projection, before the split
      into heads (OLMoE's model code; the config has no key for it)
  rotary on q and k, per head over all D dimensions, theta 10000,
      half-rotation pairing (x[:D/2] with x[D/2:]), at the token's
      absolute position
  causal softmax attention, scale D^-0.5;  x = x + o Wo
  h = rms(x; norm2)
  p = softmax_f32(h Wr) over the experts; the k largest p and their
      experts; the weights are those p AS THEY ARE (`norm_topk_prob`
      false: not renormalised, they sum to less than 1)
  y = sum_j p_j * (silu(h Wg_j) * (h Wu_j)) Wd_j;  x = x + y

After the last layer rms(x; final_norm) and an untied head. No bias
anywhere. rms(x; w) = x * rsqrt(mean(x^2) + eps) * w, in f32.

Every token goes through every expert behind a dense 0/1 mask (a scan
over the experts): nothing here can drop a token.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _f32(a):
    return a.astype(jnp.float32)


def _rms(x, w, eps):
    axes = tuple(range(-w.ndim, 0))
    var = jnp.mean(jnp.square(x), axis=axes, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(w)


def _rope(x, theta):
    """x (S, H, D) at positions 0..S-1."""
    s, _, d = x.shape
    half = d // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(a, h, theta, eps):
    q = _rms(jnp.einsum("se,ehd->shd", h, _f32(a["wq"])), a["q_norm"], eps)
    k = _rms(jnp.einsum("se,ehd->shd", h, _f32(a["wk"])), a["k_norm"], eps)
    v = jnp.einsum("se,ehd->shd", h, _f32(a["wv"]))
    q, k = _rope(q, theta), _rope(k, theta)
    s = h.shape[0]
    scores = jnp.einsum("ihd,jhd->hij", q, k) * (q.shape[-1] ** -0.5)
    causal = jnp.tril(jnp.ones((s, s), dtype=bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hij,jhd->ihd", probs, v)
    return jnp.einsum("shd,hde->se", o, _f32(a["wo"]))


def router(m, h, k: int, router_dtype=None):
    """-> (probabilities (S, E), the k largest (S, k), their experts).
    `router_dtype` rounds the router's inputs and logits to a lower
    precision: the reading that has to come out as NOT correct."""
    if router_dtype is None:
        logits = h @ _f32(m["gate"])
    else:
        logits = _f32((h.astype(router_dtype)
                       @ m["gate"].astype(router_dtype)))
    probs = jax.nn.softmax(logits, axis=-1)
    vals, idx = jax.lax.top_k(probs, k)
    return probs, vals, idx


def _experts(m, h, k: int, router_dtype=None):
    """-> (the layer's output, its router's probabilities)."""
    probs, vals, idx = router(m, h, k, router_dtype)
    n_experts = m["gate"].shape[1]
    # (S, E): the token's weight for the expert, 0 where it is not
    # among its k
    weight = jnp.sum(jax.nn.one_hot(idx, n_experts, dtype=jnp.float32)
                     * vals[..., None], axis=1)

    def one(y, ew):
        wg, wu, wd, w_e = ew
        z = jax.nn.silu(h @ _f32(wg)) * (h @ _f32(wu))
        return y + w_e[:, None] * (z @ _f32(wd)), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (m["wg"], m["wu"], m["wd"], weight.T))
    return y, probs


def hidden_states(params, tokens, num_layers: int, experts_per_token: int,
                  rope_theta: float, rms_eps: float, router_dtype=None):
    """(S,) int32 tokens -> ((S, E) float32 after the final norm, each
    layer's (S, experts) router probabilities)."""
    x = _f32(jnp.take(params["tok_embed"]["kernel"], tokens, axis=0))
    routed = []
    for i in range(num_layers):
        h = _rms(x, params[f"layer{i}_norm1"]["scale"], rms_eps)
        x = x + _attention(params[f"layer{i}_attn"], h, rope_theta, rms_eps)
        h = _rms(x, params[f"layer{i}_norm2"]["scale"], rms_eps)
        y, probs = _experts(params[f"layer{i}_moe"], h, experts_per_token,
                            router_dtype)
        x = x + y
        routed.append(probs)
    return _rms(x, params["final_norm"]["scale"], rms_eps), routed


def logits_at(params, tokens, rows, num_layers: int,
              experts_per_token: int = 8, rope_theta: float = 10000.0,
              rms_eps: float = 1e-5, router_dtype=None):
    """Logits (len(rows), V) of one sequence (1, S) at positions `rows`."""
    with jax.default_matmul_precision("highest"):
        h, _ = hidden_states(params, tokens[0], num_layers,
                             experts_per_token, rope_theta, rms_eps,
                             router_dtype)
        return jnp.take(h, rows, axis=0) @ _f32(params["lm_head"]["kernel"])


def near_ties(params, tokens, num_layers: int, experts_per_token: int = 8,
              rope_theta: float = 10000.0, rms_eps: float = 1e-5,
              within: float = 1e-3):
    """How often the k-th and (k+1)-th router probabilities of a token
    lie within `within` (relative) of each other: where bf16 arithmetic
    may pick another expert than f32 does. -> (near ties, decisions)."""
    k = experts_per_token
    with jax.default_matmul_precision("highest"):
        _, routed = hidden_states(params, tokens[0], num_layers, k,
                                  rope_theta, rms_eps)
    vals = jax.lax.top_k(jnp.stack(routed), k + 1)[0]     # (L, S, k + 1)
    near = (vals[..., k - 1] - vals[..., k]) <= within * vals[..., k - 1]
    return jnp.sum(near), near.size
