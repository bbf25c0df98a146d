"""The plain reference of the decoder LM every configuration here runs.

Straightforward `jax.numpy` in float32 under
`default_matmul_precision("highest")`: token + learned-position
embeddings, pre-LN blocks (causal softmax attention over the whole
sequence, ReLU feed-forward), final LN, untied head. No kernel, no
cache, no batching across requests, no sharding rule. It reads the
SYSTEM's parameter arrays (a dict of op name -> weight name -> array,
float32 masters) and nothing else of the program.

Departures from the published OPT block, all the program's own and
listed in the configuration files under `assumed`: the position table
has no offset of 2, attention projections carry no q/k/v bias.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


def _ln(p, x):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _dense(p, x):
    y = x @ p["kernel"]
    return y + p["bias"] if "bias" in p else y


def _block(params, i, x):
    a = params[f"layer{i}_attn"]
    h = _ln(params[f"layer{i}_ln1"], x)
    q = jnp.einsum("bse,ehd->bshd", h, a["wq"])
    k = jnp.einsum("bse,ehd->bshd", h, a["wk"])
    v = jnp.einsum("bse,ehd->bshd", h, a["wv"])
    s = x.shape[1]
    scores = jnp.einsum("bihd,bjhd->bhij", q, k) * (q.shape[-1] ** -0.5)
    causal = jnp.tril(jnp.ones((s, s), dtype=bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bhij,bjhd->bihd", probs, v)
    y = jnp.einsum("bshd,hde->bse", o, a["wo"])
    if "bo" in a:
        y = y + a["bo"]
    x = x + y
    h = _ln(params[f"layer{i}_ln2"], x)
    h = jax.nn.relu(_dense(params[f"layer{i}_ff1"], h))
    return x + _dense(params[f"layer{i}_ff2"], h)


def hidden_states(params, tokens, num_layers: int, remat: bool = False):
    """(B, S) int32 tokens -> (B, S, E) float32 after the final LN."""
    f32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
    x = (jnp.take(f32["tok_embed"]["kernel"], tokens, axis=0)
         + jnp.take(f32["pos_embed"]["kernel"], positions, axis=0)[None])
    for i in range(num_layers):
        block = functools.partial(_block, f32, i)
        x = jax.checkpoint(block)(x) if remat else block(x)
    return _ln(f32["final_ln"], x)


def logits_at(params, tokens, rows, num_layers: int):
    """Logits (len(rows), V) of one sequence (1, S) at positions `rows`."""
    with jax.default_matmul_precision("highest"):
        h = hidden_states(params, tokens, num_layers)[0]
        return _dense(jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), params["lm_head"]),
            jnp.take(h, rows, axis=0))


def loss_fn(params, tokens, labels, num_layers: int, remat: bool = True):
    """Mean next-token cross-entropy over every position of the batch;
    also returns the (B, S, V) logits."""
    with jax.default_matmul_precision("highest"):
        h = hidden_states(params, tokens, num_layers, remat=remat)
        logits = _dense(jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), params["lm_head"]), h)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)
        return jnp.mean(nll), logits


def loss_logits_grad_samples(params, tokens, labels, logit_rows,
                             grad_index, num_layers: int):
    """The reference's first step: loss, logits at the flat (batch *
    seq) positions `logit_rows`, and for every parameter tensor the
    gradient entries at the flat indices `grad_index[op][weight]`.
    Sampling happens inside the jitted function so that no more than a
    few whole gradient tensors need to be alive at once."""
    (loss, logits), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(params, tokens, labels, num_layers)
    picked = jnp.take(logits.reshape(-1, logits.shape[-1]), logit_rows,
                      axis=0)
    samples = {op: {w: jnp.take(grads[op][w].reshape(-1), idx)
                    for w, idx in ws.items()}
               for op, ws in grad_index.items()}
    return loss, picked, samples
