"""What the serve engine asks of the model it serves.

The engine (serve/engine.py) owns lanes, pages, the paged kernel, the
head's top-k and every program; the MODEL is a description it asks:
how a token is embedded, a layer's norm and projections at the lanes'
positions, its output projection, its feed-forward, the head — and what
it cannot do, which raises at engine build (no silent fallback). A
description reads the parameters of a compiled FFModel through the op
names its builder wrote, and mirrors those ops' numerics.

Two clients: `TransformerLM` (models/transformer.build_transformer_lm:
learned positions, LayerNorm, ReLU feed-forward — the OPT block) and
`OLMoE` (models/olmoe.build_olmoe_lm: RMSNorm, rotary attention with
QK-norm, dropless top-k SwiGLU experts).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops.common import rms_norm, rotary
from ..ops.moe import (dropless_combine, dropless_dispatch, grouped_ffn,
                       route_top_k)


def _ln(p, x, eps):
    """LayerNorm with f32 statistics — must mirror ops/elementwise.py
    LayerNorm.forward exactly (the reference-parity contract)."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def _dense(p, x, activation=None, psum_axis=None):
    """Dense layer. `psum_axis` is the tensor-parallel row-parallel
    hook: under sharding the kernel's CONTRACTION dim is sharded, so
    each device's matmul is a partial sum that all-reduces over the
    axis BEFORE the (replicated) bias — exactly the Megatron pattern
    the cost model prices. None (single device) is the unchanged
    bit-exact path."""
    y = jnp.dot(x, p["kernel"].astype(x.dtype),
                preferred_element_type=jnp.float32).astype(x.dtype)
    if psum_axis is not None:
        y = jax.lax.psum(y, psum_axis)
    if "bias" in p:
        y = y + p["bias"].astype(x.dtype)
    if activation == "relu":
        y = jax.nn.relu(y)
    return y


def _project(p, h):
    """h (..., E) -> q, k, v (..., H, D) through wq, wk, wv."""
    return tuple(jnp.einsum("...e,ehd->...hd", h, p[w].astype(h.dtype))
                 for w in ("wq", "wk", "wv"))


def _count_layers(ops) -> int:
    n = 0
    while f"layer{n}_attn" in ops:
        n += 1
    if n == 0:
        raise ValueError("model has no layer{i}_attn blocks")
    if not ops["layer0_attn"].causal:
        raise ValueError("serving needs causal attention blocks")
    return n


class TransformerLM:
    """The build_transformer_lm block: token + learned-position
    embeddings, pre-LN causal attention, ReLU feed-forward, final LN,
    untied head. Serves every engine path."""

    kind = "transformer_lm"
    experts = 0                 # no expert layer: the step counts none
    experts_per_token = 0

    def __init__(self, model, ops):
        self.vocab_size = ops["tok_embed"].num_entries
        self.max_positions = ops["pos_embed"].num_entries
        self.layer_norm = "layer0_ln1" in ops
        self.num_layers = _count_layers(ops)
        attn0 = ops["layer0_attn"]
        self.num_heads = attn0.num_heads
        self.head_dim = attn0.head_dim
        self.hidden = attn0.embed_dim
        self.ln_eps = ops["layer0_ln1"].eps if self.layer_norm else 1e-5
        # serving activation dtype = whatever the LM graph's embeddings
        # emit (build_transformer_lm wires FFConfig.compute_dtype here):
        # every block below follows its input dtype, so a bf16 LM
        # serves bf16 end-to-end — and generate_reference embeds
        # through the SAME cast, so the greedy parity oracle holds at
        # the engine's own precision. KV pages keep their configured
        # (f32) dtype: bf16 K/V upcasts exactly, so cached and
        # recomputed attention stay bit-identical.
        self.act_dtype = jnp.dtype(ops["tok_embed"].out_dtype)
        self.ff_dim = int(
            model.state.params["layer0_ff1"]["kernel"].shape[1])

    def refuse(self, *, tp: int, adapters: bool) -> None:
        """Raise for an engine path this model is not served on."""

    def embed(self, params, tokens, positions):
        # mode="clip": padded lanes/positions past the learned tables
        # must read SOME finite row — they are masked or never read
        # back, but jnp.take's "fill" OOB default yields NaN, and a
        # NaN K/V poisons every lane that softmax-weights it (0 * NaN
        # = NaN survives the causal mask's zeroed probability). Bit
        # for bit identical for all in-range indices. (The same OOB
        # trap as ops/embedding's flat slot-offset gather, PR 2.)
        te = jnp.take(params["tok_embed"]["kernel"], tokens, axis=0,
                      mode="clip")
        pe = jnp.take(params["pos_embed"]["kernel"], positions, axis=0,
                      mode="clip")
        return (te + pe).astype(self.act_dtype)

    def norm1(self, params, i, x):
        return _ln(params[f"layer{i}_ln1"], x, self.ln_eps) \
            if self.layer_norm else x

    def qkv(self, params, i, h, positions, lora=None):
        """h (..., E) -> q, k, v (..., H, D). `lora` (mixed step only,
        h is (T, E)) is the lanes' gathered per-layer adapter rows
        (a_qkv (T, 3, E, r), b_qkv (T, 3, r, H[/t], D), scale (T,)):
        each lane adds ITS tenant's low-rank delta; slot-0 lanes gather
        the zero slab and their delta is exactly 0.0. `positions` are
        not read: this block's positions are in its embedding."""
        q, k, v = _project(params[f"layer{i}_attn"], h)
        if lora is not None:
            aq, bq, s = lora
            u = jnp.einsum("te,tjer->tjr", h, aq.astype(h.dtype))
            d = jnp.einsum("tjr,tjrhd->tjhd", u, bq.astype(h.dtype))
            d = d * s.astype(h.dtype)[:, None, None, None]
            q = q + d[:, 0]
            k = k + d[:, 1]
            v = v + d[:, 2]
        return q, k, v

    def attn_out(self, params, i, o, x, psum_axis=None, lora=None):
        p = params[f"layer{i}_attn"]
        y = jnp.einsum("...hd,hde->...e", o, p["wo"].astype(o.dtype))
        if lora is not None:
            # a_wo contracts the (sharded) head dim, so under tp the
            # delta is a local partial the psum below completes —
            # exact by linearity
            a, b, s = lora
            u = jnp.einsum("thd,thdr->tr", o, a.astype(o.dtype))
            y = y + jnp.einsum("tr,tre->te", u, b.astype(o.dtype)) \
                * s.astype(o.dtype)[:, None]
        if psum_axis is not None:
            # head-row-parallel wo: each device contracted its H/t
            # heads; the all-reduce completes the sum (Megatron)
            y = jax.lax.psum(y, psum_axis)
        if "bo" in p:
            y = y + p["bo"].astype(y.dtype)
        return x + y

    def ffn(self, params, i, x, live=None, psum_axis=None, lora=None):
        """-> (x after the feed-forward and its residual, None: no
        expert counts). One scope, `ffn`."""
        with jax.named_scope("ffn"):
            return self._ffn(params, i, x, psum_axis, lora), None

    def _ffn(self, params, i, x, psum_axis, lora):
        h = _ln(params[f"layer{i}_ln2"], x, self.ln_eps) \
            if self.layer_norm else x
        if lora is None:
            h = _dense(params[f"layer{i}_ff1"], h, activation="relu")
            h = _dense(params[f"layer{i}_ff2"], h, psum_axis=psum_axis)
            return x + h
        # adapted FFN: ff1's delta lands PRE-activation (the merged
        # reference folds A@B into the kernel, which relu then sees)
        # and ff2's delta is a pre-psum local partial like wo's
        a1, b1, a2, b2, s = lora
        s = s.astype(h.dtype)
        p1 = params[f"layer{i}_ff1"]
        z = jnp.dot(h, p1["kernel"].astype(h.dtype),
                    preferred_element_type=jnp.float32).astype(h.dtype)
        u1 = jnp.einsum("te,ter->tr", h, a1.astype(h.dtype))
        z = z + jnp.einsum("tr,trf->tf", u1, b1.astype(h.dtype)) \
            * s[:, None]
        if "bias" in p1:
            z = z + p1["bias"].astype(z.dtype)
        h2 = jax.nn.relu(z)
        p2 = params[f"layer{i}_ff2"]
        y = jnp.dot(h2, p2["kernel"].astype(h2.dtype),
                    preferred_element_type=jnp.float32).astype(h2.dtype)
        u2 = jnp.einsum("tf,tfr->tr", h2, a2.astype(h2.dtype))
        y = y + jnp.einsum("tr,tre->te", u2, b2.astype(h2.dtype)) \
            * s[:, None]
        if psum_axis is not None:
            y = jax.lax.psum(y, psum_axis)
        if "bias" in p2:
            y = y + p2["bias"].astype(y.dtype)
        return x + y

    def final_norm(self, params, x):
        return _ln(params["final_ln"], x, self.ln_eps) \
            if self.layer_norm else x

    def head(self, params, x):
        return _dense(params["lm_head"], self.final_norm(params, x))


class OLMoE:
    """The build_olmoe_lm block (models/olmoe.py holds the equations).
    Served by the mixed step on one device; what it does not get yet
    raises in `refuse`."""

    kind = "olmoe"

    def __init__(self, model, ops):
        self.vocab_size = ops["tok_embed"].num_entries
        self.layer_norm = True      # a norm before each half, and a last
        self.num_layers = _count_layers(ops)
        attn0, moe0 = ops["layer0_attn"], ops["layer0_moe"]
        # rotary has no table: the positions served are the graph's own
        self.max_positions = int(attn0.inputs[3].shape[1])
        if not (attn0.qk_norm and attn0.rotary_theta > 0
                and moe0.dropless):
            raise ValueError(
                "ServeEngine reads a build_olmoe_lm-shaped model: rotary "
                "attention with QK-norm and a dropless gated MoEFFN")
        self.num_heads = attn0.num_heads
        self.head_dim = attn0.head_dim
        self.hidden = attn0.embed_dim
        self.rope_theta = attn0.rotary_theta
        self.ln_eps = ops["layer0_norm1"].eps
        self.act_dtype = jnp.dtype(ops["tok_embed"].out_dtype)
        self.experts = moe0.num_experts
        self.experts_per_token = moe0.k
        self.norm_topk = moe0.norm_topk
        self.activation = moe0.activation
        self.ff_dim = moe0.hidden_dim
        w = model.state.params["layer0_moe"]["wg"]
        # what one expert's three matrices weigh as they are resident:
        # the bytes the expert phase reads for every expert it touches
        self.expert_bytes = int(3 * self.hidden * self.ff_dim
                                * w.dtype.itemsize)

    def refuse(self, *, tp: int, adapters: bool) -> None:
        if tp > 1:
            raise NotImplementedError(
                "OLMoE serving is single-device: tensor-parallel serving "
                f"(tp={tp}) would have to split the experts and the "
                "QK-norm's statistics across devices, which is not built")
        if adapters:
            raise NotImplementedError(
                "OLMoE serving has no adapter pool: adapter_rank > 0 "
                "adapts the dense feed-forward, which this model lacks")

    def embed(self, params, tokens, positions):
        return jnp.take(params["tok_embed"]["kernel"], tokens, axis=0,
                        mode="clip").astype(self.act_dtype)

    def norm1(self, params, i, x):
        return rms_norm(x, params[f"layer{i}_norm1"]["scale"], self.ln_eps)

    def qkv(self, params, i, h, positions, lora=None):
        """h (..., E), positions (...) -> q, k, v (..., H, D): the
        projections, the RMS norm of q and k over the whole projection,
        rotary at the lanes' absolute positions."""
        p = params[f"layer{i}_attn"]
        q, k, v = _project(p, h)
        q = rotary(rms_norm(q, p["q_norm"], self.ln_eps), positions,
                   self.rope_theta)
        k = rotary(rms_norm(k, p["k_norm"], self.ln_eps), positions,
                   self.rope_theta)
        return q, k, v

    def attn_out(self, params, i, o, x, psum_axis=None, lora=None):
        p = params[f"layer{i}_attn"]
        return x + jnp.einsum("...hd,hde->...e", o,
                              p["wo"].astype(o.dtype))

    def ffn(self, params, i, x, live=None, psum_axis=None, lora=None):
        """The expert layer, four scopes: `router` (the norm, f32
        softmax and top-k), `moe_dispatch` (slots sorted by expert),
        `experts` (the grouped matmuls), `moe_combine`. `live` (T,)
        bool: lanes that are not live route nowhere, so the expert work
        follows the live lanes. -> (x, (E,) int32 live slots per
        expert)."""
        m = params[f"layer{i}_moe"]
        scope = jax.named_scope
        with scope("router"):
            h = rms_norm(x, params[f"layer{i}_norm2"]["scale"],
                         self.ln_eps).reshape(-1, self.hidden)
            _, gate_vals, assign = route_top_k(
                h, m["gate"], self.experts_per_token, self.norm_topk)
        with scope("moe_dispatch"):
            rows, order, counts = dropless_dispatch(
                h, assign, self.experts, live)
        with scope("experts"):
            ys = grouped_ffn(rows, counts, m["wg"], m["wu"], m["wd"],
                             self.activation)
        with scope("moe_combine"):
            y = dropless_combine(ys, order, gate_vals)
            return x + y.astype(x.dtype).reshape(x.shape), counts

    def final_norm(self, params, x):
        return rms_norm(x, params["final_norm"]["scale"], self.ln_eps)

    def head(self, params, x):
        return _dense(params["lm_head"], self.final_norm(params, x))


def describe(model):
    """The description of a compiled FFModel, chosen by the op names
    its builder wrote."""
    ops = {op.name: op for op in model.ops}
    if "tok_embed" in ops and "lm_head" in ops:
        if "pos_embed" in ops:
            return TransformerLM(model, ops)
        if "layer0_moe" in ops and "final_norm" in ops:
            return OLMoE(model, ops)
    missing = [n for n in ("tok_embed", "lm_head", "pos_embed")
               if n not in ops]
    raise ValueError(
        f"ServeEngine reads build_transformer_lm- and build_olmoe_lm-"
        f"shaped models; this one is neither (missing ops: {missing})")
