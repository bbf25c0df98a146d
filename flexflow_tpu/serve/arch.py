"""What the serve engine asks of the model it serves.

The engine (serve/engine.py) owns lanes, pages, the head's top-k and
every program, and serve/mixers.py owns what each layer's MIXER KIND
does in the step (its body around the paged kernel or the scans, its
grid bound, its counts); the MODEL is a description they ask: how a
token is embedded, a layer's norm and projections at the lanes'
positions, its output projection, its feed-forward, the head — and what
it cannot do, which raises at engine build (no silent fallback). A
description reads the parameters of a compiled FFModel through the op
names its builder wrote, and mirrors those ops' numerics. This module
imports neither the engine nor the scheduler.

Nine clients: `TransformerLM` (models/transformer.build_transformer_lm:
learned positions, LayerNorm, ReLU feed-forward — the OPT block),
`OLMoE` (models/olmoe.build_olmoe_lm: RMSNorm, rotary attention with
QK-norm, dropless top-k SwiGLU experts), `Phi4Flash`
(models/phi4flash.build_phi4flash_lm: state-space, window, full, gated
memory and cross layers; no positions) and `CommandAPlus`
(models/cmdaplus.build_cmdaplus_lm: a parallel block of grouped window
or full attention beside sigmoid-routed experts, of which this chip
holds a share, and averaged shared experts) and `MiniCPMSala`
(models/minicpm_sala.build_minicpm_sala_lm: block-sparse attention over
a learned selection of the context in some layers, lightning linear
attention with a matrix state a sequence in the others) and `Qwen3Next`
(models/qwen3_next.build_qwen3_next_lm: the gated delta rule with a
matrix state and a convolution tail a sequence in three layers of four,
gated softmax attention in the fourth, top-k experts of which this chip
holds a share beside a gated shared expert) and `OlmoHybrid`
(models/olmo_hybrid.build_olmo_hybrid_lm: the delta rule with negative
eigenvalues in three layers of four, plain multi-head attention with
QK-norm and no rotation in the fourth, a dense gated feed-forward, every
norm AFTER its sub-layer) and `FalconH1`
(models/falcon_h1.build_falcon_h1_lm: Mamba-2 heads with a matrix state
and a convolution tail a sequence BESIDE grouped rotary attention on
pages in every layer, both read from the layer's one norm, a dense gated
feed-forward, scalar multipliers on the activations) and `LFM2MoE`
(models/lfm2_moe.build_lfm2_moe_lm: gated short convolutions whose whole
cache is a convolution tail a sequence in three layers of four, grouped
rotary attention with per-HEAD QK-norm in the fourth, leading dense
layers and then sigmoid-routed experts chosen under a selection bias,
the token table as the head).

What a description answers (docs/serving.md "What a description must
answer"): the dimensions; per layer the MIXER KIND (`mixer(i)`: one of
the eleven names serve/mixers.py has a body for — "attn",
models/phi4flash's five, models/minicpm_sala's two, models/qwen3_next's
one, models/falcon_h1's one, which runs TWO sequence mixers,
models/lfm2_moe's one, which holds a tail and no state) and the
projections that kind's body calls; the geometry of the K/V it
pages (`kv_heads`, `kv_head_dim`, `paged_layers`, `attn_scale`); what a
sequence holds besides pages (`hybrid_spec`, a serve/kv_cache.HybridSpec
or None); and `refuse`, which raises BY NAME for every engine path the
model is not served on.
"""

from __future__ import annotations

import math
import jax
import jax.numpy as jnp

from ..models.falcon_h1 import SSD_ATTN
from ..models.lfm2_moe import CONV
from ..models.minicpm_sala import LINEAR, SPARSE
from ..models.phi4flash import CROSS, FULL, GMU, SSM, WINDOW
from ..models.qwen3_next import DELTA
from ..ops import diff_attention as DA
from ..ops import gated_attention as GA
from ..ops import gated_delta as GD
from ..ops import linear_attention as LA
from ..ops import sparse_attention as SA
from ..ops import ssd as SD
from ..ops import ssm as S
from ..ops.common import rms_norm, rotary
from ..ops.gated import gated_ffn, gated_memory
from ..ops.moe import (dropless_combine, dropless_dispatch, expert_impl,
                       grouped_ffn, route_top_k, shared_ffn, shared_scale)

ATTN = "attn"       # the mixer kind of every layer of the plain decoders


def _ln(p, x, eps):
    """LayerNorm with f32 statistics — must mirror ops/elementwise.py
    LayerNorm.forward exactly (the reference-parity contract)."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32)
    if "bias" in p:
        y = y + p["bias"].astype(jnp.float32)
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


def _graph_logits(model, params, tokens, positions: bool):
    """(1, S) tokens -> (S, V): the op graph's own full-sequence
    forward (no cache, no kernel), the engine's naive oracle;
    `positions`: the graph takes the tokens' positions as an input."""
    inputs = {"tokens": tokens}
    if positions:
        inputs["positions"] = jnp.arange(
            tokens.shape[1], dtype=jnp.int32)[None, :]
    values, _ = model.executor.forward_values(
        params, {}, inputs, training=False, rng=None)
    return values[model.ops[-1].outputs[0].uid][0]


def _held_expert_layer(arch, m, h, live):
    """The expert layer of which this chip holds a share, up to its
    combine, over `h` (N, E), four scopes: `router` (f32 scores over all
    the experts, top-k, renormalised), `moe_dispatch` (slots sorted by
    held expert; a slot of an absent expert routes nowhere, as a dead
    lane's), `experts` (the gated expert over the held experts),
    `shared_experts` (plain matmuls; times sigmoid(h w_sg) where the
    layer has the gate `sgate`). -> (ys in expert order, order, the k
    weights a token, the shared term or 0.0, (held + 1,) int32: live
    slots per held expert, then the live slots whose expert is
    absent)."""
    scope = jax.named_scope
    k = arch.experts_per_token
    with scope("router"):
        _, gate_vals, assign = route_top_k(
            h, m["gate"], k, arch.norm_topk, arch.score)
    with scope("moe_dispatch"):
        rows, order, counts = dropless_dispatch(
            h, assign, arch.experts, live, arch.experts_held)
        slots = k * (h.shape[0] if live is None
                     else jnp.sum(live, dtype=jnp.int32))
        counts = jnp.concatenate(
            [counts, (slots - jnp.sum(counts))[None]])
    with scope("experts"):
        ys = grouped_ffn(rows, counts[:-1], m["wg"], m["wu"], m["wd"],
                         arch.activation, **arch.kernels)
    with scope("shared_experts"):
        f = shared_ffn(h, m["sg"], m["su"], m["sd"], arch.activation,
                       arch.shared_experts) \
            if arch.shared_experts else 0.0
        if "sgate" in m:
            f = f * shared_scale(h, m["sgate"])
    return ys, order, gate_vals, f, counts


class Description:
    """What every description answers the same way unless it says
    otherwise: one attention layer a layer, a key/value head a query
    head, every layer paged, nothing held besides pages, no expert
    layer, every engine path served."""

    kind = "?"
    builder = "?"               # the builder whose op names it reads
    reads = ()                  # the op names `describe` knows it by
    experts = 0                 # no expert layer: the step counts none
    experts_per_token = 0
    # (first, count): the experts whose weights live HERE, one share of
    # an expert-parallel layer (None: all of them). The step's counts
    # are then over the held experts, and one more: the live slots
    # whose expert is absent (read by ServeSession._count_experts)
    experts_held = None
    # the WINDOW layers' window (0: none): mixers.geometry bounds their
    # grid by it, their body and step_counts walk the rings under it
    window = 0
    # width of the selector's row a page (serve/kv_cache.KVPool.kc); 0:
    # the model selects nothing and the pool has no such leaf (read at
    # engine build, for KVCacheConfig)
    selector_dim = 0
    # a model that SELECTS its context: the positions under which a
    # lane attends every key before it, through the paged kernel (0: no
    # selection, every lane does). mixers.walked is the one reader
    dense_len = 0
    # differential attention: the paged call's output goes through
    # `diff_norm` before `attn_out` (the attention body of mixers.py)
    differential = False
    # an output GATE born in the query projection: `qkv` returns it
    # fourth, and the paged call's output goes through `attn_gate`
    # before `attn_out` (the attention body of mixers.py)
    output_gate = False
    # a PARALLEL block: one norm a layer, `attn_out` and `ffn` (which is
    # handed `h`, that norm's output) return their BRANCH alone and the
    # step adds x + (a + f) once (ServeEngine._mixed_layer). False: each
    # returns x + its branch
    parallel_block = False
    # a POST-NORM block: no norm before a sub-layer (`norm1` hands x
    # on), `attn_out` / the mixer's own output projection and `ffn`
    # (which reads x itself) return their BRANCH alone, and the step
    # adds x + branch_norm(branch) after each, under `post_norm`
    # (ServeEngine._mixed_layer). False: each returns x + its branch
    post_norm = False
    # (params, (1, S) tokens) -> (S, V): a full-sequence forward to use
    # as the engine's naive oracle in place of its own attention-only
    # one (None: the engine's)
    forward_logits = None
    # engine path -> why this model is not served on it (`refuse`)
    refused = {}
    # the engine's resolved kernel choice (use_pallas, interpret), which
    # it sets once: what a description's own kernels run under
    kernels = {}

    def mixer(self, i: int) -> str:
        return ATTN

    def expert_impl(self, lanes: int):
        """Which implementation the expert layer of a step of `lanes`
        lanes runs (ops/moe.py::expert_impl); None: no expert layer."""
        return None

    def hybrid_spec(self, chunk: int):
        """What a sequence holds besides pages, as a
        kv_cache.HybridSpec (`chunk`: the most tokens of one sequence a
        step writes); None: pages alone."""
        return None

    @property
    def kv_heads(self) -> int:
        return self.num_heads

    @property
    def kv_head_dim(self) -> int:
        return self.head_dim

    @property
    def paged_layers(self) -> int:
        return self.num_layers

    @property
    def attn_scale(self) -> float:
        return 1.0 / math.sqrt(self.head_dim)

    def refuse(self, *, tp: int = 1, adapters: bool = False,
               speculation: bool = False, prefix_cache: bool = False,
               host_tier: bool = False, handoff: bool = False) -> None:
        """Raise, by name, for an engine path this model is not served
        on. One signature for every description: the engine passes
        every path it is about to arm."""
        asked = {"tp": tp > 1, "adapters": adapters,
                 "speculation": speculation, "prefix_cache": prefix_cache,
                 "host_tier": host_tier, "handoff": handoff}
        for path, on in asked.items():
            if on and path in self.refused:
                raise NotImplementedError(
                    f"{self.kind} serving refuses {path}"
                    + (f" (tp={tp})" if path == "tp" else "")
                    + f": {self.refused[path]}")


class TransformerLM(Description):
    """The build_transformer_lm block: token + learned-position
    embeddings, pre-LN causal attention, ReLU feed-forward, final LN,
    untied head. Serves every engine path."""

    kind = "transformer_lm"
    builder = "build_transformer_lm"
    reads = ("tok_embed", "lm_head", "pos_embed")

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


class OLMoE(Description):
    """The build_olmoe_lm block (models/olmoe.py holds the equations).
    Served by the mixed step on one device; what it does not get yet
    raises in `refuse`."""

    kind = "olmoe"
    builder = "build_olmoe_lm"
    reads = ("tok_embed", "lm_head", "layer0_moe", "final_norm")
    refused = {
        "tp": "single-device: tensor-parallel serving would have to "
              "split the experts and the QK-norm's statistics across "
              "devices, which is not built",
        "adapters": "no adapter pool: adapter_rank > 0 adapts the dense "
                    "feed-forward, which this model lacks",
    }

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
        self._expert_weights = jax.ShapeDtypeStruct(w.shape, w.dtype)

    def expert_impl(self, lanes: int):
        rows = jax.ShapeDtypeStruct(
            (lanes * self.experts_per_token, self.hidden), self.act_dtype)
        return expert_impl(rows, self._expert_weights, **self.kernels)

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
        `experts` (the gated expert: one kernel or three grouped
        matmuls, `expert_impl`), `moe_combine`. `live` (T,)
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
                             self.activation, **self.kernels)
        with scope("moe_combine"):
            y = dropless_combine(ys, order, gate_vals)
            return x + y.astype(x.dtype).reshape(x.shape), counts

    def final_norm(self, params, x):
        return rms_norm(x, params["final_norm"]["scale"], self.ln_eps)

    def head(self, params, x):
        return _dense(params["lm_head"], self.final_norm(params, x))


class Phi4Flash(Description):
    """The build_phi4flash_lm block (models/phi4flash.py holds the
    layer pattern, ops/ssm.py, ops/diff_attention.py and ops/gated.py
    the equations). Served by the mixed step on one device.

    What it pages: ONE layer's K and V (the full layer's; every cross
    layer reads them), as `kv_heads` = Hk / 2 heads of 2 D (the grouped
    identity of ops/diff_attention.py). What a sequence holds besides
    (`hybrid`): a ring of the window layers' last keys, and a scan
    state and a convolution tail for each state-space layer."""

    kind = "phi4flash"
    builder = "build_phi4flash_lm"
    reads = ("tok_embed", "lm_head", "layer0_ssm", "final_ln")
    differential = True
    _state = ("a sequence's scan state and window keys live in its "
              "slot, not in pages: ")
    refused = {
        "tp": "single-device: the scan state and the paired heads are "
              "not split over a mesh",
        "adapters": "no adapter pool for the gated units",
        "speculation": _state + "rolling back rejected tokens would "
                       "need a snapshot of the state (serve_spec_decode "
                       "must be off)",
        "prefix_cache": _state + "a prefix hit would need the state at "
                        "the prefix's end (serve_prefix_cache must be "
                        "off)",
        "host_tier": _state + "the host tier spills pages only",
        "handoff": _state + "the disaggregated handoff ships pages only",
    }

    def __init__(self, model, ops):
        self.model = model
        self.vocab_size = ops["tok_embed"].num_entries
        self.layer_norm = True
        n = 0
        while f"layer{n}_ln1" in ops:
            n += 1
        self.num_layers = n
        self.kinds = []
        for i in range(n):
            a = ops.get(f"layer{i}_attn")
            if f"layer{i}_ssm" in ops:
                self.kinds.append(SSM)
            elif f"layer{i}_gmu" in ops:
                self.kinds.append(GMU)
            elif a is None:
                raise ValueError(f"layer {i} has no mixer this "
                                 f"description reads")
            else:
                self.kinds.append(CROSS if a.kv_from else FULL
                                  if a.emit_kv else WINDOW)
        if self.kinds.count(FULL) != 1:
            raise ValueError("ServeEngine reads a build_phi4flash_lm-"
                             "shaped model: ONE full attention layer")
        self.full = self.kinds.index(FULL)
        self.full_layers = [self.full]
        self.ssm_layers = [i for i, k in enumerate(self.kinds) if k == SSM]
        self.window_layers = [i for i, k in enumerate(self.kinds)
                              if k == WINDOW]
        self.memory_layer = self.ssm_layers[-1]
        attn = ops[f"layer{self.full}_attn"]
        self.num_heads = attn.num_heads
        self.head_dim = attn.head_dim
        self._kv_heads = attn.num_kv_heads // 2
        self.window = ops[f"layer{self.window_layers[0]}_attn"].window
        self.hidden = attn.embed_dim
        self.ln_eps = ops["layer0_ln1"].eps
        self.sub_eps = attn.eps
        self.lam0 = {i: ops[f"layer{i}_attn"].lam0 for i in range(n)
                     if f"layer{i}_attn" in ops}
        self.act_dtype = jnp.dtype(ops["tok_embed"].out_dtype)
        self.ff_dim = ops["layer0_ffn"].hidden_dim
        ssm = ops["layer0_ssm"]
        self.d_inner, self.d_state = ssm.d_inner, ssm.d_state
        self.d_conv, self.dt_rank = ssm.d_conv, ssm.dt_rank
        # no positional table: the positions served are the graph's own
        self.max_positions = int(ops["tok_embed"].inputs[0].shape[1])

    def mixer(self, i: int) -> str:
        return self.kinds[i]

    def hybrid_spec(self, chunk: int):
        from .kv_cache import HybridSpec
        return HybridSpec(
            window_layers=len(self.window_layers), window=self.window,
            chunk=int(chunk), state_layers=len(self.ssm_layers),
            state_shape=(self.d_state, self.d_inner),
            tail_shape=(self.d_conv - 1, self.d_inner),
            tail_dtype=str(self.act_dtype))

    @property
    def kv_heads(self) -> int:
        return self._kv_heads

    @property
    def kv_head_dim(self) -> int:
        return 2 * self.head_dim

    @property
    def paged_layers(self) -> int:
        return 1

    def embed(self, params, tokens, positions):
        return jnp.take(params["tok_embed"]["kernel"], tokens, axis=0,
                        mode="clip").astype(self.act_dtype)

    def norm1(self, params, i, x):
        return _ln(params[f"layer{i}_ln1"], x, self.ln_eps)

    def qkv(self, params, i, h, positions, lora=None):
        """h (T, E) -> the grouped identity's q (T, H, 2D), k, v (T,
        Hk/2, 2D); a cross layer has q alone (k, v None). Positions are
        not read: the model has no positional encoding."""
        p = params[f"layer{i}_attn"]
        q = DA.project(p, h, "q")
        if self.kinds[i] == CROSS:
            return DA.grouped_qkv(q, None, None)
        return DA.grouped_qkv(q, DA.project(p, h, "k"),
                              DA.project(p, h, "v"))

    def diff_norm(self, params, i, o):
        """The grouped call's output (T, H, 2D) -> RMSNorm(A1 - lam A2)
        (1 - lam0), (T, H/2, 2D)."""
        a1, a2 = DA.split_grouped(o)
        return DA.diff_combine(params[f"layer{i}_attn"], a1, a2,
                               self.lam0[i], self.sub_eps)

    def attn_out(self, params, i, o, x, psum_axis=None, lora=None):
        p = params[f"layer{i}_attn"]
        y = jnp.einsum("...hd,hde->...e", o, p["wo"].astype(o.dtype))
        return x + y + p["bo"].astype(y.dtype)

    # the state-space layer, in the pieces the step scopes apart
    def ssm_in(self, params, i, h):
        """-> (u, z) (T, d_inner), the raw input projection."""
        p = params[f"layer{i}_ssm"]
        uz = jnp.dot(h, p["w_in"].astype(h.dtype),
                     preferred_element_type=jnp.float32).astype(h.dtype)
        return jnp.split(uz, 2, axis=-1)

    def ssm_scan_inputs(self, params, i, u):
        return S.scan_inputs(params[f"layer{i}_ssm"], u, self.d_state,
                             self.dt_rank)

    def ssm_out(self, params, i, g, x):
        p = params[f"layer{i}_ssm"]
        return x + jnp.dot(g, p["w_out"].astype(g.dtype),
                           preferred_element_type=jnp.float32
                           ).astype(x.dtype)

    def gmu(self, params, i, h, memory, x):
        return x + gated_memory(params[f"layer{i}_gmu"], h, memory)

    def ffn(self, params, i, x, live=None, psum_axis=None, lora=None):
        with jax.named_scope("ffn"):
            h = _ln(params[f"layer{i}_ln2"], x, self.ln_eps)
            return x + gated_ffn(params[f"layer{i}_ffn"], h), None

    def final_norm(self, params, x):
        return _ln(params["final_ln"], x, self.ln_eps)

    def head(self, params, x):
        """Tied: the token table is the head."""
        h = self.final_norm(params, x)
        table = params["tok_embed"]["kernel"].astype(h.dtype)
        return jnp.dot(h, table.T,
                       preferred_element_type=jnp.float32).astype(h.dtype)

    def forward_logits(self, params, tokens):
        return _graph_logits(self.model, params, tokens, positions=False)


class CommandAPlus(Description):
    """The build_cmdaplus_lm block (models/cmdaplus.py holds the
    equations): a parallel block, window layers on rings beside full
    layers on pages (no state), grouped key/value heads, and an expert
    layer of which this chip may hold a share (`experts_held`). Served
    by the mixed step on one device."""

    kind = "command_a_plus"
    builder = "build_cmdaplus_lm"
    reads = ("tok_embed", "lm_head", "layer0_ln", "layer0_moe", "final_ln")
    parallel_block = True
    _ring = ("a sequence's window keys live in its slot's ring, not in "
             "pages: ")
    refused = {
        "tp": "single-device: the held experts, the grouped heads and "
              "the rings are not split over a mesh (the layer's exchange "
              "between shares is not built: ROADMAP M1)",
        "adapters": "no adapter pool for the expert layer",
        "speculation": _ring + "rolling back rejected tokens would need "
                       "the ring as it was (serve_spec_decode must be off)",
        "prefix_cache": _ring + "a prefix hit would need the window "
                        "layers' keys at the prefix's end "
                        "(serve_prefix_cache must be off)",
        "host_tier": _ring + "the host tier spills pages only",
        "handoff": _ring + "the disaggregated handoff ships pages only",
    }

    def __init__(self, model, ops):
        self.model = model
        self.vocab_size = ops["tok_embed"].num_entries
        self.layer_norm = True
        n = 0
        while f"layer{n}_ln" in ops:
            n += 1
        self.num_layers = n
        attns = [ops[f"layer{i}_attn"] for i in range(n)]
        moe0 = ops["layer0_moe"]
        self.kinds = [WINDOW if a.window else FULL for a in attns]
        self.window_layers = [i for i, k in enumerate(self.kinds)
                              if k == WINDOW]
        self.full_layers = [i for i, k in enumerate(self.kinds)
                            if k == FULL]
        if not (self.window_layers and self.full_layers and moe0.dropless
                and all(a.causal and not a.qk_norm for a in attns)):
            raise ValueError(
                "ServeEngine reads a build_cmdaplus_lm-shaped model: "
                "window AND full causal attention layers, a dropless "
                "MoEFFN")
        a0 = attns[self.window_layers[0]]
        self.window = a0.window
        self.num_heads, self._kv_heads = a0.num_heads, a0.num_kv_heads
        self.head_dim = a0.head_dim
        self.hidden = a0.embed_dim
        # rotary by layer: (theta, interleaved), theta 0 = no rotation
        self.rope = [(a.rotary_theta, a.rotary_interleaved) for a in attns]
        self.ln_eps = ops["layer0_ln"].eps
        self.logit_scale = ops["lm_head"].scale
        self.act_dtype = jnp.dtype(ops["tok_embed"].out_dtype)
        # rotary has no table: the positions served are the graph's own
        self.max_positions = int(ops["tok_embed"].inputs[0].shape[1])
        self.experts = moe0.num_experts
        self.experts_per_token = moe0.k
        self.experts_held = moe0.experts_held or (0, moe0.num_experts)
        self.shared_experts = moe0.shared_experts
        self.norm_topk, self.score = moe0.norm_topk, moe0.score
        self.activation = moe0.activation
        self.ff_dim = moe0.hidden_dim
        w = model.state.params["layer0_moe"]["wg"]
        # one expert's three matrices as they are resident, and what a
        # step reads of the shared experts: all of them, every layer
        self.expert_bytes = int(3 * self.hidden * self.ff_dim
                                * w.dtype.itemsize)
        self.shared_bytes = n * self.shared_experts * self.expert_bytes
        self._expert_weights = jax.ShapeDtypeStruct(w.shape, w.dtype)

    def mixer(self, i: int) -> str:
        return self.kinds[i]

    def expert_impl(self, lanes: int):
        rows = jax.ShapeDtypeStruct(
            (lanes * self.experts_per_token, self.hidden), self.act_dtype)
        return expert_impl(rows, self._expert_weights, **self.kernels)

    def hybrid_spec(self, chunk: int):
        from .kv_cache import HybridSpec
        return HybridSpec(window_layers=len(self.window_layers),
                          window=self.window, chunk=int(chunk))

    @property
    def kv_heads(self) -> int:
        return self._kv_heads

    @property
    def paged_layers(self) -> int:
        return len(self.full_layers)

    def embed(self, params, tokens, positions):
        return jnp.take(params["tok_embed"]["kernel"], tokens, axis=0,
                        mode="clip").astype(self.act_dtype)

    def norm1(self, params, i, x):
        return _ln(params[f"layer{i}_ln"], x, self.ln_eps)

    def qkv(self, params, i, h, positions, lora=None):
        """h (T, E), positions (T,) -> q (T, H, D), k, v (T, Hk, D): a
        window layer's q and k rotated at the lanes' absolute
        positions, a full layer's as they are (no position signal)."""
        q, k, v = _project(params[f"layer{i}_attn"], h)
        theta, interleaved = self.rope[i]
        if theta > 0:
            q = rotary(q, positions, theta, interleaved)
            k = rotary(k, positions, theta, interleaved)
        return q, k, v

    def attn_out(self, params, i, o, x, psum_axis=None, lora=None):
        """The attention BRANCH alone (parallel_block)."""
        p = params[f"layer{i}_attn"]
        return jnp.einsum("...hd,hde->...e", o, p["wo"].astype(o.dtype))

    def ffn(self, params, i, x, h=None, live=None, psum_axis=None,
            lora=None):
        """The expert BRANCH alone, of `h` (the layer's one norm), five
        scopes: `router` (f32 sigmoid, top-k, renormalised), `moe_dispatch`
        (slots sorted by held expert; a slot of an absent expert routes
        nowhere, as a dead lane's), `experts` (the gated expert over the
        held experts), `shared_experts` (plain matmuls), `moe_combine`.
        -> (f, (held + 1,) int32: live slots per held expert, then the
        live slots whose expert is absent)."""
        ys, order, gate_vals, f, counts = _held_expert_layer(
            self, params[f"layer{i}_moe"], h.reshape(-1, self.hidden), live)
        with jax.named_scope("moe_combine"):
            f = f + dropless_combine(ys, order, gate_vals)
            return f.astype(x.dtype).reshape(x.shape), counts

    def final_norm(self, params, x):
        return _ln(params["final_ln"], x, self.ln_eps)

    def head(self, params, x):
        """Tied: the token table (this chip's slice of it) is the head,
        times `logit_scale`."""
        h = self.final_norm(params, x)
        table = params["tok_embed"]["kernel"].astype(h.dtype)
        y = jnp.dot(h, table.T, preferred_element_type=jnp.float32)
        if self.logit_scale != 1.0:
            y = y * self.logit_scale
        return y.astype(h.dtype)

    def forward_logits(self, params, tokens):
        return _graph_logits(self.model, params, tokens, positions=True)


class MiniCPMSala(Description):
    """The build_minicpm_sala_lm block (models/minicpm_sala.py holds the
    equations, ops/sparse_attention.py and ops/linear_attention.py the
    mixers'). Served by the mixed step on one device.

    What it pages: the SPARSE layers' K and V (`kv_heads` grouped
    key/value heads) and, a row a page a head, the selector's compressed
    keys (`selector_dim`). How the pool lays a head's pages out is the
    pool's (serve/kv_cache.KVCacheConfig.split_heads).
    What a sequence holds besides (`hybrid_spec`): a (D, H * D) f32
    matrix state for each LINEAR layer; no ring, no tail."""

    kind = "minicpm_sala"
    builder = "build_minicpm_sala_lm"
    reads = ("tok_embed", "lm_head", "embed_scale", "head_scale",
             "final_norm")
    _state = ("a sequence's matrix states live in its slot, not in "
              "pages: ")
    refused = {
        "tp": "single-device: the matrix states, the grouped heads and "
              "the selection are not split over a mesh",
        "adapters": "no adapter pool for the gated mixers",
        "speculation": _state + "rolling back rejected tokens would "
                       "need a snapshot of the state (serve_spec_decode "
                       "must be off)",
        "prefix_cache": _state + "a prefix hit would need the state at "
                        "the prefix's end (serve_prefix_cache must be "
                        "off)",
        "host_tier": _state + "the host tier spills pages only",
        "handoff": _state + "the disaggregated handoff ships pages only",
    }

    def __init__(self, model, ops):
        self.model = model
        self.vocab_size = ops["tok_embed"].num_entries
        self.layer_norm = True
        n = 0
        while f"layer{n}_norm1" in ops:
            n += 1
        self.num_layers = n
        self.kinds = [SPARSE if f"layer{i}_sparse" in ops else LINEAR
                      for i in range(n)]
        self.sparse_layers = [i for i, k in enumerate(self.kinds)
                              if k == SPARSE]
        self.linear_layers = [i for i, k in enumerate(self.kinds)
                              if k == LINEAR]
        if not (self.sparse_layers and self.linear_layers and all(
                f"layer{i}_linear" in ops for i in self.linear_layers)):
            raise ValueError(
                "ServeEngine reads a build_minicpm_sala_lm-shaped model: "
                "sparse AND linear attention layers")
        sp = ops[f"layer{self.sparse_layers[0]}_sparse"]
        lin = ops[f"layer{self.linear_layers[0]}_linear"]
        self.num_heads, self._kv_heads = sp.num_heads, sp.num_kv_heads
        self.head_dim = sp.head_dim
        self.sparse = sp.sparse
        self.dense_len = sp.sparse.dense_len
        self.selector_dim = sp.head_dim
        self.linear_heads, self.linear_head_dim = lin.num_heads, lin.head_dim
        self.rope_theta = lin.rotary_theta
        # a kept layer's decays are those of its PUBLISHED index
        self.decays = {i: ops[f"layer{i}_linear"].decay()
                       for i in self.linear_layers}
        self.hidden = sp.embed_dim
        self.ln_eps = ops["layer0_norm1"].eps
        self.scale_emb = ops["embed_scale"].scalar
        self.residual = ops["layer0_scale1"].scalar
        self.head_scale = ops["head_scale"].scalar
        self.act_dtype = jnp.dtype(ops["tok_embed"].out_dtype)
        self.ff_dim = ops["layer0_ffn"].hidden_dim
        # rotary has no table: the positions served are the graph's own
        self.max_positions = int(ops["tok_embed"].inputs[0].shape[1])

    def mixer(self, i: int) -> str:
        return self.kinds[i]

    def hybrid_spec(self, chunk: int):
        from .kv_cache import HybridSpec
        d = self.linear_head_dim
        return HybridSpec(
            window_layers=0, window=0, chunk=int(chunk),
            state_layers=len(self.linear_layers),
            state_shape=(d, self.linear_heads * d))

    @property
    def kv_heads(self) -> int:
        return self._kv_heads

    @property
    def paged_layers(self) -> int:
        return len(self.sparse_layers)

    def embed(self, params, tokens, positions):
        x = jnp.take(params["tok_embed"]["kernel"], tokens, axis=0,
                     mode="clip").astype(self.act_dtype)
        return x * self.scale_emb

    def norm1(self, params, i, x):
        return rms_norm(x, params[f"layer{i}_norm1"]["scale"], self.ln_eps)

    def sparse_qkv(self, params, i, h):
        """h (T, E) -> q (T, H, D), k, v (T, G, D), q and k normed; no
        position signal."""
        return SA.project_qkv(params[f"layer{i}_sparse"], h, self.ln_eps)

    def sparse_out(self, params, i, o, h, x):
        y = SA.gate_and_project(params[f"layer{i}_sparse"], o, h)
        return x + y * self.residual

    def linear_qkv(self, params, i, h, positions):
        """h (T, E), positions (T,) -> q, k (normed, rotated), v, each
        (T, H, D)."""
        return LA.project_qkv(params[f"layer{i}_linear"], h, positions,
                              self.rope_theta, self.ln_eps)

    def linear_out(self, params, i, o, h, x):
        """o (T, H, D) f32, the recurrence's output over sqrt(D)."""
        y = LA.gate_and_project(params[f"layer{i}_linear"], o, h,
                                self.ln_eps)
        return x + y * self.residual

    def ffn(self, params, i, x, live=None, psum_axis=None, lora=None):
        with jax.named_scope("ffn"):
            h = rms_norm(x, params[f"layer{i}_norm2"]["scale"], self.ln_eps)
            f = gated_ffn(params[f"layer{i}_ffn"], h)
            return x + f * self.residual, None

    def final_norm(self, params, x):
        return rms_norm(x, params["final_norm"]["scale"], self.ln_eps)

    def head(self, params, x):
        h = self.final_norm(params, x) * self.head_scale
        return _dense(params["lm_head"], h)

    def forward_logits(self, params, tokens):
        return _graph_logits(self.model, params, tokens, positions=True)


class _DeltaAndFull:
    """What the descriptions whose layers are DELTA or FULL answer
    alike (Qwen3Next, OlmoHybrid): the kinds, what a sequence holds
    besides pages, the paged layers, and the delta layer's pieces up to
    its output. They set `kinds`, `delta` (a delta layer's op),
    `delta_layers`, `full_layers`, `act_dtype`."""

    def mixer(self, i: int) -> str:
        return self.kinds[i]

    def hybrid_spec(self, chunk: int):
        from .kv_cache import HybridSpec
        d = self.delta
        return HybridSpec(
            window_layers=0, window=0, chunk=int(chunk),
            state_layers=len(self.delta_layers),
            state_shape=d.state_shape,
            tail_shape=(d.d_conv - 1, d.channels),
            tail_dtype=str(self.act_dtype))

    @property
    def paged_layers(self) -> int:
        return len(self.full_layers)

    def embed(self, params, tokens, positions):
        return jnp.take(params["tok_embed"]["kernel"], tokens, axis=0,
                        mode="clip").astype(self.act_dtype)

    # the delta layer, in the pieces the step scopes apart
    def delta_in(self, params, i, h):
        """-> (u (T, channels) the convolution's raw input, z (T, Hv,
        Dv), beta, g (T, Hv) f32), beta in (0, 2) where the layer
        allows negative eigenvalues."""
        p = params[f"layer{i}_delta"]
        u, z, b, a = GD.project(p, h, *self.delta.shape_args)
        return (u, z) + GD.gates(p, b, a, self.delta.beta_scale)

    def delta_heads(self, u):
        """The convolution's output after silu -> q, k, v by value
        head, f32."""
        return GD.split_heads(u, *self.delta.shape_args)


class Qwen3Next(_DeltaAndFull, Description):
    """The build_qwen3_next_lm block (models/qwen3_next.py holds the
    equations, ops/gated_delta.py and ops/gated_attention.py the
    mixers'). Served by the mixed step on one device.

    What it pages: the FULL layers' K and V (`kv_heads` grouped
    key/value heads of `head_dim`, 256 as published). What a sequence
    holds besides (`hybrid_spec`): for each DELTA layer a (Hv * Dk, Dv)
    f32 matrix state and a convolution tail of d_conv - 1 rows over the
    q, k and v channels; no ring. The expert layer is a share of an
    expert-parallel one where the builder says so (`experts_held`)."""

    kind = "qwen3_next"
    builder = "build_qwen3_next_lm"
    reads = ("tok_embed", "lm_head", "layer0_delta", "layer0_moe",
             "final_norm")
    output_gate = True
    score = "softmax"           # the router's, over all the experts
    _state = ("a sequence's matrix states and convolution tails live in "
              "its slot, not in pages: ")
    refused = {
        "tp": "single-device: the matrix states, the grouped heads and "
              "the held experts are not split over a mesh (the layer's "
              "exchange between shares is not built: ROADMAP M1)",
        "adapters": "no adapter pool for the gated mixers and the expert "
                    "layer",
        "speculation": _state + "rolling back rejected tokens would "
                       "need a snapshot of the state (serve_spec_decode "
                       "must be off)",
        "prefix_cache": _state + "a prefix hit would need the state at "
                        "the prefix's end (serve_prefix_cache must be "
                        "off)",
        "host_tier": _state + "the host tier spills pages only",
        "handoff": _state + "the disaggregated handoff ships pages only",
    }

    def __init__(self, model, ops):
        self.model = model
        self.vocab_size = ops["tok_embed"].num_entries
        self.layer_norm = True
        n = 0
        while f"layer{n}_norm1" in ops:
            n += 1
        self.num_layers = n
        self.kinds = [DELTA if f"layer{i}_delta" in ops else FULL
                      for i in range(n)]
        self.delta_layers = [i for i, k in enumerate(self.kinds)
                             if k == DELTA]
        self.full_layers = [i for i, k in enumerate(self.kinds)
                            if k == FULL]
        moe0 = ops["layer0_moe"]
        if not (self.full_layers and moe0.dropless and moe0.shared_gate
                and all(f"layer{i}_attn" in ops for i in self.full_layers)
                and all(ops[f"layer{i}_norm1"].zero_centered
                        for i in range(n))):
            raise ValueError(
                "ServeEngine reads a build_qwen3_next_lm-shaped model: "
                "delta AND gated attention layers under zero-centred "
                "norms, a dropless MoEFFN with a gated shared expert")
        attn = ops[f"layer{self.full_layers[0]}_attn"]
        self.delta = ops["layer0_delta"]
        self.num_heads, self._kv_heads = attn.num_heads, attn.num_kv_heads
        self.head_dim = attn.head_dim
        self.rope_theta, self.rotary_dim = attn.rotary_theta, attn.rotary_dim
        self.hidden = attn.embed_dim
        self.ln_eps = ops["layer0_norm1"].eps
        self.act_dtype = jnp.dtype(ops["tok_embed"].out_dtype)
        # rotary has no table: the positions served are the graph's own
        self.max_positions = int(ops["tok_embed"].inputs[0].shape[1])
        self.experts = moe0.num_experts
        self.experts_per_token = moe0.k
        self.experts_held = moe0.experts_held or (0, moe0.num_experts)
        self.shared_experts = moe0.shared_experts
        self.norm_topk, self.activation = moe0.norm_topk, moe0.activation
        self.ff_dim = moe0.hidden_dim
        w = model.state.params["layer0_moe"]["wg"]
        # one expert's three matrices as they are resident, and what a
        # step reads of the shared experts: all of them, every layer
        self.expert_bytes = int(3 * self.hidden * self.ff_dim
                                * w.dtype.itemsize)
        self.shared_bytes = n * self.shared_experts * self.expert_bytes
        self._expert_weights = jax.ShapeDtypeStruct(w.shape, w.dtype)

    def expert_impl(self, lanes: int):
        rows = jax.ShapeDtypeStruct(
            (lanes * self.experts_per_token, self.hidden), self.act_dtype)
        return expert_impl(rows, self._expert_weights, **self.kernels)

    @property
    def kv_heads(self) -> int:
        return self._kv_heads

    def norm1(self, params, i, x):
        return GA.rms_norm0(x, params[f"layer{i}_norm1"]["scale"],
                            self.ln_eps)

    def qkv(self, params, i, h, positions, lora=None):
        """h (T, E), positions (T,) -> q (T, H, D), k, v (T, Hk, D), q
        and k normed and rotated over their first `rotary_dim` dims, and
        the output gate (T, H, D)."""
        return GA.project_qkv(params[f"layer{i}_attn"], h, positions,
                              self.rope_theta, self.rotary_dim, self.ln_eps)

    attn_gate = staticmethod(GA.gate_output)

    def attn_out(self, params, i, o, x, psum_axis=None, lora=None):
        p = params[f"layer{i}_attn"]
        return x + jnp.einsum("...hd,hde->...e", o,
                              p["wo"].astype(o.dtype))

    def delta_out(self, params, i, o, z, x):
        return x + GD.gate_and_project(params[f"layer{i}_delta"], o, z,
                                       self.ln_eps)

    def ffn(self, params, i, x, live=None, psum_axis=None, lora=None):
        """The expert layer, five scopes as CommandAPlus.ffn's: `router`
        (the norm, f32 softmax over all the experts, top-k,
        renormalised), `moe_dispatch` (slots sorted by held expert; a
        slot of an absent expert routes nowhere), `experts`,
        `shared_experts` (plain matmuls, times sigmoid(h w_sg)),
        `moe_combine`. -> (x, (held + 1,) int32: live slots per held
        expert, then the live slots whose expert is absent)."""
        with jax.named_scope("router"):
            h = GA.rms_norm0(x, params[f"layer{i}_norm2"]["scale"],
                             self.ln_eps).reshape(-1, self.hidden)
        ys, order, gate_vals, f, counts = _held_expert_layer(
            self, params[f"layer{i}_moe"], h, live)
        with jax.named_scope("moe_combine"):
            f = f + dropless_combine(ys, order, gate_vals)
            return x + f.astype(x.dtype).reshape(x.shape), counts

    def final_norm(self, params, x):
        return GA.rms_norm0(x, params["final_norm"]["scale"], self.ln_eps)

    def head(self, params, x):
        return _dense(params["lm_head"], self.final_norm(params, x))

    def forward_logits(self, params, tokens):
        return _graph_logits(self.model, params, tokens, positions=True)


class OlmoHybrid(_DeltaAndFull, Description):
    """The build_olmo_hybrid_lm block (models/olmo_hybrid.py holds the
    equations, ops/gated_delta.py the delta rule's). Served by the mixed
    step on one device.

    What it pages: the FULL layers' K and V, a key/value head a query
    head (`head_dim` 128 as published, no rotation unless the builder
    was given a theta). What a sequence holds besides (`hybrid_spec`):
    for each DELTA layer an f32 matrix state, laid out as
    ops/gated_delta.state_shape says (heads in pairs on the lanes at the
    published 96 x 192), and a convolution tail of d_conv - 1 rows over
    the q, k and v channels; no ring. A POST-NORM block: `norm1` is the
    identity and every branch comes back alone (`post_norm`)."""

    kind = "olmo_hybrid"
    builder = "build_olmo_hybrid_lm"
    reads = ("tok_embed", "lm_head", "layer0_delta", "layer0_post_norm1",
             "layer0_mlp", "final_norm")
    post_norm = True
    _state = Qwen3Next._state
    refused = {
        "tp": "single-device: the matrix states and the heads are not "
              "split over a mesh",
        "adapters": "no adapter pool for the gated mixers and the gated "
                    "feed-forward",
        **{path: Qwen3Next.refused[path] for path in (
            "speculation", "prefix_cache", "host_tier", "handoff")},
    }

    def __init__(self, model, ops):
        self.model = model
        self.vocab_size = ops["tok_embed"].num_entries
        self.layer_norm = True
        n = 0
        while f"layer{n}_post_norm1" in ops:
            n += 1
        self.num_layers = n
        self.kinds = [DELTA if f"layer{i}_delta" in ops else FULL
                      for i in range(n)]
        self.delta_layers = [i for i, k in enumerate(self.kinds)
                             if k == DELTA]
        self.full_layers = [i for i, k in enumerate(self.kinds)
                            if k == FULL]
        attns = [ops.get(f"layer{i}_attn") for i in self.full_layers]
        if not (attns and all(
                a is not None and a.causal and a.qk_norm and not a.window
                and a.num_kv_heads == a.num_heads for a in attns)):
            raise ValueError(
                "ServeEngine reads a build_olmo_hybrid_lm-shaped model: "
                "delta AND causal multi-head attention layers with "
                "QK-norm, each sub-layer under a post-norm")
        attn = attns[0]
        self.delta = ops["layer0_delta"]
        self.num_heads, self.head_dim = attn.num_heads, attn.head_dim
        self.rope_theta = attn.rotary_theta     # 0: no rotation
        self.hidden = attn.embed_dim
        self.ln_eps = ops["layer0_post_norm1"].eps
        self.act_dtype = jnp.dtype(ops["tok_embed"].out_dtype)
        self.ff_dim = ops["layer0_mlp"].hidden_dim
        # no table is sized by them: the positions served are the graph's
        self.max_positions = int(ops["tok_embed"].inputs[0].shape[1])

    def norm1(self, params, i, x):
        """None stands before a sub-layer: it reads the stream itself."""
        return x

    def branch_norm(self, params, i, which: int, y):
        """The norm on a sub-layer's output: `which` 1 the mixer's, 2
        the feed-forward's."""
        return rms_norm(y, params[f"layer{i}_post_norm{which}"]["scale"],
                        self.ln_eps)

    def qkv(self, params, i, h, positions, lora=None):
        """h (T, E) -> q, k, v (T, H, D): the projections, the RMS norm
        of q and k over the whole projection; rotated at the lanes'
        positions only where the builder was given a theta (else the
        positions are not read: the delta layers alone carry order)."""
        p = params[f"layer{i}_attn"]
        q, k, v = _project(p, h)
        q = rms_norm(q, p["q_norm"], self.ln_eps)
        k = rms_norm(k, p["k_norm"], self.ln_eps)
        if self.rope_theta > 0:
            q = rotary(q, positions, self.rope_theta)
            k = rotary(k, positions, self.rope_theta)
        return q, k, v

    def attn_out(self, params, i, o, x, psum_axis=None, lora=None):
        """The attention BRANCH alone (post_norm)."""
        p = params[f"layer{i}_attn"]
        return jnp.einsum("...hd,hde->...e", o, p["wo"].astype(o.dtype))

    def delta_out(self, params, i, o, z, x):
        """The delta BRANCH alone (post_norm)."""
        return GD.gate_and_project(params[f"layer{i}_delta"], o, z,
                                   self.ln_eps)

    def ffn(self, params, i, x, live=None, psum_axis=None, lora=None):
        """The feed-forward BRANCH alone, of the stream itself; one
        scope, `ffn`. -> (f, None: no expert counts)."""
        with jax.named_scope("ffn"):
            return gated_ffn(params[f"layer{i}_mlp"], x), None

    def final_norm(self, params, x):
        return rms_norm(x, params["final_norm"]["scale"], self.ln_eps)

    def head(self, params, x):
        return _dense(params["lm_head"], self.final_norm(params, x))

    def forward_logits(self, params, tokens):
        return _graph_logits(self.model, params, tokens,
                             positions=self.rope_theta > 0)


class FalconH1(Description):
    """The build_falcon_h1_lm block (models/falcon_h1.py holds the
    equations, ops/ssd.py the Mamba-2 heads'). Served by the mixed step
    on one device.

    EVERY layer is of the one kind SSD_ATTN, whose body
    (serve/mixers.py) runs two sequence mixers off the layer's one norm
    and adds both branches to the stream once — so every layer is paged
    AND holds state. What it pages: each layer's K and V, `kv_heads`
    grouped heads of `head_dim`, the keys scaled by `key_multiplier` and
    rotated. What a sequence holds besides (`hybrid_spec`): for each
    layer an f32 matrix state (N, H P) (ops/ssd.Dims.state_shape) and a
    convolution tail of d_conv - 1 rows over the x, B and C channels; no
    ring. The multipliers stand where the builder's ops put them."""

    kind = "falcon_h1"
    builder = "build_falcon_h1_lm"
    reads = ("tok_embed", "embed_scale", "lm_head", "logit_scale",
             "layer0_ssm", "layer0_attn", "layer0_mlp", "final_norm")
    _state = Qwen3Next._state
    refused = {
        "tp": "single-device: the matrix states, their two groups and "
              "the grouped heads are not split over a mesh (ROADMAP M1)",
        "adapters": "no adapter pool for the two mixers of a layer and "
                    "the gated feed-forward",
        **{path: Qwen3Next.refused[path] for path in (
            "speculation", "prefix_cache", "host_tier", "handoff")},
    }

    def __init__(self, model, ops):
        self.model = model
        self.vocab_size = ops["tok_embed"].num_entries
        self.layer_norm = True
        n = 0
        while f"layer{n}_ssm" in ops:
            n += 1
        self.num_layers = n
        attns = [ops.get(f"layer{i}_attn") for i in range(n)]
        if not all(a is not None and a.causal and not a.window
                   and not a.qk_norm and a.rotary_theta > 0
                   for a in attns):
            raise ValueError(
                "ServeEngine reads a build_falcon_h1_lm-shaped model: "
                "Mamba-2 heads AND causal rotary attention in every "
                "layer")
        # every layer writes pages AND a state slot and a tail
        self.full_layers = self.ssd_layers = list(range(n))
        attn, ssm = attns[0], ops["layer0_ssm"]
        self.ssd = ssm                          # a layer's Mamba-2 op
        self.num_heads, self.head_dim = attn.num_heads, attn.head_dim
        self._kv_heads = attn.num_kv_heads
        self.rope_theta = attn.rotary_theta
        self.hidden = attn.embed_dim
        self.ln_eps = ops["layer0_ln"].eps
        self.act_dtype = jnp.dtype(ops["tok_embed"].out_dtype)
        self.ff_dim = ops["layer0_mlp"].hidden_dim
        self.embedding_multiplier = ops["embed_scale"].scalar
        self.lm_head_multiplier = ops["logit_scale"].scalar
        self.attention_in_multiplier = ops["layer0_attn_in"].scalar
        self.attention_out_multiplier = ops["layer0_attn_scale"].scalar
        self.key_multiplier = attn.key_multiplier
        self.mlp_multipliers = ops["layer0_mlp"].multipliers
        # no table is sized by them: the positions served are the graph's
        self.max_positions = int(ops["tok_embed"].inputs[0].shape[1])

    def mixer(self, i: int) -> str:
        return SSD_ATTN

    def hybrid_spec(self, chunk: int):
        from .kv_cache import HybridSpec
        return HybridSpec(
            window_layers=0, window=0, chunk=int(chunk),
            state_layers=self.num_layers,
            state_shape=self.ssd.dims.state_shape,
            tail_shape=(self.ssd.d_conv - 1, self.ssd.dims.channels),
            tail_dtype=str(self.act_dtype))

    @property
    def kv_heads(self) -> int:
        return self._kv_heads

    def embed(self, params, tokens, positions):
        return jnp.take(params["tok_embed"]["kernel"], tokens, axis=0,
                        mode="clip").astype(self.act_dtype) \
            * self.embedding_multiplier

    def norm1(self, params, i, x):
        """The layer's ONE norm: both mixers read it."""
        return rms_norm(x, params[f"layer{i}_ln"]["scale"], self.ln_eps)

    # the Mamba-2 branch, in the pieces the step scopes apart
    def ssd_in(self, params, i, h):
        """-> (z (T, d_ssm), the convolution's raw input [x | B | C],
        the raw dt (T, H)), the multipliers applied."""
        m = self.ssd
        return SD.project(params[f"layer{i}_ssm"], h, m.dims,
                          m.in_multiplier, m.multipliers)

    def ssd_scan_inputs(self, params, i, u, dt):
        return SD.scan_inputs(params[f"layer{i}_ssm"], u, dt,
                              self.ssd.dims)

    def ssd_out(self, params, i, y, z):
        """The Mamba-2 BRANCH alone."""
        m = self.ssd
        return SD.gate_and_project(params[f"layer{i}_ssm"], y, z, m.dims,
                                   m.eps, m.out_multiplier)

    def qkv(self, params, i, h, positions, lora=None):
        """h (T, E) -> q (T, H, D), k, v (T, Hk, D): the projections of
        h * attention_in_multiplier, the keys times key_multiplier, q
        and k rotated at the lanes' positions."""
        if self.attention_in_multiplier != 1.0:
            h = h * self.attention_in_multiplier
        q, k, v = _project(params[f"layer{i}_attn"], h)
        if self.key_multiplier != 1.0:
            k = k * self.key_multiplier
        return (rotary(q, positions, self.rope_theta),
                rotary(k, positions, self.rope_theta), v)

    def attn_out(self, params, i, o, x, psum_axis=None, lora=None):
        """The attention BRANCH alone."""
        p = params[f"layer{i}_attn"]
        return jnp.einsum("...hd,hde->...e", o, p["wo"].astype(o.dtype)) \
            * self.attention_out_multiplier

    def ffn(self, params, i, x, live=None, psum_axis=None, lora=None):
        with jax.named_scope("ffn"):
            h = rms_norm(x, params[f"layer{i}_ln2"]["scale"], self.ln_eps)
            return x + gated_ffn(params[f"layer{i}_mlp"], h,
                                 self.mlp_multipliers), None

    def final_norm(self, params, x):
        return rms_norm(x, params["final_norm"]["scale"], self.ln_eps)

    def head(self, params, x):
        return _dense(params["lm_head"], self.final_norm(params, x)) \
            * self.lm_head_multiplier

    def forward_logits(self, params, tokens):
        return _graph_logits(self.model, params, tokens, positions=True)


class LFM2MoE(Description):
    """The build_lfm2_moe_lm block (models/lfm2_moe.py holds the
    equations, ops/short_conv.py the convolution's). Served by the mixed
    step on one device.

    What it pages: the FULL layers' K and V, `kv_heads` grouped heads of
    `head_dim`, q and k normed a HEAD at a time (one (head_dim,) weight
    the heads share) and then rotated. What a sequence holds besides
    (`hybrid_spec`): for each CONV layer a convolution tail of taps - 1
    rows of the hidden size — and NOTHING else: no state, no ring
    (kv_cache.HybridSpec.tail_layers). The first `dense_layers` layers'
    feed-forward is dense (scope `ffn`, no counts); the others route
    (`router`, `moe_dispatch`, `experts`, `moe_combine`), and the step's
    expert counts are over those layers alone."""

    kind = "lfm2_moe"
    builder = "build_lfm2_moe_lm"
    reads = ("tok_embed", "lm_head", "layer0_operator_norm",
             "embedding_norm")
    _tail = ("a sequence's convolution tails live in its slot, not in "
             "pages: ")
    refused = {
        "tp": "single-device: the experts, the grouped heads and the "
              "tails are not split over a mesh (ROADMAP M1)",
        "adapters": "no adapter pool for the convolution's projections "
                    "and the expert layer",
        "speculation": _tail + "rolling back rejected tokens would need "
                       "the tails as they were (serve_spec_decode must be "
                       "off)",
        "prefix_cache": _tail + "a prefix hit would need the tails at the "
                        "prefix's end (serve_prefix_cache must be off)",
        "host_tier": _tail + "the host tier spills pages only",
        "handoff": _tail + "the disaggregated handoff ships pages only",
    }

    def __init__(self, model, ops):
        self.model = model
        self.vocab_size = ops["tok_embed"].num_entries
        self.layer_norm = True
        n = 0
        while f"layer{n}_operator_norm" in ops:
            n += 1
        self.num_layers = n
        self.kinds = [CONV if f"layer{i}_conv" in ops else FULL
                      for i in range(n)]
        self.conv_layers = [i for i, k in enumerate(self.kinds)
                            if k == CONV]
        self.full_layers = [i for i, k in enumerate(self.kinds)
                            if k == FULL]
        self.moe_layers = [i for i in range(n) if f"layer{i}_moe" in ops]
        attns = [ops.get(f"layer{i}_attn") for i in self.full_layers]
        moes = [ops[f"layer{i}_moe"] for i in self.moe_layers]
        # the dense layers LEAD: the routing layers are the rest
        self.dense_layers = n - len(moes)
        if not (attns and moes and self.conv_layers and all(
                a is not None and a.causal and a.qk_norm_per_head
                and a.rotary_theta > 0 and not a.window for a in attns)
                and self.moe_layers == list(range(self.dense_layers, n))
                and all(f"layer{i}_mlp" in ops
                        for i in range(self.dense_layers))
                and all(m.dropless and not m.shared_experts
                        and not m.experts_held for m in moes)):
            raise ValueError(
                "ServeEngine reads a build_lfm2_moe_lm-shaped model: gated "
                "short convolutions AND causal rotary attention with "
                "per-head QK-norm, leading dense layers, then dropless "
                "MoEFFN layers that hold every expert")
        attn, moe0 = attns[0], moes[0]
        self.conv = ops[f"layer{self.conv_layers[0]}_conv"]
        self.num_heads, self.head_dim = attn.num_heads, attn.head_dim
        self._kv_heads = attn.num_kv_heads
        self.rope_theta = attn.rotary_theta
        self.hidden = attn.embed_dim
        self.ln_eps = ops["layer0_operator_norm"].eps
        self.act_dtype = jnp.dtype(ops["tok_embed"].out_dtype)
        # rotary has no table: the positions served are the graph's own
        self.max_positions = int(ops["tok_embed"].inputs[0].shape[1])
        self.experts = moe0.num_experts
        self.experts_per_token = moe0.k
        self.norm_topk, self.score = moe0.norm_topk, moe0.score
        self.expert_bias = moe0.expert_bias is not None
        self.activation = moe0.activation
        self.ff_dim = moe0.hidden_dim
        w = model.state.params[f"layer{self.moe_layers[0]}_moe"]["wg"]
        # what one expert's three matrices weigh as they are resident:
        # the bytes the expert phase reads for every expert it touches
        self.expert_bytes = int(3 * self.hidden * self.ff_dim
                                * w.dtype.itemsize)
        self._expert_weights = jax.ShapeDtypeStruct(w.shape, w.dtype)

    def mixer(self, i: int) -> str:
        return self.kinds[i]

    def expert_impl(self, lanes: int):
        rows = jax.ShapeDtypeStruct(
            (lanes * self.experts_per_token, self.hidden), self.act_dtype)
        return expert_impl(rows, self._expert_weights, **self.kernels)

    def hybrid_spec(self, chunk: int):
        """No window, no state: tails of (taps - 1, hidden) in the CONV
        layers, and they are all a slot holds."""
        from .kv_cache import HybridSpec
        return HybridSpec(
            window_layers=0, window=0, chunk=int(chunk),
            tail_layers=len(self.conv_layers),
            tail_shape=(self.conv.taps - 1, self.hidden),
            tail_dtype=str(self.act_dtype))

    @property
    def kv_heads(self) -> int:
        return self._kv_heads

    @property
    def paged_layers(self) -> int:
        return len(self.full_layers)

    def embed(self, params, tokens, positions):
        return jnp.take(params["tok_embed"]["kernel"], tokens, axis=0,
                        mode="clip").astype(self.act_dtype)

    def norm1(self, params, i, x):
        return rms_norm(x, params[f"layer{i}_operator_norm"]["scale"],
                        self.ln_eps)

    def qkv(self, params, i, h, positions, lora=None):
        """h (T, E), positions (T,) -> q (T, H, D), k, v (T, Hk, D): the
        projections, the RMS norm of q and k over EACH head's D dims
        (one (D,) weight the heads share), then the rotation at the
        lanes' absolute positions."""
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
        """A leading DENSE layer: one scope, `ffn` -> (x, None: the
        layer routes nothing). An expert layer, four scopes: `router`
        (the norm, f32 sigmoid, the k largest of score + expert_bias,
        their scores renormalised), `moe_dispatch`, `experts`,
        `moe_combine` -> (x, (E,) int32 live slots per expert)."""
        scope = jax.named_scope
        norm2 = lambda: rms_norm(
            x, params[f"layer{i}_ffn_norm"]["scale"], self.ln_eps)
        if i < self.dense_layers:
            with scope("ffn"):
                return x + gated_ffn(params[f"layer{i}_mlp"], norm2()), None
        m = params[f"layer{i}_moe"]
        with scope("router"):
            h = norm2().reshape(-1, self.hidden)
            _, gate_vals, assign = route_top_k(
                h, m["gate"], self.experts_per_token, self.norm_topk,
                self.score, m.get("expert_bias"))
        with scope("moe_dispatch"):
            rows, order, counts = dropless_dispatch(
                h, assign, self.experts, live)
        with scope("experts"):
            ys = grouped_ffn(rows, counts, m["wg"], m["wu"], m["wd"],
                             self.activation, **self.kernels)
        with scope("moe_combine"):
            y = dropless_combine(ys, order, gate_vals)
            return x + y.astype(x.dtype).reshape(x.shape), counts

    def final_norm(self, params, x):
        return rms_norm(x, params["embedding_norm"]["scale"], self.ln_eps)

    def head(self, params, x):
        """Tied: the token table is the head."""
        h = self.final_norm(params, x)
        table = params["tok_embed"]["kernel"].astype(h.dtype)
        return jnp.dot(h, table.T,
                       preferred_element_type=jnp.float32).astype(h.dtype)

    def forward_logits(self, params, tokens):
        return _graph_logits(self.model, params, tokens, positions=True)


SHAPES = (TransformerLM, LFM2MoE, FalconH1, OlmoHybrid, Qwen3Next, OLMoE,
          Phi4Flash, CommandAPlus, MiniCPMSala)


def describe(model):
    """The description of a compiled FFModel, chosen by the op names
    its builder wrote: the first of SHAPES whose names are all there
    (Qwen3Next's before OLMoE's, whose names it has too; OlmoHybrid's,
    FalconH1's and LFM2MoE's are nobody else's)."""
    ops = {op.name: op for op in model.ops}
    for cls in SHAPES:
        if all(n in ops for n in cls.reads):
            return cls(model, ops)
    missing = "; ".join(
        f"{cls.builder}: {[n for n in cls.reads if n not in ops]}"
        for cls in SHAPES)
    raise ValueError(
        f"ServeEngine reads models shaped by "
        f"{', '.join(cls.builder for cls in SHAPES)}; this one is "
        f"neither (missing ops, by builder: {missing})")
