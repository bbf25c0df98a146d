"""Fused Mixture-of-Experts FFN with expert parallelism.

The reference composes MoE from softmax + TopK + GroupBy + per-expert
dense ops + Aggregate, all placed by the strategy machinery but with NO
expert-parallel dispatch (SURVEY.md 2.4: "no all-to-all EP dispatch").
This op provides the TPU-first EP path: expert weights are stacked with a
leading `expert` axis; when the strategy maps that axis to a mesh axis,
GSPMD turns the dispatch/combine einsums into all-to-alls over ICI.

GShard-style: top-k gating, capacity-bounded dense dispatch masks, and a
load-balancing auxiliary loss added to the objective.

`dropless=True` is the modern layer: the experts are bias-free gated
units, (act(x wg) * (x wu)) wd (SwiGLU with activation "silu"), and
there is no capacity: slots are sorted by expert and run through
grouped matmuls (ops/moe.py), so an overloaded expert changes no
token's mathematics. `norm_topk=False` keeps the k router probabilities
unnormalised. OLMoE's layer is both (models/olmoe.py).

A dropless layer also takes (models/cmdaplus.py uses all three):
`score="sigmoid"`, the router's scoring function; `shared_experts=n`,
n gated experts of the same width that every token passes, their mean
added to the routed sum (`ops/moe.py::shared_ffn`), times
`sigmoid(x w_sg)`, a scalar a token, under `shared_gate` (Qwen3-Next's
shared expert); and
`experts_held=(first, count)`, ONE SHARE of an expert-parallel layer —
the router keeps its `num_experts` outputs and its k a token, only
`count` experts' weights exist here, a slot of an absent expert adds
nothing, and the output is this share's part of the routed sum (plus
the shared term, which every share holds whole).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..op import CHANNEL, EXPERT, SAMPLE, SEQ, Op, OpContext, WeightSpec, register_op
from .common import AC_MODE_RELU, apply_activation
from .moe import (
    dispatch_indices,
    dispatch_mask,
    dropless_combine,
    dropless_dispatch,
    grouped_ffn,
    route_top_k,
    shared_ffn,
    shared_scale,
    sorted_combine,
    sorted_dispatch,
    use_sorted_dispatch,
)


@register_op
class MoEFFN(Op):
    """input (..., D) -> output (..., out_dim) through num_experts
    two-layer FFNs with top-k routing."""

    op_type = "moe_ffn"
    has_aux_loss = True  # excluded from remat (ctx side-channel)

    def __init__(self, model, name, inputs, num_experts: int, k: int,
                 hidden_dim: int, out_dim: int = None,
                 capacity_factor: float = 1.25,
                 activation=AC_MODE_RELU, aux_loss_weight: float = 1e-2,
                 kernel_initializer: str = "glorot",
                 norm_topk: bool = True, dropless: bool = False,
                 score: str = "softmax", shared_experts: int = 0,
                 experts_held=None, shared_gate: bool = False,
                 expert_bias=None):
        super().__init__(model, name, inputs)
        # a SELECTION bias, leaf `expert_bias` (E,) f32 (route_top_k's
        # `bias`): None: no such leaf; else how it starts — True: at 0,
        # a number: normal at that deviation, a callable: that custom
        # initializer. It enters the choice of experts alone, so its
        # gradient is exactly 0 and a training step leaves it as it is
        # (the family moves it outside the optimizer, by the load)
        self.expert_bias = expert_bias
        self.norm_topk = bool(norm_topk)
        self.dropless = bool(dropless)
        self.score = str(score)
        self.shared_experts = int(shared_experts)
        self.shared_gate = bool(shared_gate)
        if self.shared_gate and not self.shared_experts:
            raise ValueError(f"{name}: shared_gate gates the shared "
                             f"experts (shared_experts > 0)")
        self.experts_held = None if experts_held is None \
            else (int(experts_held[0]), int(experts_held[1]))
        if not self.dropless and (self.score != "softmax"
                                  or self.shared_experts
                                  or self.experts_held
                                  or self.expert_bias is not None):
            raise ValueError(
                f"{name}: score, shared_experts, experts_held and "
                f"expert_bias are the dropless layer's (dropless=True)")
        self.num_experts = int(num_experts)
        self.k = int(k)
        self.hidden_dim = int(hidden_dim)
        self.in_dim = inputs[0].shape[-1]
        self.out_dim = int(out_dim) if out_dim else self.in_dim
        self.capacity_factor = float(capacity_factor)
        self.activation = activation
        self.aux_loss_weight = aux_loss_weight
        self.kernel_initializer = kernel_initializer
        n_tokens = 1
        for s in inputs[0].shape[:-1]:
            n_tokens *= s
        self.n_tokens = n_tokens
        # dropless: no buffer; `capacity` is then the MEAN load of an
        # expert, which is what the search prices (cost_model's EP
        # all-to-all, flops() below)
        self.capacity = max(
            1, int((1.0 if self.dropless else self.capacity_factor)
                   * self.k * n_tokens / self.num_experts))
        self.attrs = {"num_experts": num_experts, "k": k,
                      "hidden_dim": hidden_dim, "out_dim": self.out_dim,
                      "capacity": self.capacity}
        if self.dropless:
            self.attrs.update(dropless=True, norm_topk=self.norm_topk)
        if self.score != "softmax" or self.shared_experts \
                or self.experts_held:
            self.attrs.update(score=self.score,
                              shared_experts=self.shared_experts,
                              experts_held=self.experts_held)
        if self.shared_gate:
            self.attrs["shared_gate"] = True
        if self.expert_bias is not None:
            self.attrs["expert_bias"] = True

    def output_shapes(self):
        return [tuple(self.inputs[0].shape[:-1]) + (self.out_dim,)]

    def weight_specs(self):
        e, d, h, o = self.num_experts, self.in_dim, self.hidden_dim, self.out_dim
        from ..core.initializers import named
        # one initializer for every matrix, or a dict with one a leaf
        # by name for the dropless layer's router and experts
        init = lambda w: named(self.kernel_initializer, w) \
            if self.dropless else self.kernel_initializer
        gate = WeightSpec((d, e), initializer=init("gate"),
                          axes=(CHANNEL, None))
        if self.dropless:
            def w(name, shape, fi, fo):
                return WeightSpec(shape, axes=(EXPERT, None, None),
                                  initializer=init(name),
                                  fan_in=fi, fan_out=fo)
            if self.experts_held:
                e = self.experts_held[1]
            specs = {"gate": gate, "wg": w("wg", (e, d, h), d, h),
                     "wu": w("wu", (e, d, h), d, h),
                     "wd": w("wd", (e, h, o), h, o)}
            if self.shared_experts:
                # the shared experts' matrices side by side (shared_ffn)
                def sw(shape, fi, fo):
                    return WeightSpec(shape, axes=(None, None),
                                      initializer=self.kernel_initializer,
                                      fan_in=fi, fan_out=fo)
                n = self.shared_experts * h
                specs.update(sg=sw((d, n), d, h), su=sw((d, n), d, h),
                             sd=sw((n, o), h, o))
                if self.shared_gate:
                    specs["sgate"] = sw((d, 1), d, 1)
            if self.expert_bias is not None:
                from ..core.initializers import make_normal
                start = self.expert_bias
                specs["expert_bias"] = WeightSpec(
                    (self.num_experts,), initializer="zeros",
                    keep_dtype=True, custom_init=None if start is True
                    else start if callable(start)
                    else make_normal(0.0, float(start)))
            return specs
        return {
            "gate": gate,
            "w1": WeightSpec((e, d, h), initializer=self.kernel_initializer,
                             axes=(EXPERT, None, None), fan_in=d, fan_out=h),
            "b1": WeightSpec((e, h), initializer="zeros",
                             axes=(EXPERT, None)),
            "w2": WeightSpec((e, h, o), initializer=self.kernel_initializer,
                             axes=(EXPERT, None, None), fan_in=h, fan_out=o),
            "b2": WeightSpec((e, o), initializer="zeros",
                             axes=(EXPERT, None)),
        }

    def forward(self, params, xs, ctx: OpContext):
        (x,) = xs
        orig_shape = x.shape
        d = orig_shape[-1]
        tokens = x.reshape(-1, d)  # (N, D)
        n = tokens.shape[0]
        e, cap, k = self.num_experts, self.capacity, self.k

        probs, gate_vals, assign = route_top_k(
            tokens, params["gate"], k, self.norm_topk, self.score,
            params.get("expert_bias"))          # (N, E), (N, k) x 2
        if self.dropless:
            rows, order, counts = dropless_dispatch(
                tokens, assign, e, held=self.experts_held)
            ys = grouped_ffn(rows, counts, params["wg"], params["wu"],
                             params["wd"], self.activation)
            out = dropless_combine(ys, order, gate_vals)
            if self.shared_experts:
                shared = shared_ffn(
                    tokens, params["sg"], params["su"], params["sd"],
                    self.activation, self.shared_experts)
                if self.shared_gate:
                    shared = shared * shared_scale(tokens, params["sgate"])
                out = out + shared
            self._aux_loss(ctx, assign, probs)
            return [out.astype(x.dtype).reshape(
                orig_shape[:-1] + (self.out_dim,))]

        xrep = jnp.repeat(tokens, k, axis=0)  # (N*k, D) slot-major
        sorted_path = use_sorted_dispatch(
            self.model, n * k, e, cap,
            expert_sharded=ctx.mesh_axis_size(EXPERT) > 1)
        if sorted_path:
            # scalable routing: no (S, E, C) mask (VERDICT r3 #8) —
            # identical semantics (stable argsort ranks = cumsum ranks)
            pos, kept = dispatch_indices(assign.astype(jnp.int32), e, cap)
            expert_in = sorted_dispatch(xrep, pos, kept, e, cap)
        else:
            mask = dispatch_mask(assign.astype(jnp.int32), e, cap)
            expert_in = jnp.einsum("snc,sd->ncd", mask,
                                   xrep.astype(jnp.float32)).astype(x.dtype)

        # per-expert FFN — batched over the (shardable) expert axis
        h = jnp.einsum("ecd,edh->ech", expert_in,
                       params["w1"].astype(x.dtype),
                       preferred_element_type=jnp.float32).astype(x.dtype)
        h = apply_activation(h + params["b1"][:, None, :].astype(x.dtype),
                             self.activation)
        out_e = jnp.einsum("ech,eho->eco", h, params["w2"].astype(x.dtype),
                           preferred_element_type=jnp.float32).astype(x.dtype)
        out_e = out_e + params["b2"][:, None, :].astype(x.dtype)

        # combine: weight each slot by its (renormalized) gate value
        if sorted_path:
            combined = sorted_combine(out_e, pos, kept).astype(jnp.float32)
        else:
            combined = jnp.einsum("snc,nco->so", mask,
                                  out_e.astype(jnp.float32))  # (N*k, O)
        combined = combined.reshape(n, k, self.out_dim)
        out = jnp.sum(combined * gate_vals[..., None], axis=1)

        self._aux_loss(ctx, assign, probs)
        return [out.astype(x.dtype).reshape(orig_shape[:-1] + (self.out_dim,))]

    def _aux_loss(self, ctx: OpContext, assign, probs) -> None:
        """GShard load-balancing loss: E * sum_e f_e * p_e where f_e is
        the fraction of tokens whose top-1 goes to e and p_e the mean
        gate probability of e."""
        if not ctx.training:
            return
        e = self.num_experts
        top1 = jax.nn.one_hot(assign[:, 0], e, dtype=jnp.float32)
        f = jnp.mean(top1, axis=0)
        p = jnp.mean(probs, axis=0)
        ctx.aux_loss = (self.aux_loss_weight * e
                        * jnp.sum(f * p)).astype(jnp.float32)

    def output_axes(self):
        n = len(self.outputs[0].shape)
        axes = [None] * n
        axes[0] = SAMPLE
        if n == 3:
            axes[1] = SEQ
        return [tuple(axes)]

    input_axes = output_axes

    def flops(self) -> float:
        # gate + the FFN GEMMs over what is dispatched: the capacity
        # buffers or, dropless, exactly k experts a token (three GEMMs
        # each) and a sort in place of the dispatch masks
        gate = 2.0 * self.n_tokens * self.in_dim * self.num_experts
        up = (2 if self.dropless else 1) * self.in_dim * self.hidden_dim
        per_row = 2.0 * (up + self.hidden_dim * self.out_dim)
        if self.dropless:
            # a share holds count / num_experts of the k slots a token,
            # in the mean; every token passes every shared expert
            held = self.experts_held[1] / self.num_experts \
                if self.experts_held else 1.0
            return gate + self.n_tokens * per_row * (
                self.k * held + self.shared_experts)
        ffn = self.num_experts * self.capacity * per_row
        dispatch = 2.0 * self.n_tokens * self.k * self.num_experts * self.capacity
        return gate + ffn + dispatch
