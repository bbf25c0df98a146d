"""Mixture-of-Experts routing ops: GroupBy (dispatch) and Aggregate
(combine).

Reference: src/ops/group_by.cc (CPU-only scatter of samples to per-expert
tensors with capacity factor `alpha`) and src/ops/aggregate.cc (CPU-only
weighted combine). The reference registers these LOC_PROC (CPU) because
irregular scatter is hostile to GPUs (model.cc:2525-2568).

TPU-native design: GShard-style *dense dispatch*. Routing becomes one-hot
dispatch masks contracted with the data on the MXU — no scatter at all,
fully differentiable, and the expert dimension is a real array axis that
can be sharded over a mesh `expert` axis so GSPMD inserts the all-to-all
(expert parallelism, which the reference lacked — SURVEY.md section 2.4).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import grouped_ffn as fused
from ..kernels.paged_ragged_v2 import PALLAS, PALLAS_INTERPRET
from ..op import EXPERT, SAMPLE, Op, OpContext, register_op


def dispatch_mask(assign: jax.Array, n_experts: int, capacity: int):
    """Build a dense dispatch mask from top-k expert assignments.

    assign: (batch, k) int — expert id per (sample, slot).
    Returns (batch*k, n_experts, capacity) float mask. Slot s of sample b
    routes to position `rank` within its expert's capacity buffer, where
    rank counts earlier (sample, slot) pairs assigned to the same expert;
    overflow beyond capacity is dropped (the reference drops too:
    group_by.cc capacity factor alpha).
    """
    flat = assign.reshape(-1).astype(jnp.int32)  # (B*k,)
    onehot = jax.nn.one_hot(flat, n_experts, dtype=jnp.float32)  # (S, n)
    ranks = jnp.cumsum(onehot, axis=0) * onehot - onehot  # rank within expert
    rank = jnp.sum(ranks, axis=1).astype(jnp.int32)  # (S,)
    keep = (rank < capacity).astype(jnp.float32)
    pos = jax.nn.one_hot(rank, capacity, dtype=jnp.float32)  # (S, cap)
    return onehot[:, :, None] * pos[:, None, :] * keep[:, None, None]


# Above this many mask elements (S * E * C floats) the dense dispatch
# mask is pure HBM waste; the sorted-scatter path does the same routing
# in O(S log S + S * D). Override with FFConfig.moe_dispatch.
DENSE_MASK_ELEMENT_LIMIT = 1 << 22


def dispatch_indices(assign: jax.Array, n_experts: int, capacity: int):
    """Sorted-scatter routing: the same (rank-within-expert, capacity
    drop) semantics as `dispatch_mask` without materializing the
    (S, E, C) mask — the scalable path for large expert counts
    (VERDICT r3 #8; capacity semantics preserved from
    /root/reference/src/ops/group_by.cc:1-381).

    assign: (batch, k) int. Returns (pos (S,), keep (S,)) where
    pos = expert * capacity + rank indexes a flat (E*C, ...) buffer and
    keep masks slots that exceeded their expert's capacity. Ranks count
    earlier slots (original slot order) routed to the same expert —
    jnp.argsort is stable, so this matches the dense mask bit-for-bit.
    """
    flat = assign.reshape(-1).astype(jnp.int32)  # (S,)
    s = flat.shape[0]
    order = jnp.argsort(flat)  # stable: preserves slot order per expert
    sorted_e = flat[order]
    idx = jnp.arange(s, dtype=jnp.int32)
    # index of each sorted run's first element, broadcast via cummax
    boundary = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_e[1:] != sorted_e[:-1]])
    run_start = jax.lax.cummax(jnp.where(boundary, idx, 0))
    rank_sorted = idx - run_start
    rank = jnp.zeros((s,), jnp.int32).at[order].set(rank_sorted)
    # out-of-range expert ids (e.g. -1 padding) silently contribute
    # nothing in the dense path (one_hot zeroes them) — match that
    # here, and DON'T let a negative pos wrap (jnp advanced indexing
    # normalizes negatives before mode="drop" can reject them)
    keep = (rank < capacity) & (flat >= 0) & (flat < n_experts)
    # dropped slots park out of range: scatters use mode="drop",
    # gathers mode="fill" — no valid position is ever clobbered
    pos = jnp.where(keep, flat * capacity + rank,
                    n_experts * capacity)
    return pos, keep


def sorted_dispatch(xrep: jax.Array, pos: jax.Array, keep: jax.Array,
                    n_experts: int, capacity: int):
    """Scatter slot-major tokens (S, D) into (E, C, D) expert buffers.
    Kept positions are unique by construction, so the add is a write."""
    d = xrep.shape[-1]
    masked = jnp.where(keep[:, None], xrep, jnp.zeros_like(xrep))
    buf = jnp.zeros((n_experts * capacity, d), xrep.dtype)
    buf = buf.at[pos].add(masked, mode="drop")
    return buf.reshape(n_experts, capacity, d)


def sorted_combine(out_e: jax.Array, pos: jax.Array, keep: jax.Array):
    """Gather expert outputs (E, C, O) back to slot-major (S, O);
    dropped slots read zeros (same as the dense mask contraction)."""
    flat = out_e.reshape(-1, out_e.shape[-1])
    gathered = flat.at[pos].get(mode="fill", fill_value=0)
    return jnp.where(keep[:, None], gathered, jnp.zeros_like(gathered))


def use_sorted_dispatch(model, n_slots: int, n_experts: int,
                        capacity: int, expert_sharded: bool) -> bool:
    """Dispatch-path policy. "auto": dense masks feed the MXU and lower
    to clean all-to-alls when the expert axis is mesh-sharded (EP), so
    keep them unless the mask itself would be huge; sorted-scatter
    takes over above DENSE_MASK_ELEMENT_LIMIT elements."""
    mode = getattr(getattr(model, "config", None), "moe_dispatch", "auto")
    if mode == "dense":
        return False
    if mode == "sorted":
        return True
    if expert_sharded:
        return False  # einsum -> all-to-all is the EP-friendly lowering
    return n_slots * n_experts * capacity > DENSE_MASK_ELEMENT_LIMIT


RAGGED_DOT = "ragged_dot"        # `grouped_ffn` as XLA's grouped matmuls


# ---- dropless routing: every slot reaches its expert, whatever the load
def route_top_k(tokens: jax.Array, gate_w: jax.Array, k: int,
                norm_topk: bool, score: str = "softmax", bias=None):
    """The router: tokens (N, D) -> (scores (N, E) f32, the k largest
    (N, k) f32, their expert ids (N, k) int32). Logits accumulate in
    f32 and are never rounded; the scoring function (`score`: "softmax"
    over all the experts, or "sigmoid" of each logit alone) and top-k
    run in f32. `norm_topk` renormalises the k weights to sum to 1.
    `bias` (E,): a SELECTION bias (LFM2's `expert_bias`) — the k experts
    are those of the largest scores + bias, their weights the scores
    alone, renormalised with the family's 1e-6 in the divisor: the bias
    chooses and never weighs, and no gradient reaches it."""
    logits = jnp.dot(tokens, gate_w.astype(tokens.dtype),
                     preferred_element_type=jnp.float32)
    if score == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
    elif score == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"route_top_k: no scoring function {score!r}")
    if bias is not None:
        _, assign = jax.lax.top_k(
            probs + jax.lax.stop_gradient(bias.astype(jnp.float32)), k)
        gate_vals = jnp.take_along_axis(probs, assign, axis=-1)
        if norm_topk:
            gate_vals = gate_vals / (
                jnp.sum(gate_vals, axis=-1, keepdims=True) + 1e-6)
        return probs, gate_vals, assign.astype(jnp.int32)
    gate_vals, assign = jax.lax.top_k(probs, k)
    if norm_topk:
        gate_vals = gate_vals / jnp.clip(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    return probs, gate_vals, assign.astype(jnp.int32)


def dropless_dispatch(tokens: jax.Array, assign: jax.Array,
                      n_experts: int, live=None, held=None):
    """Sort the N*k slots by expert (stable; slot s belongs to token
    s // k). `live` (N,) bool: slots of tokens that are not live route
    NOWHERE — they sort behind every expert's rows and no expert counts
    them. `held` (first, count): only experts first .. first + count - 1
    of the `n_experts` live HERE (one share of an expert-parallel
    layer): a slot of an absent expert routes nowhere too, and the
    counts are over the held experts. -> (rows (S, D) in expert order,
    order (S,), counts (E or count,) int32 live slots per expert: the
    grouped matmul's group sizes)."""
    k = assign.shape[1]
    flat = assign.reshape(-1)
    if held is not None:
        first, n_experts = held
        flat = flat - first
        flat = jnp.where((flat >= 0) & (flat < n_experts), flat, n_experts)
    if live is not None:
        flat = jnp.where(jnp.repeat(live, k), flat, n_experts)
    order = jnp.argsort(flat)
    counts = jnp.zeros((n_experts,), jnp.int32).at[flat].add(
        1, mode="drop")
    return jnp.take(tokens, order // k, axis=0), order, counts


def shared_ffn(tokens: jax.Array, wg, wu, wd, activation,
               n_shared: int) -> jax.Array:
    """The experts EVERY token passes, averaged: (1 / n) sum_m
    (act(x wg_m) * (x wu_m)) wd_m, with the n experts' matrices side by
    side — wg, wu (D, n * F), wd (n * F, D) — so the sum over m is the
    down projection's own contraction: three plain matmuls, f32
    accumulation, `g`, `u` and `h` rounded to the tokens' dtype.
    -> (N, D) f32."""
    from .common import apply_activation
    dt = tokens.dtype

    def mm(a, w):
        return jnp.dot(a, w.astype(dt), preferred_element_type=jnp.float32)

    h = apply_activation(mm(tokens, wg).astype(dt), activation) \
        * mm(tokens, wu).astype(dt)
    return mm(h, wd) * (1.0 / n_shared)


def shared_scale(tokens: jax.Array, w) -> jax.Array:
    """sigmoid(x w), w (D, 1): the scalar a token that scales what the
    shared experts give it. -> (N, 1) f32."""
    return jax.nn.sigmoid(jnp.dot(tokens, w.astype(tokens.dtype),
                                  preferred_element_type=jnp.float32))


def ragged_ffn(rows: jax.Array, counts: jax.Array, wg, wu, wd,
               activation) -> jax.Array:
    """`grouped_ffn` as three grouped matmuls (`jax.lax.ragged_dot`,
    f32 accumulation, each product rounded to the rows' dtype) whose
    group e is the `counts[e]` rows of expert e: what runs wherever the
    fused kernel does not, that kernel's jnp twin and its backward."""
    from .common import apply_activation
    dt = rows.dtype

    def gmm(a, w):
        return jax.lax.ragged_dot(
            a, w.astype(dt), counts,
            preferred_element_type=jnp.float32).astype(dt)

    h = apply_activation(gmm(rows, wg), activation) * gmm(rows, wu)
    y = gmm(h, wd)
    routed = jnp.arange(rows.shape[0]) < jnp.sum(counts)
    return jnp.where(routed[:, None], y, jnp.zeros_like(y))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _fused_ffn(rows, counts, wg, wu, wd, activation, interpret):
    return fused.grouped_ffn(rows, counts, wg, wu, wd, activation,
                             interpret=interpret)


def _fused_fwd(rows, counts, wg, wu, wd, activation, interpret):
    return (_fused_ffn(rows, counts, wg, wu, wd, activation, interpret),
            (rows, counts, wg, wu, wd))


def _fused_bwd(activation, interpret, saved, dy):
    rows, counts, *ws = saved
    _, vjp = jax.vjp(
        lambda r, *w: ragged_ffn(r, counts, *w, activation), rows, *ws)
    d_rows, *d_ws = vjp(dy)
    return (d_rows, np.zeros(counts.shape, jax.dtypes.float0), *d_ws)


_fused_ffn.defvjp(_fused_fwd, _fused_bwd)


def expert_impl(rows, wg, *, use_pallas=None, interpret=False) -> str:
    """Which implementation `grouped_ffn` runs for these operands
    (anything with `shape` and `dtype`): "pallas" | "pallas_interpret"
    (kernels/grouped_ffn.py, wherever it takes them: a tpu backend or
    the interpreter by argument, bf16, tiled widths) or "ragged_dot"
    (also by argument: use_pallas=False)."""
    if use_pallas is False or not fused.supported(rows, wg,
                                                  interpret=interpret):
        return RAGGED_DOT
    return PALLAS_INTERPRET if interpret else PALLAS


def grouped_ffn(rows: jax.Array, counts: jax.Array, wg, wu, wd,
                activation, *, use_pallas=None,
                interpret=False) -> jax.Array:
    """The gated bias-free expert over rows sorted by expert:
    (act(rows wg_e) * (rows wu_e)) wd_e with f32 accumulation, where
    expert e's rows are the next `counts[e]`. Rows past sum(counts)
    belong to no expert and come out zero. One fused kernel where
    `expert_impl` says so (differentiated as `ragged_ffn`), else
    `ragged_ffn`."""
    if expert_impl(rows, wg, use_pallas=use_pallas,
                   interpret=interpret) == RAGGED_DOT:
        return ragged_ffn(rows, counts, wg, wu, wd, activation)
    return _fused_ffn(rows, counts, wg, wu, wd, activation, interpret)


def dropless_combine(ys: jax.Array, order: jax.Array,
                     gate_vals: jax.Array) -> jax.Array:
    """Expert-ordered outputs (S, O) back to their tokens: unsort,
    weight each slot by its router weight and sum a token's k slots, in
    f32. -> (N, O) f32."""
    n, k = gate_vals.shape
    slots = jnp.take(ys, jnp.argsort(order), axis=0).astype(jnp.float32)
    return jnp.sum(slots.reshape(n, k, -1) * gate_vals[..., None], axis=1)


@register_op
class GroupBy(Op):
    """inputs: (data (B, D), assign (B, k)); outputs: n tensors (cap, D)."""

    op_type = "group_by"

    def __init__(self, model, name, inputs, n: int, alpha: float):
        super().__init__(model, name, inputs)
        self.n = int(n)
        self.alpha = float(alpha)
        data, assign = inputs
        batch = data.shape[0]
        k = assign.shape[1]
        self.k = k
        # capacity per expert, matching group_by.cc's alpha*k*B/n
        self.capacity = max(1, int(self.alpha * k * batch / self.n))
        self.attrs = {"n": n, "alpha": alpha, "capacity": self.capacity}

    def output_shapes(self):
        d = self.inputs[0].shape[-1]
        return [(self.capacity, d)] * self.n

    def output_dtypes(self):
        return [self.inputs[0].dtype] * self.n

    def forward(self, params, xs, ctx: OpContext):
        data, assign = xs
        xrep = jnp.repeat(data, self.k, axis=0)  # (S, D), slot-major
        if use_sorted_dispatch(self.model, xrep.shape[0], self.n,
                               self.capacity, expert_sharded=False):
            pos, keep = dispatch_indices(assign, self.n, self.capacity)
            expert_in = sorted_dispatch(xrep, pos, keep, self.n,
                                        self.capacity)
        else:
            mask = dispatch_mask(assign, self.n, self.capacity)
            expert_in = jnp.einsum("snc,sd->ncd", mask,
                                   xrep.astype(jnp.float32))
            expert_in = expert_in.astype(data.dtype)
        return [expert_in[i] for i in range(self.n)]

    def output_axes(self):
        return [(SAMPLE, None)] * self.n


@register_op
class Aggregate(Op):
    """inputs: (gate_preds (B,k), assign (B,k), exp_pred_0..n-1 (cap, D));
    output: (B, D) weighted combine. Reference: aggregate.cc."""

    op_type = "aggregate"

    def __init__(self, model, name, inputs, n: int, capacity: int = None,
                 alpha: float = None):
        super().__init__(model, name, inputs)
        self.n = int(n)
        gate, assign = inputs[0], inputs[1]
        self.k = assign.shape[1]
        batch = gate.shape[0]
        if capacity is None:
            capacity = inputs[2].shape[0]
        self.capacity = int(capacity)
        self.attrs = {"n": n, "capacity": self.capacity}

    def output_shapes(self):
        b = self.inputs[0].shape[0]
        d = self.inputs[2].shape[-1]
        return [(b, d)]

    def output_dtypes(self):
        return [self.inputs[2].dtype]

    def forward(self, params, xs, ctx: OpContext):
        gate, assign = xs[0], xs[1]
        experts = jnp.stack(xs[2:], axis=0)  # (n, cap, D)
        mask = dispatch_mask(assign, self.n, self.capacity)  # (S, n, cap)
        gathered = jnp.einsum("snc,ncd->sd", mask,
                              experts.astype(jnp.float32))  # (B*k, D)
        b, k = assign.shape
        gathered = gathered.reshape(b, k, -1)
        out = jnp.sum(gathered * gate[:, :, None].astype(jnp.float32), axis=1)
        return [out.astype(experts.dtype)]

    def output_axes(self):
        return [(SAMPLE, None)]
