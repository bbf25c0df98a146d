"""Pipeline parallelism: GPipe-style microbatch schedule over a mesh
`pipe` axis via shard_map + collective permute.

The reference has NO pipeline schedule — its "model parallelism" is
per-op device placement with concurrency only from Legion dataflow
asynchrony (SURVEY.md 2.4). Here PP is a first-class axis: a stack of
identical blocks (leading dim L) is split into S = |pipe| stages of L/S
layers; M microbatches stream through the ring. Device s computes
microbatch m at tick t = m + s; activations hop stages via ppermute.
Bubble fraction = (S-1)/(M+S-1), the standard GPipe bound.

All devices run the same SPMD program (XLA requirement); stage-dependent
behavior comes from `lax.axis_index`.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(block_fn: Callable, stacked_params, x, mesh: Mesh,
                   *, pipe_axis: str = "pipe", num_microbatches: int,
                   num_layers: int, data_axis: str = "data"):
    """Run x through L stacked blocks, pipelined over `pipe_axis`.

    block_fn(layer_params, h, layer_idx) -> (y, aux) with
    y.shape == h.shape and aux a float32 scalar (0.0 if unused).
    stacked_params: pytree, every leaf has leading dim L (L % S == 0);
    may be empty for weightless blocks.
    x: (B, ...) global batch; B % num_microbatches == 0.
    Returns (out (B, ...), aux_total scalar).

    Note: under PP the aux term is the mean over microbatches of the
    per-microbatch aux — for nonlinear aux losses (e.g. MoE balancing)
    this is an approximation of the full-batch value.
    """
    L = num_layers

    if pipe_axis not in mesh.shape or mesh.shape[pipe_axis] == 1:
        def body(carry, inp):
            h, aux = carry
            layer_params, li = inp
            y, a = block_fn(layer_params, h, li)
            return (y, aux + a), None
        (out, aux), _ = lax.scan(
            body, (x, jnp.float32(0.0)),
            (stacked_params, jnp.arange(L)), length=L)
        return out, aux

    S = mesh.shape[pipe_axis]
    M = num_microbatches
    B = x.shape[0]
    assert B % M == 0, f"batch {B} not divisible by {M} microbatches"
    mb = B // M
    xm = x.reshape((M, mb) + x.shape[1:])
    assert L % S == 0, f"{L} layers not divisible by {S} stages"
    l_loc = L // S

    data_ax = data_axis if data_axis in mesh.shape else None
    # params: layer dim sharded over pipe; x: microbatches replicated over
    # pipe (each sharded over data on the batch dim inside the microbatch)
    param_spec = jax.tree_util.tree_map(
        lambda l: P(pipe_axis, *([None] * (l.ndim - 1))), stacked_params)
    x_spec = P(None, data_ax, *([None] * (x.ndim - 1)))

    def local_fn(params_local, xm_local):
        # params_local leaves: (L/S, ...); xm_local: (M, mb_local, ...)
        idx = lax.axis_index(pipe_axis)
        zero = jnp.zeros_like(xm_local[0])

        def stage_compute(carry_in, t):
            # first stage consumes microbatch t; later stages consume the
            # activation handed over from the previous stage
            mb_idx = jnp.clip(t, 0, M - 1)
            my_in = jnp.where(idx == 0,
                              lax.dynamic_index_in_dim(
                                  xm_local, mb_idx, keepdims=False),
                              carry_in)

            def layer(carry, inp):
                h, aux = carry
                lp, lj = inp
                y, a = block_fn(lp, h, idx * l_loc + lj)
                return (y, aux + a), None
            (out, aux), _ = lax.scan(
                layer, (my_in, jnp.float32(0.0)),
                (params_local, jnp.arange(l_loc)), length=l_loc)
            return out, aux

        def tick(carry, t):
            carry_act, outputs, aux_acc = carry
            out, aux = stage_compute(carry_act, t)
            # this stage's compute is meaningful only for 0 <= t-idx < M
            # (warmup/drain ticks process garbage; mask their aux)
            valid = jnp.logical_and(t - idx >= 0, t - idx < M)
            aux_acc = aux_acc + jnp.where(valid, aux, 0.0)
            # hand activation to the next stage (ring; last->first wraps
            # but the wrapped value is ignored by stage 0)
            perm = [(i, (i + 1) % S) for i in range(S)]
            nxt = lax.ppermute(out, pipe_axis, perm)
            # last stage finished microbatch t-(S-1) this tick
            done_idx = t - (S - 1)
            write = jnp.logical_and(idx == S - 1, done_idx >= 0)
            safe_idx = jnp.clip(done_idx, 0, M - 1)
            cur = lax.dynamic_index_in_dim(outputs, safe_idx,
                                           keepdims=False)
            upd = jnp.where(write, out, cur)
            outputs = lax.dynamic_update_index_in_dim(
                outputs, upd, safe_idx, 0)
            return (nxt, outputs, aux_acc), None

        outputs0 = jnp.zeros_like(xm_local)
        (_, outputs, aux_acc), _ = lax.scan(
            tick, (zero, outputs0, jnp.float32(0.0)),
            jnp.arange(M + S - 1))
        # results live on the last stage; broadcast to all stages so the
        # output spec can stay replicated over pipe
        outputs = lax.psum(
            jnp.where(idx == S - 1, outputs, jnp.zeros_like(outputs)),
            pipe_axis)
        # aux: sum over stages' valid ticks, averaged over microbatches
        aux_total = lax.psum(aux_acc, pipe_axis) / M
        return outputs, aux_total

    out, aux = shard_map(local_fn, mesh=mesh,
                         in_specs=(param_spec, x_spec),
                         out_specs=(x_spec, P()),
                         check_vma=False)(stacked_params, xm)
    return out.reshape((B,) + x.shape[1:]), aux
