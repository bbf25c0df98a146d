"""The selective state-space mixer (Mamba-1, arXiv:2312.00752) and the
scans under it.

Per token t of a sequence, x (E):
  (u, z) = split(x W_in);  u = silu(conv(u)): a causal depthwise
  convolution over the last `d_conv` positions, with bias;
  (r, B_t, C_t) = split(u W_x);  dt = softplus(r W_dt + b_dt);
  A = -exp(A_log);  s_t = exp(dt_t A) * s_{t-1} + (dt_t u_t) B_t^T;
  y_t = s_t C_t + D * u_t;  out = (y * silu(z)) W_out.
The state s (d_state, d_inner), the recurrence, the convolution and the
gate run in f32 whatever the activation dtype. `y` (the scan's output
WITH its D term, BEFORE the gate) is the MEMORY a later gated memory
unit reads (ops/gated.py).

State is laid out (d_state, d_inner): the wide axis on the lanes.

Two scans: `selective_scan` over whole sequences from a zero state (the
graph op's forward), and `segmented_scan` over the LANES of a serving
step — runs of consecutive lanes of one sequence, each resuming from its
slot's stored state (serve/engine.py): the jnp twin of the Pallas kernel
kernels/ssm_scan.py, which the engine runs wherever it runs the paged
kernel.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..op import CHANNEL_IN, CHANNEL_OUT, SAMPLE, SEQ, Op, OpContext, \
    WeightSpec, register_op

F32 = jnp.float32
# lanes a trip of the TWIN's loop (lax.scan's `unroll`): `segmented_scan`
# below is the jnp twin of kernels/ssm_scan.py, which runs the serving
# step's recurrence on a tpu backend since PR 33; the twin runs where
# jnp attention runs (the CPU tests' engines) and where the kernel does
# not take the shape. As an XLA loop it is a chain of small operations
# on one (d_state, d_inner) state: on a v5e at the served shape (576
# lanes, 16 x 5120; PERF.md section 6, PR 32) a layer's scan took 7.66
# ms at 1, 4.68 at 2, 4.34 at 4, 4.17 at 8, 4.12 at 16, bit for bit the
# same
SCAN_UNROLL = 8


def scan_inputs(p, u, d_state: int, dt_rank: int):
    """u (..., d_inner) after the convolution and silu (f32) ->
    (dt (..., d_inner), B (..., N), C (..., N)), f32. The two small
    projections take operands in the weights' dtype and accumulate in
    f32."""
    w_x = p["w_x"]
    rbc = jnp.dot(u.astype(w_x.dtype), w_x, preferred_element_type=F32)
    r, b, c = jnp.split(rbc, [dt_rank, dt_rank + d_state], axis=-1)
    dt = jnp.dot(r.astype(p["w_dt"].dtype), p["w_dt"],
                 preferred_element_type=F32) + p["b_dt"].astype(F32)
    return jax.nn.softplus(dt), b, c


def scan_step(s, a_neg, d_skip, dt, u, b, c):
    """One token of the recurrence: s (N, d_inner) -> (s', y (d_inner)),
    all f32. a_neg = -exp(A_log) (N, d_inner)."""
    s = jnp.exp(dt[None, :] * a_neg) * s + (dt * u)[None, :] * b[:, None]
    return s, jnp.sum(s * c[:, None], axis=0) + d_skip * u


def selective_scan(p, u, dt, b, c):
    """Whole sequences from a zero state: u, dt (B, S, d_inner), b, c
    (B, S, N) -> y (B, S, d_inner), f32."""
    a_neg = -jnp.exp(p["A_log"].astype(F32))
    d_skip = p["D"].astype(F32)

    def one(u, dt, b, c):
        def step(s, x):
            return scan_step(s, a_neg, d_skip, *x)
        s0 = jnp.zeros(a_neg.shape, F32)
        return jax.lax.scan(step, s0, (dt, u, b, c))[1]

    return jax.vmap(one)(u, dt, b, c)


def causal_conv(p, u):
    """u (B, S, d_inner) -> the causal depthwise convolution over the
    last d_conv positions (zeros before the sequence), f32; with the
    bias `conv_b` where the layer has one."""
    w = p["conv_w"].astype(F32)                     # (d_conv, d_inner)
    k = w.shape[0]
    uf = u.astype(F32)
    pad = jnp.pad(uf, ((0, 0), (k - 1, 0), (0, 0)))
    s = u.shape[1]
    y = sum(pad[:, j:j + s] * w[j] for j in range(k))
    return y + p["conv_b"].astype(F32) if "conv_b" in p else y


# ------------------------------------------------- the serving step's scans
def run_starts(lane_slots, positions, xp=jnp):
    """(T,) bool: the lane starts a RUN — the step's first lane, or its
    slot or position does not continue the lane before it (numpy where
    the host counts the runs' forms)."""
    prev_s = xp.concatenate([lane_slots[:1] - 1, lane_slots[:-1]])
    prev_p = xp.concatenate([positions[:1], positions[:-1]])
    return (lane_slots != prev_s) | (positions != prev_p + 1)


def run_offsets(starts):
    """(T,) int32: the lane's index inside its run."""
    lane = jnp.arange(starts.shape[0], dtype=jnp.int32)
    first = jax.lax.cummax(jnp.where(starts, lane, 0), axis=0)
    return lane - first


def run_write_slots(starts, live, lane_slots, sink: int):
    """(T,) int32: the slot a lane's state (and tail) is written back
    to — its own where the lane is its run's last live one, else the
    slabs' `sink` row."""
    ends = jnp.concatenate([starts[1:] | ~live[1:], jnp.ones((1,), bool)])
    return jnp.where(live & ends, lane_slots, sink)


def run_tail_lanes(wslots, slots: int):
    """(slots,) int32: for each slot the lane whose last d_conv - 1
    inputs replace the slot's tail — the last lane `wslots` aims at it
    (a run's last live one) — or -1 where no run of the slot ends live
    in this step (no scatter: a compare of every lane with every
    slot)."""
    lane = jnp.arange(wslots.shape[0], dtype=jnp.int32)
    slot = jnp.arange(slots, dtype=jnp.int32)
    return jnp.max(jnp.where(wslots[None, :] == slot[:, None],
                             lane[None, :], -1), axis=1)


def segmented_conv(p, u, tail, lane_slots, positions, offsets, tail_lanes):
    """The convolution over the step's lanes. u (T, d_inner) raw
    projections; tail (slots + 1, (d_conv - 1) * d_inner) each slot's
    last raw inputs, flat (row `slots`, the other slabs' write sink, is
    left as it lies); a lane whose run offset
    is under d_conv - 1 reads what it lacks from its slot's tail, or
    zeros where the sequence starts inside the run. `tail_lanes`
    (slots,): the lane whose last d_conv - 1 inputs replace the slot's
    tail (`run_tail_lanes`), -1 where the tail stays: the write-back
    follows the runs, a gather of at most `slots` rows and a select, not
    the lanes. -> (conv output f32 (T, d_inner), tail)."""
    w = p["conv_w"].astype(F32)
    k = w.shape[0]
    t = u.shape[0]
    old = jnp.take(tail, lane_slots, axis=0).reshape(t, k - 1, -1)
    # the slot's tail holds positions P-k+1 .. P-1 for P the run's
    # first position; a sequence that starts in this run has none
    run_pos0 = positions - offsets
    old = jnp.where((run_pos0 > 0)[:, None, None], old, 0)
    hist = []                                  # x_{p-j}, j = k-1 .. 1
    for j in range(k - 1, 0, -1):
        shifted = jnp.concatenate(
            [jnp.zeros((j,) + u.shape[1:], u.dtype), u[:t - j]])
        idx = jnp.clip(k - 1 + offsets - j, 0, k - 2)
        from_tail = jnp.take_along_axis(
            old, idx[:, None, None], axis=1)[:, 0]
        hist.append(jnp.where((offsets >= j)[:, None], shifted, from_tail))
    y = u.astype(F32) * w[k - 1]
    if "conv_b" in p:           # a convolution without bias has no leaf
        y = y + p["conv_b"].astype(F32)
    for j, h in zip(range(k - 1, 0, -1), hist):
        y = y + h.astype(F32) * w[k - 1 - j]
    slots = tail_lanes.shape[0]
    src = jnp.maximum(tail_lanes, 0)
    new = jnp.concatenate([h[src] for h in hist[1:] + [u]],
                          axis=1).astype(tail.dtype)
    kept = jnp.where((tail_lanes >= 0)[:, None], new, tail[:slots])
    return y, tail.at[:slots].set(kept)


def segmented_scan(p, u, dt, b, c, state, lane_slots, positions, starts,
                   wslots):
    """The recurrence over the step's lanes, one after another (the
    jnp twin of kernels/ssm_scan.py::ssm_scan, which takes the whole
    slab and a layer, walks the live lanes only and leaves the dead
    lanes' rows of y zero). state
    (slots + 1, N, d_inner): a run's first lane takes its slot's state
    (zeros at position 0), every lane writes the state to `wslots`
    (its slot where the lane is a run's last live one, else the sink
    row). The carried state is rounded to the slab's dtype at every
    lane (a no-op on the f32 slab), so what a run carries does not
    depend on where a step cut it. -> (y (T, d_inner) f32, state)."""
    a_neg = -jnp.exp(p["A_log"].astype(F32))
    d_skip = p["D"].astype(F32)

    def step(carry, x):
        s, state = carry
        dt, u, b, c, slot, pos, start, wslot = x
        s0 = jnp.where(pos > 0, state[slot].astype(F32), 0.0)
        s = jnp.where(start, s0, s)
        s, y = scan_step(s, a_neg, d_skip, dt, u, b, c)
        stored = s.astype(state.dtype)
        s = stored.astype(F32)
        state = jax.lax.dynamic_update_index_in_dim(
            state, stored, wslot, 0)
        return (s, state), y

    (_, state), y = jax.lax.scan(
        step, (jnp.zeros(a_neg.shape, F32), state),
        (dt, u, b, c, lane_slots, positions, starts, wslots),
        unroll=SCAN_UNROLL)
    return y, state


# ----------------------------------------------------------------- the op
def _a_log_init(key, shape, dtype=F32):
    """A = -(1 .. N) in every channel (Mamba's S4D-real start)."""
    n = shape[0]
    return jnp.broadcast_to(
        jnp.log(jnp.arange(1, n + 1, dtype=F32))[:, None], shape
    ).astype(dtype)


def _dt_bias_init(key, shape, dtype=F32):
    """softplus^-1 of a step drawn log-uniformly in [1e-3, 1e-1]."""
    dt = jnp.exp(jax.random.uniform(key, shape, F32)
                 * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


@register_op
class SelectiveScanMixer(Op):
    """x (B, S, E) -> [out (B, S, E)] and, with `emit_memory`, the
    scan's output before the gate (B, S, d_inner) as a second output."""

    op_type = "selective_scan_mixer"

    def __init__(self, model, name, inputs, d_inner: int, d_state: int = 16,
                 d_conv: int = 4, dt_rank: int = 0,
                 emit_memory: bool = False,
                 kernel_initializer: str = "glorot"):
        super().__init__(model, name, inputs)
        self.embed_dim = int(inputs[0].shape[-1])
        self.d_inner = int(d_inner)
        self.d_state = int(d_state)
        self.d_conv = int(d_conv)
        self.dt_rank = int(dt_rank) or -(-self.embed_dim // 16)
        self.emit_memory = bool(emit_memory)
        self.kernel_initializer = kernel_initializer
        self.attrs = {"d_inner": self.d_inner, "d_state": self.d_state,
                      "d_conv": self.d_conv, "dt_rank": self.dt_rank,
                      "emit_memory": self.emit_memory}

    def output_shapes(self):
        lead = tuple(self.inputs[0].shape[:-1])
        out = [lead + (self.embed_dim,)]
        if self.emit_memory:
            out.append(lead + (self.d_inner,))
        return out

    def output_dtypes(self):
        return [self.inputs[0].dtype] * (2 if self.emit_memory else 1)

    def weight_specs(self):
        e, di, n, r = (self.embed_dim, self.d_inner, self.d_state,
                       self.dt_rank)
        init = self.kernel_initializer
        return {
            "w_in": WeightSpec((e, 2 * di), initializer=init,
                               axes=(CHANNEL_IN, CHANNEL_OUT)),
            "conv_w": WeightSpec((self.d_conv, di), initializer=init,
                                 fan_in=self.d_conv, fan_out=self.d_conv),
            "conv_b": WeightSpec((di,), initializer="zeros"),
            "w_x": WeightSpec((di, r + 2 * n), initializer=init),
            "w_dt": WeightSpec((r, di), initializer=init),
            "b_dt": WeightSpec((di,), custom_init=_dt_bias_init),
            "A_log": WeightSpec((n, di), custom_init=_a_log_init),
            "D": WeightSpec((di,), initializer="ones"),
            "w_out": WeightSpec((di, e), initializer=init,
                                axes=(CHANNEL_IN, CHANNEL_OUT)),
        }

    def forward(self, params, xs, ctx: OpContext):
        (x,) = xs
        p = params
        uz = jnp.dot(x, p["w_in"].astype(x.dtype),
                     preferred_element_type=F32).astype(x.dtype)
        u, z = jnp.split(uz, 2, axis=-1)
        u = jax.nn.silu(causal_conv(p, u))
        dt, b, c = scan_inputs(p, u, self.d_state, self.dt_rank)
        y = selective_scan(p, u, dt, b, c)
        g = (y * jax.nn.silu(z.astype(F32))).astype(x.dtype)
        out = jnp.dot(g, p["w_out"].astype(x.dtype),
                      preferred_element_type=F32).astype(x.dtype)
        return [out, y.astype(x.dtype)] if self.emit_memory else [out]

    def output_axes(self):
        return [(SAMPLE, SEQ, None)] * len(self.outputs)

    def input_axes(self):
        return [(SAMPLE, SEQ, None)]

    def flops(self) -> float:
        n_tok = 1
        for s in self.inputs[0].shape[:-1]:
            n_tok *= s
        e, di, n, r = (self.embed_dim, self.d_inner, self.d_state,
                       self.dt_rank)
        per = 2.0 * (e * 2 * di + di * (r + 2 * n) + r * di + di * e) \
            + di * (2 * self.d_conv + 9 * n)
        return n_tok * per
