"""Generalized pipeline parallelism over ARBITRARY op graphs.

Reference FlexFlow executes per-op device placement by routing each op's
index-task points to its `ParallelConfig.device_ids`
(/root/reference/src/mapper/mapper.cc:346-440); concurrency between ops
placed on different devices comes from Legion's dataflow asynchrony.
XLA's SPMD model has no per-op device routing — every device runs one
program — so the TPU-native execution of "layer L on device d" is a
PIPELINE: stages are contiguous groups of ops, the mesh `pipe` axis
assigns one stage per device coordinate, and microbatches stream
through the ring (shard_map + lax.switch on the stage index +
lax.ppermute hops). This file is that lowering:

  * ``StagePlan``     — partition of the op graph into S stages, with
    the boundary (cut) tensors each inter-stage hop must carry.
    Built either from a strategy's explicit whole-op device pins
    (`assignment_from_pins`, the executable form of the reference's
    propagate-placed strategies model.cc:1807-1903) or by flops-balanced
    auto-cut (`balanced_stages`, the analog of SURVEY §7 hard part (c):
    searching stage boundaries).
  * ``PackSpec``      — per-stage parameter flat-packing: every stage's
    weights flatten into one (S, L) row per dtype, sharded over the
    pipe axis, so each device PHYSICALLY holds only its stage's
    parameters (and its optimizer state rows) — true weight residency,
    not replication. Elementwise optimizers (SGD/Adam) apply to packed
    rows unchanged.
  * ``pipeline_logits`` — the schedule. GPipe semantics: M microbatches,
    M + S - 1 ticks, bubble fraction (S-1)/(M+S-1); backward runs as the
    autodiff transpose of the same schedule (reverse pipeline).
    `schedule="1f1b"` interleaves each stage's backward with remaining
    forwards via a two-wire (activation + cotangent) steady state,
    cutting peak per-stage activation storage from M to S microbatches.

Heterogeneous stages are expressed as `lax.switch` branches on
`lax.axis_index(pipe)`: XLA compiles every stage body once, each device
executes its own branch — the one-program answer to Legion's per-device
task variants.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..op import Op, OpContext


# --------------------------------------------------------------------------
# stage planning
# --------------------------------------------------------------------------

@dataclasses.dataclass
class StagePlan:
    """Partition of a model's op graph into pipeline stages.

    stages[s]    ops of stage s, in topological order
    stage_of     op name -> stage index
    cuts[i]      tensors crossing the boundary between stages <= i and
                 stages > i (each must ride hop i of the wire)
    """

    stages: List[List[Op]]
    stage_of: Dict[str, int]
    cuts: List[List]  # List[List[Tensor]]

    @property
    def num_stages(self) -> int:
        return len(self.stages)


def _check_supported(model, stage_of: Dict[str, int]) -> None:
    # stateful ops (BatchNorm) are legal under BOTH schedules: packed
    # state rows advance per microbatch in order at fwd ticks
    # (grad-accumulation semantics); 1F1B's backward recompute reads
    # state as a constant, guarded by Op.training_output_reads_state
    # (StagedExecutor rejects ops that set it)
    for op in model.ops:
        if op.op_type == "pipeline_blocks":
            raise NotImplementedError(
                f"graph pipeline: {op.name!r} is itself a pipeline "
                f"meta-op; nesting pipelines is not supported")
        if op.name not in stage_of:
            raise ValueError(f"op {op.name!r} has no stage assignment")


def build_stage_plan(model, stage_of: Dict[str, int]) -> StagePlan:
    """Materialize a StagePlan from an op->stage map. Validates that
    data flows forward (producer stage <= consumer stage) and computes
    the cut tensors every hop must carry."""
    _check_supported(model, stage_of)
    S = max(stage_of.values()) + 1
    producer = {}
    for op in model.ops:
        for t in op.outputs:
            producer[t.uid] = op.name
    input_uids = {t.uid for t in model.input_tensors}
    for op in model.ops:
        for t in op.inputs:
            if t.uid in input_uids:
                continue  # graph inputs are microbatch-fed to every stage
            ps = stage_of[producer[t.uid]]
            if ps > stage_of[op.name]:
                raise ValueError(
                    f"stage assignment sends tensor {t.uid} backward: "
                    f"producer {producer[t.uid]!r} is stage {ps}, "
                    f"consumer {op.name!r} is stage "
                    f"{stage_of[op.name]} — pipeline hops only go "
                    f"forward")
    stages: List[List[Op]] = [[] for _ in range(S)]
    for op in model.ops:  # model.ops is topological order
        stages[stage_of[op.name]].append(op)

    # last consumer stage per tensor; the model output is virtually
    # consumed at the last stage (it must arrive there to be emitted)
    last_use: Dict[int, int] = {}
    for op in model.ops:
        for t in op.inputs:
            if t.uid in input_uids:
                continue
            last_use[t.uid] = max(last_use.get(t.uid, 0),
                                  stage_of[op.name])
    final_uid = model.final_tensor.uid
    last_use[final_uid] = S - 1

    cuts: List[List] = []
    by_uid = {}
    for op in model.ops:
        for t in op.outputs:
            by_uid[t.uid] = t
    batch = model.input_tensors[0].shape[0] if model.input_tensors \
        else None
    for i in range(S - 1):
        cut = [by_uid[uid] for uid, last in sorted(last_use.items())
               if stage_of[producer[uid]] <= i < last]
        for t in cut:
            # the wire microbatches dim 0: a tensor whose dim 0 is NOT
            # the batch (e.g. GroupBy's (capacity, D) expert buffers)
            # would be silently reinterpreted sample-wise
            if batch is not None and (not t.shape
                                      or t.shape[0] != batch):
                raise NotImplementedError(
                    f"graph pipeline: tensor {t.uid} "
                    f"(shape {t.shape}, producer "
                    f"{producer[t.uid]!r}) crosses the stage-"
                    f"{i}/{i + 1} boundary but its dim 0 is not the "
                    f"batch dim ({batch}); cut elsewhere")
        cuts.append(cut)
    return StagePlan(stages=stages, stage_of=dict(stage_of), cuts=cuts)


def balanced_stages(model, num_stages: int) -> Dict[str, int]:
    """Flops-balanced contiguous auto-cut: partition the topological op
    order into `num_stages` segments minimizing the max per-stage flops
    (linear-partition DP). The searchable analog of the reference's
    hand-chosen per-layer placements."""
    ops = model.ops
    n = len(ops)
    S = min(num_stages, n)
    costs = [max(float(op.flops()), 1.0) for op in ops]
    prefix = np.concatenate([[0.0], np.cumsum(costs)])

    def seg(i, j):  # cost of ops[i:j]
        return prefix[j] - prefix[i]

    INF = float("inf")
    # dp[k][j] = best max-stage-cost splitting ops[:j] into k stages
    dp = [[INF] * (n + 1) for _ in range(S + 1)]
    cut = [[0] * (n + 1) for _ in range(S + 1)]
    dp[0][0] = 0.0
    for k in range(1, S + 1):
        for j in range(k, n + 1):
            for i in range(k - 1, j):
                c = max(dp[k - 1][i], seg(i, j))
                if c < dp[k][j]:
                    dp[k][j] = c
                    cut[k][j] = i
    bounds = [n]
    j = n
    for k in range(S, 0, -1):
        j = cut[k][j]
        bounds.append(j)
    bounds.reverse()  # [0, c1, ..., n]
    stage_of = {}
    for s in range(S):
        for op in ops[bounds[s]:bounds[s + 1]]:
            stage_of[op.name] = s
    return stage_of


def assignment_from_pins(model, strategy) -> Optional[Dict[str, int]]:
    """Derive a stage assignment from a strategy's whole-op device pins
    (length-1 `__devices__` tuples on non-embedding ops) — the
    executable lowering of reference propagate-placed strategies
    (model.cc:1807-1903). Stage order = device-id order. Unpinned ops
    inherit the latest stage among their producers. Returns None when no
    such pins exist; raises if the pins cannot form a forward pipeline
    (caller falls back to replication with the compile warning)."""
    pins = {}
    for op in model.ops:
        s = strategy.for_op(op.name)
        ids = s.device_ids
        if ids is None or op.op_type == "distributed_embedding":
            continue
        if len(set(ids)) != 1:
            raise ValueError(
                f"op {op.name!r}: multi-device pin {ids} has no "
                f"executable lowering (whole-op pins = one device id; "
                f"use axis_map sharding for intra-op splits)")
        pins[op.name] = int(ids[0])
    if not pins:
        return None
    order = sorted(set(pins.values()))
    rank = {d: i for i, d in enumerate(order)}
    producer = {}
    for op in model.ops:
        for t in op.outputs:
            producer[t.uid] = op.name
    input_uids = {t.uid for t in model.input_tensors}
    stage_of: Dict[str, int] = {}
    for op in model.ops:
        inherited = 0
        for t in op.inputs:
            if t.uid not in input_uids:
                inherited = max(inherited, stage_of[producer[t.uid]])
        stage_of[op.name] = (rank[pins[op.name]] if op.name in pins
                             else inherited)
    # pipelining is only meaningful for SEQUENTIAL placements: each
    # consecutive stage pair must be bridged by a real data edge
    # (producer in stage i feeding a consumer in stage i+1). Pins on
    # parallel SIBLING branches (e.g. DLRM's independent per-table
    # embeddings round-robined over devices) express concurrency, not
    # a pipeline — serializing them into stages would slow them down;
    # they fall back to the simulator's per-device concurrency pricing
    # (and, for embeddings, the distributed_embedding slot layout is
    # the executable form).
    S = max(stage_of.values()) + 1
    if S > 1:
        bridged = [False] * (S - 1)
        for op in model.ops:
            dst = stage_of[op.name]
            for t in op.inputs:
                if t.uid in input_uids:
                    continue
                src = stage_of[producer[t.uid]]
                if src == dst - 1:
                    bridged[src] = True
        if not all(bridged):
            gap = bridged.index(False)
            raise ValueError(
                f"pins do not form a sequential pipeline: no tensor "
                f"flows from stage {gap} to stage {gap + 1} (the "
                f"pinned ops are parallel siblings — placement there "
                f"means concurrency, not pipelining)")
    return stage_of


def pick_pipe_axis(mesh: Mesh, num_stages: int) -> Optional[str]:
    """Mesh axis to pipeline over: prefer an axis literally named
    'pipe'/'layer' of the right size, else any non-'data' axis whose
    size equals the stage count."""
    if mesh is None:
        return None
    for name in ("pipe", "layer"):
        if mesh.shape.get(name) == num_stages:
            return name
    for name, size in mesh.shape.items():
        if name != "data" and size == num_stages:
            return name
    return None


# --------------------------------------------------------------------------
# parameter flat-packing
# --------------------------------------------------------------------------

@dataclasses.dataclass
class _Segment:
    stage: int
    dtype: str
    offset: int
    size: int
    shape: Tuple[int, ...]
    row: int = -1  # physical row in the packed array (= stage unless
    #                an interleaved layout permutes ownership)

    def __post_init__(self):
        if self.row < 0:
            self.row = self.stage


@dataclasses.dataclass
class PackSpec:
    """Layout of per-stage flat-packed parameters.

    Packed form: {dtype_str: (S, L_dtype)} — one row per stage
    (weights flattened, concatenated, zero-padded to the longest
    stage). Sharded P(pipe, None): each device holds its rows, so
    weights (and elementwise-optimizer state, which mirrors the packed
    tree) physically reside on their pinned device.

    Interleaved layouts (virtual_stages v > 1 over D devices): stage s
    lives on device s % D (round-robin — every pipeline hop is a ring
    neighbor), but NamedSharding blocks rows contiguously per device,
    so stages pack in DEVICE-MAJOR row order: row(s) = (s % D) * v +
    s // D. Device d then owns rows [d*v, (d+1)*v) = its stages
    {d, d+D, ...}.
    """

    segments: Dict[Tuple[str, str], _Segment]  # (op, weight) -> segment
    lengths: Dict[str, int]                    # dtype -> L
    num_stages: int
    virtual_stages: int = 1

    def row_layout(self, stage: int) -> List[Tuple[str, str, _Segment]]:
        return [(op, w, seg) for (op, w), seg in self.segments.items()
                if seg.stage == stage]


def make_pack_spec(plan: StagePlan, n_dev: Optional[int] = None,
                   specs_of=None, pad_to: int = 1) -> PackSpec:
    """Flat-pack layout for per-stage tensors. `specs_of` selects what
    packs (default: weight_specs; pass `lambda op: op.state_specs()`
    for the functional-state rows BatchNorm et al. carry). `pad_to`
    rounds each dtype's row length up to a multiple — set to the data
    axis size so ZeRO can shard the optimizer rows' L dimension."""
    if specs_of is None:
        specs_of = lambda op: op.weight_specs()  # noqa: E731
    S = plan.num_stages
    v = 1
    if n_dev is not None and n_dev > 0 and S != n_dev:
        if S % n_dev != 0:
            # a truncated v would map two stages onto one packed row
            # and silently overwrite weights
            raise ValueError(
                f"{S} stages do not divide over {n_dev} devices")
        v = S // n_dev

    def row_of(s: int) -> int:
        return (s % n_dev) * v + s // n_dev if v > 1 else s

    segments: Dict[Tuple[str, str], _Segment] = {}
    lengths: Dict[str, int] = {}
    for s, ops in enumerate(plan.stages):
        offsets: Dict[str, int] = {}
        for op in ops:
            for wname, spec in specs_of(op).items():
                dt = np.dtype(spec.dtype).name
                size = int(np.prod(spec.shape)) if spec.shape else 1
                off = offsets.get(dt, 0)
                segments[(op.name, wname)] = _Segment(
                    stage=s, dtype=dt, offset=off, size=size,
                    shape=tuple(spec.shape), row=row_of(s))
                offsets[dt] = off + size
        for dt, end in offsets.items():
            lengths[dt] = max(lengths.get(dt, 0), end)
    if not lengths:  # weightless graph: keep one dummy lane so the
        lengths["float32"] = 1  # packed tree / optimizer state is non-empty
    if pad_to > 1:
        lengths = {dt: -(-L // pad_to) * pad_to
                   for dt, L in lengths.items()}
    return PackSpec(segments=segments, lengths=lengths,
                    num_stages=S, virtual_stages=v)


def pack_params(spec: PackSpec, params_by_op: Dict[str, Dict[str, np.ndarray]]):
    """Host-side: {op: {w: array}} -> {dtype: (S, L) ndarray}."""
    packed = {dt: np.zeros((spec.num_stages, L), dtype=dt)
              for dt, L in spec.lengths.items()}
    for (opn, wn), seg in spec.segments.items():
        arr = np.asarray(params_by_op[opn][wn]).reshape(-1)
        packed[seg.dtype][seg.row, seg.offset:seg.offset + seg.size] = arr
    return packed


def unpack_stage(spec: PackSpec, packed_row: Dict[str, jax.Array],
                 stage: int) -> Dict[str, Dict[str, jax.Array]]:
    """Trace-time: slice one stage's weights out of its packed row
    ({dtype: (L,)}). `stage` is static (each switch branch closes over
    its own)."""
    out: Dict[str, Dict[str, jax.Array]] = {}
    for opn, wn, seg in spec.row_layout(stage):
        flat = lax.dynamic_slice_in_dim(packed_row[seg.dtype],
                                        seg.offset, seg.size)
        out.setdefault(opn, {})[wn] = flat.reshape(seg.shape)
    return out


def update_stage_row(spec: PackSpec, row: Dict[str, jax.Array],
                     stage: int, by_op: Dict[str, Dict[str, jax.Array]]
                     ) -> Dict[str, jax.Array]:
    """Trace-time: write per-op entries (e.g. ctx.state_out) back into
    one stage's packed row ({dtype: (L,)}). `stage` is static."""
    out = dict(row)
    for opn, wn, seg in spec.row_layout(stage):
        val = by_op.get(opn, {}).get(wn)
        if val is None:
            continue
        out[seg.dtype] = lax.dynamic_update_slice_in_dim(
            out[seg.dtype],
            val.reshape(-1).astype(out[seg.dtype].dtype),
            seg.offset, axis=0)
    return out


def read_op_weights(spec: PackSpec, packed, op_name: str):
    """Host-side view of one op's weights out of the packed arrays."""
    out = {}
    for (opn, wn), seg in spec.segments.items():
        if opn != op_name:
            continue
        row = np.asarray(packed[seg.dtype][seg.row])
        out[wn] = row[seg.offset:seg.offset + seg.size].reshape(seg.shape)
    return out


def write_op_weights(spec: PackSpec, packed, op_name: str,
                     weights: Dict[str, np.ndarray]):
    """Return a new packed dict with `op_name`'s weights replaced."""
    host = {dt: np.asarray(a).copy() for dt, a in packed.items()}
    for wn, arr in weights.items():
        seg = spec.segments.get((op_name, wn))
        if seg is None:
            raise KeyError(
                f"{op_name!r} has no weight {wn!r} in the stage packing")
        a = np.asarray(arr)
        if tuple(a.shape) != seg.shape:
            raise ValueError(
                f"{op_name}.{wn}: shape {a.shape} != declared {seg.shape}")
        host[seg.dtype][seg.row,
                        seg.offset:seg.offset + seg.size] = \
            a.astype(host[seg.dtype].dtype, copy=False).reshape(-1)
    return host


# --------------------------------------------------------------------------
# wire (inter-stage hop buffer)
# --------------------------------------------------------------------------

def _wire_layouts(plan: StagePlan, model=None):
    """Per-cut flat layout and per-dtype max hop width. The wire is one
    {dtype: (W,)} buffer: every device sends/receives the same shapes
    (SPMD), each interprets its own cut's layout.

    Under an active compute_dtype policy (core/precision.py) FLOAT cut
    tensors ride the wire at the compute dtype: stage activations are
    already compute-dtype inside the stage, and an f32 wire would both
    double the hop bytes and silently upcast the downstream stage's
    whole compute (ops follow their input dtype)."""
    from ..core import precision as MP
    wire_dt = None
    if model is not None and MP.policy_active(model.config):
        wire_dt = np.dtype(model.config.compute_dtype).name
    layouts = []
    widths: Dict[str, int] = {}
    for cut in plan.cuts:
        lay = []
        offsets: Dict[str, int] = {}
        for t in cut:
            dt = np.dtype(t.dtype).name
            if wire_dt is not None and jnp.issubdtype(jnp.dtype(dt),
                                                      jnp.floating):
                dt = wire_dt
            size = int(np.prod(t.shape[1:]))  # per-sample; dim0 = batch
            off = offsets.get(dt, 0)
            lay.append((t.uid, dt, off, size, tuple(t.shape[1:])))
            offsets[dt] = off + size
        for dt, end in offsets.items():
            widths[dt] = max(widths.get(dt, 0), end)
        layouts.append(lay)
    if not widths:
        widths["float32"] = 1
    return layouts, widths


# --------------------------------------------------------------------------
# the pipelined forward
# --------------------------------------------------------------------------

def _make_stage_runner(plan: StagePlan, pack: PackSpec, model, layouts,
                       widths, mb_local: int, *, training: bool,
                       seq_length: int, remat: bool = False,
                       state_pack: Optional[PackSpec] = None):
    """Shared stage body for both schedules: unpack weights + incoming
    wire, run the stage's ops, emit (wire_out, final, aux,
    state_row_out). Pure compute — collectives stay at the tick level
    (SPMD-uniform across switch branches). `state_pack` carries
    functional state (BatchNorm running stats) as packed per-stage
    rows, updated in place each tick; without it state_row passes
    through untouched. `remat=True` wraps each stage tick in
    jax.checkpoint so the GPipe backward recomputes stage activations
    from the saved tick inputs instead of storing every intermediate —
    most of 1F1B's activation savings without the interleaved schedule
    (the 1F1B path recomputes inherently and must NOT also remat)."""
    S = plan.num_stages
    final_t = model.final_tensor
    name_of_input = {t.name: t.uid for t in model.input_tensors}
    # mixed-precision policy: stage weights unpack from their (f32)
    # master rows and are cast to compute_dtype per tick, INSIDE the
    # (possibly vjp'd) stage body — cotangents upcast at the cast, so
    # 1F1B's explicit per-stage gradients and GPipe's autodiff
    # transpose both accumulate into f32 packed rows. Float microbatch
    # inputs cast the same way; the wire already carries compute-dtype
    # activations (_wire_layouts).
    from ..core import precision as MP
    mp_dtype = (jnp.dtype(model.config.compute_dtype)
                if MP.policy_active(model.config) else None)

    def run_stage(s: int, row: Dict[str, jax.Array],
                  wire_in: Dict[str, jax.Array],
                  mb_in: Dict[str, jax.Array], mb_rng,
                  state_row: Optional[Dict[str, jax.Array]] = None):
        if state_row is None:
            state_row = {}
        if remat and training and mb_rng is not None:
            # prevent_cse=False: the CSE-prevention barriers exist for
            # remat OUTSIDE scans; inside the tick lax.scan they only
            # block fusion (per the jax.checkpoint docs)
            return jax.checkpoint(functools.partial(_stage_core, s),
                                  prevent_cse=False)(
                row, wire_in, mb_in, mb_rng, state_row)
        return _stage_core(s, row, wire_in, mb_in, mb_rng, state_row)

    def _stage_core(s: int, row: Dict[str, jax.Array],
                    wire_in: Dict[str, jax.Array],
                    mb_in: Dict[str, jax.Array], mb_rng,
                    state_row: Dict[str, jax.Array]):
        values: Dict[int, jax.Array] = {}
        for name, v in mb_in.items():
            if mp_dtype is not None and MP.is_float_array(v) \
                    and v.dtype != mp_dtype:
                v = v.astype(mp_dtype)
            values[name_of_input[name]] = v
        if s > 0:
            for uid, dt, off, size, shape in layouts[s - 1]:
                flat = lax.dynamic_slice_in_dim(
                    wire_in[dt], off * mb_local, size * mb_local)
                values[uid] = flat.reshape((mb_local,) + shape)
        params_s = unpack_stage(pack, row, s)
        if mp_dtype is not None:
            params_s = MP.cast_floats(params_s, mp_dtype)
        states_s = (unpack_stage(state_pack, state_row, s)
                    if state_pack is not None else {})
        state_updates: Dict[str, Dict[str, jax.Array]] = {}
        aux = jnp.float32(0.0)
        for i, op in enumerate(plan.stages[s]):
            ctx = OpContext(
                training=training,
                rng=(jax.random.fold_in(mb_rng, i)
                     if mb_rng is not None else None),
                seq_length=seq_length,
                state_in=states_s.get(op.name, {}),
                mesh=None, op_strategy=None)
            xs = [values[t.uid] for t in op.inputs]
            ys = op.forward(params_s.get(op.name, {}), xs, ctx)
            if mp_dtype is not None:
                # value stream stays compute-dtype (dtype-pinning ops
                # like Embedding would upcast the rest of the stage —
                # mirror of the base executor's walk)
                ys = [y.astype(mp_dtype)
                      if MP.is_float_array(y) and y.dtype != mp_dtype
                      else y for y in ys]
            for t, y in zip(op.outputs, ys):
                values[t.uid] = y
            if ctx.aux_loss is not None:
                aux = aux + ctx.aux_loss
            if ctx.state_out:
                state_updates[op.name] = ctx.state_out
        state_row_out = (update_stage_row(state_pack, state_row, s,
                                          state_updates)
                         if state_pack is not None and state_updates
                         else state_row)
        wire_out = {dt: jnp.zeros((w * mb_local,), dtype=dt)
                    for dt, w in widths.items()}
        if s < S - 1:
            for uid, dt, off, size, shape in layouts[s]:
                wire_out[dt] = lax.dynamic_update_slice_in_dim(
                    wire_out[dt],
                    values[uid].reshape(-1).astype(wire_out[dt].dtype),
                    off * mb_local, axis=0)
        if s == S - 1:
            # declared dtype, not the compute dtype: every lax.switch
            # branch must return identical types, and the non-final
            # stages emit final_t.dtype zeros
            final = values[final_t.uid].astype(final_t.dtype)
        else:
            final = jnp.zeros((mb_local,) + tuple(final_t.shape[1:]),
                              dtype=final_t.dtype)
        return wire_out, final, aux, state_row_out

    return run_stage


def _data_split(mesh: Mesh, data_axis: Optional[str], mb: int):
    """(data_ax or None, n_data, mb_local): microbatches shard over the
    data axis inside each stage when divisible, else replicate."""
    data_ax = data_axis if (data_axis and data_axis in mesh.shape) else None
    ndata = mesh.shape[data_ax] if data_ax else 1
    if mb % ndata != 0:
        data_ax, ndata = None, 1
    return data_ax, ndata, mb // ndata


def pipeline_logits(plan: StagePlan, pack: PackSpec, packed,
                    inputs: Dict[str, jax.Array], rng, mesh: Mesh,
                    pipe_axis: str, data_axis: Optional[str],
                    num_microbatches: int, model, *, training: bool,
                    seq_length: int = -1, schedule: str = "gpipe",
                    state_pack: Optional[PackSpec] = None,
                    state_packed=None):
    """Run the staged graph pipelined over `pipe_axis`; returns
    (logits (B, ...), aux_loss scalar, new_state_packed).

    `state_pack`/`state_packed` carry functional state (BatchNorm
    running stats) as {dtype: (S, L)} rows sharded like the weights;
    each stage's forward tick updates its row in microbatch order —
    gradient-accumulation semantics. On a data axis every shard
    computes LOCAL batch statistics (standard DDP BatchNorm behavior)
    and the returned rows are the mean over data shards.

    GPipe schedule, M microbatches over S stages: tick t has stage s
    computing microbatch t - s; activations hop via ppermute. Backward
    is the autodiff transpose (a reverse pipeline). Bubble fraction
    (S-1)/(M+S-1) forward, same again backward — `simulate_step_scaling`
    predicts step-time scaling, tests hold measurements against it.
    The 1F1B schedule lives in `pipeline_1f1b_grads` (it computes
    gradients directly instead of relying on the autodiff transpose).
    """
    S = plan.num_stages
    M = int(num_microbatches)
    if schedule != "gpipe":
        raise ValueError(
            f"pipeline_logits runs the gpipe schedule; use "
            f"pipeline_1f1b_grads for 1F1B (got {schedule!r})")
    final_t = model.final_tensor
    B = next(iter(inputs.values())).shape[0]
    if B % M != 0:
        raise ValueError(f"batch {B} not divisible by {M} microbatches")
    mb = B // M
    layouts, widths = _wire_layouts(plan, model)

    # (B, ...) -> (M, mb, ...)
    inputs_mb = {k: v.reshape((M, mb) + v.shape[1:])
                 for k, v in inputs.items()}

    data_ax, ndata, mb_local = _data_split(mesh, data_axis, mb)
    run_stage = _make_stage_runner(
        plan, pack, model, layouts, widths, mb_local,
        training=training, seq_length=seq_length,
        remat=bool(getattr(model.config, "remat", False)),
        state_pack=state_pack)
    has_state = state_pack is not None and state_packed is not None
    if state_packed is None:
        state_packed = {}

    def local_fn(packed_local, inputs_local, state_local, rng_op):
        # packed_local: {dt: (1, L)}; inputs_local: {name: (M, mb_l, ...)}
        idx = lax.axis_index(pipe_axis)
        row = {dt: a[0] for dt, a in packed_local.items()}
        st_row0 = {dt: a[0] for dt, a in state_local.items()}
        branches = [functools.partial(run_stage, s) for s in range(S)]

        def tick(carry, t):
            wire, outputs, aux_acc, st_row = carry
            mb_idx = jnp.clip(t - idx, 0, M - 1)
            mb_in = {k: lax.dynamic_index_in_dim(v, mb_idx,
                                                 keepdims=False)
                     for k, v in inputs_local.items()}
            mb_rng = (jax.random.fold_in(rng_op, mb_idx)
                      if rng_op is not None else None)
            wire_out, final, aux, st_new = lax.switch(
                idx, branches, row, wire, mb_in, mb_rng, st_row)
            valid = jnp.logical_and(t - idx >= 0, t - idx < M)
            aux_acc = aux_acc + jnp.where(valid, aux, 0.0)
            # state updates only on valid ticks (warmup/drain garbage
            # microbatches must not touch running stats)
            st_row = {dt: jnp.where(valid, st_new[dt], st_row[dt])
                      for dt in st_row}
            perm = [(i, (i + 1) % S) for i in range(S)]
            wire_nxt = {dt: lax.ppermute(a, pipe_axis, perm)
                        for dt, a in wire_out.items()}
            done_idx = t - (S - 1)
            write = jnp.logical_and(idx == S - 1, done_idx >= 0)
            safe = jnp.clip(done_idx, 0, M - 1)
            cur = lax.dynamic_index_in_dim(outputs, safe, keepdims=False)
            outputs = lax.dynamic_update_index_in_dim(
                outputs, jnp.where(write, final, cur), safe, 0)
            return (wire_nxt, outputs, aux_acc, st_row), None

        wire0 = {dt: jnp.zeros((w * mb_local,), dtype=dt)
                 for dt, w in widths.items()}
        outputs0 = jnp.zeros(
            (M, mb_local) + tuple(final_t.shape[1:]),
            dtype=final_t.dtype)
        (_, outputs, aux_acc, st_row), _ = lax.scan(
            tick, (wire0, outputs0, jnp.float32(0.0), st_row0),
            jnp.arange(M + S - 1))
        outputs = lax.psum(
            jnp.where(idx == S - 1, outputs, jnp.zeros_like(outputs)),
            pipe_axis)
        # aux: mean over (microbatches x data shards). Averaging over
        # the data axis too keeps the P() aux output genuinely uniform —
        # each data shard sees different samples, and a per-shard value
        # declared replicated is undefined under check_vma=False
        aux_total = lax.psum(
            aux_acc, (pipe_axis,) if data_ax is None
            else (pipe_axis, data_ax)) / (M * ndata)
        # state rows: per-data-shard local statistics (DDP BatchNorm
        # behavior) mean-reduced over the data axis so the returned
        # rows are deterministic and replica-uniform
        if data_ax is not None:
            st_row = {dt: lax.pmean(a, data_ax)
                      for dt, a in st_row.items()}
        st_out = {dt: a[None] for dt, a in st_row.items()}
        return outputs, aux_total, st_out

    packed_spec = {dt: P(pipe_axis, None) for dt in packed}
    state_spec = {dt: P(pipe_axis, None) for dt in state_packed}
    in_spec = {k: P(None, data_ax, *([None] * (v.ndim - 2)))
               for k, v in inputs_mb.items()}
    out_spec = P(None, data_ax,
                 *([None] * (len(final_t.shape) - 1)))

    out, aux, st = shard_map(
        local_fn, mesh=mesh,
        in_specs=(packed_spec, in_spec, state_spec, P()),
        out_specs=(out_spec, P(), state_spec),
        check_vma=False)(packed, inputs_mb, state_packed, rng)
    logits = out.reshape((B,) + tuple(final_t.shape[1:]))
    return logits, aux, (st if has_state else None)


# --------------------------------------------------------------------------
# 1F1B schedule
# --------------------------------------------------------------------------

IDLE, FWD, BWD = 0, 1, 2


def _ring_depth(fwd_done, consume_done, S: int, M: int, start: int,
                what: str) -> int:
    """Smallest safe activation ring-buffer depth for a generated
    schedule. The hazard is the ARRIVAL tick: act(m2) lands in stage
    s's buffer one tick after fwd(s-1, m2) runs (not when fwd(s, m2)
    runs), so slot m2 % depth must not be overwritten before the
    consumer has used act(m) — consumption is bwd(s, m) for training
    schedules, fwd(s, m) for forward-only ones."""
    def conflict_free(dep: int) -> bool:
        for s in range(1, S):  # stage 0 takes no wire arrivals
            for m in range(M):
                for m2 in range(m + 1, M):
                    if m2 % dep != m % dep:
                        continue
                    if fwd_done[s - 1][m2] + 1 <= consume_done[s][m]:
                        return False
        return True

    depth = max(1, start)
    while depth < M and not conflict_free(depth):
        depth += 1
    if not conflict_free(depth):
        raise AssertionError(
            f"{what} has no conflict-free ring depth <= {M}")
    return depth


def _arrival_tables(kind, mbi, sidx, n_dev: int, S: int):
    """Per-(tick, device) wire-arrival tables (-1 mb = nothing
    arrived): stage s running fwd(m) at t-1 puts act(m) on stage s+1's
    device ((s+1) % n_dev — a +1 ring neighbor by the round-robin
    layout) at tick t, landing in that stage's chunk ((s+1) // n_dev)
    buffer; bwd cotangents mirror on the -1 ring. Forward-only
    schedules simply leave the bwd tables empty."""
    T = kind.shape[0]
    arr_f = np.full((T, n_dev), -1, np.int32)
    arrc_f = np.zeros((T, n_dev), np.int32)
    arr_b = np.full((T, n_dev), -1, np.int32)
    arrc_b = np.zeros((T, n_dev), np.int32)
    for t in range(1, T):
        for d in range(n_dev):
            s = int(sidx[t - 1, d])
            if kind[t - 1, d] == FWD and s < S - 1:
                rd = (s + 1) % n_dev
                arr_f[t, rd] = mbi[t - 1, d]
                arrc_f[t, rd] = (s + 1) // n_dev
            elif kind[t - 1, d] == BWD and s > 0:
                rd = (s - 1) % n_dev
                arr_b[t, rd] = mbi[t - 1, d]
                arrc_b[t, rd] = (s - 1) // n_dev
    return arr_f, arrc_f, arr_b, arrc_b


def _ring_io(widths, mb_local: int, depth: int, v: int, M: int):
    """(zero_wire, slot, deposit) helpers shared by the interleaved
    training and forward-only tick loops: the uniform wire buffer, the
    flat (chunk, microbatch) ring-buffer slot, and the arrival deposit
    keyed by the static tables."""
    def zero_wire():
        return {dt: jnp.zeros((w * mb_local,), dtype=dt)
                for dt, w in widths.items()}

    def slot(chunk, m):
        return chunk * depth + m % depth

    def deposit(buf, wire, m_arrived, chunk_arrived):
        ok = m_arrived >= 0
        sl = jnp.clip(chunk_arrived, 0, v - 1) * depth \
            + jnp.clip(m_arrived, 0, M - 1) % depth
        out = {}
        for dt, a in buf.items():
            cur = lax.dynamic_index_in_dim(a, sl, keepdims=False)
            upd = jnp.where(ok, wire[dt], cur)
            out[dt] = lax.dynamic_update_index_in_dim(a, upd, sl, 0)
        return out

    return zero_wire, slot, deposit


def one_f_one_b_schedule(S: int, M: int):
    """Plain (non-interleaved) 1F1B: the v=1 case of
    `interleaved_schedule`, kept as the historical entry point —
    one stage per device, kind/mbi tables only."""
    kind, mbi, _sidx, _depth = interleaved_schedule(S, 1, M)
    return kind, mbi


def interleaved_schedule(n_dev: int, v: int, M: int):
    """Interleaved (virtual-stage) 1F1B: S = v * n_dev stages, stage s
    lives on device s % n_dev (round-robin, so every s -> s+1 hop is a
    +1 ring neighbor), each DEVICE runs one unit per tick. With v > 1 a
    device starts chunk c+1's forwards while chunk c waits on
    downstream, dividing the warmup/drain bubble by ~v (the Megatron
    interleaved schedule). v=1 reduces to plain 1F1B.

    Greedy event-driven generation with backward priority (memory
    bound); among ready forwards, the smallest (microbatch, stage)
    first — pushing each microbatch deep as early as possible.

    Returns (kind (T, D), mbi (T, D), sidx (T, D), depth) where sidx is
    the GLOBAL stage id worked each tick (-1 idle) and `depth` is the
    per-stage ring-buffer depth the executor must allocate (validated
    conflict-free against the schedule).
    """
    D, S = n_dev, v * n_dev
    fwd_done = [[-1] * M for _ in range(S)]
    bwd_done = [[-1] * M for _ in range(S)]
    next_f = [0] * S
    next_b = [0] * S
    kind_rows, mbi_rows, sidx_rows = [], [], []
    t = 0
    while any(nb < M for nb in next_b):
        krow = [IDLE] * D
        mrow = [-1] * D
        srow = [-1] * D
        for d in range(D):
            stages = [d + c * D for c in range(v)]
            # backward first: smallest microbatch, then DEEPEST stage
            # (its cotangent unblocks the longest chain)
            best = None
            for s in sorted(stages, reverse=True):
                m = next_b[s]
                if m >= M:
                    continue
                ready = (s == S - 1 and 0 <= fwd_done[s][m] < t) or \
                    (s < S - 1 and 0 <= bwd_done[s + 1][m] < t)
                if ready:
                    if best is None or m < best[1]:
                        best = (s, m, BWD)
            if best is None:
                # fwd in WAVES: microbatch groups of D run chunk-major
                # (chunk c's wave completes before chunk c+1's), the
                # Megatron interleaved pattern — measurably the best of
                # the policies tried (30-60% bubble reduction at v=4
                # across D/M sweeps; see test_interleaved_schedule)
                cand = []
                for s in stages:
                    m = next_f[s]
                    if m >= M or next_f[s] - next_b[s] >= max(1, S - s):
                        continue
                    if s == 0 or 0 <= fwd_done[s - 1][m] < t:
                        cand.append((m // D, s // D, m, s))
                if cand:
                    _, _, m, s = min(cand)
                    best = (s, m, FWD)
            if best is not None:
                s, m, k = best
                krow[d], mrow[d], srow[d] = k, m, s
                if k == FWD:
                    fwd_done[s][m] = t
                    next_f[s] += 1
                else:
                    bwd_done[s][m] = t
                    next_b[s] += 1
        kind_rows.append(krow)
        mbi_rows.append(mrow)
        sidx_rows.append(srow)
        t += 1
        if t > 4 * v * (M + S) + 8:
            raise AssertionError("interleaved schedule did not converge")
    # ring-buffer depth: start at the max in-flight forwards any stage
    # holds, then grow until slot-reuse is provably safe (_ring_depth;
    # consumption = the bwd tick)
    inflight = [0] * S
    peak = [0] * S
    for krow, srow in zip(kind_rows, sidx_rows):
        for k, s in zip(krow, srow):
            if k == FWD:
                inflight[s] += 1
                peak[s] = max(peak[s], inflight[s])
            elif k == BWD:
                inflight[s] -= 1
    depth = _ring_depth(
        fwd_done, bwd_done, S, M, start=max(peak),
        what=f"interleaved schedule (D={n_dev}, v={v}, M={M})")
    return (np.asarray(kind_rows, np.int32),
            np.asarray(mbi_rows, np.int32),
            np.asarray(sidx_rows, np.int32), depth)


def schedule_bubble(kind) -> float:
    """Idle fraction of the device timeline a generated schedule
    leaves (warmup + drain + dependency stalls)."""
    total = kind.size
    busy = int((kind != IDLE).sum())
    return 1.0 - busy / total


def pipeline_1f1b_grads(plan: StagePlan, pack: PackSpec, packed,
                        inputs: Dict[str, jax.Array],
                        label, loss_fn, rng, mesh: Mesh,
                        pipe_axis: str, data_axis: Optional[str],
                        num_microbatches: int, model, *,
                        seq_length: int = -1,
                        state_pack: Optional[PackSpec] = None,
                        state_packed=None):
    """One-forward-one-backward pipelined TRAINING step: returns
    (logits (B, ...), aux scalar, grads {dtype: (S, L)},
    new_state_packed).

    Functional state (BatchNorm running stats): fwd ticks run OUTSIDE
    the vjp, so state rows advance there per microbatch in order —
    identical semantics to the GPipe path — while the bwd recompute
    reads the state row as a constant and its state writes are
    discarded (in training mode gradients do not depend on state_in,
    which only feeds the running-stat momentum update).

    Unlike the GPipe path (autodiff transpose of the forward schedule),
    this computes gradients EXPLICITLY inside the tick loop: each
    stage's backward recomputes its forward from the saved input
    activation via `jax.vjp` (remat-1F1B) as soon as the downstream
    cotangent arrives, so peak live activations per stage drop from M
    microbatches to min(S - s, M). Two wires ride the ring each tick:
    activations forward (ppermute i->i+1), cotangents backward
    (ppermute i->i-1). Ring buffers of depth min(S, M) hold arrived
    activations/cotangents between their arrival tick and use tick.
    """
    S = plan.num_stages
    M = int(num_microbatches)
    final_t = model.final_tensor
    B = next(iter(inputs.values())).shape[0]
    if B % M != 0:
        raise ValueError(f"batch {B} not divisible by {M} microbatches")
    mb = B // M
    layouts, widths = _wire_layouts(plan, model)
    for dt in widths:
        # jnp.issubdtype, not np: ml_dtypes' bfloat16 is floating but
        # plain numpy's issubdtype does not know its hierarchy
        if not jnp.issubdtype(jnp.dtype(dt), jnp.floating):
            raise NotImplementedError(
                f"1F1B: non-float tensor (dtype {dt}) crosses a stage "
                f"boundary; cotangent wires need float dtypes — use "
                f"the gpipe schedule")

    inputs_mb = {k: v.reshape((M, mb) + v.shape[1:])
                 for k, v in inputs.items()}
    label_mb = (label.reshape((M, mb) + label.shape[1:])
                if label is not None else None)

    data_ax, ndata, mb_local = _data_split(mesh, data_axis, mb)
    run_stage = _make_stage_runner(
        plan, pack, model, layouts, widths, mb_local,
        training=True, seq_length=seq_length, state_pack=state_pack)
    has_state = state_pack is not None and state_packed is not None
    if state_packed is None:
        state_packed = {}

    n_dev = int(mesh.shape[pipe_axis])
    v = S // n_dev
    if S != v * n_dev:
        raise ValueError(
            f"{S} stages do not divide over the {n_dev}-device "
            f"{pipe_axis!r} axis")
    kind, mbi, sidx, depth = interleaved_schedule(n_dev, v, M)
    T = kind.shape[0]
    arr_f, arrc_f, arr_b, arrc_b = _arrival_tables(
        kind, mbi, sidx, n_dev, S)
    # branch index per (tick, device): 0 idle, 1+s fwd(s), 1+S+s bwd(s)
    bidx = np.where(kind == IDLE, 0,
                    np.where(kind == FWD, 1 + sidx, 1 + S + sidx))

    kind_a = jnp.asarray(kind)
    mbi_a = jnp.asarray(mbi)
    sidx_a = jnp.asarray(sidx)
    arr_f_a = jnp.asarray(arr_f)
    arrc_f_a = jnp.asarray(arrc_f)
    arr_b_a = jnp.asarray(arr_b)
    arrc_b_a = jnp.asarray(arrc_b)
    bidx_a = jnp.asarray(bidx.astype(np.int32))

    # objective scaling (matches the GPipe/autodiff path): the reported
    # loss is mean over the GLOBAL batch; each (stage, data-shard)
    # device's per-microbatch loss contributes 1/(M * ndata); aux
    # contributes 1/M per device (psum'd over pipe only)
    loss_scale = 1.0 / (M * ndata)
    # aux averages over data shards too (the GPipe path psums aux over
    # (pipe, data) and divides by M*ndata — grads must match)
    aux_scale = 1.0 / (M * ndata)

    _zero_wire, slot, _deposit = _ring_io(widths, mb_local, depth, v, M)

    def local_fn(packed_local, inputs_local, state_local, rng_op,
                 label_local):
        idx = lax.axis_index(pipe_axis)
        # packed_local: {dt: (v, L)} — this device's chunk rows in
        # device-major order; stage s (s % n_dev == this device) reads
        # local row s // n_dev
        rows = packed_local

        def mb_inputs_at(m):
            return {k: lax.dynamic_index_in_dim(v_, m, keepdims=False)
                    for k, v_ in inputs_local.items()}

        def st_stage(st, c):
            return {dt: a[c] for dt, a in st.items()}

        def fwd_branch(s, rows, act_buf, ct_buf, wire_f, wire_b, m,
                       mb_rng, gacc, st):
            c = s // n_dev
            row = {dt: a[c] for dt, a in rows.items()}
            mb_in = mb_inputs_at(m)
            wire_in = {dt: lax.dynamic_index_in_dim(
                act_buf[dt], slot(c, m), keepdims=False)
                for dt in act_buf}
            wire_out, final, aux, st_new = run_stage(
                s, row, wire_in, mb_in, mb_rng,
                state_row=st_stage(st, c))
            st = {dt: st[dt].at[c].set(st_new[dt]) for dt in st}
            return wire_out, _zero_wire(), final, gacc, aux, st

        def bwd_branch(s, rows, act_buf, ct_buf, wire_f, wire_b, m,
                       mb_rng, gacc, st):
            c = s // n_dev
            row = {dt: a[c] for dt, a in rows.items()}
            mb_in = mb_inputs_at(m)
            wire_in = {dt: lax.dynamic_index_in_dim(
                act_buf[dt], slot(c, m), keepdims=False)
                for dt in act_buf}
            # the recompute reads state as a CONSTANT (no grad flows
            # through running stats in training mode); its state
            # writes are discarded — fwd ticks own the state advance
            st_c = st_stage(st, c)
            if s == S - 1:
                def objective(r, w):
                    _wire_o, final, aux, _st = run_stage(
                        s, r, w, mb_in, mb_rng, state_row=st_c)
                    obj = aux_scale * aux
                    if loss_fn is not None and label_local is not None:
                        lbl = lax.dynamic_index_in_dim(
                            label_local, m, keepdims=False)
                        obj = obj + loss_scale * loss_fn(final, lbl)
                    return obj
                _obj, pull = jax.vjp(objective, row, wire_in)
                d_row, d_wire = pull(jnp.float32(1.0))
            else:
                def emit(r, w):
                    wire_o, _final, aux, _st = run_stage(
                        s, r, w, mb_in, mb_rng, state_row=st_c)
                    return wire_o, aux
                _out, pull = jax.vjp(emit, row, wire_in)
                ct_wire = {dt: lax.dynamic_index_in_dim(
                    ct_buf[dt], slot(c, m), keepdims=False)
                    for dt in ct_buf}
                d_row, d_wire = pull((ct_wire,
                                      jnp.float32(aux_scale)))
            gacc = {dt: gacc[dt].at[c].add(
                d_row[dt].astype(gacc[dt].dtype)) for dt in gacc}
            final0 = jnp.zeros((mb_local,) + tuple(final_t.shape[1:]),
                               dtype=final_t.dtype)
            return (_zero_wire(), d_wire, final0, gacc,
                    jnp.float32(0.0), st)

        def idle_branch(rows, act_buf, ct_buf, wire_f, wire_b, m,
                        mb_rng, gacc, st):
            final0 = jnp.zeros((mb_local,) + tuple(final_t.shape[1:]),
                               dtype=final_t.dtype)
            return (_zero_wire(), _zero_wire(), final0, gacc,
                    jnp.float32(0.0), st)

        branches = ([idle_branch]
                    + [functools.partial(fwd_branch, s)
                       for s in range(S)]
                    + [functools.partial(bwd_branch, s)
                       for s in range(S)])

        def tick(carry, t):
            (act_buf, ct_buf, wire_f, wire_b, gacc, outputs, aux_acc,
             st) = carry
            # deposit arrivals into the (chunk, mb) ring buffers
            act_buf = _deposit(act_buf, wire_f, arr_f_a[t, idx],
                               arrc_f_a[t, idx])
            ct_buf = _deposit(ct_buf, wire_b, arr_b_a[t, idx],
                              arrc_b_a[t, idx])

            m = mbi_a[t, idx]
            safe_m = jnp.clip(m, 0, M - 1)
            mb_rng = (jax.random.fold_in(rng_op, safe_m)
                      if rng_op is not None else None)
            b = bidx_a[t, idx]
            wire_f_out, wire_b_out, final, gacc, aux, st = lax.switch(
                b, branches, rows, act_buf, ct_buf, wire_f, wire_b,
                safe_m, mb_rng, gacc, st)

            # every 1F1B fwd tick is real work (idle replaces the
            # GPipe warmup garbage), so fwd-tick aux sums are exact
            aux_acc = aux_acc + aux
            k = kind_a[t, idx]
            is_last_fwd = jnp.logical_and(k == FWD,
                                          sidx_a[t, idx] == S - 1)
            outputs = _write_mb(outputs, final, safe_m, is_last_fwd)

            fperm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
            bperm = [(i, (i - 1) % n_dev) for i in range(n_dev)]
            wire_f = {dt: lax.ppermute(a, pipe_axis, fperm)
                      for dt, a in wire_f_out.items()}
            wire_b = {dt: lax.ppermute(a, pipe_axis, bperm)
                      for dt, a in wire_b_out.items()}
            return (act_buf, ct_buf, wire_f, wire_b, gacc, outputs,
                    aux_acc, st), None

        def _write_mb(outputs, final, m, flag):
            cur = lax.dynamic_index_in_dim(outputs, m, keepdims=False)
            upd = jnp.where(flag, final, cur)
            return lax.dynamic_update_index_in_dim(outputs, upd, m, 0)

        zw = _zero_wire()
        act_buf0 = {dt: jnp.zeros((v * depth,) + a.shape, a.dtype)
                    for dt, a in zw.items()}
        ct_buf0 = {dt: jnp.zeros_like(a) for dt, a in act_buf0.items()}
        gacc0 = {dt: jnp.zeros((v, L), dtype=packed_local[dt].dtype)
                 for dt, L in pack.lengths.items()}
        outputs0 = jnp.zeros((M, mb_local) + tuple(final_t.shape[1:]),
                             dtype=final_t.dtype)
        (_, _, _, _, gacc, outputs, aux_acc, st_rows), _ = lax.scan(
            tick, (act_buf0, ct_buf0, zw, dict(zw), gacc0, outputs0,
                   jnp.float32(0.0), state_local),
            jnp.arange(T))
        # the last stage lives on the last device (S-1 = v*n_dev-1)
        outputs = lax.psum(
            jnp.where(idx == n_dev - 1, outputs,
                      jnp.zeros_like(outputs)),
            pipe_axis)
        aux_total = lax.psum(
            aux_acc, (pipe_axis,) if data_ax is None
            else (pipe_axis, data_ax)) / (M * ndata)
        # weight grads: each device owns its chunk rows; replicas
        # across the data axis hold partial sums -> reduce there
        if data_ax is not None:
            gacc = {dt: lax.psum(a, data_ax) for dt, a in gacc.items()}
            # state rows: per-shard local stats (DDP BatchNorm) ->
            # deterministic replica-uniform mean, same as GPipe
            st_rows = {dt: lax.pmean(a, data_ax)
                       for dt, a in st_rows.items()}
        return outputs, aux_total, gacc, st_rows

    packed_spec = {dt: P(pipe_axis, None) for dt in packed}
    state_spec = {dt: P(pipe_axis, None) for dt in state_packed}
    in_spec = {k: P(None, data_ax, *([None] * (v.ndim - 2)))
               for k, v in inputs_mb.items()}
    lbl_spec = (P(None, data_ax,
                  *([None] * (label_mb.ndim - 2)))
                if label_mb is not None else P())
    out_spec = P(None, data_ax, *([None] * (len(final_t.shape) - 1)))
    grad_spec = {dt: P(pipe_axis, None) for dt in packed}

    outputs, aux, grads, st = shard_map(
        local_fn, mesh=mesh,
        in_specs=(packed_spec, in_spec, state_spec, P(), lbl_spec),
        out_specs=(out_spec, P(), grad_spec, state_spec),
        check_vma=False)(packed, inputs_mb, state_packed, rng,
                         label_mb)
    logits = outputs.reshape((B,) + tuple(final_t.shape[1:]))
    return logits, aux, grads, (st if has_state else None)


def interleaved_forward_schedule(n_dev: int, v: int, M: int):
    """Forward-only interleaved schedule (eval/predict under virtual
    stages): same wave policy as `interleaved_schedule` minus the
    backward units and the in-flight memory cap — eval stores no
    activations for a backward, so microbatches stream as fast as the
    ring delivers them. Returns (kind (T, D), mbi, sidx, depth) with
    the same conventions (kind is FWD or IDLE only).
    """
    D, S = n_dev, v * n_dev
    fwd_done = [[-1] * M for _ in range(S)]
    next_f = [0] * S
    kind_rows, mbi_rows, sidx_rows = [], [], []
    t = 0
    while any(nf < M for nf in next_f):
        krow = [IDLE] * D
        mrow = [-1] * D
        srow = [-1] * D
        for d in range(D):
            stages = [d + c * D for c in range(v)]
            cand = []
            for s in stages:
                m = next_f[s]
                if m >= M:
                    continue
                if s == 0 or 0 <= fwd_done[s - 1][m] < t:
                    cand.append((m // D, s // D, m, s))
            if cand:
                _, _, m, s = min(cand)
                krow[d], mrow[d], srow[d] = FWD, m, s
                fwd_done[s][m] = t
                next_f[s] += 1
        kind_rows.append(krow)
        mbi_rows.append(mrow)
        sidx_rows.append(srow)
        t += 1
        if t > 4 * v * (M + S) + 8:
            raise AssertionError(
                "interleaved forward schedule did not converge")
    # forward-only consumption is the fwd tick itself
    depth = _ring_depth(
        fwd_done, fwd_done, S, M, start=1,
        what=f"forward schedule (D={n_dev}, v={v}, M={M})")
    return (np.asarray(kind_rows, np.int32),
            np.asarray(mbi_rows, np.int32),
            np.asarray(sidx_rows, np.int32), depth)


def pipeline_logits_interleaved(plan: StagePlan, pack: PackSpec, packed,
                                inputs: Dict[str, jax.Array], rng,
                                mesh: Mesh, pipe_axis: str,
                                data_axis: Optional[str],
                                num_microbatches: int, model, *,
                                training: bool, seq_length: int = -1,
                                state_pack: Optional[PackSpec] = None,
                                state_packed=None):
    """Forward-only pipelined run under an interleaved (virtual-stage)
    layout: S = v * n_dev stages, stage s on device s % n_dev, packed
    rows in device-major order (PackSpec.row_of). The eval/predict
    counterpart of `pipeline_1f1b_grads` — same tick machinery (static
    schedule tables, lax.switch branch per stage, activation ring
    buffers, +1-ring ppermute) without the backward wire. Returns
    (logits (B, ...), aux scalar)."""
    S = plan.num_stages
    M = int(num_microbatches)
    final_t = model.final_tensor
    B = next(iter(inputs.values())).shape[0]
    if B % M != 0:
        raise ValueError(f"batch {B} not divisible by {M} microbatches")
    mb = B // M
    layouts, widths = _wire_layouts(plan, model)

    inputs_mb = {k: v_.reshape((M, mb) + v_.shape[1:])
                 for k, v_ in inputs.items()}
    data_ax, ndata, mb_local = _data_split(mesh, data_axis, mb)
    run_stage = _make_stage_runner(
        plan, pack, model, layouts, widths, mb_local,
        training=training, seq_length=seq_length,
        state_pack=state_pack)
    if state_packed is None:
        state_packed = {}

    n_dev = int(mesh.shape[pipe_axis])
    v = S // n_dev
    if S != v * n_dev:
        raise ValueError(
            f"{S} stages do not divide over the {n_dev}-device "
            f"{pipe_axis!r} axis")
    kind, mbi, sidx, depth = interleaved_forward_schedule(n_dev, v, M)
    T = kind.shape[0]
    arr_f, arrc_f, _arr_b, _arrc_b = _arrival_tables(
        kind, mbi, sidx, n_dev, S)
    bidx = np.where(kind == IDLE, 0, 1 + sidx)

    kind_a = jnp.asarray(kind)
    mbi_a = jnp.asarray(mbi)
    sidx_a = jnp.asarray(sidx)
    arr_f_a = jnp.asarray(arr_f)
    arrc_f_a = jnp.asarray(arrc_f)
    bidx_a = jnp.asarray(bidx.astype(np.int32))

    _zero_wire, slot, _deposit = _ring_io(widths, mb_local, depth, v, M)

    def local_fn(packed_local, inputs_local, state_local, rng_op):
        idx = lax.axis_index(pipe_axis)
        rows = packed_local  # {dt: (v, L)} device-major chunk rows

        def fwd_branch(s, rows, act_buf, m, mb_rng):
            c = s // n_dev
            row = {dt: a[c] for dt, a in rows.items()}
            mb_in = {k: lax.dynamic_index_in_dim(v_, m, keepdims=False)
                     for k, v_ in inputs_local.items()}
            wire_in = {dt: lax.dynamic_index_in_dim(
                act_buf[dt], slot(c, m), keepdims=False)
                for dt in act_buf}
            # state is read-only here (eval/predict: BN consumes its
            # running stats; updates are dropped — no step stores them)
            wire_out, final, aux, _st = run_stage(
                s, row, wire_in, mb_in, mb_rng,
                state_row={dt: a[c] for dt, a in state_local.items()})
            return wire_out, final, aux

        def idle_branch(rows, act_buf, m, mb_rng):
            final0 = jnp.zeros((mb_local,) + tuple(final_t.shape[1:]),
                               dtype=final_t.dtype)
            return _zero_wire(), final0, jnp.float32(0.0)

        branches = [idle_branch] + [functools.partial(fwd_branch, s)
                                    for s in range(S)]

        def tick(carry, t):
            act_buf, wire_f, outputs, aux_acc = carry
            act_buf = _deposit(act_buf, wire_f, arr_f_a[t, idx],
                               arrc_f_a[t, idx])
            m = mbi_a[t, idx]
            safe_m = jnp.clip(m, 0, M - 1)
            mb_rng = (jax.random.fold_in(rng_op, safe_m)
                      if rng_op is not None else None)
            wire_out, final, aux = lax.switch(
                bidx_a[t, idx], branches, rows, act_buf, safe_m, mb_rng)
            aux_acc = aux_acc + aux  # every fwd tick is real work
            is_last = jnp.logical_and(kind_a[t, idx] == FWD,
                                      sidx_a[t, idx] == S - 1)
            cur = lax.dynamic_index_in_dim(outputs, safe_m,
                                           keepdims=False)
            outputs = lax.dynamic_update_index_in_dim(
                outputs, jnp.where(is_last, final, cur), safe_m, 0)
            fperm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
            wire_f = {dt: lax.ppermute(a, pipe_axis, fperm)
                      for dt, a in wire_out.items()}
            return (act_buf, wire_f, outputs, aux_acc), None

        zw = _zero_wire()
        act_buf0 = {dt: jnp.zeros((v * depth,) + a.shape, a.dtype)
                    for dt, a in zw.items()}
        outputs0 = jnp.zeros((M, mb_local) + tuple(final_t.shape[1:]),
                             dtype=final_t.dtype)
        (_, _, outputs, aux_acc), _ = lax.scan(
            tick, (act_buf0, zw, outputs0, jnp.float32(0.0)),
            jnp.arange(T))
        # stage S-1 = v*n_dev - 1 lives on device n_dev - 1
        outputs = lax.psum(
            jnp.where(idx == n_dev - 1, outputs,
                      jnp.zeros_like(outputs)),
            pipe_axis)
        aux_total = lax.psum(
            aux_acc, (pipe_axis,) if data_ax is None
            else (pipe_axis, data_ax)) / (M * ndata)
        return outputs, aux_total

    packed_spec = {dt: P(pipe_axis, None) for dt in packed}
    state_spec = {dt: P(pipe_axis, None) for dt in state_packed}
    in_spec = {k: P(None, data_ax, *([None] * (v_.ndim - 2)))
               for k, v_ in inputs_mb.items()}
    out_spec = P(None, data_ax, *([None] * (len(final_t.shape) - 1)))

    out, aux = shard_map(
        local_fn, mesh=mesh,
        in_specs=(packed_spec, in_spec, state_spec, P()),
        out_specs=(out_spec, P()),
        check_vma=False)(packed, inputs_mb, state_packed, rng)
    return out.reshape((B,) + tuple(final_t.shape[1:])), aux


# --------------------------------------------------------------------------
# analytics
# --------------------------------------------------------------------------

def bubble_fraction(num_stages: int, num_microbatches: int) -> float:
    """GPipe bubble: idle fraction of each device's timeline."""
    S, M = num_stages, num_microbatches
    return (S - 1) / (M + S - 1)


def simulate_step_scaling(num_stages: int, m_a: int, m_b: int) -> float:
    """Predicted step-time ratio time(M=m_a)/time(M=m_b) at fixed global
    batch: per-microbatch work scales 1/M, ticks = M + S - 1, so step
    time ∝ (M + S - 1)/M. The measurable form of the bubble model (the
    sim-vs-measured agreement tests hold CPU-mesh timings against it)."""
    S = num_stages
    return ((m_a + S - 1) / m_a) / ((m_b + S - 1) / m_b)


def peak_microbatches(num_stages: int, num_microbatches: int,
                      schedule: str) -> int:
    """Peak in-flight microbatches whose activations a stage must hold:
    GPipe stores all M before backward drains; 1F1B caps at S."""
    if schedule == "1f1b":
        return min(num_stages, num_microbatches)
    return num_microbatches
