"""Executor: compiles the op graph into jitted train/eval steps.

Replaces the reference's per-iteration Legion machinery (SURVEY.md 3.3):
forward/zero_gradients/backward/update index launches + begin/end_trace
become ONE jitted function per step — XLA tracing plays the role Legion
tracing played (record once, replay thereafter), `jax.grad` replaces the
hand-written backward tasks, and GSPMD inserts every collective the
mapper/NCCL layer used to orchestrate.

State layout (all pytrees, shardable):
  params     {op_name: {weight_name: array}}
  states     {op_name: {state_name: array}}     (e.g. BN running stats)
  opt_state  optimizer-specific mirror of params
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..op import Op, OpContext
from ..tensor import Tensor
from . import initializers as I
from . import losses as L
from . import metrics as M
from . import precision as MP
from .optimizers import Optimizer
from ..parallel.pconfig import Strategy
from ..parallel.sharding import (
    batch_sharding,
    effective_op_strategy,
    op_output_sharding,
    place_global,
    place_process_local,
    spec_for_axes,
    weight_sharding,
)

# sentinel marking "no pinned sharding" in the recorded optimizer-slot
# sharding tree (None would read as an empty pytree under tree_map)
_NO_SHARDING = object()


def zero_applicable(config, mesh) -> bool:
    """The single ZeRO-1 eligibility rule (base and staged executors
    must agree): requested AND a data axis > 1 exists to shard over."""
    return bool(getattr(config, "zero_optimizer_sharding", False)
                and mesh is not None
                and mesh.shape.get("data", 1) > 1)


class TrainState:
    """Flat container; registered as a pytree for jit/donation."""

    def __init__(self, params, states, opt_state, step):
        self.params = params
        self.states = states
        self.opt_state = opt_state
        self.step = step

    def tree_flatten(self):
        return (self.params, self.states, self.opt_state, self.step), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    TrainState, TrainState.tree_flatten, TrainState.tree_unflatten)


def _permute_nhwc_sharding(s, mesh):
    """NCHW-axes NamedSharding -> the same logical sharding over an
    NHWC-permuted runtime value (executor NHWC residency)."""
    sp = tuple(s.spec) + (None,) * (4 - len(tuple(s.spec)))
    return NamedSharding(mesh, P(sp[0], sp[2], sp[3], sp[1]))


class Executor:
    def __init__(self, model, optimizer: Optimizer, loss_fn, metric_names,
                 mesh: Optional[Mesh] = None,
                 strategy: Optional[Strategy] = None,
                 comp_mode: str = "training"):
        self.model = model
        self.config = model.config
        self.optimizer = optimizer
        # reference COMP_MODE_INFERENCE (ffconst.h): no optimizer state
        # is allocated and the train steps refuse to build — forward/
        # evaluate only, at half the parameter memory of a training
        # compile (no momentum/m/v slots)
        if comp_mode not in ("training", "inference"):
            raise ValueError(
                f"comp_mode must be CompMode.TRAINING ('training') or "
                f"CompMode.INFERENCE ('inference'), got {comp_mode!r}")
        self.comp_mode = comp_mode
        self.loss_fn = L.resolve(loss_fn) if loss_fn is not None else None
        self.loss_name = loss_fn if isinstance(loss_fn, str) else "custom"
        self.metric_names = list(metric_names or [])
        self.mesh = mesh
        self.strategy = strategy or Strategy()
        # mixed-precision policy (core/precision.py): float params and
        # optimizer state live in param_dtype (f32 masters by default);
        # when compute_dtype != f32 the step casts params + float
        # activations down on the way in (forward_values) and computes
        # the loss/metrics on f32-upcast logits. compute_dtype == f32
        # is the no-cast fast path — builder-level bf16 models
        # (dtype=jnp.bfloat16 activations) keep their exact numerics.
        self.compute_dtype = jnp.dtype(self.config.compute_dtype)
        self.param_dtype = jnp.dtype(self.config.param_dtype)
        self._mp_active = MP.policy_active(self.config)
        self._train_step = None
        self._train_step_multi = None
        self._train_step_accum = None
        # bucketed backward-overlapped gradient sync (core/overlap.py):
        # bucket partition + the custom_vjp sync-point op are cached
        # against the sparse routing (sparse tables scatter outside the
        # bucketed reduction) and rebuilt when it changes. An unset
        # grad_bucket_mb (None) auto-tunes from the machine model for
        # THIS mesh (resolve_bucket_mb; 0 = monolithic when there is no
        # data axis to sync over); explicit values are authoritative.
        from .overlap import resolve_bucket_mb
        self._grad_bucket_mb = resolve_bucket_mb(self.config, model,
                                                 mesh=mesh)
        self._grad_buckets_cache = None
        self._bucket_tagger = None
        # runtime LR multiplier (model.set_learning_rate / keras
        # LearningRateScheduler): passed into every jitted step as a
        # traced scalar, so changing it NEVER recompiles
        self._lr_scale: float = 1.0
        self._lr_device = None  # cached device scalar (see _lr)
        self._lr_device_scale = None
        # resolved scan-vs-unroll decision for train_step_multi, keyed
        # on config.multi_step_unroll (see the property)
        self._train_step_multi_mode = None
        self._train_step_multi_unroll = None
        self._eval_step = None
        self._eval_step_multi = None
        self._sparse_ops_cache = None
        self._sparse_cache_key = None
        # the shared program registry (core/programs.py): train-step
        # dispatch resolves through it, so fit's compiled steps get the
        # same exact compile counting + AOT snapshot/warm-boot story as
        # the serving programs (--program-cache-dir). Lazy: built on
        # first dispatch
        self._programs = None
        self._last_aux_losses = []
        # lower device-explicit placements (strategy device_ids) into
        # the stacked-embedding slot layout BEFORE any weight_specs()
        # read — the executable form of the reference's slice_task
        # routing (mapper.cc:346-440); re-entrant across recompiles
        from ..ops.embedding import DistributedEmbedding
        for op in model.ops:
            if isinstance(op, DistributedEmbedding):
                s = self.strategy.for_op(op.name)
                op.apply_placement(s.device_ids or None, mesh)
        # fusion (reference apply_fusion, model.cc:1472): constrain
        # sharding only at fused-group boundaries.
        self._sharding_boundary = None
        if self.config.perform_fusion:
            from .fusion import boundary_ops, compute_fusion_groups
            self._sharding_boundary = boundary_ops(
                compute_fusion_groups(model, self.strategy))
        # sibling-conv batching (core/fusion.conv_sibling_groups): the
        # group leader runs the merged conv at its walk position; the
        # other members pop their pre-sliced output. Skipped when a
        # member has its own sharding strategy entry (a per-branch
        # channel-out split would shard the merged conv differently).
        self._conv_merge_leader = {}
        if getattr(self.config, "sibling_conv_fusion", True):
            from .fusion import _strategy_key, conv_sibling_groups
            for group in conv_sibling_groups(model):
                strat_keys = {_strategy_key(self.strategy, op.name)
                              for op in group}
                if len(strat_keys) > 1:
                    continue
                self._conv_merge_leader[group[0].name] = group
        # NHWC layout residency: under conv_layout="NHWC", values flow
        # channels-last BETWEEN conv-family ops instead of each op
        # transposing in and out. Per-op transpose pairs rely on XLA
        # cancellation, which breaks at Concat module boundaries and
        # ballooned compile time (round-4 NHWC arm >600s); residency
        # removes the pairs structurally. _nhwc_resident = tensor uids
        # whose runtime value is NHWC-permuted; _nhwc_reads = ops that
        # consume their inputs in that form.
        self._nhwc_resident, self._nhwc_reads = (
            self._compute_nhwc_resident()
            if self.config.conv_layout == "NHWC" else (set(), set()))

    def _compute_nhwc_resident(self):
        """Static dataflow pass for conv_layout="NHWC": which tensor
        values stay NHWC-permuted between ops, and which ops read them
        that way. Conv/Pool/BN always EMIT resident outputs (they
        compute in NHWC anyway); Concat-on-channels and same-shape
        pointwise ops PROPAGATE residency when every tensor input is
        resident; everything else reads NCHW (the walk inserts the
        transpose at the read). Per-op NCHW semantics (weights, state,
        output_axes, get/set_weights) are untouched — this is purely
        about the runtime value layout between ops."""
        core = {"conv2d", "pool2d", "batch_norm"}
        pointwise = {"element_unary", "element_binary", "dropout"}
        resident: set = set()
        reads: set = set()
        for op in self.model.ops:
            ins = op.inputs
            all_res = bool(ins) and all(t.uid in resident for t in ins)
            out4 = (op.outputs
                    and len(op.outputs[0].shape) == 4)
            if op.op_type in core and out4 \
                    and len(ins[0].shape) == 4:
                if all_res:
                    reads.add(op.name)
                resident.update(t.uid for t in op.outputs)
            elif (op.op_type == "concat" and out4 and all_res
                    and getattr(op, "axis", None) == 1):
                reads.add(op.name)
                resident.update(t.uid for t in op.outputs)
            elif (op.op_type in pointwise and out4 and all_res
                    and all(tuple(t.shape) == tuple(op.outputs[0].shape)
                            for t in ins)):
                # pointwise on identical shapes: layout-transparent
                reads.add(op.name)
                resident.update(t.uid for t in op.outputs)
        return resident, reads

    # ---------------- initialization ----------------
    def init_state(self, rng) -> TrainState:
        """Create params/states with per-parameter folded keys, sharded
        per strategy. Replaces reference initializer index launches
        (initializer.cc) + optimizer->init replicas (optimizer.cc:22-41)."""
        params: Dict[str, Dict[str, jax.Array]] = {}
        states: Dict[str, Dict[str, jax.Array]] = {}
        for op in self.model.ops:
            wspecs = op.weight_specs()
            if wspecs:
                op_params = {}
                for wname, spec in wspecs.items():
                    key = jax.random.fold_in(
                        jax.random.fold_in(rng, _stable_hash(op.name)),
                        _stable_hash(wname))
                    init_fn = spec.custom_init or I.resolve(spec.initializer)
                    if spec.fan_in is not None or spec.fan_out is not None:
                        arr = init_fn(key, spec.shape, spec.dtype,
                                      fan_in=spec.fan_in,
                                      fan_out=spec.fan_out)
                    else:
                        arr = init_fn(key, spec.shape, spec.dtype)
                    # master storage dtype: f32-declared float weights
                    # store at param_dtype; an EXPLICIT non-f32 spec
                    # dtype (a builder's bf16 table) wins over the knob
                    if (self.param_dtype != jnp.float32
                            and not spec.keep_dtype
                            and jnp.dtype(spec.dtype) == jnp.float32):
                        arr = arr.astype(self.param_dtype)
                    if self.mesh is not None:
                        sh = weight_sharding(
                            spec,
                            effective_op_strategy(
                                op, self.strategy.for_op(op.name),
                                self.mesh),
                            self.mesh)
                        arr = place_global(arr, sh)
                    op_params[wname] = arr
                params[op.name] = op_params
            sspecs = op.state_specs()
            if sspecs:
                op_states = {}
                for sname, sspec in sspecs.items():
                    # host-side init: placing from device via the
                    # multi-process callback would round-trip device->
                    # host->device for nothing
                    arr = np.full(sspec.shape, sspec.init_value,
                                  np.dtype(sspec.dtype))
                    if self.mesh is not None:
                        arr = place_global(
                            arr, NamedSharding(self.mesh, P()))
                    else:
                        arr = jnp.asarray(arr)
                    op_states[sname] = arr
                states[op.name] = op_states
        opt_state = (self.optimizer.init_state(params)
                     if self.optimizer and self.comp_mode != "inference"
                     else {})
        opt_state = self._zero_shard_slots(opt_state)
        return TrainState(params, states, opt_state, self._init_step())

    def _zero_shard_slots(self, opt_state):
        """ZeRO-1 (config.zero_optimizer_sharding): re-place dense
        optimizer slots sharded over the `data` axis — the first
        still-unsharded dimension that divides takes it. Pure GSPMD:
        the update's sharding constraint (_apply_update) keeps them
        there across steps and XLA inserts the reduce-scatter /
        all-gather. Sparse-table slots keep their layout (their scatter
        update addresses rows by index). Records the slot sharding tree
        either way so _apply_update can pin outputs."""
        self._opt_shardings = None
        if not opt_state:
            return opt_state
        if zero_applicable(self.config, self.mesh):
            nd = self.mesh.shape["data"]
            sparse = {op.name for op in self.model.ops
                      if op.op_type in ("embedding",
                                        "distributed_embedding")}

            def place(path, arr):
                if not isinstance(arr, jax.Array) or arr.ndim == 0:
                    return arr
                # path = (slot, op_name, weight_name)
                if len(path) >= 2 and str(getattr(
                        path[1], "key", "")) in sparse:
                    return arr
                sh = arr.sharding
                spec = (list(sh.spec) if isinstance(sh, NamedSharding)
                        else [])
                spec += [None] * (arr.ndim - len(spec))
                used = {ax for e in spec if e
                        for ax in (e if isinstance(e, tuple) else (e,))}
                if "data" in used:
                    return arr
                for i in range(arr.ndim):
                    if spec[i] is None and arr.shape[i] % nd == 0:
                        spec[i] = "data"
                        # freshly-initialized slots are zeros by
                        # construction (SGD momentum / Adam m,v), so
                        # materialize host-side and place_global —
                        # multi-controller meshes span devices this
                        # process cannot address (device_put/device_get
                        # would both fail there)
                        return place_global(
                            np.zeros(arr.shape, arr.dtype),
                            NamedSharding(self.mesh, P(*spec)))
                return arr

            opt_state = jax.tree_util.tree_map_with_path(place,
                                                         opt_state)
            self._opt_shardings = jax.tree_util.tree_map(
                lambda a: (a.sharding
                           if isinstance(a, jax.Array)
                           and isinstance(a.sharding, NamedSharding)
                           else _NO_SHARDING),
                opt_state)
        return opt_state

    def _init_step(self):
        """Step counter, committed to the mesh (replicated) when one
        exists: a checkpoint restore otherwise brings it back committed
        to ONE device, and jit rejects the mixed device assignment
        against mesh-sharded params."""
        if self.mesh is None:
            return jnp.zeros((), jnp.int32)
        return place_global(np.zeros((), np.int32),
                            NamedSharding(self.mesh, P()))

    # ---------------- forward ----------------
    def forward_values(self, params, states, inputs: Dict[str, jax.Array],
                      training: bool, rng, seq_length: int = -1):
        """Topological walk of the graph; returns (tensor-values map,
        new_states)."""
        # mixed precision: master params (param_dtype) and float inputs
        # cast to compute_dtype HERE, inside whatever function is being
        # differentiated — the cast's transpose upcasts cotangents, so
        # gradients leave the bf16 region in the master dtype. Labels
        # are not inputs and never pass through this cast.
        if self._mp_active:
            params = MP.cast_floats(params, self.compute_dtype)
        values: Dict[int, jax.Array] = {}
        for t in self.model.input_tensors:
            if t.name not in inputs:
                raise KeyError(f"missing input {t.name!r}; have {list(inputs)}")
            v = inputs[t.name]
            if self._mp_active and MP.is_float_array(v) \
                    and v.dtype != self.compute_dtype:
                v = v.astype(self.compute_dtype)
            values[t.uid] = v
        new_states: Dict[str, Dict[str, jax.Array]] = {}
        aux_losses = []
        # pre-sliced outputs of merged sibling convs, keyed by the
        # member op that will claim them at its own walk position
        merged_pending: Dict[str, jax.Array] = {}
        for op in self.model.ops:
            ctx = OpContext(
                training=training,
                rng=(jax.random.fold_in(rng, _stable_hash(op.name))
                     if rng is not None else None),
                seq_length=seq_length,
                state_in=states.get(op.name, {}),
                mesh=self.mesh,
                op_strategy=self.strategy.for_op(op.name),
                nhwc_in=op.name in self._nhwc_reads,
                nhwc_out=bool(op.outputs
                              and op.outputs[0].uid
                              in self._nhwc_resident),
            )
            xs = []
            for t in op.inputs:
                v = values[t.uid]
                if (t.uid in self._nhwc_resident
                        and op.name not in self._nhwc_reads):
                    # layout boundary: this consumer wants NCHW (XLA
                    # CSEs the duplicate when several consumers read)
                    v = jnp.transpose(v, (0, 3, 1, 2))
                xs.append(v)
            op_params = params.get(op.name, {})
            # remat: recompute this op's activations in backward instead of
            # saving them (HBM-for-FLOPs trade, SURVEY.md env notes). Ops
            # with functional state (BN) or aux losses (MoE) are excluded —
            # their ctx side-channel values must not escape the
            # checkpointed trace (tracer leak otherwise).
            # every branch runs under the op's name as a named scope
            # (metadata only): a profiler trace then names each device
            # operation's op, forward `jvp(<op>)` and backward
            # `transpose(jvp(<op>))` alike (docs/observability.md)
            if op.name in merged_pending:
                ys = [merged_pending.pop(op.name)]
            elif op.name in self._conv_merge_leader:
                from ..ops.conv import merged_conv_forward
                group = self._conv_merge_leader[op.name]
                plist = [params.get(m.name, {}) for m in group]
                # group members share the leader's input and geometry,
                # so the leader's residency flags speak for the group
                nin, nout = ctx.nhwc_in, ctx.nhwc_out
                with jax.named_scope(op.name):
                    if self.config.remat:
                        outs = jax.checkpoint(
                            lambda ps, x, _g=group, _i=nin, _o=nout:
                            merged_conv_forward(_g, ps, x, _i, _o))(
                                plist, xs[0])
                    else:
                        outs = merged_conv_forward(group, plist, xs[0],
                                                   nin, nout)
                for m, y in zip(group[1:], outs[1:]):
                    merged_pending[m.name] = y
                ys = [outs[0]]
            elif (self.config.remat and op.weight_specs()
                    and not op.state_specs()
                    and not getattr(op, "has_aux_loss", False)):
                with jax.named_scope(op.name):
                    ys = jax.checkpoint(
                        lambda p, x, _op=op, _ctx=ctx:
                        _op.forward(p, x, _ctx))(op_params, xs)
            else:
                with jax.named_scope(op.name):
                    ys = op.forward(op_params, xs, ctx)
            if self.mesh is not None and (
                    self._sharding_boundary is None
                    or op.name in self._sharding_boundary):
                shardings = op_output_sharding(
                    op, self.strategy.for_op(op.name), self.mesh)
                # NHWC-resident values are permuted (N,H,W,C) at
                # runtime while op axes speak NCHW — permute the spec
                # with them or the constraint pins the wrong dims
                shardings = [
                    _permute_nhwc_sharding(s, self.mesh)
                    if (t.uid in self._nhwc_resident
                        and len(t.shape) == 4) else s
                    for t, s in zip(op.outputs, shardings)]
                ys = [jax.lax.with_sharding_constraint(y, s)
                      for y, s in zip(ys, shardings)]
            if self._mp_active:
                # keep the VALUE stream at compute_dtype: ops that pin
                # their output dtype (Embedding's out_dtype defaults
                # f32) would otherwise silently upcast everything
                # downstream of them back to f32. State/aux outputs
                # (BN statistics, MoE aux loss) are NOT values and
                # stay f32.
                ys = [y.astype(self.compute_dtype)
                      if MP.is_float_array(y)
                      and y.dtype != self.compute_dtype else y
                      for y in ys]
            for t, y in zip(op.outputs, ys):
                values[t.uid] = y
            if ctx.state_out:
                new_states[op.name] = ctx.state_out
            if ctx.aux_loss is not None:
                aux_losses.append(ctx.aux_loss)
        # carry through untouched states (eval path of ops w/o forward call)
        for name, s in states.items():
            new_states.setdefault(name, s)
        self._last_aux_losses = aux_losses
        # normalize NHWC-resident values back to logical NCHW so every
        # caller (loss, metrics, tests reading intermediate tensors)
        # sees declared shapes; under jit the unused transposes are DCE'd
        for uid in self._nhwc_resident:
            if uid in values and values[uid].ndim == 4:
                values[uid] = jnp.transpose(values[uid], (0, 3, 1, 2))
        return values, new_states

    # ---------------- bucketed grad-sync points (core/overlap.py) -----
    def _grad_buckets(self):
        """Cached walk-order sync-bucket partition (list of (names,
        bytes)); [] when grad_bucket_mb is 0 (legacy monolithic)."""
        if self._grad_buckets_cache is None:
            from .overlap import grad_buckets
            self._grad_buckets_cache = grad_buckets(
                self.model, self._grad_bucket_mb,
                sparse_ops=set(self._sparse_table_ops()))
        return self._grad_buckets_cache

    def grad_bucket_info(self) -> Dict[str, Any]:
        """Bucket layout for profiling.train_report."""
        buckets = self._grad_buckets()
        return {"count": len(buckets),
                "bucket_mb": self._grad_bucket_mb,
                "bytes": [b for _, b in buckets]}

    def _tag_grad_buckets(self, params):
        """Thread the bucketed params through the sync-point op so each
        bucket's gradient all-reduce anchors inside the backward pass at
        grad-completion (identity on values — grads stay bit-identical;
        see core/overlap.make_bucket_tagger)."""
        buckets = self._grad_buckets()
        if not buckets:
            return params
        if self._bucket_tagger is None:
            from .overlap import make_bucket_tagger
            self._bucket_tagger = make_bucket_tagger(
                [names for names, _ in buckets])
        sub = {n: params[n] for names, _ in buckets for n in names
               if n in params}
        if not sub:
            return params
        tagged = self._bucket_tagger(sub)
        return {**params, **tagged}

    def _outputs_and_loss(self, params, states, batch, training, rng,
                          seq_length):
        if training and self._grad_bucket_mb > 0:
            params = self._tag_grad_buckets(params)
        values, new_states = self.forward_values(
            params, states, batch, training, rng, seq_length)
        logits = values[self.model.final_tensor.uid]
        if self._mp_active and MP.is_float_array(logits):
            # losses and metrics score f32-upcast logits — the one
            # policy-exempt region (precision.py): a bf16 NLL would
            # round away exactly the signal the parity gate measures
            logits = logits.astype(jnp.float32)
        with jax.named_scope("loss"):
            loss = jnp.asarray(0.0, jnp.float32)
            if self.loss_fn is not None and "label" in batch:
                loss = self.loss_fn(logits, batch["label"])
            for aux in self._last_aux_losses:
                loss = loss + aux
        return loss, (logits, new_states)

    # ---------------- sparse-table routing ----------------
    def _sparse_table_ops(self) -> Dict[str, Op]:
        """Embedding-family ops eligible for the sparse-update path:
        their index tensors are graph INPUTS (so the executor can gather
        the touched rows before differentiation) and the optimizer has a
        sparse row form (Optimizer.sparse_mode): "exact" is used freely,
        "lazy" (stale untouched rows, SparseAdam-style) only when
        config.sparse_embedding_lazy opts in. Reference analog: the
        scatter-add embedding backward + per-table update of
        src/ops/embedding.cu — the dense-gradient alternative writes the
        full (vocab, dim) table's worth of zeros + updates every step,
        ruinous at DLRM scale.

        Eligibility is keyed on the live sparse flags + optimizer; if
        they change after steps were compiled, the stale compiled steps
        are dropped so the next dispatch rebuilds with the new routing
        (cost_model.py reads config live — keep the two in agreement)."""
        # the optimizer OBJECT (not id(): a recycled address after gc
        # could false-match) — default object __eq__ is identity and the
        # strong ref pins it
        key = (self.config.sparse_embedding_updates,
               self.config.sparse_embedding_lazy,
               self.optimizer,
               self.optimizer.sparse_mode() if self.optimizer else None)
        if self._sparse_ops_cache is not None:
            if self._sparse_cache_key == key:
                return self._sparse_ops_cache
            # routing changed post-build: invalidate compiled steps that
            # baked in the old sparse/dense split (and the grad-sync
            # bucket partition, which excludes sparse tables)
            self._train_step = None
            self._train_step_multi = None
            self._train_step_accum = None
            self._grad_buckets_cache = None
            self._bucket_tagger = None
        from ..ops.embedding import DistributedEmbedding, Embedding
        out: Dict[str, Op] = {}
        mode = (self.optimizer.sparse_mode() if self.optimizer else None)
        allowed = mode == "exact" or (
            mode == "lazy" and self.config.sparse_embedding_lazy)
        if self.config.sparse_embedding_updates and allowed:
            input_uids = {t.uid for t in self.model.input_tensors}
            for op in self.model.ops:
                if not isinstance(op, (Embedding, DistributedEmbedding)):
                    continue
                if all(t.uid in input_uids for t in op.inputs):
                    out[op.name] = op
        self._sparse_ops_cache = out
        self._sparse_cache_key = key
        return out

    # ---------------- step builders ----------------
    def _compute_grads(self, params, states, batch, rng):
        """Gradients for one (micro)batch. For sparse tables the touched
        rows are pre-gathered OUTSIDE the differentiated function
        (forward consumes them via the "__rows__" override), so autodiff
        returns row-gradients instead of a dense table.

        -> (loss, logits, new_states, grads, sparse_idx) where `grads`
        has {"__rows__": ...} entries for sparse ops."""
        from ..ops.embedding import DistributedEmbedding
        seq_length = self.config.iter_config.seq_length
        sparse_ops = self._sparse_table_ops()
        diff_params = params
        sparse_idx: Dict[str, jax.Array] = {}
        if sparse_ops:
            diff_params = dict(params)
            for name, op in sparse_ops.items():
                table = params[name]["kernel"]
                if isinstance(op, DistributedEmbedding):
                    # slot order (matches the kernel layout, incl.
                    # device-placed permutations)
                    idx = op.slot_ids([batch[t.name]
                                       for t in op.inputs])
                    # flat slot-offset gather, NOT vmap(take): the
                    # batched-gather form mis-partitions under GSPMD
                    # when the slot axis is sharded (ops/embedding.py
                    # _slot_gather has the full story)
                    from ..ops.embedding import _slot_gather
                    rows = _slot_gather(table, idx)
                else:
                    idx = batch[op.inputs[0].name].astype(jnp.int32)
                    rows = jnp.take(table, idx, axis=0, mode="clip")
                sparse_idx[name] = idx
                diff_params[name] = {"__rows__": rows}
        grad_fn = jax.value_and_grad(
            self._outputs_and_loss, argnums=0, has_aux=True)
        (loss, (logits, new_states)), grads = grad_fn(
            diff_params, states, batch, True, rng, seq_length)
        return loss, logits, new_states, grads, sparse_idx

    @jax.named_scope("optimizer")
    def _apply_update(self, state: TrainState, grads, sparse_idx,
                      new_states, lr_scale=1.0) -> TrainState:
        """Apply the optimizer to dense grads + scatter-apply sparse row
        grads; returns the next TrainState (metrics are the caller's)."""
        from ..ops.embedding import DistributedEmbedding
        sparse_ops = self._sparse_table_ops()
        if sparse_ops:
            dense_params = {k: v for k, v in state.params.items()
                            if k not in sparse_ops}
            dense_grads = {k: grads[k] for k in dense_params}
            # optimizer state mirrors params at the top (op-name) level
            # for both built-ins ({"v": {op: ...}} / {"m","v"}): split
            # out the sparse tables' slots so the dense update's tree
            # structures match, then merge the scatter-updated slots back
            dense_opt = {slot: {k: v for k, v in tree.items()
                                if k not in sparse_ops}
                         for slot, tree in state.opt_state.items()}
            new_params, new_opt = self.optimizer.update(
                dense_params, dense_grads, dense_opt, state.step,
                lr_scale=lr_scale)
            new_params = dict(new_params)
            new_opt = {slot: dict(tree) for slot, tree in new_opt.items()}
            for name, op in sparse_ops.items():
                table = state.params[name]["kernel"]
                g = grads[name]["__rows__"]
                dim = table.shape[-1]
                slots = {slot: tree[name]["kernel"]
                         for slot, tree in state.opt_state.items()
                         if name in tree}
                if isinstance(op, DistributedEmbedding):
                    # ONE flat scatter over the (S*vocab, dim) view with
                    # slot-offset row ids — the update-side twin of
                    # _slot_gather. vmap(sparse_update) is a batched
                    # scatter whose operand is sharded on its batch
                    # (slot) dim; with it in the program GSPMD
                    # mis-partitions the step on a data x model mesh
                    # (loss 3.9% off the unsharded reference under jax
                    # 0.9.0). Slot blocks never share a global row id,
                    # so the flat update is the per-table update.
                    ntab, vocab = table.shape[0], table.shape[1]
                    gid = sparse_idx[name].reshape(ntab, -1) + (
                        jnp.arange(ntab, dtype=jnp.int32)[:, None]
                        * vocab)
                    newt, new_slots = self.optimizer.sparse_update(
                        table.reshape(ntab * vocab, dim),
                        gid.reshape(-1), g.reshape(-1, dim),
                        {k: v.reshape(ntab * vocab, dim)
                         for k, v in slots.items()},
                        state.step, lr_scale=lr_scale)
                    newt = newt.reshape(table.shape)
                    new_slots = {k: v.reshape(table.shape)
                                 for k, v in new_slots.items()}
                else:
                    newt, new_slots = self.optimizer.sparse_update(
                        table, sparse_idx[name].reshape(-1),
                        g.reshape(-1, dim), slots, state.step,
                        lr_scale=lr_scale)
                new_params[name] = {**state.params[name], "kernel": newt}
                for slot, arr in new_slots.items():
                    new_opt[slot][name] = {
                        **state.opt_state[slot][name], "kernel": arr}
        else:
            new_params, new_opt = self.optimizer.update(
                state.params, grads, state.opt_state, state.step,
                lr_scale=lr_scale)
        shardings = getattr(self, "_opt_shardings", None)
        if shardings is not None:
            # ZeRO slots must STAY data-sharded across steps: without
            # the constraint XLA's propagation may emit replicated slot
            # outputs, silently un-sharding them after one step
            new_opt = jax.tree_util.tree_map(
                lambda a, sh: (a if sh is _NO_SHARDING
                               else jax.lax.with_sharding_constraint(
                                   a, sh)),
                new_opt, shardings)
        return TrainState(new_params, new_states, new_opt, state.step + 1)

    def _step_body(self, state: TrainState, batch: Dict[str, jax.Array],
                   rng, lr_scale=1.0
                   ) -> Tuple[TrainState, Dict[str, jax.Array]]:
        """One optimizer step (pure; shared by the single-step and the
        scanned multi-step compilations)."""
        loss, logits, new_states, grads, sparse_idx = self._compute_grads(
            state.params, state.states, batch, rng)
        new_state = self._apply_update(state, grads, sparse_idx,
                                       new_states, lr_scale)
        metrics = {"loss": loss}
        if "label" in batch and self.metric_names:
            sparse = self.loss_name.startswith("sparse")
            metrics.update(M.compute_metrics(
                self.metric_names, logits, batch["label"], sparse))
        return new_state, metrics

    def build_train_step(self):
        return jax.jit(self._step_body, donate_argnums=(0,))

    def _multi_step_unroll(self) -> bool:
        """Should train_step_multi unroll its K steps instead of
        lax.scan? config.multi_step_unroll: True / False / "auto".
        Auto unrolls only when the donated params are a large fraction
        of device memory (the scan's double-buffered carry would 2x
        them); everything else keeps the scan (constant compile time)."""
        mode = getattr(self.config, "multi_step_unroll", "auto")
        if mode is True or mode is False:
            return mode
        dev = jax.devices()[0]
        if dev.platform != "tpu":
            return False  # CPU/GPU alias scan carries in place
        # a TPU reports its HBM limit; a missing key is an error, not
        # a reason to assume some chip's size
        limit = dev.memory_stats()["bytes_limit"]
        state = getattr(self.model, "state", None)
        if state is None:
            return False
        # the double-buffered carry is the WHOLE donated TrainState:
        # params + op states + optimizer slots (Adam's m/v triple the
        # param bytes), not just params — counted PER DEVICE: on a
        # multi-device mesh a sharded leaf occupies only its shard
        # bytes per chip, and comparing global bytes against one
        # chip's bytes_limit would over-trigger the unrolled body
        # (paying K-times compile) on models that actually fit scanned
        def _per_device_bytes(x):
            itemsize = jnp.dtype(x.dtype).itemsize
            shd = getattr(x, "sharding", None)
            if shd is not None:
                try:
                    shard_shape = shd.shard_shape(x.shape)
                    n = 1
                    for d in shard_shape:
                        n *= d
                    return n * itemsize
                except Exception:
                    pass
            return x.size * itemsize

        pbytes = sum(
            _per_device_bytes(x)
            for x in jax.tree_util.tree_leaves(
                (state.params, state.states, state.opt_state)))
        return pbytes > 0.25 * limit

    def build_train_step_multi(self):
        """K optimizer steps per device dispatch, via `lax.scan` over the
        leading (step) axis of a stacked batch. This is the TPU analog of
        the reference's Legion trace record/replay (begin_trace/end_trace,
        SURVEY.md 3.3): one host round trip launches many iterations, so
        per-dispatch latency is amortized instead of paid per step. Metrics come back stacked
        with a leading (K,) axis."""

        unroll = self._train_step_multi_unroll
        if unroll is None:  # direct build_* callers (tests): resolve now
            unroll = self._multi_step_unroll()
        if unroll:
            # UNROLLED K steps: a lax.scan carry is double-buffered on
            # TPU (old + new buffer live across the body), which doubles
            # the resident footprint of the donated params — at DLRM
            # scale (26x1M-row tables = 6.2G) the scanned program needs
            # 2x-table scratch and OOMs a 16G chip that the single-step
            # program fits comfortably. Straight-line sequential updates
            # alias in place, keeping the one-dispatch amortization
            # without the 2x liveness. Compile time grows with K, so
            # this is gated on param bytes (big-param models have small
            # graphs in practice).
            def train_multi(state: TrainState, batches, rngs, lr_scale):
                k = jax.tree_util.tree_leaves(batches)[0].shape[0]
                out = []
                for i in range(k):
                    batch = jax.tree_util.tree_map(lambda x: x[i], batches)
                    state, metrics = self._step_body(
                        state, batch, rngs[i], lr_scale)
                    out.append(metrics)
                stacked = jax.tree_util.tree_map(
                    lambda *ms: jnp.stack(ms), *out)
                return state, stacked
        else:
            def train_multi(state: TrainState, batches, rngs, lr_scale):
                def body(st, xs):
                    batch, rng = xs
                    return self._step_body(st, batch, rng, lr_scale)

                return jax.lax.scan(body, state, (batches, rngs))

        return jax.jit(train_multi, donate_argnums=(0,))

    def build_train_step_accum(self):
        """Gradient accumulation: scan K MICRObatches computing and
        summing gradients, then apply ONE optimizer update with the mean
        — the effective batch is K x microbatch without K x the
        activation memory. No reference analog (FlexFlow scales batch by
        adding GPUs, multi_gpu_tests.sh GPUS*64); on TPU this is the
        standard single-chip route to large-batch parity. Sparse-table
        row gradients are CONCATENATED across microbatches and applied
        in one scatter, so the result is identical to a K x-sized batch
        (duplicates across microbatches coalesce exactly like duplicates
        within one). BN statistics advance per microbatch (each sees its
        own microbatch moments, as torch/keras accumulation loops do)."""
        sparse_ops = self._sparse_table_ops()

        def train_accum(state: TrainState, batches, rngs, lr_scale):
            k = jax.tree_util.tree_leaves(batches)[0].shape[0]
            dense_zero = jax.tree_util.tree_map(
                lambda w: jnp.zeros(w.shape, jnp.float32),
                {n: p for n, p in state.params.items()
                 if n not in sparse_ops})

            def body(carry, xs):
                states_c, gacc = carry
                batch, rng = xs
                loss, logits, new_states, grads, sidx = \
                    self._compute_grads(state.params, states_c, batch,
                                        rng)
                dense_g = {n: grads[n] for n in gacc}
                gacc = jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(jnp.float32),
                    gacc, dense_g)
                rows = {n: grads[n]["__rows__"] for n in sparse_ops}
                metrics = {"loss": loss}
                if "label" in batch and self.metric_names:
                    sparse = self.loss_name.startswith("sparse")
                    metrics.update(M.compute_metrics(
                        self.metric_names, logits, batch["label"],
                        sparse))
                return (new_states, gacc), (rows, sidx, metrics)

            (new_states, gsum), (rows_st, sidx_st, metrics) = \
                jax.lax.scan(body, (state.states, dense_zero),
                             (batches, rngs))
            # mean over microbatches = the K x-batch loss gradient
            gmean = jax.tree_util.tree_map(lambda g: g / k, gsum)
            grads = dict(gmean)
            sparse_idx = {}
            for name, op in sparse_ops.items():
                r = rows_st[name] / k          # (K, ...) row grads
                i = sidx_st[name]              # (K, ...) indices
                from ..ops.embedding import DistributedEmbedding
                if isinstance(op, DistributedEmbedding):
                    # (K, E, ...) -> (E, K*...): per-table concat
                    r = jnp.moveaxis(r, 0, 1)
                    i = jnp.moveaxis(i, 0, 1)
                    ntab = r.shape[0]
                    r = r.reshape(ntab, -1, r.shape[-1])
                    i = i.reshape(ntab, -1)
                else:
                    r = r.reshape(-1, r.shape[-1])
                    i = i.reshape(-1)
                grads[name] = {"__rows__": r}
                sparse_idx[name] = i
            new_state = self._apply_update(state, grads, sparse_idx,
                                           new_states, lr_scale)
            # one optimizer step happened, whatever K was: fold the
            # per-microbatch metrics like one K x batch (sums of
            # sum-style metrics, mean loss)
            metrics = {name: jnp.sum(v, axis=0)
                       for name, v in metrics.items()}
            metrics["loss"] = metrics["loss"] / k
            return new_state, metrics

        return jax.jit(train_accum, donate_argnums=(0,))

    def _eval_body(self, state: TrainState, batch: Dict[str, jax.Array]):
        loss, (logits, _) = self._outputs_and_loss(
            state.params, state.states, batch, False, None,
            self.config.iter_config.seq_length)
        metrics = {"loss": loss}
        if "label" in batch and self.metric_names:
            sparse = self.loss_name.startswith("sparse")
            metrics.update(M.compute_metrics(
                self.metric_names, logits, batch["label"], sparse))
        return logits, metrics

    def build_eval_step(self):
        return jax.jit(self._eval_body)

    def build_eval_step_multi(self):
        """K eval batches per dispatch (scan over the stacked step axis;
        read-only twin of train_step_multi). Returns metrics stacked
        (K,) — logits are dropped to keep the dispatch output small."""

        def eval_multi(state: TrainState, batches):
            def body(_, batch):
                _logits, metrics = self._eval_body(state, batch)
                return (), metrics

            _, metrics = jax.lax.scan(body, (), batches)
            return metrics

        return jax.jit(eval_multi)

    def _require_training(self):
        if self.comp_mode == "inference":
            raise RuntimeError(
                "model was compiled with comp_mode=INFERENCE (no "
                "optimizer state); recompile with comp_mode=TRAINING "
                "to train")

    # ---------------- program registry ----------------
    def _opt_sig(self):
        """Stable token for the optimizer's PROGRAM identity: class +
        scalar hyperparameters (they are baked into the compiled step
        as constants — the runtime lr_scale is the only traced knob)."""
        opt = self.optimizer
        if opt is None:
            return None
        hp = {k: v for k, v in vars(opt).items()
              if isinstance(v, (int, float, bool, str))}
        return (type(opt).__name__, tuple(sorted(hp.items())))

    def _train_fingerprint(self) -> dict:
        """Cache identity of this executor's train programs — the
        analog of ServeEngine._program_fingerprint for fit's step
        (argument shapes/dtypes/shardings are keyed per call by the
        registry; this folds what the arguments cannot express)."""
        cfg = self.config
        mesh_sig = None
        device_ids = (jax.devices()[0].id,)
        if self.mesh is not None:
            mesh_sig = tuple(sorted(
                (str(k), int(v))
                for k, v in dict(self.mesh.shape).items()))
            # an executable runs only on the devices it was compiled
            # for, in mesh order — part of the store's identity
            device_ids = tuple(int(d.id) for d in self.mesh.devices.flat)
        arch = tuple((op.name, type(op).__name__)
                     for op in self.model.ops)
        return {
            "kind": "train",
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "devices": jax.device_count(),
            "arch": arch,
            "mesh": mesh_sig,
            "device_ids": device_ids,
            "compute_dtype": str(self.compute_dtype),
            "param_dtype": str(self.param_dtype),
            "loss": self.loss_name,
            "metrics": tuple(self.metric_names),
            "grad_bucket_mb": self._grad_bucket_mb,
            "fusion": bool(cfg.perform_fusion),
            "seq_length": cfg.iter_config.seq_length,
        }

    def _train_variant(self) -> str:
        """Per-dispatch build-variant token folded into the registry
        key: everything _sparse_table_ops / the multi-mode check can
        rebuild the jitted step over WITHOUT any argument changing
        shape. A stale-variant executable therefore can never be
        resolved for a rebuilt step."""
        mode = self.optimizer.sparse_mode() if self.optimizer else None
        return repr((self.config.sparse_embedding_updates,
                     self.config.sparse_embedding_lazy,
                     self._opt_sig(), mode,
                     self._train_step_multi_unroll))

    def program_registry(self):
        """The executor's ProgramRegistry (built on first use)."""
        if self._programs is None:
            from .programs import ProgramRegistry
            self._programs = ProgramRegistry(
                self._train_fingerprint(),
                cache_dir=getattr(self.config, "program_cache_dir", None),
                phase=self.model.setup_phase)
            self._programs.load_warm()
        return self._programs

    def compile_counts(self) -> dict:
        """Exact per-family compile counts for the train programs
        (registry query — empty dict before the first dispatch)."""
        reg = self._programs
        return {} if reg is None else reg.compile_counts()

    def boot_record(self) -> dict:
        """What the train programs' registry cost so far
        (ProgramRegistry.boot_record; empty before the first
        dispatch)."""
        reg = self._programs
        return {} if reg is None else reg.boot_record()

    def save_programs(self) -> int:
        """Snapshot freshly compiled train executables to
        config.program_cache_dir (no-op when unarmed/clean). fit calls
        this at exit so the next process boots the step warm."""
        reg = self._programs
        if reg is None or not reg.cache_dir or not reg._dirty:
            return 0
        return reg.save()

    def _lr(self):
        """The runtime LR multiplier as a traced scalar input — a value
        change re-dispatches, never recompiles.

        The device scalar is CACHED: re-making it per dispatch would put
        one synchronous host->device transfer on every train_batches
        call, serializing the otherwise-async dispatch queue on host
        round trips — all other dispatch arguments (donated state,
        staged batches) are already device-resident by design."""
        if (self._lr_device is None
                or self._lr_device_scale != self._lr_scale):
            self._lr_device = jnp.asarray(self._lr_scale, jnp.float32)
            self._lr_device_scale = self._lr_scale
        return self._lr_device

    @property
    def train_step(self):
        self._require_training()
        # consult the sparse routing FIRST: a post-build change to the
        # sparse flags/optimizer invalidates the cached compiled step
        # (see _sparse_table_ops), so the rebuild happens on dispatch
        self._sparse_table_ops()
        if self._train_step is None:
            self._train_step = self.build_train_step()
        jitted = self._train_step
        reg = self.program_registry()
        var = self._train_variant()
        return lambda st, b, r: reg.call(
            "train_step", jitted, st, b, r, self._lr(), extra_key=var)

    @property
    def train_step_multi(self):
        self._require_training()
        self._sparse_table_ops()
        # the compiled body bakes in the scan-vs-unroll choice: a
        # post-build change to config.multi_step_unroll (the documented
        # OOM override) must rebuild, same as the sparse-routing key.
        # The RESOLVED decision is cached against the config value —
        # _multi_step_unroll() itself touches jax.devices().
        # memory_stats() and sums the param tree, which must not run
        # per dispatch in the hot loop this property serves
        mode = getattr(self.config, "multi_step_unroll", "auto")
        if (self._train_step_multi_mode != mode
                or self._train_step_multi_unroll is None):
            self._train_step_multi = None
            self._train_step_multi_mode = mode
            self._train_step_multi_unroll = self._multi_step_unroll()
        if self._train_step_multi is None:
            self._train_step_multi = self.build_train_step_multi()
        jitted = self._train_step_multi
        reg = self.program_registry()
        var = self._train_variant()
        return lambda st, bs, rs: reg.call(
            "train_step_multi", jitted, st, bs, rs, self._lr(),
            extra_key=var)

    @property
    def train_step_accum(self):
        self._require_training()
        self._sparse_table_ops()
        if self._train_step_accum is None:
            self._train_step_accum = self.build_train_step_accum()
        jitted = self._train_step_accum
        reg = self.program_registry()
        var = self._train_variant()
        return lambda st, bs, rs: reg.call(
            "train_step_accum", jitted, st, bs, rs, self._lr(),
            extra_key=var)

    @property
    def eval_step(self):
        if self._eval_step is None:
            self._eval_step = self.build_eval_step()
        return self._eval_step

    @property
    def eval_step_multi(self):
        if self._eval_step_multi is None:
            self._eval_step_multi = self.build_eval_step_multi()
        return self._eval_step_multi

    # ---------------- data placement ----------------
    @property
    def declared_input_dtypes(self) -> Dict[str, Any]:
        """Target device dtype per input name — THE dtype-resolution rule
        for batches (shard_batch, shard_batch_stacked, and fit()'s
        prefetch loader all share it so every path casts identically).
        Under an active compute_dtype policy float inputs declare the
        COMPUTE dtype, so the dataloader casts in the host->device
        transfer (half the transfer bytes) and the in-step cast is a
        no-op."""
        out: Dict[str, Any] = {}
        for t in self.model.input_tensors:
            dt = t.dtype
            if self._mp_active and jnp.issubdtype(dt, jnp.floating):
                dt = self.compute_dtype
            out[t.name] = dt
        return out

    def shard_batch(self, batch: Dict[str, np.ndarray]):
        """Place a host batch on device(s), sharded over the data axis —
        the TPU analog of SingleDataLoader::next_batch's per-part copies
        (flexflow_dataloader.cc:649-740). Inputs are cast to their
        DECLARED tensor dtype (a bf16 model fed f32 numpy trains in bf16,
        like the reference loader honoring the region's type)."""
        declared = self.declared_input_dtypes
        multi = jax.process_count() > 1
        out = {}
        for k, v in batch.items():
            want = declared.get(k)
            if self.mesh is not None and multi:
                # multi-controller SPMD: each process holds ITS shard of
                # the global batch (global batch = concat over
                # processes); device_put cannot address remote devices —
                # this is the make_array_from_process_local_data path
                # SURVEY §7.7 prescribes for the loader
                if isinstance(v, jax.Array) \
                        and not v.is_fully_addressable:
                    # already a global array (loader/caller placed it);
                    # an eager cast is impossible here, so a declared-
                    # dtype mismatch must fail, not silently train wide
                    if want is not None and v.dtype != want:
                        raise TypeError(
                            f"input {k!r}: pre-placed global array has "
                            f"dtype {v.dtype}, declared {want}; place "
                            f"it with the declared dtype")
                    out[k] = v
                    continue
                host = np.asarray(v, dtype=want) if want is not None \
                    else np.asarray(v)
                out[k] = place_process_local(
                    host, batch_sharding(self.mesh, host.ndim))
                continue
            # single-pass conversion: asarray+astype would materialize
            # the batch twice on device per step; likewise a host batch
            # bound for a mesh is cast on HOST and device_put ONCE
            # straight to the sharding (jnp.asarray first would land it
            # on the default device and copy it again — the
            # host_to_device double-materialization, core/dataloader.py)
            if self.mesh is not None and not isinstance(v, jax.Array):
                host = np.asarray(v) if want is None \
                    else np.asarray(v, dtype=jnp.dtype(want))
                out[k] = jax.device_put(
                    host, batch_sharding(self.mesh, host.ndim))
                continue
            arr = jnp.asarray(v, dtype=want) if want is not None \
                else jnp.asarray(v)
            if self.mesh is not None:
                out[k] = jax.device_put(
                    arr, batch_sharding(self.mesh, arr.ndim))
            else:
                out[k] = arr
        return out


    def shard_batch_stacked(self, batches: List[Dict[str, np.ndarray]]):
        """Stack K host batches along a new leading (step) axis and place
        them on device for `train_step_multi` — the data axis moves to
        dim 1, the step axis stays unsharded (each scan iteration
        consumes one slice). Values that already live on device are
        stacked device-side (never round-tripped through the host — a
        device->host pull per dispatch would dwarf the dispatch cost the
        multi-step path exists to amortize)."""
        declared = self.declared_input_dtypes
        keys = batches[0].keys()
        out = {}
        multi = jax.process_count() > 1

        def stacked_sharding(ndim):
            # spec of one step-slice, shifted right past the step axis
            sh = batch_sharding(self.mesh, ndim - 1)
            spec = P(None, *sh.spec) if sh.spec else P()
            return NamedSharding(self.mesh, spec)

        for k in keys:
            vals = [b[k] for b in batches]
            want = declared.get(k)
            if multi and any(isinstance(v, jax.Array) for v in vals):
                # eager stack/device_put cannot place onto the global
                # mesh from one process; grouped dispatch over
                # pre-placed device batches is a single-process feature
                raise NotImplementedError(
                    "steps_per_dispatch over device-resident batches is "
                    "not supported in multi-process runs; pass host "
                    "numpy batches (each process's shard)")
            if all(isinstance(v, jax.Array) for v in vals):
                arr = jnp.stack([
                    v if want is None or v.dtype == want else v.astype(want)
                    for v in vals])
            else:
                stacked = np.stack([np.asarray(v) for v in vals])
                if self.mesh is not None and multi:
                    host = stacked.astype(want) if want is not None \
                        else stacked
                    out[k] = place_process_local(
                        host, stacked_sharding(host.ndim))
                    continue
                arr = jnp.asarray(stacked, dtype=want) if want is not None \
                    else jnp.asarray(stacked)
            if self.mesh is not None:
                out[k] = jax.device_put(arr, stacked_sharding(arr.ndim))
            else:
                out[k] = arr
        return out


def _stable_hash(s: str) -> int:
    """Deterministic string hash (Python's hash() is salted per-process)."""
    h = 2166136261
    for c in s.encode():
        h = ((h ^ c) * 16777619) & 0x7FFFFFFF
    return h
