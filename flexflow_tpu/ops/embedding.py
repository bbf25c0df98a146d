"""Embedding lookup with SUM/AVG aggregation.

Reference: src/ops/embedding.cu (custom gather/scatter-add kernels) plus a
hand-vectorized AVX2 CPU embedding-bag (embedding_avx2.cc:15-296). The op
takes int indices of shape (batch, bag) and produces (batch, out_dim),
aggregating over the bag dimension — DLRM-style embedding bag.

TPU-native design: a plain `take` gather; XLA lowers it to an efficient
one-hot-matmul or dynamic-gather depending on table size. The table's
`vocab` logical axis can be mapped to a mesh axis for DLRM parameter
parallelism (the reference placed whole tables on specific GPUs via
strategies, SURVEY.md 2.3; sharding the vocab dim over ICI is the TPU
generalization).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..op import (
    CHANNEL_OUT,
    SAMPLE,
    TABLE,
    VOCAB,
    Op,
    OpContext,
    WeightSpec,
    register_op,
)

AGGR_MODE_NONE = "none"
AGGR_MODE_SUM = "sum"
AGGR_MODE_AVG = "avg"


def _slot_gather(tables, ids):
    """(S, vocab, dim) slot-stacked tables x (S, batch, bag) per-slot
    ids -> (S, batch, bag, dim) rows, via ONE flat gather over the
    reshaped (S*vocab, dim) table with slot-offset global row ids.

    Deliberately NOT `vmap(take)`: a batched gather whose OPERAND is
    sharded on its batch (slot) dim trips XLA's SPMD partitioner — the
    vocab index component gets rescaled by the shard factor, so the
    kernel reads row 2*v on a 2-way table axis (NaN under take's
    "fill" OOB default, silently wrong rows under "clip"; the
    combined-mesh dryrun loss=nan, ROADMAP open item). The flat form
    keeps dim 0 sharded (slot blocks stay contiguous, so the layout —
    and the per-device residency the cost model prices — is unchanged)
    and single-dim gathers partition correctly; mode="clip" matches
    XLA's native clamp semantics, and real ids are in-bounds by
    construction (tests/test_distributed_embedding.py pins forward
    equality to the unsharded reference)."""
    S, V, _ = tables.shape
    flat = tables.reshape(S * V, tables.shape[-1])
    gid = ids + (jnp.arange(S, dtype=ids.dtype)[:, None, None] * V)
    return jnp.take(flat, gid, axis=0, mode="clip")


@register_op
class Embedding(Op):
    op_type = "embedding"

    def __init__(self, model, name, inputs, num_entries: int, out_dim: int,
                 aggr: str = AGGR_MODE_SUM, kernel_initializer: str = "glorot",
                 dtype=None, emit_table: bool = False):
        super().__init__(model, name, inputs)
        # a second output, the (num_entries, out_dim) table itself: what
        # a head TIED to this embedding reads (ops/gated.TiedHead)
        self.emit_table = bool(emit_table)
        self.num_entries = int(num_entries)
        self.out_dim = int(out_dim)
        self.aggr = aggr
        self.kernel_initializer = kernel_initializer
        # output/activation dtype; the table itself stays f32 (mixed
        # precision: downstream compute follows the activation dtype)
        self.out_dtype = jnp.dtype(dtype) if dtype is not None \
            else jnp.dtype(jnp.float32)
        self.attrs = {"num_entries": num_entries, "out_dim": out_dim,
                      "aggr": aggr}

    def output_shapes(self):
        in_shape = self.inputs[0].shape
        table = [(self.num_entries, self.out_dim)] * self.emit_table
        if self.aggr == AGGR_MODE_NONE:
            return [tuple(in_shape) + (self.out_dim,)] + table
        # (batch, bag) -> (batch, out_dim): aggregate over the bag dim.
        return [(in_shape[0], self.out_dim)] + table

    def output_dtypes(self):
        return [self.out_dtype] * (1 + self.emit_table)

    def weight_specs(self):
        return {
            "kernel": WeightSpec(
                shape=(self.num_entries, self.out_dim),
                initializer=self.kernel_initializer,
                axes=(VOCAB, CHANNEL_OUT),
            )
        }

    def forward(self, params, xs, ctx: OpContext):
        (idx,) = xs
        if "__rows__" in params:
            # sparse-update path (executor pre-gathered the touched rows
            # outside the differentiated function): the gradient flows to
            # the ROWS, not the full table, and the optimizer applies a
            # scatter update — the TPU analog of the reference's
            # scatter-add embedding backward (src/ops/embedding.cu)
            emb = params["__rows__"]
        else:
            # mode="clip", not the "fill" (NaN) OOB default: fill mode
            # wraps the gather in an OOB-validity select that interacts
            # badly with GSPMD partitioning of sharded gathers (see
            # _slot_gather); clip is XLA's native clamp semantics and
            # partitions cleanly, and real ids are in-bounds anyway.
            emb = jnp.take(params["kernel"], idx.astype(jnp.int32), axis=0,
                           mode="clip")
        if self.aggr == AGGR_MODE_SUM:
            emb = jnp.sum(emb, axis=-2)
        elif self.aggr == AGGR_MODE_AVG:
            emb = jnp.mean(emb, axis=-2)
        if self.emit_table:
            return [emb.astype(self.out_dtype),
                    params["kernel"].astype(self.out_dtype)]
        return [emb.astype(self.out_dtype)]

    def output_axes(self):
        n = len(self.outputs[0].shape)
        axes = [None] * n
        axes[0] = SAMPLE
        axes[-1] = CHANNEL_OUT
        return [tuple(axes)] + [(None, None)] * self.emit_table

    def input_axes(self):
        axes = [None] * len(self.inputs[0].shape)
        axes[0] = SAMPLE
        return [tuple(axes)]

    def flops(self) -> float:
        bag = self.inputs[0].shape[-1] if len(self.inputs[0].shape) > 1 else 1
        return float(self.inputs[0].shape[0] * bag * self.out_dim)


@register_op
class DistributedEmbedding(Op):
    """E same-vocab embedding bags as ONE stacked (E, vocab, dim) weight
    whose `table` logical axis maps to a mesh axis — the EXECUTABLE form
    of the reference's per-device table placement (DLRM strategies pin
    table i to GPU i, examples/cpp/DLRM/strategies/dlrm_strategy.cc:1-50;
    GSPMD cannot address single devices, so whole-table-per-device
    becomes table-axis sharding: with E == mesh-axis size each device
    holds exactly one vocab-complete table, lookups run concurrently
    where the tables live, and XLA inserts the output all-gather the
    simulator prices for placed ops).

    Inputs: E index tensors of shape (batch, bag); outputs: E tensors of
    shape (batch, dim) in the same order (drop-in for a list of
    `Embedding` ops, models/dlrm.py).

    Device-EXPLICIT placement (reference ParallelConfig.device_ids,
    executed by slice_task mapper.cc:346-440): `apply_placement` lowers
    a per-table device-id tuple from the strategy into a SLOT layout —
    tables are grouped by assigned device, padded to K tables per
    device, and stacked as (n_dev*K, vocab, dim) whose slot axis shards
    over the FULL mesh in device order, so slot block d literally lives
    on mesh.devices.flat[d]. An arbitrary search-placed assignment
    (scattered, skewed, or blocked) then EXECUTES under GSPMD instead of
    falling back to replication; outputs are returned in original table
    order via the inverse slot map."""

    op_type = "distributed_embedding"

    def __init__(self, model, name, inputs, num_entries: int, out_dim: int,
                 aggr: str = AGGR_MODE_SUM,
                 kernel_initializer: str = "glorot", dtype=None):
        super().__init__(model, name, inputs)
        assert len(inputs) >= 1
        bag = inputs[0].shape
        assert len(bag) == 2, (
            f"distributed_embedding inputs must be (batch, bag), got "
            f"{bag}; reshape 1-D indices to (batch, 1)")
        for t in inputs:
            assert tuple(t.shape) == tuple(bag), (
                "all sparse inputs must share (batch, bag) shape")
        self.num_tables = len(inputs)
        self.num_entries = int(num_entries)
        self.out_dim = int(out_dim)
        self.aggr = aggr
        self.kernel_initializer = kernel_initializer
        self.out_dtype = jnp.dtype(dtype) if dtype is not None \
            else jnp.dtype(jnp.float32)
        self.attrs = {"num_tables": self.num_tables,
                      "num_entries": num_entries, "out_dim": out_dim,
                      "aggr": aggr}
        # device-explicit placement state (set at executor build via
        # apply_placement; None = plain table-axis stacking)
        self.placement = None       # per-table device ids
        self._slots = None          # slot -> table index (-1 = pad)
        self._slot_of_table = None  # table -> slot
        self.num_slots = self.num_tables

    def apply_placement(self, device_ids, mesh=None) -> None:
        """Lower per-table `device_ids` to the executable slot layout
        (see class docstring), or reset to plain stacking when None.
        Re-entrant: the executor calls this at every compile so a
        strategy change relays out the weight. A length-1 tuple pins ALL
        tables to that one device (the reference's whole-op pin)."""
        if device_ids is not None and len(device_ids) == 1 \
                and self.num_tables > 1:
            device_ids = tuple(device_ids) * self.num_tables
        if device_ids is not None and mesh is None:
            # meshless compile: a device-explicit placement cannot
            # execute, and building the padded slot layout anyway would
            # only multiply kernel memory — reset to plain stacking
            import warnings
            warnings.warn(
                f"{self.name}: device-explicit placement {device_ids} "
                f"ignored — no mesh to place on (meshless compile)")
            device_ids = None
        if device_ids is None:
            self.placement = None
            self._slots = None
            self._slot_of_table = None
            self.num_slots = self.num_tables
            return
        if len(device_ids) != self.num_tables:
            raise ValueError(
                f"{self.name}: device_ids length {len(device_ids)} != "
                f"num_tables {self.num_tables} (per-table placement "
                f"needs one device id per table, or exactly one id to "
                f"pin all tables)")
        n_dev = int(mesh.size)
        ids = [int(d) for d in device_ids]
        if any(d < 0 or d >= n_dev for d in ids):
            raise ValueError(
                f"{self.name}: device ids {ids} out of range for "
                f"{n_dev} devices")
        groups = [[] for _ in range(n_dev)]
        for t, d in enumerate(ids):
            groups[d].append(t)
        k = max(1, max(len(g) for g in groups))
        if n_dev * k >= 4 * self.num_tables:
            # the slot layout pads every device to the LARGEST group, so
            # a skewed assignment multiplies kernel memory (a (E,v,d)
            # table becomes (n_dev*k,v,d)); the cost model prices this
            # (search/cost_model.py pad factor) — surface it for
            # hand-written strategies too
            import warnings
            warnings.warn(
                f"{self.name}: placement {ids} pads {self.num_tables} "
                f"tables to {n_dev * k} slots ({n_dev * k / self.num_tables:.1f}x "
                f"kernel memory); balance tables across devices to "
                f"avoid the padding")
        slots = []
        for g in groups:
            slots += g + [-1] * (k - len(g))
        self.placement = tuple(ids)
        self._slots = tuple(slots)
        self._slot_of_table = tuple(slots.index(t)
                                    for t in range(self.num_tables))
        self.num_slots = n_dev * k

    def to_table_order(self, kernel):
        """(num_slots, vocab, dim) slot-layout kernel -> (num_tables,
        vocab, dim) in TABLE order (pads dropped) — the user-facing
        layout get_weights returns regardless of placement."""
        if self._slot_of_table is None:
            return kernel
        return kernel[list(self._slot_of_table)]

    def from_table_order(self, kernel_tables, current):
        """Inverse of to_table_order: scatter a table-ordered kernel
        into the slot layout (pad slots keep `current`'s values)."""
        if self._slot_of_table is None:
            return kernel_tables
        out = np.array(current, copy=True)
        for t, s in enumerate(self._slot_of_table):
            out[s] = kernel_tables[t]
        return out

    def slot_ids(self, xs):
        """Stack per-table index arrays into the (num_slots, batch, bag)
        slot order the kernel is laid out in; pad slots read row 0 of
        their (unused) pad table."""
        if self._slots is None:
            cols = xs
        else:
            zero = None
            cols = []
            for t in self._slots:
                if t >= 0:
                    cols.append(xs[t])
                else:
                    if zero is None:
                        zero = jnp.zeros_like(xs[0])
                    cols.append(zero)
        return jnp.stack([c.astype(jnp.int32) for c in cols], axis=0)

    def output_shapes(self):
        bs = self.inputs[0].shape[0]
        if self.aggr == AGGR_MODE_NONE:
            return [tuple(self.inputs[0].shape) + (self.out_dim,)] \
                * self.num_tables
        return [(bs, self.out_dim)] * self.num_tables

    def output_dtypes(self):
        return [self.out_dtype] * self.num_tables

    def weight_specs(self):
        return {
            "kernel": WeightSpec(
                shape=(self.num_slots, self.num_entries, self.out_dim),
                initializer=self.kernel_initializer,
                axes=(TABLE, VOCAB, CHANNEL_OUT),
                fan_in=self.num_entries, fan_out=self.out_dim,
            )
        }

    def forward(self, params, xs, ctx: OpContext):
        if "__rows__" in params:
            emb = params["__rows__"]  # (S, batch, bag, dim) pre-gathered
        else:
            tables = params["kernel"]  # (S, vocab, dim), slot order
            ids = self.slot_ids(xs)
            # flat slot-offset gather (sharded on `table` or
            # device-placed via slots, each device reads only its
            # resident tables and GSPMD gathers the result) —
            # _slot_gather explains why this must not be vmap(take)
            emb = _slot_gather(tables, ids)
        if self.aggr == AGGR_MODE_SUM:
            emb = jnp.sum(emb, axis=-2)
        elif self.aggr == AGGR_MODE_AVG:
            emb = jnp.mean(emb, axis=-2)
        order = (self._slot_of_table if self._slot_of_table is not None
                 else range(self.num_tables))
        return [emb[s].astype(self.out_dtype) for s in order]

    def output_axes(self):
        n = len(self.outputs[0].shape)  # 3-D when aggr == "none"
        axes = [None] * n
        axes[0] = SAMPLE
        axes[-1] = CHANNEL_OUT
        return [tuple(axes)] * self.num_tables

    def input_axes(self):
        axes = [None] * len(self.inputs[0].shape)
        axes[0] = SAMPLE
        return [tuple(axes)] * self.num_tables

    def flops(self) -> float:
        bs, bag = self.inputs[0].shape[0], self.inputs[0].shape[-1]
        return float(self.num_tables * bs * bag * self.out_dim)
