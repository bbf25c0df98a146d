"""Device mesh construction.

The reference discovers GPUs/CPUs and their memories inside the mapper
(mapper.cc:55-145) and encodes machines analytically in `MachineModel`
(machine_model.cc). On TPU the machine is a `jax.sharding.Mesh`: an N-D
array of devices with named axes. Canonical axis names:

  data      — batch (DP; reference "sample parallel")
  model     — tensor parallel (reference "parameter/attribute parallel")
  seq       — sequence/context parallel (new, no reference analog)
  expert    — expert parallel for MoE (new)
  pipe      — pipeline stages (new)

Meshes should be laid out so the fastest-varying axes ride ICI; multi-host
meshes put `data` on DCN (jax device order already enumerates
process-local devices contiguously, which achieves this).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

DATA = "data"
MODEL = "model"
SEQ_AX = "seq"
EXPERT_AX = "expert"
PIPE = "pipe"
# serving-side tensor parallelism (docs/serving.md "Sharded serving"):
# the ONE mixed prefill+decode program shards over a 1-D mesh on this
# axis — head-parallel attention, head-sharded KV pages, vocab-sharded
# embedding/head. Named distinctly from the training axes because a
# serve mesh is built per engine, not per FFModel.
TENSOR = "tensor"

ALL_AXES = (DATA, MODEL, SEQ_AX, EXPERT_AX, PIPE)


@dataclasses.dataclass
class MachineSpec:
    """Analytic description of the target machine for the cost model
    (replaces reference EnhancedMachineModel, simulator.h:99-236).

    Defaults approximate a TPU v5p pod slice.
    """

    num_chips: int = 1
    # per-chip
    peak_flops: float = 459e12  # bf16 FLOP/s per v5p chip
    hbm_bandwidth: float = 2.765e12  # bytes/s
    hbm_capacity: float = 95e9  # bytes
    vmem_capacity: float = 128e6
    # interconnect
    ici_bandwidth: float = 9e10 * 2  # bytes/s per link, 3D torus, bidir
    ici_latency: float = 1e-6
    dcn_bandwidth: float = 25e9
    dcn_latency: float = 10e-6
    # chips sharing one host NIC: DCN collectives funnel every local
    # chip's traffic through it, so effective per-chip DCN bandwidth is
    # dcn_bandwidth/chips_per_host (the reference's EnhancedMachineModel
    # models the same shared-NIC congestion, machine_model.cc:172+)
    chips_per_host: int = 4
    # host link (PCIe-class DMA between a chip's HBM and its host's
    # DRAM): the path a DISAGGREGATED serving deployment ships finished
    # KV pages over (prefill engine -> host -> decode engine,
    # serve/disagg.py). Priced by TPUMachineModel.host_transfer so the
    # placement search can weigh the page-handoff link against the
    # compute it frees (search/serve_place.optimize_serve_disagg).
    host_link_bandwidth: float = 5e10  # bytes/s per chip<->host DMA
    host_link_latency: float = 5e-6
    # physical ICI torus factorization of the slice, e.g. (4, 4, 4) for
    # a 64-chip v5p cube or (16, 16) for a v5e pod; () = flat/unknown
    # (every mesh axis priced as a single ring). A mesh axis laid out
    # over k torus dims runs its collective phases over k link sets
    # concurrently (the analog of reference get_comm_path routing over
    # the physical hierarchy, machine_model.cc:695).
    ici_torus_dims: tuple = ()
    # wraparound links present (torus vs line): halves worst-case hop
    # distance and doubles bisection
    ici_wraparound: bool = True

    @staticmethod
    def v5e(num_chips: int = 1) -> "MachineSpec":
        return MachineSpec(
            num_chips=num_chips, peak_flops=197e12, hbm_bandwidth=8.1e11,
            hbm_capacity=16e9, ici_bandwidth=4.5e10, dcn_bandwidth=25e9)

    @staticmethod
    def for_device_kind(device_kind: str) -> Optional["MachineSpec"]:
        """The spec for a TPU `device_kind` string as JAX reports it
        ("TPU v5 lite" is a v5e chip), or None for a kind the repo
        holds no numbers for — callers on a TPU backend treat None as
        an error, never as "price it like a v5e"."""
        kind = device_kind.lower()
        if "v5 lite" in kind or "v5e" in kind:
            return MachineSpec.v5e()
        if "v5p" in kind:
            return MachineSpec()  # the dataclass defaults are a v5p's
        return None


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> Mesh:
    """Build a Mesh from axis sizes/names over the available devices."""
    if devices is None:
        devices = jax.devices()
    n = int(np.prod(shape))
    assert n <= len(devices), (
        f"mesh needs {n} devices, have {len(devices)}")
    arr = np.array(devices[:n]).reshape(tuple(shape))
    return Mesh(arr, tuple(axes))


def default_mesh(num_devices: Optional[int] = None) -> Mesh:
    """Pure data-parallel mesh over all devices (the reference's default
    strategy is pure DP too — mapper.cc:118-145 seeds 1D-5D DP)."""
    devices = jax.devices()
    if num_devices is not None:
        devices = devices[:num_devices]
    return make_mesh((len(devices),), (DATA,), devices)


def single_device_mesh() -> Mesh:
    return make_mesh((1,), (DATA,), jax.devices()[:1])


def replica_devices(index: int, degree: int,
                    devices: Optional[Sequence] = None,
                    platform: Optional[str] = None) -> tuple:
    """The chips a serving pool's replica `index` of tensor degree
    `degree` owns: devices [index*degree, (index+1)*degree) — replicas
    never stack on chip 0. On a tpu platform a replica that reaches past
    the last chip is an error naming both numbers. The virtual-CPU test
    platform builds more replicas than it has devices, so there the
    range wraps (and the pool's report shows the shared devices).
    `devices` / `platform` default to jax.devices() and its platform."""
    if devices is None:
        devices = jax.devices()
    if platform is None:
        platform = devices[0].platform
    lo, hi = int(index) * int(degree), (int(index) + 1) * int(degree)
    if hi > len(devices) and platform == "tpu":
        raise ValueError(
            f"replica {index} at tensor degree {degree} needs chips "
            f"[{lo}, {hi}) but this host has {len(devices)}")
    return tuple(devices[j % len(devices)] for j in range(lo, hi))


def serve_tensor_mesh(tensor_parallel: int,
                      devices: Optional[Sequence] = None) -> Mesh:
    """The 1-D serving mesh ServeEngine shards the mixed program over:
    `tensor_parallel` devices on the TENSOR axis (head-parallel
    attention + head-sharded KV pages + vocab-sharded embedding/head,
    docs/serving.md)."""
    return make_mesh((int(tensor_parallel),), (TENSOR,), devices)
