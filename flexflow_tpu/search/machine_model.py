"""TPU machine model: analytic costs for compute, HBM, and collectives.

Replaces the reference `MachineModel` hierarchy (include/simulator.h:99-236,
machine_model.cc — membus/UPI/NIC/PCIe/NVLink paths with per-segment
pipelining). On TPU the comm fabric collapses to two tiers: ICI (intra-pod
torus) and DCN (cross-slice); GSPMD's collectives have closed-form cost on
a ring/torus, so `get_comm_path` becomes per-collective formulas.

Calibration: `efficiency` factors default to typical XLA/TPU achieved
fractions and can be overwritten from real microbenchmarks
(search/measure.py) — the analog of the reference timing real kernels in
`measure_operator_cost`.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Dict, Optional

from ..parallel.mesh import MachineSpec


@dataclasses.dataclass
class TPUMachineModel:
    spec: MachineSpec
    # achieved-fraction calibration knobs (overridable via measure.py)
    efficiency: Dict[str, float] = dataclasses.field(default_factory=lambda: {
        "matmul": 0.55,      # MXU-bound ops (dense/attention GEMMs)
        "conv": 0.45,        # conv MXU fraction (im2col/layout overheads
        #                      put it below big-GEMM; MEASURED on device
        #                      by measure.py, reference conv_2d.cu:173-260
        #                      measures per-shape algorithms)
        "elementwise": 0.8,  # HBM-bound ops (fraction of peak HBM bw)
        "collective": 0.75,  # fraction of peak ICI bw
    })
    # per-dtype MXU rate relative to spec.peak_flops (which is the
    # bf16 basis — TPU datasheets quote bf16): f32 matmuls run at half
    # the bf16 rate (one MXU pass per f32 operand pair vs packed bf16),
    # f16 matches bf16. Overridable per machine file / calibration.
    dtype_flops_scale: Dict[str, float] = dataclasses.field(
        default_factory=lambda: {
            "bfloat16": 1.0, "float16": 1.0, "float32": 0.5})
    # mesh axes that ride DCN instead of ICI (multi-host `data` axis)
    dcn_axes: tuple = ()
    # mesh axis -> tuple of physical torus dims it spans (from
    # assign_axis_topology); {} = flat (one ring per axis). A k-dim
    # axis runs ring phases over k link sets concurrently, and
    # all-to-all is bisection-bound by its LARGEST dim — the TPU form
    # of the reference's physical comm paths (machine_model.cc:695).
    axis_topology: Dict[str, tuple] = dataclasses.field(
        default_factory=dict)

    def _phys(self, axis: Optional[str], axis_size: int):
        """(k concurrent link sets, largest physical dim) for an axis.
        DCN axes are switched, not tori — always flat."""
        dims = (self.axis_topology.get(axis)
                if axis and axis not in self.dcn_axes else None)
        if not dims:
            return 1, axis_size
        return len(dims), max(dims)

    # ---- compute ----
    def peak_flops_for(self, dtype: Optional[str] = None) -> float:
        """Peak MXU rate for a compute dtype. None keeps the raw
        spec.peak_flops (bf16 basis) — the pre-precision-policy
        behavior callers outside op_cost still rely on."""
        if dtype is None:
            return self.spec.peak_flops
        return self.spec.peak_flops * self.dtype_flops_scale.get(
            str(dtype), 1.0)

    def _eff(self, key: str, dtype: Optional[str]) -> float:
        """Per-family efficiency with an optional per-dtype override:
        "matmul:float32" (written by measure.calibrate's per-dtype
        pass) beats the family factor "matmul"."""
        base = self.efficiency.get(key, self.efficiency["matmul"])
        if dtype is None:
            return base
        return self.efficiency.get(f"{key}:{dtype}", base)

    def compute_time(self, flops: float, bytes_moved: float,
                     is_matmul: bool = True,
                     kind: Optional[str] = None,
                     dtype: Optional[str] = None) -> float:
        """Roofline: max of MXU time and HBM time. `kind` selects a
        measured per-family MXU efficiency ("conv" today); default is
        the big-GEMM factor. `dtype` prices the op at that compute
        dtype's peak rate and (when calibrated) its measured per-dtype
        efficiency — the cost-model half of the mixed-precision policy
        (callers scale `bytes_moved` by the dtype itemsize themselves,
        cost_model.op_cost)."""
        eff = self._eff(kind if kind is not None else "matmul", dtype)
        t_flops = flops / (self.peak_flops_for(dtype) * eff)
        t_mem = bytes_moved / (self.spec.hbm_bandwidth
                               * self.efficiency["elementwise"])
        return max(t_flops, t_mem)

    # ---- collectives (ring formulas over the relevant axis) ----
    def _bw_lat(self, axis: Optional[str]):
        if axis is not None and axis in self.dcn_axes:
            # shared-NIC congestion: every chip on the host funnels its
            # cross-host traffic through one NIC (reference
            # EnhancedMachineModel congestion, machine_model.cc:172+)
            sharers = max(1, self.spec.chips_per_host)
            return (self.spec.dcn_bandwidth / sharers,
                    self.spec.dcn_latency)
        return (self.spec.ici_bandwidth * self.efficiency["collective"],
                self.spec.ici_latency)

    def _ring_bw_mult(self, axis: Optional[str], k: int) -> float:
        """Bandwidth multiplier for ring collectives: k concurrent link
        sets on a torus; a line (no wraparound) cannot close the ring,
        so the bidirectional algorithm degrades to ~half the torus
        bandwidth (ICI only — DCN is switched)."""
        if axis is not None and axis in self.dcn_axes:
            return 1.0
        wrap = 1.0 if self.spec.ici_wraparound else 0.5
        return k * wrap

    def all_reduce(self, nbytes: float, axis_size: int,
                   axis: Optional[str] = None) -> float:
        if axis_size <= 1:
            return 0.0
        bw, lat = self._bw_lat(axis)
        k, dmax = self._phys(axis, axis_size)
        # k-dim torus: per-dim ring phases run over disjoint link sets
        # concurrently -> k x bandwidth; latency chain follows the
        # LONGEST dim's ring (other dims' hops overlap it)
        mult = self._ring_bw_mult(axis, k)
        return 2.0 * (axis_size - 1) / axis_size * nbytes / (bw * mult) \
            + 2 * (dmax - 1) * lat

    def all_gather(self, nbytes_out: float, axis_size: int,
                   axis: Optional[str] = None) -> float:
        if axis_size <= 1:
            return 0.0
        bw, lat = self._bw_lat(axis)
        k, dmax = self._phys(axis, axis_size)
        mult = self._ring_bw_mult(axis, k)
        return (axis_size - 1) / axis_size * nbytes_out / (bw * mult) \
            + (dmax - 1) * lat

    reduce_scatter = all_gather  # same ring cost

    def all_to_all(self, nbytes_local: float, axis_size: int,
                   axis: Optional[str] = None) -> float:
        if axis_size <= 1:
            return 0.0
        bw, lat = self._bw_lat(axis)
        k, dmax = self._phys(axis, axis_size)
        # bisection-bound: total V_local*n/4 bytes cross the worst cut;
        # a torus cut perpendicular to the largest dim has 2*n/dmax
        # (wraparound) link pairs -> T = V_local * dmax / (8 * bw) per
        # direction-pair; a line (no wraparound) halves the cut. The
        # old (n-1)/n ring formula underpriced large-n all-to-alls by
        # ~n/4 (EP dispatch misranking).
        wrap = 2.0 if self.spec.ici_wraparound else 1.0
        if axis is not None and axis in self.dcn_axes:
            # DCN is switched, not a torus: the NIC serializes the
            # (n-1)/n exchange — keep the flat formula
            return (axis_size - 1) / axis_size * nbytes_local / bw \
                + (axis_size - 1) * lat
        # worst-case hop distance: dmax/2 around a torus ring, dmax
        # end-to-end on a line
        hops = dmax / 2 if self.spec.ici_wraparound else dmax
        return nbytes_local * dmax / (4.0 * wrap * bw) + hops * lat

    def ppermute(self, nbytes: float, axis: Optional[str] = None) -> float:
        bw, lat = self._bw_lat(axis)
        return nbytes / bw + lat

    # ---- host link (disaggregated serving's page-handoff path) ----
    def host_transfer(self, nbytes: float) -> float:
        """Seconds to move `nbytes` over the chip<->host DMA link — the
        path a prefill engine ships finished KV pages over to a decode
        engine (serve/disagg.py). Priced like ppermute on the host-link
        spec: the search's transfer term, so a KV-dtype flip (fewer
        bytes per page) changes the handoff cost it weighs a
        prefill:decode ratio against."""
        if nbytes <= 0:
            return 0.0
        bw = max(1.0, float(getattr(self.spec, "host_link_bandwidth",
                                    5e10)))
        lat = float(getattr(self.spec, "host_link_latency", 5e-6))
        return nbytes / bw + lat

    # ---- memory penalty (reference simulator.cc:603-628: 1ms per MB
    # over framebuffer capacity) ----
    def memory_penalty(self, bytes_per_device: float) -> float:
        over = bytes_per_device - self.spec.hbm_capacity
        if over <= 0:
            return 0.0
        return over * 1e-9  # 1 ms per MB, same constant as the reference

    # ---- calibration I/O ----
    def save_calibration(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.efficiency, f)

    def load_calibration(self, path: str) -> None:
        with open(path) as f:
            self.efficiency.update(json.load(f))


def assign_axis_topology(mesh, torus_dims: tuple,
                         dcn_axes: tuple = ()) -> Dict[str, tuple]:
    """Lay mesh axes out over the physical torus factorization, in mesh
    axis order (the standard TPU layout: contiguous torus dims per mesh
    axis). Each axis consumes whole torus dims while their product
    divides the axis size; an axis that cannot be covered exactly (or
    once dims run out) falls back to a single ring. DCN-resident axes
    span hosts, not ICI links — they consume no torus dims. Mirrors
    what jax.experimental.mesh_utils.create_device_mesh arranges
    physically."""
    out: Dict[str, tuple] = {}
    if mesh is None or not torus_dims:
        return out
    remaining = list(torus_dims)
    for name, size in mesh.shape.items():
        if name in dcn_axes:
            continue
        got: list = []
        prod = 1
        while remaining and prod < size and size % (
                prod * remaining[0]) == 0:
            prod *= remaining[0]
            got.append(remaining.pop(0))
        if prod == size and got:
            out[name] = tuple(got)
        else:
            # not exactly coverable: restore and price as one ring
            remaining = got + remaining
    return out


def default_machine_model(mesh=None, spec: Optional[MachineSpec] = None,
                          machine_file: Optional[str] = None
                          ) -> TPUMachineModel:
    """Build a model for the current device. On a TPU the spec comes
    from the device's `device_kind` (MachineSpec.for_device_kind); a
    kind the repo holds no peaks for is an error unless `machine_file`
    describes the machine. Off-TPU (the CPU test platform) the v5e spec
    stands, as the stated simulation target.
    `machine_file` (FFConfig.machine_model_file) may override MachineSpec
    fields via JSON — the analog of the reference's machine config file
    (machine_config_example). A multi-host run marks the mesh's `data`
    axis as DCN-resident (cross-slice collectives priced at DCN rates)."""
    import jax
    user_spec = spec is not None
    if spec is None:
        spec = MachineSpec.v5e()
        dev = jax.devices()[0]
        if dev.platform == "tpu":
            known = MachineSpec.for_device_kind(dev.device_kind)
            if known is not None:
                spec = known
            elif not machine_file:
                raise ValueError(
                    f"no MachineSpec for TPU device_kind "
                    f"{dev.device_kind!r}: add it to "
                    f"parallel/mesh.MachineSpec.for_device_kind or "
                    f"describe the machine with --machine-model-file")
    file_keys = set()
    file_data: Dict = {}
    if machine_file:
        with open(machine_file) as f:
            file_data = json.load(f)
        for k, v in file_data.items():
            if hasattr(spec, k):
                setattr(spec, k, v)
                file_keys.add(k)
    dcn_axes = ()
    if mesh is not None:
        spec.num_chips = int(mesh.size)
        if jax.process_count() > 1 and "data" in mesh.shape:
            dcn_axes = ("data",)
            # autodetected topology must not clobber an explicit
            # value — from the machine file OR a caller-built spec
            if "chips_per_host" not in file_keys and not user_spec:
                spec.chips_per_host = max(1, jax.local_device_count())
    # physical-torus layout: machine-file per-axis pins
    # ({"axis_topology": {"data": [4, 4]}}) fully govern the axes they
    # mention — a pin dropped as invalid leaves THAT axis flat-ring, as
    # warned; axes the file does not mention derive from
    # spec.ici_torus_dims ({"ici_torus_dims": [4, 4, 4]}) when set
    pins: Dict[str, tuple] = {}
    pinned_axes: tuple = ()
    if "axis_topology" in file_data:
        raw = {k: tuple(v) for k, v in file_data["axis_topology"].items()}
        pinned_axes = tuple(raw)  # dropped pins stay excluded (= flat)
        import math
        import warnings
        for name, dims in raw.items():
            size = mesh.shape.get(name) if mesh is not None else None
            if size is not None and math.prod(dims) != size:
                warnings.warn(
                    f"machine file axis_topology[{name!r}]={dims} "
                    f"does not factor the mesh axis size {size}; "
                    f"ignoring the pin (flat-ring pricing)")
            else:
                pins[name] = dims
    # pins occupy physical dims: remove them (by multiset) from the
    # pool before deriving the unmentioned axes, or two mesh axes could
    # be priced on the same physical ICI dimension
    pool = list(getattr(spec, "ici_torus_dims", ()) or ())
    for dims in pins.values():
        for d in dims:
            if d in pool:
                pool.remove(d)
    derived = assign_axis_topology(mesh, tuple(pool),
                                   dcn_axes + pinned_axes)
    return TPUMachineModel(spec=spec, dcn_axes=dcn_axes,
                           axis_topology={**derived, **pins})
