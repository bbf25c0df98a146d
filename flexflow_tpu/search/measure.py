"""On-device microbenchmarks to calibrate the cost model.

The analog of the reference's `inner_measure_operator_cost`
(src/runtime/model.cu:20-62): run real kernels (warmup + repeats) and
record achieved efficiency. On TPU we calibrate the machine model's
efficiency factors once (matmul MXU fraction, elementwise HBM fraction)
instead of timing every (op, config) pair — candidate strategies can't be
individually timed without a recompile each (SURVEY.md 7 hard part (d)).

Timing regions are closed by a device->host scalar fetch (`_sync`),
which cannot return before the device has produced the value.
"""

from __future__ import annotations

import json
import os
import time
from typing import Optional

import numpy as np

from .machine_model import TPUMachineModel, default_machine_model


def _sync(x) -> float:
    import jax.numpy as jnp
    return float(jnp.ravel(x)[0])


def measure_matmul_efficiency(mm: TPUMachineModel, n: int = 8192,
                              repeats: int = 30, dtype=None) -> float:
    # repeats must be large enough that total device time >> one
    # host<->device round trip
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(dtype if dtype is not None else jnp.bfloat16)
    x = jnp.ones((n, n), dtype)

    @jax.jit
    def f(a):
        return jnp.dot(a, a, preferred_element_type=jnp.float32).astype(
            dtype)

    y = f(x)
    _sync(y)
    t0 = time.perf_counter()
    for _ in range(repeats):
        y = f(y)
    _sync(y)
    dt = (time.perf_counter() - t0) / repeats
    achieved = 2.0 * n ** 3 / dt
    # achieved fraction of THAT dtype's peak (peak_flops_for), so the
    # factor composes with the per-dtype rate instead of double-
    # counting it (machine_model.compute_time)
    return min(1.0, achieved / mm.peak_flops_for(dtype.name))


def measure_conv_efficiency(mm: TPUMachineModel, repeats: int = 20
                            ) -> float:
    """Achieved MXU fraction for convolution — measured separately from
    big GEMM because im2col/layout overheads put convs well below the
    dense-matmul roofline, and ranking conv strategies by the GEMM
    factor is a guess (VERDICT r2 #3; reference conv_2d.cu:173-260
    auto-selects per-shape algorithms by measurement). Two Inception-
    representative shapes (3x3 s1 mid-size, 1x1 channel-mixing),
    NHWC/bf16 — the bench compute layout; returns the FLOP-weighted
    achieved fraction."""
    import jax
    import jax.numpy as jnp
    from functools import partial

    shapes = [
        # (batch, h, w, cin, cout, k)
        (64, 56, 56, 64, 128, 3),
        (64, 28, 28, 256, 256, 1),
    ]
    dn = jax.lax.conv_dimension_numbers(
        (1, 1, 1, 1), (1, 1, 1, 1), ("NHWC", "HWIO", "NHWC"))
    total_flops = 0.0
    total_time = 0.0
    for (b, h, w, cin, cout, k) in shapes:
        x = jnp.ones((b, h, w, cin), jnp.bfloat16)
        kern = jnp.ones((k, k, cin, cout), jnp.bfloat16)

        @partial(jax.jit)
        def f(a, kr):
            return jax.lax.conv_general_dilated(
                a, kr, (1, 1), "SAME", dimension_numbers=dn,
                preferred_element_type=jnp.float32).astype(jnp.bfloat16)

        y = f(x, kern)
        _sync(y)
        t0 = time.perf_counter()
        for _ in range(repeats):
            y = f(x, kern)
        _sync(y)
        total_time += (time.perf_counter() - t0) / repeats
        total_flops += 2.0 * b * h * w * cout * cin * k * k
    # back-to-back effective rate over the shape mix
    achieved = total_flops / total_time
    return min(1.0, achieved / mm.spec.peak_flops)


def measure_elementwise_efficiency(mm: TPUMachineModel, n: int = 16384,
                                   repeats: int = 100) -> float:
    import jax
    import jax.numpy as jnp
    x = jnp.ones((n, n), jnp.float32)

    @jax.jit
    def f(a):
        return a * 1.0001 + 0.5

    y = f(x)
    _sync(y)
    t0 = time.perf_counter()
    for _ in range(repeats):
        y = f(y)
    _sync(y)
    dt = (time.perf_counter() - t0) / repeats
    achieved_bytes = 2.0 * x.size * 4 / dt  # read + write
    return min(1.0, achieved_bytes / mm.spec.hbm_bandwidth)


def measure_step_overhead(repeats: int = 50) -> float:
    """Fixed per-dispatch cost of one queued train step (host
    dispatch). Measured by timing a trivial jitted op with the
    queue kept full — the regime fit()/bench use. The reference's analog
    is Legion's per-task runtime overhead, amortized there by tracing."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def tiny(a):
        return a * 1.0001 + 1.0

    x = jnp.ones((8, 8), jnp.float32)
    y = tiny(x)
    _sync(y)
    t0 = time.perf_counter()
    for _ in range(repeats):
        y = tiny(y)
    _sync(y)
    return (time.perf_counter() - t0) / repeats


def calibrate(mm: TPUMachineModel, save_path: Optional[str] = None
              ) -> None:
    """Update mm.efficiency from real kernel timings on this device.
    A measurement that throws propagates: a search priced by the
    analytic constants while a device is attached is a wrong answer,
    not a degraded one."""
    import jax.numpy as jnp
    mm.efficiency["matmul"] = max(0.05, measure_matmul_efficiency(mm))
    # per-dtype calibration: f32 GEMMs achieve a DIFFERENT fraction
    # of their (halved) peak than bf16 does of its own — the
    # "matmul:<dtype>" keys override the family factor when
    # compute_time prices that dtype (mixed-precision cost model).
    # bf16's factor IS the family default (TPU datasheet basis).
    mm.efficiency["matmul:float32"] = max(
        0.05, measure_matmul_efficiency(mm, dtype=jnp.float32))
    mm.efficiency["matmul:bfloat16"] = mm.efficiency["matmul"]
    mm.efficiency["conv"] = max(0.05, measure_conv_efficiency(mm))
    mm.efficiency["elementwise"] = max(
        0.05, measure_elementwise_efficiency(mm))
    mm.efficiency["step_overhead_s"] = measure_step_overhead()
    if save_path:
        try:
            mm.save_calibration(save_path)
        except OSError as e:  # unwritable cache must not abort a search
            import warnings
            warnings.warn(f"could not persist calibration to "
                          f"{save_path}: {e}")


# per-device-kind efficiency factors, measured once per machine and
# persisted (the analog of the reference timing real kernels inside
# every search run, src/runtime/model.cu:20-62 — on TPU the factors are
# shape-stable so one measurement amortizes over all searches).
_CAL_MEMO: dict = {}


def cache_file(prefix: str, device_kind: str) -> str:
    """Per-machine measurement cache path (shared by the calibration
    and per-op cost caches so the root/sanitization policy lives
    once)."""
    from ..utils.cache_dirs import measurement_cache_dir
    safe = device_kind.lower().replace(" ", "_")
    return os.path.join(measurement_cache_dir(), f"{prefix}_{safe}.json")


def calibration_cache_path(device_kind: str) -> str:
    return cache_file("calibration", device_kind)


def calibrated_machine_model(mesh=None, machine_file: Optional[str] = None,
                             force: bool = False) -> TPUMachineModel:
    """`default_machine_model`, with efficiency factors measured on the
    real device when one is present (VERDICT round-1 item 3: no search
    runs on the hard-coded 0.55/0.8 guesses when hardware is attached).

    Off-TPU (the forced-CPU test platform) the analytic defaults stand —
    there is no MXU/HBM to measure. Results are memoized per device kind
    in-process and persisted under
    utils/cache_dirs.measurement_cache_dir() so one machine measures
    once."""
    import jax
    mm = default_machine_model(mesh, machine_file=machine_file)
    if jax.default_backend() != "tpu":
        return mm
    kind = jax.devices()[0].device_kind
    if not force and kind in _CAL_MEMO:
        mm.efficiency.update(_CAL_MEMO[kind])
        return mm
    path = calibration_cache_path(kind)
    if not force and os.path.exists(path):
        try:
            mm.load_calibration(path)
            _CAL_MEMO[kind] = dict(mm.efficiency)
            return mm
        except (OSError, json.JSONDecodeError):
            pass
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
    except OSError:
        path = None  # measure anyway; just don't persist
    calibrate(mm, save_path=path)
    _CAL_MEMO[kind] = dict(mm.efficiency)
    return mm
