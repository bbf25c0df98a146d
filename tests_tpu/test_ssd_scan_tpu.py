"""Mamba-2's lane kernel COMPILED on the chip (kernels/ssd_scan.py,
PR 56) at Falcon-H1's served shape — 608 lanes, 97 slot rows of 256 x
4096 f32 (32 heads of 128 x 256, 2 groups), six layers in one slab:
parity with its jnp twin (ops/ssd.py::lane_pass under `segmented`) over
decode lanes, a short tail and a run that goes lanes, chunk-form blocks,
lanes; the time of a layer's call with one, 77 and 96 one-lane runs and
with a 512-lane chunk beside 64 decode lanes, in us and as a share of
819 GB/s (the lanes) and of 197 TFLOP/s (the chunk blocks), beside the
twin's. Run with `-s` to see the table; it is also written to
chiprun_out/ssd_scan_tpu.json (kept as evidence/ssd_scan_tpu.json).
"""

import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.kernels import ssd_scan as K
from flexflow_tpu.ops import gated_delta as GD
from flexflow_tpu.ops import ssd as SD
from flexflow_tpu.ops import ssm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
from lib import ssd_counts  # noqa: E402

T, SLOTS, H, P, G, N, LAYERS = 608, 96, 32, 128, 2, 256, 6
STATE_BYTES = H * P * N * 4
HBM_GBS, MXU_TFLOPS = 819.0, 197.0


def _inputs(seed):
    r = np.random.default_rng(seed)
    f = lambda a: jnp.asarray(a, jnp.float32)
    return (f(r.standard_normal((T, H, P)) * 0.05),
            f(r.standard_normal((T, G, N))), f(r.standard_normal((T, G, N))),
            f(-np.exp(r.uniform(-6, 0, (T, H)))))


def _lanes(runs):
    """runs: (slot, first position, lanes) one after another from lane
    0; the lanes behind them are dead."""
    slots, pos = np.zeros(T, np.int32), np.zeros(T, np.int32)
    n = 0
    for slot, p0, k in runs:
        slots[n:n + k] = slot
        pos[n:n + k] = np.arange(p0, p0 + k)
        n += k
    live = jnp.arange(T) < n
    slots, pos = jnp.asarray(slots), jnp.asarray(pos)
    return slots, pos, live, ssm.run_starts(slots, pos), n


def _decode(n, first=0):
    return [(s, 900 + 37 * s, 1) for s in range(first, first + n)]


def _slab(seed):
    return jax.random.normal(jax.random.key(seed),
                             (LAYERS, SLOTS + 1, N, H * P), jnp.float32)


def _segmented(lane_pass, slab, layer, v, b, c, la, slots, pos, live,
               starts, n):
    plan = GD.lane_plan(slots, pos, live, starts, n)
    return SD.segmented(v, b, c, la, slab, layer, slots, pos, plan,
                        lane_pass=lane_pass)


_kernel = functools.partial(_segmented, K.lane_pass)
_twin = functools.partial(_segmented, SD.lane_pass)

CASES = {
    "decode_24": _decode(24),
    "decode_96_a_fresh_one": _decode(95) + [(95, 0, 1)],
    "a_tail_of_15": [(7, 640, 15)],
    "lanes_chunks_lanes": _decode(24) + [(40, 100, 40 + 3 * 64 + 9)]
    + [(41, 7, 1)],
    "two_chunks_a_shared_block": [(3, 0, 64 + 30), (9, 50, 34 + 64 + 5)],
}

LAYER = 4
_TWIN_AT = jax.jit(lambda slab, *a: _twin(slab, LAYER, *a),
                   donate_argnums=(0,))
_KERNEL_AT = jax.jit(lambda slab, *a: _kernel(slab, LAYER, *a),
                     donate_argnums=(0,))


def test_the_kernel_takes_the_served_shape():
    assert K.supported(T, H, P, G, N)


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_compiled_matches_its_twin(case):
    args = _inputs(len(case))
    lanes = _lanes(CASES[case])
    n = lanes[-1]
    y0, s0 = _TWIN_AT(_slab(1), *args, *lanes)
    y0, s0 = np.asarray(y0), np.asarray(s0[LAYER])
    before = np.asarray(_slab(1)[LAYER - 1])
    y1, s1 = _KERNEL_AT(_slab(1), *args, *lanes)
    np.testing.assert_allclose(np.asarray(y1[:n]), y0[:n], atol=1e-4,
                               rtol=1e-4)
    assert not np.asarray(y1[n:]).any()
    np.testing.assert_allclose(np.asarray(s1[LAYER]), s0, atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(s1[LAYER - 1]), before)


_SIX = {}
ROUNDS = 8


def _us_a_layer(fn, slab, args, reps=5):
    """All six layers, ROUNDS times over, in one program (compiled once
    a `fn` and lane count; the rounds a loop, so that the device's time
    and not the host's dispatch is what is read), `reps` calls: us a
    layer."""
    def six(slab, *a):
        def a_round(_, carry):
            acc, slab = carry
            for layer in range(LAYERS):
                y, slab = fn(slab, layer, *a)
                acc = acc + y[0, 0, 0]
            return acc, slab
        return jax.lax.fori_loop(0, ROUNDS, a_round, (0.0, slab))

    if fn not in _SIX:
        _SIX[fn] = jax.jit(six, donate_argnums=(0,))
    six = _SIX[fn]
    _, slab = six(slab, *args)
    jax.block_until_ready(slab)
    t0 = time.perf_counter()
    for _ in range(reps):
        acc, slab = six(slab, *args)
    jax.block_until_ready((acc, slab))
    return (time.perf_counter() - t0) / reps / LAYERS / ROUNDS * 1e6, slab


def test_time_a_layer_by_what_is_live():
    """us a layer of the recurrence (both lane passes and the chunk
    blocks' loop) by what the step holds, the kernel beside its twin."""
    args = _inputs(7)
    rows = {}
    slab = _slab(2)
    steps = {"nothing": [], "one_lane": _decode(1), "decode_77": _decode(77),
             "decode_96": _decode(96),
             "chunk_512_beside_64": _decode(64) + [(80, 0, 512)]}
    for name, runs in steps.items():
        lanes = _lanes(runs)
        us, slab = _us_a_layer(_kernel, slab, args + lanes)
        twin, slab = _us_a_layer(_twin, slab, args + lanes, reps=2)
        one_lane = sum(1 for r in runs if r[2] == 1)
        blocks = sum(r[2] // 64 for r in runs if r[2] >= 64)
        moved = 2 * STATE_BYTES * (one_lane + blocks)
        rows[name] = {
            "kernel_us": us, "twin_us": twin, "runs": len(runs),
            "state_bytes_moved": moved,
            "hbm_share_pct": 100 * moved / (us * 1e-6) / (HBM_GBS * 1e9)
            if moved else None}
    base = rows["nothing"]["kernel_us"]
    one = rows["one_lane"]
    one["one_lane_run_us"] = one["kernel_us"] - base
    one["at_peak_us"] = 2 * STATE_BYTES / (HBM_GBS * 1e9) * 1e6
    ch = rows["chunk_512_beside_64"]
    # the chunk blocks alone: the step less its 64 one-lane runs at the
    # 96-lane step's price a run
    per_run = (rows["decode_96"]["kernel_us"] - base) / 96
    ch["chunk_blocks_us"] = ch["kernel_us"] - base - 64 * per_run
    flops = 8 * ssd_counts.chunk_block_flops(H, P, G, N)
    ch["chunk_block_flops"] = flops
    ch["mxu_share_pct"] = 100 * flops / (ch["chunk_blocks_us"] * 1e-6) \
        / (MXU_TFLOPS * 1e12)
    for name, row in rows.items():
        print(name, json.dumps(row))
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "ssd_scan_tpu.json"), "w") as f:
        json.dump({"shape": {"lanes": T, "slots": SLOTS, "heads": H,
                             "head_dim": P, "groups": G, "d_state": N,
                             "layers": LAYERS},
                   "device": jax.devices()[0].device_kind, "rows": rows},
                  f, indent=1)
    assert rows["decode_96"]["kernel_us"] < rows["decode_96"]["twin_us"]
