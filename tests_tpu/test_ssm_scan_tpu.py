"""The serving scan kernel COMPILED on the chip (kernels/ssm_scan.py, PR
33) at Phi-4-mini-flash's served shape — 576 lanes, 65 slot rows of
16 x 5120 f32, nine layers in one slab: parity with its jnp twin
(ops/ssm.py::segmented_scan), and the time a layer's scan takes at 40
live lanes (a decode-only step) and at 552 (a 512-lane chunk beside 40
decode lanes), over the candidate blocks of d_inner and beside the twin.
Run with `-s` to see the table; it is also written to
chiprun_out/ssm_scan_tpu.json.
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.kernels import ssm_scan as K
from flexflow_tpu.ops import ssm

T, SLOTS, N, D, LAYERS = 576, 64, 16, 5120, 9
BLOCKS = (256, 512, 640, 1280)
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _inputs(seed):
    r = np.random.default_rng(seed)
    f = lambda a: jnp.asarray(a, jnp.float32)
    p = {"A_log": f(np.log(np.arange(1, N + 1))[:, None]
                    + 0.1 * r.standard_normal((N, D))),
         "D": f(r.standard_normal(D))}
    u = f(r.standard_normal((T, D)))
    dt = f(np.exp(r.uniform(np.log(1e-3), np.log(1e-1), (T, D))))
    b, c = f(r.standard_normal((T, N))), f(r.standard_normal((T, N)))
    return p, u, dt, b, c


def _lanes(chunk: int, decode: int):
    """One chunk of `chunk` lanes of slot 63 from position 700, then
    `decode` lanes of slots 0.. at scattered positions; the rest dead."""
    slots, pos = np.zeros(T, np.int32), np.zeros(T, np.int32)
    slots[:chunk] = SLOTS - 1
    pos[:chunk] = np.arange(700, 700 + chunk)
    n = chunk + decode
    slots[chunk:n] = np.arange(decode)
    pos[chunk:n] = 900 + 37 * np.arange(decode)
    live = jnp.arange(T) < n
    slots, pos = jnp.asarray(slots), jnp.asarray(pos)
    starts = ssm.run_starts(slots, pos)
    return (slots, pos, starts,
            ssm.run_write_slots(starts, live, slots, SLOTS), n)


def _slab(seed):
    return jax.random.normal(jax.random.key(seed),
                             (LAYERS, SLOTS + 1, N, D), jnp.float32)


@pytest.mark.parametrize("chunk,decode", [(0, 40), (512, 40), (512, 64)])
def test_kernel_compiled_matches_its_twin(chunk, decode):
    p, u, dt, b, c = _inputs(chunk + decode)
    slots, pos, starts, wslots, n = _lanes(chunk, decode)
    slab = _slab(1)
    layer = 4
    y0, row = jax.jit(ssm.segmented_scan)(p, u, dt, b, c, slab[layer],
                                          slots, pos, starts, wslots)
    before = np.asarray(slab)
    y1, out = jax.jit(
        lambda *a: K.ssm_scan(*a[:6], layer, *a[6:]), donate_argnums=(5,))(
        p, u, dt, b, c, slab, slots, pos, starts, wslots, n)
    y0, y1, out = np.asarray(y0), np.asarray(y1), np.asarray(out)
    np.testing.assert_allclose(y1[:n], y0[:n], atol=2e-5, rtol=2e-5)
    assert not y1[n:].any()
    np.testing.assert_allclose(out[layer, :SLOTS], np.asarray(row)[:SLOTS],
                               atol=2e-5, rtol=2e-5)
    for other in range(LAYERS):
        if other != layer:
            np.testing.assert_array_equal(out[other], before[other])


def _ms_a_layer(fn, slab, args, reps=10):
    """All nine layers in one program, `reps` calls: ms a layer."""
    def nine(slab, *a):
        ys = 0.0
        for layer in range(LAYERS):
            y, slab = fn(slab, layer, *a)
            ys = ys + y[0, 0]
        return ys, slab

    nine = jax.jit(nine, donate_argnums=(0,))
    _, slab = nine(slab, *args)
    jax.block_until_ready(slab)
    t0 = time.perf_counter()
    for _ in range(reps):
        ys, slab = nine(slab, *args)
    jax.block_until_ready((ys, slab))
    return (time.perf_counter() - t0) / reps / LAYERS * 1e3


def test_a_layer_s_scan_time_by_block_and_live_lanes():
    p, u, dt, b, c = _inputs(0)
    table = {"device": jax.devices()[0].device_kind, "shape": [T, SLOTS + 1,
                                                               N, D]}
    for chunk, decode in ((0, 40), (512, 40)):
        slots, pos, starts, wslots, n = _lanes(chunk, decode)
        args = (p, u, dt, b, c, slots, pos, starts, wslots)
        row = {}
        for block in BLOCKS:
            def kern(slab, layer, p, u, dt, b, c, *lanes, block=block):
                return K.ssm_scan(p, u, dt, b, c, slab, layer, *lanes, n,
                                  block=block)
            row[f"block_{block}"] = _ms_a_layer(kern, _slab(2), args)

        def twin(slab, layer, p, u, dt, b, c, *lanes):
            y, state = ssm.segmented_scan(p, u, dt, b, c, slab[layer],
                                          *lanes)
            return y, slab.at[layer].set(state)
        row["twin"] = _ms_a_layer(twin, _slab(2), args, reps=3)
        table[f"live_{n}"] = row
        print(f"live {n}: " + ", ".join(f"{k} {v:.3f} ms"
                                        for k, v in row.items()))
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "ssm_scan_tpu.json"), "w") as f:
        json.dump(table, f, indent=1)
    chosen = f"block_{K.choose_block(D)}"
    # the loop it replaces took 2.8 ms a layer whatever it computed
    assert table["live_552"][chosen] < 1.0, table
    assert table["live_40"][chosen] < table["live_552"][chosen], table
