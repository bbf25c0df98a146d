"""The delta rule's lane kernel COMPILED on the chip (kernels/
gated_delta_scan.py, PR 51) at Qwen3-Next's served shape — 576 lanes,
65 slot rows of 4096 x 128 f32 (32 value heads of 128 x 128), six
layers in one slab — or, with DELTA_SHAPE=olmohybrid in the
environment (PR 52), at Olmo-Hybrid's: 544 lanes, 30 heads of 96 x 192,
their states in head pairs, 1440 x 384: parity with its jnp twin (ops/gated_delta.py::
segmented) over decode lanes, a short tail and a run that goes lanes,
chunk-form blocks, lanes; the time of a layer's call with 8 / 24 / 48
one-lane runs, with one 15-lane tail and with nothing live, beside the
twin's; and where the lane form and the chunk form cross. Run with `-s`
to see the table; it is also written to chiprun_out/gated_delta_tpu.json
(gated_delta_tpu.olmohybrid.json at the other shape).
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.kernels import gated_delta_scan as K
from flexflow_tpu.ops import gated_delta as GD
from flexflow_tpu.ops import ssm

SHAPE = os.environ.get("DELTA_SHAPE", "qwen3next")
T, SLOTS, H, DK, DV, LAYERS = {
    "qwen3next": (576, 64, 32, 128, 128, 6),
    "olmohybrid": (544, 64, 30, 96, 192, 6)}[SHAPE]
STATE_BYTES = H * DK * DV * 4
HBM_GBS = 819.0
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _inputs(seed):
    r = np.random.default_rng(seed)
    f = lambda a: jnp.asarray(a, jnp.float32)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    return (f(unit(r.standard_normal((T, H, DK))) / np.sqrt(DK)),
            f(unit(r.standard_normal((T, H, DK)))),
            f(r.standard_normal((T, H, DV))),
            f(-np.exp(r.uniform(-6, 0, (T, H)))), f(r.uniform(0, 1, (T, H))))


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
    starts = ssm.run_starts(slots, pos)
    return (slots, pos, live, starts,
            ssm.run_write_slots(starts, live, slots, SLOTS), n)


def _decode(n):
    return [(s, 900 + 37 * s, 1) for s in range(n)]


def _slab(seed):
    return jax.random.normal(jax.random.key(seed),
                             (LAYERS, SLOTS + 1) + GD.state_shape(H, DK, DV),
                             jnp.float32)


def _kernel(slab, layer, q, k, v, g, beta, slots, pos, live, starts,
            wslots, n):
    plan = GD.lane_plan(slots, pos, live, starts, n)
    return K.gated_delta_scan(q, k, v, g, beta, slab, layer, slots, pos,
                              plan)


def _twin(slab, layer, q, k, v, g, beta, slots, pos, live, starts, wslots,
          n):
    return GD.segmented(q, k, v, g, beta, slab, slots, pos, live, starts,
                        wslots, n, layer=layer)


CASES = {
    "decode_24": _decode(24),
    "decode_48_a_fresh_one": _decode(47) + [(50, 0, 1)],
    "a_tail_of_15": [(7, 640, 15)],
    "lanes_chunks_lanes": _decode(24) + [(40, 100, 40 + 3 * 64 + 9)]
    + [(41, 7, 1)],
    "two_chunks_a_shared_block": [(3, 0, 64 + 30), (9, 50, 34 + 64 + 5)],
}


LAYER = 4
_TWIN_AT = jax.jit(lambda slab, *a: _twin(slab, LAYER, *a))
_KERNEL_AT = jax.jit(lambda slab, *a: _kernel(slab, LAYER, *a),
                     donate_argnums=(0,))


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_compiled_matches_its_twin(case):
    args = _inputs(len(case))
    lanes = _lanes(CASES[case])
    n = lanes[-1]
    o0, s0 = _TWIN_AT(_slab(1), *args, *lanes)
    before = np.asarray(_slab(1)[LAYER - 1])
    o1, s1 = _KERNEL_AT(_slab(1), *args, *lanes)
    np.testing.assert_allclose(np.asarray(o1[:n]), np.asarray(o0[:n]),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(s1[LAYER, :SLOTS]),
                               np.asarray(s0[LAYER, :SLOTS]), atol=2e-5,
                               rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(s1[LAYER - 1]), before)


_SIX = {}
ROUNDS = 8


def _us_a_layer(fn, slab, args, reps=5):
    """All six layers, ROUNDS times over, in one program (compiled once
    a `fn`; the rounds a loop, so that the device's time and not the
    host's dispatch is what is read), `reps` calls: us a layer."""
    def six(slab, *a):
        def a_round(_, carry):
            acc, slab = carry
            for layer in range(LAYERS):
                o, slab = fn(slab, layer, *a)
                acc = acc + o[0, 0, 0]
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
    return (time.perf_counter() - t0) / reps / LAYERS / ROUNDS * 1e6


def _pass_alone(slab, layer, q, k, v, g, beta, slots, pos, live, starts,
                wslots, n):
    """ONE call of the kernel on every live lane, whatever form the
    rule would give their blocks."""
    mask = jnp.arange(T) < n
    seg = GD._segments(mask, starts, slots, pos)
    return K.lane_pass(q, k, v, jnp.exp(g), beta,
                       jnp.zeros((T, H, DV), jnp.float32), slab, layer, seg)


def test_a_layer_s_time_by_live_lanes_and_where_the_forms_cross():
    args = _inputs(0)
    table = {"device": jax.devices()[0].device_kind,
             "shape": [T, SLOTS + 1, *GD.state_shape(H, DK, DV)],
             "heads": [H, DK, DV], "layers": LAYERS}
    rows = {"nothing_live": [], "decode_8": _decode(8),
            "decode_24": _decode(24), "decode_48": _decode(48),
            "a_tail_of_15": [(7, 640, 15)]}
    for name, runs in rows.items():
        lanes = _lanes(runs)
        n = lanes[-1]
        row = {"lanes": n, "runs": len(runs),
               "layer_us": _us_a_layer(_kernel, _slab(2), args + lanes),
               "kernel_call_us": _us_a_layer(_pass_alone, _slab(2),
                                             args + lanes),
               "twin_us": _us_a_layer(_twin, _slab(2), args + lanes,
                                      reps=2)}
        if n:
            moved = 2 * len(runs) * STATE_BYTES
            base = table["nothing_live"]["kernel_call_us"]
            row["kernel_us_a_lane"] = (row["kernel_call_us"] - base) / n
            row["kernel_gb_s"] = moved / (
                row["kernel_call_us"] - base) * 1e-3
            row["kernel_hbm_share"] = row["kernel_gb_s"] / HBM_GBS
            row["twin_us_a_lane"] = (row["twin_us"] - table[
                "nothing_live"]["twin_us"]) / n
        table[name] = row
        print(f"{name}: " + ", ".join(
            f"{k} {v:.3f}" if isinstance(v, float) else f"{k} {v}"
            for k, v in row.items()))
    # ONE run of L lanes: lane by lane in the kernel (whatever the rule
    # says) against the layer as the rule sorts it (the chunk form from
    # CHUNK_MIN_LANES lanes up)
    cross = {}
    for length in (4, 8, 12, 15, 16, 24, 32, 48, 64):
        lanes = _lanes([(5, 300, length)])
        cross[length] = {
            "lane_form_us": _us_a_layer(_pass_alone, _slab(3), args + lanes),
            "by_the_rule_us": _us_a_layer(_kernel, _slab(3), args + lanes)}
        print(f"one run of {length}: " + ", ".join(
            f"{k} {v:.1f}" for k, v in cross[length].items()))
    table["one_run_of"] = cross
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    name = "gated_delta_tpu.json" if SHAPE == "qwen3next" \
        else f"gated_delta_tpu.{SHAPE}.json"
    with open(os.path.join(out, name), "w") as f:
        json.dump(table, f, indent=1)
    # the loop it replaces took 80 us a lane
    assert table["decode_24"]["kernel_us_a_lane"] < 20.0, table
    assert table["decode_24"]["layer_us"] < table["decode_24"]["twin_us"] / 3
