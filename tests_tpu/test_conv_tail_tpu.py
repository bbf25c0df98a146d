"""The serving step's convolution alone on the chip (ops/ssm.py::
segmented_conv, PR 53) at the three served shapes — Olmo-Hybrid's 544
lanes of 11,520 channels over 32 slots, Qwen3-Next's 576 of 8,192 over
64, Phi-4-mini-flash's 576 of 5,120 over 64 — six layers' tails in one
bf16 leaf, as the pool holds them: the form that writes a run's rows
back (a gather of at most `slots` rows and a select) against the form
it replaced, kept in tests/test_segmented_conv.py (a scatter of every
lane's row, which XLA expands into a loop of one-row updates at
Olmo-Hybrid's shape and keeps native at the other two): the same bits,
and us a layer with nothing live, with 24 decode lanes, and with a
512-lane chunk beside 24. Run with `-s` to see the table; it is also
written to chiprun_out/conv_tail_tpu.json (kept as
evidence/conv_tail_tpu.json).
"""

import importlib.util
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.ops import ssm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "test_segmented_conv", os.path.join(ROOT, "tests",
                                        "test_segmented_conv.py"))
_ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_ref)

SHAPES = {"olmohybrid": (544, 11520, 32), "qwen3next": (576, 8192, 64),
          "phi4flash": (576, 5120, 64)}
CASES = {"nothing_live": (0, 0), "decode_24": (0, 24),
         "chunk_512_decode_24": (512, 24)}
LAYERS, ROUNDS, D_CONV = 6, 8, _ref.D_CONV


def _lanes(t, slots, chunk, decode):
    """One chunk of `chunk` lanes of the last slot from position 700,
    then `decode` one-lane runs; the rest dead. -> (lane_slots,
    positions, offsets, wslots, tail_lanes)."""
    runs = ([(slots - 1, 700, chunk)] if chunk else []) \
        + [(s, 900 + 37 * s, 1) for s in range(decode)]
    return _ref.lane_arrays(runs, t, slots)[0]


def _inputs(t, ch, slots, seed):
    k = jax.random.split(jax.random.key(seed), 3)
    return ({"conv_w": jax.random.normal(k[0], (D_CONV, ch), jnp.bfloat16)},
            jax.random.normal(k[1], (LAYERS, t, ch), jnp.bfloat16),
            jax.random.normal(k[2], (LAYERS, slots + 1, (D_CONV - 1) * ch),
                              jnp.bfloat16))


def _gather_form(p, u, tail, slots, pos, offsets, wslots, tail_lanes):
    return ssm.segmented_conv(p, u, tail, slots, pos, offsets, tail_lanes)


def _scatter_form(p, u, tail, slots, pos, offsets, wslots, tail_lanes):
    return _ref.scatter_form(p, u, tail, slots, pos, offsets, wslots)


def _layers(form):
    """All the layers, ROUNDS times over, in one program (the rounds a
    loop, so that the device's time and not the host's dispatch is what
    is read); every layer's whole `y` is used."""
    def run(p, u, tails, *lanes):
        def a_round(_, carry):
            acc, tails = carry
            for layer in range(LAYERS):
                y, tail = form(p, u[layer], tails[layer], *lanes)
                acc = acc + jnp.sum(y)
                tails = tails.at[layer].set(tail)
            return acc, tails
        return jax.lax.fori_loop(0, ROUNDS, a_round, (0.0, tails))
    return jax.jit(run, donate_argnums=(2,))


def _us_a_layer(fn, p, u, tails, lanes, reps=5):
    _, tails = fn(p, u, tails, *lanes)
    jax.block_until_ready(tails)
    t0 = time.perf_counter()
    for _ in range(reps):
        acc, tails = fn(p, u, tails, *lanes)
    jax.block_until_ready((acc, tails))
    return (time.perf_counter() - t0) / reps / LAYERS / ROUNDS * 1e6


@pytest.mark.parametrize("shape", list(SHAPES))
def test_both_forms_the_same_bits_and_their_time_a_layer(shape):
    t, ch, slots = SHAPES[shape]
    table = {"device": jax.devices()[0].device_kind,
             "lanes_channels_slots": [t, ch, slots], "layers": LAYERS,
             "tail": [slots + 1, (D_CONV - 1) * ch]}
    one = {name: jax.jit(f) for name, f in (("gather", _gather_form),
                                            ("scatter", _scatter_form))}
    many = {"gather": _layers(_gather_form),
            "scatter": _layers(_scatter_form)}
    for case, (chunk, decode) in CASES.items():
        lanes = _lanes(t, slots, chunk, decode)
        p, u, tails = _inputs(t, ch, slots, len(case))
        y0, tail0 = one["scatter"](p, u[0], tails[0], *lanes)
        y1, tail1 = one["gather"](p, u[0], tails[0], *lanes)
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(y0))
        np.testing.assert_array_equal(
            np.asarray(tail1[:slots], np.float32),
            np.asarray(tail0[:slots], np.float32))
        np.testing.assert_array_equal(
            np.asarray(tail1[slots], np.float32),
            np.asarray(tails[0, slots], np.float32))
        row = {"lanes": chunk + decode, "runs": bool(chunk) + decode}
        for name, fn in many.items():
            row[f"{name}_us"] = _us_a_layer(
                fn, p, u, _inputs(t, ch, slots, len(case))[2], lanes)
        table[case] = row
        print(f"{shape} {case}: " + ", ".join(
            f"{k} {v:.1f}" if isinstance(v, float) else f"{k} {v}"
            for k, v in row.items()))
    out = os.path.join(ROOT, "chiprun_out", "conv_tail_tpu.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    whole = json.load(open(out)) if os.path.exists(out) else {}
    whole[shape] = table
    with open(out, "w") as f:
        json.dump(whole, f, indent=1)
    # where XLA had expanded the scatter into a loop of 544 one-row
    # updates it took milliseconds a layer; where it kept a native one,
    # what replaces it may not be dearer than the timer's own noise
    for case in CASES:
        assert table[case]["gather_us"] < table[case]["scatter_us"] + 30.0, \
            table
