"""The mixed step's tail by ROWS, alone on the chip (PR 44): the head's
product, `jax.lax.top_k(., 64)` and the argmax at the step's width (576
lanes; 544 in command-a-plus-1chip-ep8) beside the `head_rows` the
engine now hands them (`ServeEngine.head_rows`: 64, and 32), at the
hidden sizes and vocabularies the benchmark's configurations serve.
What the step's gain rests on: the sort's time follows its rows. Also
that a gathered row's logits are the row the all-lane head computes.
Run with `-s` to see the table; it is also written to
chiprun_out/head_rows_tpu.json.
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOPK = 64
# name: (hidden, vocabulary, lanes, head rows, the weights' dtype as the
# step holds them, tied: the head is the token table), as
# benchmark/configs serve them
SHAPES = {
    "opt": (2048, 50272, 576, 64, jnp.float32, False),
    "olmoe": (2048, 50304, 576, 64, jnp.bfloat16, False),
    "phi": (2560, 200064, 576, 64, jnp.bfloat16, True),
    "cmda": (4096, 32768, 544, 32, jnp.bfloat16, True),
}


def _head(x, w, tied):
    w = w.astype(x.dtype)
    return jnp.dot(x, w.T if tied else w,
                   preferred_element_type=jnp.float32).astype(x.dtype)


def _sample(logits):
    topv, topi = jax.lax.top_k(logits, TOPK)
    return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
            topv.astype(jnp.float32), topi.astype(jnp.int32))


def _ms(fn, *args, reps=20):
    fn = jax.jit(fn)
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3


def _operands(name, seed=0):
    hidden, vocab, lanes, rows, wdtype, tied = SHAPES[name]
    kx, kw = jax.random.split(jax.random.key(seed))
    x = jax.random.normal(kx, (lanes, hidden), jnp.bfloat16)
    w = (jax.random.normal(kw, (vocab, hidden) if tied else (hidden, vocab),
                           jnp.float32) * hidden ** -0.5).astype(wdtype)
    # emitting lanes scattered over the step, padded with lane 0
    live = np.sort(np.random.default_rng(seed).choice(
        lanes, rows * 5 // 8, replace=False))
    idx = np.zeros((rows,), np.int32)
    idx[:len(live)] = live
    return x, w, jnp.asarray(idx), len(live)


def test_ms_of_the_head_and_the_sort_by_rows():
    table = {"device": jax.devices()[0].device_kind, "topk": TOPK}
    for name, (hidden, vocab, lanes, rows, _, tied) in SHAPES.items():
        x, w, idx, _ = _operands(name)
        row = {"hidden": hidden, "vocab": vocab, "lanes": lanes,
               "head_rows": rows}
        for key, n in (("lanes", lanes), ("rows", rows)):
            xs = x[:n]
            logits = jax.jit(_head, static_argnums=2)(xs, w, tied)
            row[f"topk_ms_{key}"] = _ms(
                lambda lg: jax.lax.top_k(lg, TOPK), logits)
            row[f"sample_ms_{key}"] = _ms(_sample, logits)
            row[f"head_ms_{key}"] = _ms(
                lambda a, b: _head(a, b, tied), xs, w)
        # the tail as the step runs it: all lanes, and the gathered rows
        row["tail_ms_lanes"] = _ms(
            lambda a, b: _sample(_head(a, b, tied)), x, w)
        row["tail_ms_rows"] = _ms(
            lambda a, b, i: _sample(_head(a[i], b, tied)), x, w, idx)
        table[name] = row
        print(f"{name}: " + ", ".join(
            f"{k} {v:.3f}" if isinstance(v, float) else f"{k} {v}"
            for k, v in row.items()))
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "head_rows_tpu.json"), "w") as f:
        json.dump(table, f, indent=1)
    for name, row in table.items():
        if isinstance(row, dict):
            # the sort falls with its rows: at least four times for a
            # ninth (a seventeenth) of them
            assert row["topk_ms_rows"] * 4 <= row["topk_ms_lanes"], (name,
                                                                     row)


@pytest.mark.parametrize("name", list(SHAPES))
def test_a_gathered_row_is_the_row_the_all_lane_head_computes(name):
    tied = SHAPES[name][-1]
    x, w, idx, live = _operands(name, seed=1)
    full = jax.jit(lambda a, b: _sample(_head(a, b, tied)))(x, w)
    part = jax.jit(lambda a, b, i: _sample(_head(a[i], b, tied)))(x, w, idx)
    idx = np.asarray(idx)
    for got, want in zip(part, full):
        got, want = np.asarray(got), np.asarray(want)[idx]
        worst = float(np.abs(got[:live].astype(np.float64)
                             - want[:live]).max())
        print(f"{name}: {got.dtype} rows' largest difference {worst}")
        np.testing.assert_array_equal(got, want)
