"""The paged kernel's grid, COMPILED on the chip, ends at the work list's
own length (kernels/paged_ragged_v2.py, PR 46: `WorkList.count`, a
device scalar), the kernel alone at the shapes the benchmark's
configurations serve: parity with the jnp twin where nothing is live
(`count` = the tiles), on a typical decode step behind a chunk, and where
the list fills its bound (`count` = `max_items`); and ms a call by the
list's length, beside the same call over the bound's whole static grid
(what every call walked before) and over a static grid of exactly the
list's length (what a loop of that many steps costs when Mosaic knows its
end). Run with `-s` to see the table; it is also written to
chiprun_out/paged_grid_tpu.json.
"""

import dataclasses
import itertools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.kernels import paged_ragged_v2 as K
# the served shapes, the pool's head-packed form and the sweep's decode
# contexts (nothing live, 16 lanes, 40 lanes), as the one-lane body's
# on-chip test has them
from test_paged_short_tpu import (CALLS, LANES, PAGE, ROOT, SEQS, SHAPES,
                                  SWEEP, _heads)


def _bound(name):
    pp, bp, window = SHAPES[name][4:]
    return K.max_work_items(
        LANES, pp, bp, slot_changes=SEQS,
        window_blocks=K.window_block_bound(window, bp * PAGE)
        if window else 0)


def _step(name, slots, lens, seed=0, fill=False):
    """The call's arrays and its list for these live lanes (the lanes
    behind them inactive: slot 0, length 1). `fill`: the list's bound
    is its own length, so the grid ends where the arrays do."""
    hq, h, d, pages, pp, bp, window = SHAPES[name]
    rng = np.random.default_rng(seed)
    n = len(slots)
    slots = np.concatenate([slots, np.zeros(LANES - n)]).astype(np.int32)
    lens = np.concatenate([lens, np.ones(LANES - n)]).astype(np.int32)
    table = np.zeros((SEQS, pp), np.int32)
    # (round again where the served pool is too small for the lanes'
    # contexts: sequences then share pages, which no call can tell)
    free = itertools.cycle(rng.permutation(np.arange(1, pages)))
    for s in sorted(set(slots[:n])):
        longest = lens[:n][slots[:n] == s].max()
        for col in range(-(-int(longest) // PAGE)):
            table[s, col] = next(free)
    if window:          # the window layers' rings (kv_cache.ring_tables)
        ring = (pages - 1) // SEQS
        table = (1 + np.arange(SEQS)[:, None] * ring
                 + np.arange(pp)[None, :] % ring).astype(np.int32)
    counts = K.work_items(lens, slots, table, page_size=PAGE,
                          block_kv_pages=bp, max_items=_bound(name),
                          live_lanes=n, window=window, group=hq // h)
    assert counts["total"] <= counts["grid"]
    items = counts["total"] if fill else counts["grid"]
    q = jax.random.normal(jax.random.key(seed), (LANES, hq, d),
                          jnp.bfloat16)
    # head-packed, as a pool holds its pages (tests_tpu/
    # test_paged_short_tpu.py says why)
    kp, vp = (jax.random.normal(jax.random.key(seed + i),
                                (pages, PAGE, h * d), jnp.bfloat16)
              for i in (1, 2))
    table, slots, lens = (jnp.asarray(x) for x in (table, slots, lens))
    work = jax.jit(lambda t, s, n: K.build_work_list(
        t, s, n, page_size=PAGE, block_pages=bp, max_items=items,
        window=window))(table, slots, lens)
    assert int(work.count) == counts["total"]
    return (q, kp, vp, table, slots, lens), work, counts


def _idle(name):
    return np.zeros(0), np.zeros(0)


def _typical(name):
    """A 100-lane chunk of slot 63 that ends inside a tile, then 40
    decode lanes at scattered contexts."""
    top = SHAPES[name][4] * PAGE
    contexts = 1 + (np.arange(40) * 997) % (top - 1)
    return (np.concatenate([np.full(100, SEQS - 1), 1 + np.arange(40)]),
            np.concatenate([np.arange(top // 2 - 100, top // 2) + 1,
                            contexts]))


def _worst(name):
    """The arrays the bound's proof admits: every lane at the table's
    whole length, the slot changing 64 times inside the tiles."""
    top = SHAPES[name][4] * PAGE
    slots = np.zeros(LANES, np.int64)
    inside = [i for i in range(LANES) if i % K.Q_ROWS][::4][:SEQS]
    slots[inside] = 1
    slots = np.cumsum(slots) % SEQS     # a new slot from each such lane
    return slots, np.full(LANES, top)


@pytest.mark.parametrize("lanes", [_idle, _typical, _worst])
@pytest.mark.parametrize("name", ["opt", "olmoe", "phi_full", "phi_window"])
def test_the_list_length_grid_matches_the_twin(name, lanes):
    window = SHAPES[name][-1]
    slots, lens = lanes(name)
    args, work, counts = _step(name, slots, lens, seed=5,
                               fill=lanes is _worst)
    tiles = LANES // K.Q_ROWS
    if lanes is _idle:
        assert counts["total"] == tiles
    if lanes is _worst:
        # the grid ends where the arrays do; without a window that is
        # the engine's own bound
        assert work.tile.shape[0] - 1 == counts["total"]
        assert window or counts["total"] == _bound(name)
    q, kp, vp, table, slots, lens = args
    out = np.asarray(jax.jit(lambda q, kp, vp, work: (
        K.paged_attention_ragged_v2(
            q, _heads(kp, name), _heads(vp, name), table, slots, lens,
            scale=0.125, work=work, window=window)))(q, kp, vp, work),
        np.float32)
    assert np.isfinite(out).all()
    sub = np.arange(0, LANES, 41 if lanes is _worst else 11)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(lambda q, kp, vp, s, n: K._ragged_jnp(
            q, _heads(kp, name), _heads(vp, name), table, s, n, 0.125,
            window=window))(q[sub], kp, vp, slots[sub], lens[sub]),
            np.float32)
    np.testing.assert_allclose(out[sub], ref, rtol=2e-2, atol=2e-2)


def _ms_a_call(name, args, work, grid=None, reps=10):
    """CALLS calls in one program (a step's calls on one list), `reps`
    programs back to back: ms a call. `grid` an int: the call over a
    STATIC grid of that length (the walk the kernel had), else over the
    list's own."""
    q, kp, vp = args[:3]
    window = SHAPES[name][-1]
    short = K.has_short_body(SHAPES[name][0] // SHAPES[name][1])
    call = K._ragged_v2_pallas
    if grid is not None:
        call = call.__wrapped__         # a Python int stays one

    def step(q, kp, vp, work):
        if grid is not None:
            work = dataclasses.replace(work, count=grid)
        acc = jnp.zeros((), jnp.float32)
        for i in range(CALLS):
            o = call(q + i, _heads(kp, name), _heads(vp, name), work,
                     0.125, False, window=window, short=short)
            acc = acc + o[0, 0, 0].astype(jnp.float32)
        return acc

    step = jax.jit(step)
    jax.block_until_ready(step(q, kp, vp, work))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = step(q, kp, vp, work)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps / CALLS * 1e3


def test_ms_a_call_by_the_list_s_length():
    table = {"device": jax.devices()[0].device_kind, "lanes": LANES,
             "calls_a_program": CALLS}
    for name in ("opt", "olmoe", "phi_full", "phi_window"):
        rows = SWEEP[name]
        steps = [(1 + np.arange(len(c)), np.array(c)) for c in rows]
        steps.append(_typical(name))
        for slots, lens in steps:
            args, work, counts = _step(name, slots, lens)
            row = {"bound": counts["grid"], "count": counts["total"],
                   "live_items": counts["items"],
                   "static_bound": _ms_a_call(name, args, work,
                                              grid=counts["grid"]),
                   "static_count": _ms_a_call(name, args, work,
                                              grid=counts["total"]),
                   "list_length": _ms_a_call(name, args, work)}
            table.setdefault(name, []).append(row)
            print(f"{name}: " + ", ".join(
                f"{k} {v:.3f}" if isinstance(v, float) else f"{k} {v}"
                for k, v in row.items()))
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "paged_grid_tpu.json"), "w") as f:
        json.dump(table, f, indent=1)
    for name, rows in table.items():
        if not isinstance(rows, list):
            continue
        idle, busy = rows[0], rows[2]
        # us an empty step of the static walk, and what the traced bound
        # costs a walked step beside a static one of the same length
        empty = (idle["static_bound"] - idle["static_count"]) * 1e3 / (
            idle["bound"] - idle["count"])
        print(f"{name}: {empty:.3f} us an empty step; nothing live "
              f"{idle['static_bound']:.3f} -> {idle['list_length']:.3f} "
              f"ms a call, 40 decode lanes {busy['static_bound']:.3f} -> "
              f"{busy['list_length']:.3f} (static grid of its length "
              f"{busy['static_count']:.3f})")
        for row in rows:
            # the call pays for its list, not for the bound: at most a
            # fifth of the empty walk's cost is left
            saved = row["static_bound"] - row["static_count"]
            assert row["list_length"] < row["static_count"] + 0.2 * saved, \
                (name, row)
