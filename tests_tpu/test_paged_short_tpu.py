"""The paged kernel's one-lane item body COMPILED on the chip
(kernels/paged_ragged_v2.py, PR 40), the kernel alone at the shapes the
benchmark's configurations serve: parity of the two bodies with the jnp
twin on a mixed step at Phi-4-mini-flash's two lists, and ms a call by
the one-lane items a call holds, with the body (`short=True`, a private
static argument that `paged_attention_ragged_v2` sets by the shape rule
`has_short_body`) and without. OPT's and OLMoE's shapes are swept for
the record: the rule leaves their calls the one body.
Run with `-s` to see the table; it is also written to
chiprun_out/paged_short_tpu.json.
"""

import itertools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.kernels import paged_ragged_v2 as K

PAGE, LANES, SEQS, CALLS = 16, 576, 64, 8
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# name: (query heads, key/value heads, head dim, pages, table columns,
# pages a kv-block, window), as benchmark/configs serve them
SHAPES = {
    "phi_full": (40, 10, 128, 16385, 512, 13, 0),
    "phi_window": (40, 10, 128, 4161, 512, 13, 512),     # rings of 65
    "opt": (32, 32, 64, 769, 128, 16, 0),
    "olmoe": (16, 16, 128, 1537, 256, 8, 0),
}


def _decode_step(name, contexts, chunk=0, seed=0):
    """`chunk` lanes of slot 63's prompt, then one decode lane a
    context (slots 1..), then the inactive tail -> (arrays, work)."""
    hq, h, d, pages, pp, bp, window = SHAPES[name]
    rng = np.random.default_rng(seed)
    table = np.zeros((SEQS, pp), np.int32)
    # (round again where the served pool is too small for the sweep's
    # contexts: sequences then share pages, which no call can tell)
    free = itertools.cycle(rng.permutation(np.arange(1, pages)))
    slots, lens = np.zeros(LANES, np.int32), np.ones(LANES, np.int32)
    top = pp * PAGE
    if chunk:
        slots[:chunk] = SEQS - 1
        lens[:chunk] = np.arange(top // 2 - chunk, top // 2) + 1
    n = chunk + len(contexts)
    slots[chunk:n] = 1 + np.arange(len(contexts))
    lens[chunk:n] = contexts
    for s in set(slots[:n]):
        longest = lens[:n][slots[:n] == s].max()
        for col in range(-(-int(longest) // PAGE)):
            table[s, col] = next(free)
    if window:          # the window layers' rings (kv_cache.ring_tables)
        ring = (pages - 1) // SEQS
        table = (1 + np.arange(SEQS)[:, None] * ring
                 + np.arange(pp)[None, :] % ring).astype(np.int32)
    q = jax.random.normal(jax.random.key(seed), (LANES, hq, d),
                          jnp.bfloat16)
    # head-packed, as a hybrid pool holds its pages (a standalone
    # (page, 16, 10, 128) array pads 10 heads to 16 sublanes, and the
    # kernel's packing of it would be a copy of the pool a call)
    kp, vp = (jax.random.normal(jax.random.key(seed + i),
                                (pages, PAGE, h * d), jnp.bfloat16)
              for i in (1, 2))
    table, slots, lens = (jnp.asarray(x) for x in (table, slots, lens))
    items = K.max_work_items(
        LANES, pp, bp, slot_changes=SEQS,
        window_blocks=K.window_block_bound(window, bp * PAGE)
        if window else 0)
    work = jax.jit(lambda t, s, n: K.build_work_list(
        t, s, n, page_size=PAGE, block_pages=bp, max_items=items,
        window=window))(table, slots, lens)
    counts = K.work_items(
        np.asarray(lens), np.asarray(slots), np.asarray(table),
        page_size=PAGE, block_kv_pages=bp, max_items=items, live_lanes=n,
        window=window, group=4)     # group 4: count the one-lane items
    assert counts["total"] <= counts["grid"]
    return (q, kp, vp, table, slots, lens), work, counts


def _heads(pages, name):
    """(page, slot, head * dim) -> (page, slot, head, dim), inside the
    program that makes the call (KVPool.layer)."""
    return pages.reshape(pages.shape[:2] + (SHAPES[name][1], -1))


@pytest.mark.parametrize("name", ["phi_full", "phi_window"])
def test_both_bodies_match_the_twin_on_a_mixed_step(name):
    """40 decode lanes at scattered contexts (one-lane runs at every
    row of a tile) behind a 100-lane chunk that ends inside a tile."""
    window = SHAPES[name][-1]
    top = SHAPES[name][4] * PAGE
    contexts = 1 + (np.arange(40) * 997) % (top - 1)
    args, work, counts = _decode_step(name, contexts, chunk=100, seed=3)
    assert 0 < counts["short_items"] < counts["items"]
    q, kp, vp, table, slots, lens = args
    outs = [np.asarray(jax.jit(lambda q, kp, vp, work: K._ragged_v2_pallas(
        q, _heads(kp, name), _heads(vp, name), work, 0.125, False,
        window=window, short=short))(q, kp, vp, work), np.float32)
        for short in (False, True)]
    sub = np.arange(0, 160, 3)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(lambda q, kp, vp, s, n: K._ragged_jnp(
            q, _heads(kp, name), _heads(vp, name), table, s, n, 0.125,
            window=window))(q[sub], kp, vp, slots[sub], lens[sub]),
            np.float32)
    for out in outs:
        assert np.isfinite(out).all()
        np.testing.assert_allclose(out[sub], ref, rtol=2e-2, atol=2e-2)
    # the two bodies sum the same products in the same order
    np.testing.assert_allclose(outs[1][:140], outs[0][:140], rtol=1e-5,
                               atol=1e-5)


def _ms_a_call(name, args, work, window, short, reps=10):
    """CALLS calls in one program (a step's calls on one list), `reps`
    programs back to back: ms a call."""
    q, kp, vp = args[:3]

    def step(q, kp, vp, work):
        acc = jnp.zeros((), jnp.float32)
        for i in range(CALLS):
            o = K._ragged_v2_pallas(
                q + i, _heads(kp, name), _heads(vp, name), work, 0.125,
                False, window=window, short=short)
            acc = acc + o[0, 0, 0].astype(jnp.float32)
        return acc

    step = jax.jit(step)
    jax.block_until_ready(step(q, kp, vp, work))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = step(q, kp, vp, work)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps / CALLS * 1e3


# decode lanes' contexts: 0, 64 and 232 one-lane items on a full list
# (the cell's 231.7 a call), 0, 64 and 144 under the window (its 143.6)
SWEEP = {
    "phi_full": ([], [800] * 16, [1200] * 32 + [1000] * 8),
    "phi_window": ([], [900] * 16, [900] * 32 + [500] * 8),
    "opt": ([], [800] * 16, [1400] * 32 + [1200] * 8),
    "olmoe": ([], [400] * 16, [900] * 24 + [600] * 8),
}


def test_ms_a_call_by_one_lane_items_with_and_without_the_body():
    table = {"device": jax.devices()[0].device_kind, "lanes": LANES,
             "calls_a_program": CALLS}
    for name, rows in SWEEP.items():
        window = SHAPES[name][-1]
        for contexts in rows:
            args, work, counts = _decode_step(name, contexts)
            row = {"grid": counts["grid"], "items": counts["items"],
                   "one_lane_items": counts["short_items"]}
            assert counts["short_items"] == counts["items"]
            for short in (False, True):
                row["short" if short else "whole"] = _ms_a_call(
                    name, args, work, window, short)
            table.setdefault(name, []).append(row)
            print(f"{name}: " + ", ".join(
                f"{k} {v:.3f}" if isinstance(v, float) else f"{k} {v}"
                for k, v in row.items()))
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "paged_short_tpu.json"), "w") as f:
        json.dump(table, f, indent=1)
    # us a one-lane item, from the sweep's two ends
    for name in ("phi_full", "phi_window"):
        a, b = table[name][0], table[name][-1]
        n = b["items"] - a["items"]
        whole, short = ((b[k] - a[k]) * 1e3 / n for k in ("whole", "short"))
        print(f"{name}: {whole:.2f} us a one-lane item on the whole "
              f"tile, {short:.2f} on its own rows")
        assert short < 0.5 * whole, table
