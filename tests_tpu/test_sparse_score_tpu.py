"""The selector's scores alone on the chip (serve/sparse_paged.py::
lane_probs, PR 55) at MiniCPM-SALA's served shape — 544 lanes of 32
query / 2 key-value heads of 128 against 4,096 strides a sequence, the
compressed keys of 8 pool layers x 32,769 pages in one bf16 leaf, 32
slots: the step's form (every stretch on one fetch of its main
sequence's keys, the stray lanes on their own copies a stretch of them
a trip) beside the form it replaced (every lane its own copy, the
seventeen stretches one after another in the program), at three
layouts of the lanes: 8 decode lanes and a 512-lane chunk, 32 decode
lanes, 32 runs of 16 (256 stray lanes: the loop's many trips). The
same probabilities and the same selected blocks on the live lanes, and
ms a layer. Run with `-s` to see the table; it is also written to
chiprun_out/sparse_score_tpu.json (kept as
evidence/sparse_score_tpu.json).
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from flexflow_tpu.ops import sparse_attention as SA
from flexflow_tpu.serve import sparse_paged as SP
from flexflow_tpu.serve.kv_cache import KVPool

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LANES, SLOTS, PAGES, NUM_PAGES = 544, 32, 4096, 32769
HEADS, DIM, LAYERS = 32, 128, [4, 5]      # the third sparse layer's heads
SC = SA.SparseConfig()                    # the published sizes
ROUNDS = 4


def _chunk(slot, start, n):
    return [(slot, start + j) for j in range(n)]


# (slot, position) of each live lane from lane 0 up
LAYOUTS = {
    "decode_8_chunk_512": [(s, 9000 + 1500 * s) for s in range(8)]
    + _chunk(20, 30000, 512),
    "decode_32": [(s, 9000 + 1700 * s) for s in range(32)],
    "runs_32_of_16": [lane for s in range(32)
                      for lane in _chunk(s, 8192 + 1800 * s, 16)],
}


def _inputs(layout, seed=0):
    k = jax.random.split(jax.random.key(seed), 3)
    # scores with a deviation near 2: peaked enough that the top blocks
    # are no ties, as a trained model's are
    q = jax.random.normal(k[0], (LANES, HEADS, DIM), jnp.bfloat16) * 0.42
    kc = jax.random.normal(k[1], (8, NUM_PAGES, DIM), jnp.bfloat16)
    nothing = jnp.zeros((8, 1, 16, DIM), jnp.bfloat16)
    pool = KVPool(nothing, nothing, kc=kc, heads=1)
    tables = jax.random.randint(k[2], (SLOTS, PAGES), 1, NUM_PAGES,
                                jnp.int32)
    slots, positions = np.zeros((2, LANES), np.int32)
    live = np.arange(LANES) < len(layout)
    slots[live], positions[live] = np.transpose(layout)
    return (q, pool, tables, jnp.asarray(slots), jnp.asarray(positions),
            jnp.asarray(live))


def _shared(q, pool, tables, slots, positions, live):
    return SP.lane_probs(q, pool, LAYERS, tables, slots, positions, live, SC)


def _per_lane(q, pool, tables, slots, positions, live):
    """The form `lane_probs` replaced: every lane against its own
    gathered copy of its sequence's keys, LANE_TILE lanes at a time."""
    t, h, d = q.shape
    g = len(LAYERS)
    table = pool.selector_table()

    def score(qt, slot, pos):
        r = qt.shape[0]
        strides = jnp.roll(jnp.take(tables, slot, axis=0), -1, axis=1)
        qg = qt.reshape(r, g, h // g, d)
        s = jnp.stack([jnp.einsum(
            "rid,rjd->rij", qg[:, j],
            pool.selector_rows(table, layer, strides).astype(qt.dtype),
            preferred_element_type=jnp.float32)
            for j, layer in enumerate(LAYERS)], axis=1) / np.sqrt(d)
        return SA.group_probs(s, pos, SC)
    return jnp.concatenate([
        score(*(a[lo:lo + SP.LANE_TILE] for a in (q, slots, positions)))
        for lo in range(0, t, SP.LANE_TILE)])


FORMS = {"per_lane": _per_lane, "shared": _shared}


def _rounds(form):
    """ROUNDS layers in one program, each reading what the one before
    it summed through its queries and its tables (so no round's
    products or gathers are hoisted out of the loop and the device's
    time, not the host's dispatch, is what is read)."""
    def run(q, pool, tables, *rest):
        def a_round(_, acc):
            nothing = acc * 0
            return acc + jnp.sum(form(
                q + nothing.astype(q.dtype), pool,
                tables + nothing.astype(tables.dtype), *rest))
        return jax.lax.fori_loop(0, ROUNDS, a_round, jnp.float32(0))
    return jax.jit(run)


def _ms_a_layer(fn, args, reps=5):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps / ROUNDS * 1e3


def test_shared_and_per_lane_scores_agree_and_their_time_a_layer():
    table = {"device": jax.devices()[0].device_kind,
             "lanes_heads_dim": [LANES, HEADS, DIM], "strides": PAGES,
             "kv_heads": len(LAYERS), "pages": NUM_PAGES,
             "lane_tile": SP.LANE_TILE}
    one = {name: jax.jit(form) for name, form in FORMS.items()}
    many = {name: _rounds(form) for name, form in FORMS.items()}
    for case, layout in LAYOUTS.items():
        args = _inputs(layout, len(case))
        slots, positions, live = args[3:]
        _, stray = SP.main_slots(np.asarray(slots), np.asarray(live), np)
        at = np.flatnonzero(np.asarray(live))
        got, want = (np.asarray(one[name](*args))[at]
                     for name in ("shared", "per_lane"))
        pos = positions[at]
        picked = [SA.select_blocks(jnp.asarray(p), pos, SC)
                  for p in (got, want)]
        same = np.mean([np.array_equal(a, b) for a, b in zip(
            np.asarray(picked[0][0]), np.asarray(picked[1][0]))])
        row = {"live_lanes": len(at), "stray_lanes": int(stray.sum()),
               "stray_trips": int(SP.stray_batches(stray, np)),
               "probs_max_abs_diff": float(np.abs(got - want).max()),
               "lanes_with_the_same_blocks": float(same)}
        for name, fn in many.items():
            row[f"{name}_ms"] = _ms_a_layer(fn, args)
        table[case] = row
        print(f"{case}: " + ", ".join(
            f"{k} {v:.4g}" if isinstance(v, float) else f"{k} {v}"
            for k, v in row.items()))
        # the same 128 terms summed in f32, in another order at most
        assert row["probs_max_abs_diff"] < 1e-5, row
        assert same > 0.99, row
    out = os.path.join(ROOT, "chiprun_out", "sparse_score_tpu.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(table, f, indent=1)
    # a step of long chunks is the cheaper for its one fetch a stretch
    chunk = table["decode_8_chunk_512"]
    assert chunk["shared_ms"] < 0.6 * chunk["per_lane_ms"], table
