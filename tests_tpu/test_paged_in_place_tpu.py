"""The paged kernel reads a head-packed pool's leaf WHERE IT LIES
(kernels/paged_ragged_v2.py `page_base`, serve/kv_cache.py
`KVPool.layer`; PR 50), COMPILED on the chip, the kernel alone at
Qwen3-Next's served shape — 16 query heads over 2 key/value heads of 256,
49,153 pages of 16 a layer, 576 lanes, 32,768 positions: parity of the
call on the whole leaf (the layer's first row a scalar) with a reference
on the host and, bit for bit, with the call on the layer's own pages, for
every layer of a leaf of two; and ms a call on a leaf of 2 and of 8
layers, first and last layer, beside the call the engine made before — a
layer's slice of the leaf, which XLA copies out (K and V, 805 MB each)
for the call. The call's time must not depend on the leaf's depth. Run
with `-s` to see the rows; they are also written to
chiprun_out/paged_in_place_tpu.json.
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from flexflow_tpu.kernels import paged_ragged_v2 as K

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGE, LANES, SEQS = 16, 576, 64
HQ, H, D, PAGES, PP = 16, 2, 256, 49153, 2048
BP = K.choose_block_kv(PAGE, PP, H, D, 2) // PAGE


def _leaf(layers, salt):
    """(layers, PAGES, PAGE, H * D) bf16, made on the device with no
    temporary of its size: small values that differ by layer, page,
    slot and column."""
    def make():
        shape = (layers, PAGES, PAGE, H * D)
        i = sum(jax.lax.broadcasted_iota(jnp.int32, shape, ax) * m
                for ax, m in enumerate((7919, 31, 101, 3)))
        return (((i + salt) % 509 - 254) / 128).astype(jnp.bfloat16)
    return jax.jit(make)()


def _step(seed=0):
    """A 100-lane chunk of slot 63 that ends inside a tile, then 40
    decode lanes at scattered contexts, then the inactive tail."""
    rng = np.random.default_rng(seed)
    top = PP * PAGE
    slots, lens = np.zeros(LANES, np.int32), np.ones(LANES, np.int32)
    slots[:100] = SEQS - 1
    lens[:100] = np.arange(top // 2 - 100, top // 2) + 1
    slots[100:140] = 1 + np.arange(40)
    lens[100:140] = 1 + (np.arange(40) * 997) % (top - 1)
    table = np.zeros((SEQS, PP), np.int32)
    free = iter(rng.permutation(np.arange(1, PAGES)))
    for s in sorted(set(slots[:140])):
        longest = lens[:140][slots[:140] == s].max()
        for col in range(-(-int(longest) // PAGE)):
            table[s, col] = next(free)
    items = K.max_work_items(LANES, PP, BP, slot_changes=SEQS)
    counts = K.work_items(lens, slots, table, page_size=PAGE,
                          block_kv_pages=BP, max_items=items,
                          live_lanes=140, group=HQ // H)
    table, slots, lens = (jnp.asarray(x) for x in (table, slots, lens))
    work = jax.jit(lambda t, s, n: K.build_work_list(
        t, s, n, page_size=PAGE, block_pages=BP, max_items=items))(
        table, slots, lens)
    q = jax.random.normal(jax.random.key(seed), (LANES, HQ, D),
                          jnp.bfloat16)
    return q, table, slots, lens, work, counts


def _in_place(q, k, v, work, base):
    """The call on the whole leaf: its rows, and the layer's first."""
    rows = lambda a: a.reshape((-1,) + a.shape[2:])
    return K._ragged_v2_pallas(q, rows(k), rows(v), work, 0.0625, False,
                               short=K.has_short_body(HQ // H),
                               page_base=base)


def _sliced(q, k, v, work, layer):
    """The call of before: a layer's slice of the leaf."""
    heads = lambda a: a[layer].reshape(PAGES, PAGE, H, D)
    return K._ragged_v2_pallas(q, heads(k), heads(v), work, 0.0625, False,
                               short=K.has_short_body(HQ // H))


def _reference(q, k_rows, v_rows, base, table, slot, n):
    """One lane's attention in f32 on the host, from the pages its
    table names (fetched by row: base + page)."""
    pages = base + np.asarray(table[slot, :-(-n // PAGE)])
    k, v = (np.asarray(a[pages], np.float32).reshape(-1, H, D)[:n]
            for a in (k_rows, v_rows))
    qh = np.asarray(q, np.float32).reshape(H, HQ // H, D)
    s = np.einsum("hgd,khd->hgk", qh, k) * 0.0625
    p = np.exp(s - s.max(-1, keepdims=True))
    return (np.einsum("hgk,khd->hgd", p, v)
            / p.sum(-1)[..., None]).reshape(HQ, D)


def test_the_call_on_the_whole_leaf_matches_a_reference_and_the_slice():
    q, table, slots, lens, work, _ = _step(seed=3)
    k, v = _leaf(2, 0), _leaf(2, 77)
    k_rows, v_rows = (a.reshape((-1,) + a.shape[2:]) for a in (k, v))
    based = jax.jit(_in_place)
    sliced = jax.jit(_sliced, static_argnums=(4,))
    outs = []
    for layer in (0, 1):
        out = np.asarray(based(q, k, v, work, jnp.int32(layer * PAGES)),
                         np.float32)
        assert np.isfinite(out).all()
        np.testing.assert_array_equal(
            out, np.asarray(sliced(q, k, v, work, layer), np.float32))
        for lane in (0, 57, 99, 100, 117, 139):     # the chunk, decode
            ref = _reference(q[lane], k_rows, v_rows, layer * PAGES, table,
                             int(slots[lane]), int(lens[lane]))
            np.testing.assert_allclose(out[lane], ref, rtol=2e-2,
                                       atol=2e-2)
        outs.append(out)
    assert np.abs(outs[0] - outs[1])[:140].max() > 1e-2   # other pages


def _ms(fn, *args, reps=20):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3


def test_ms_a_call_does_not_depend_on_the_leaf_s_depth():
    q, table, slots, lens, work, counts = _step()
    based = jax.jit(_in_place)
    sliced = jax.jit(_sliced, static_argnums=(4,))
    rows = []
    for layers in (8, 2):       # the deep leaf first: 12.9 GB of K and V
        k, v = _leaf(layers, 0), _leaf(layers, 77)
        jax.block_until_ready((k, v))
        row = {"layers": layers,
               "leaf_bytes": int(k.size) * 2,
               "in_place_first_ms": _ms(based, q, k, v, work, jnp.int32(0)),
               "in_place_last_ms": _ms(
                   based, q, k, v, work, jnp.int32((layers - 1) * PAGES))}
        if layers == 2:
            row["sliced_last_ms"] = _ms(sliced, q, k, v, work, layers - 1)
        rows.append(row)
        print(", ".join(f"{a} {b:.3f}" if isinstance(b, float)
                        else f"{a} {b}" for a, b in row.items()))
        del k, v
    table_ = {"device": jax.devices()[0].device_kind, "lanes": LANES,
              "shape": {"query_heads": HQ, "kv_heads": H, "head_dim": D,
                        "pages_a_layer": PAGES, "block_pages": BP},
              "grid_steps": counts["total"], "live_items": counts["items"],
              "page_fetches": counts["page_fetches"], "rows": rows}
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "paged_in_place_tpu.json"), "w") as f:
        json.dump(table_, f, indent=1)
    shallow = rows[1]
    times = [r[key] for r in rows
             for key in ("in_place_first_ms", "in_place_last_ms")]
    # the same walk over the same pages, whatever lies around them
    assert max(times) < 1.15 * min(times) + 0.02, times
    # the slice pays for the slab: 2 x 805 MB read and written
    assert shallow["sliced_last_ms"] > 2.0 + shallow["in_place_last_ms"]
