"""The selector's scores through the pool (serve/sparse_paged.py, PR 55):
every stretch of lanes scores ONE fetch of its main sequence's
compressed keys in one product, and the stray lanes — those of another
sequence — their own gathered copy, a stretch of them a trip; held to
the per-lane form alone (every live lane made a stray) on the same pool
and tables, over the lane layouts the scheduler can pack.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.ops import sparse_attention as SA
from flexflow_tpu.serve import sparse_paged as SP
from flexflow_tpu.serve.kv_cache import KVPool

SC = SA.SparseConfig(kernel_size=16, kernel_stride=8, block_size=16, topk=4,
                     init_blocks=1, window_size=32, dense_len=64)
LANES, SLOTS, PAGES, PAGE = 80, 80, 16, 8       # 3 stretches: 32, 32, 16
HEADS, KV_HEADS, DIM = 8, 2, 16
LAYERS = [2, 3]                                 # the pool layers of a layer
# on `o`: the engine tests' own for f32 (tests/test_minicpm_sala.py), a
# bf16 output's last bit for bf16
TOL = {"float32": 1e-4, "bfloat16": 2e-2}


def _chunk(slot, start, n):
    return [(slot, start + j) for j in range(n)]


# (slot, position) of each live lane from lane 0 up, the main slot of
# each of the three stretches (None: nothing live, any) and the stray
# lanes
LAYOUTS = {
    "chunk_fills_stretches": (_chunk(3, 40, 64), [3, 3, None], []),
    "decode_then_chunk": (
        [(s, 70 + 9 * s) for s in range(5)] + _chunk(5, 60, 59),
        [5, 5, None], range(5)),
    "chunk_then_decode": (
        _chunk(4, 64, 64) + [(s, 127 - s) for s in range(10, 18)],
        [4, 4, 10], range(65, 72)),
    "two_chunks_meet": (_chunk(1, 100, 20) + _chunk(2, 66, 50),
                        [1, 2, 2], range(20, 32)),
    "chunk_ends_mid_stretch": (_chunk(2, 80, 40), [2, 2, None], []),
    "decode_only": ([(s, 64 + 5 * s) for s in range(10)],
                    [0, None, None], range(1, 10)),
    "one_decode_lane": ([(7, 120)], [7, None, None], []),
    "nothing_live": ([], [None, None, None], []),
    # more strays than one trip holds: the loop runs
    "runs_of_16": (
        [lane for s in range(5) for lane in _chunk(s, 64 + s, 16)],
        [0, 2, 4], list(range(16, 32)) + list(range(48, 64))),
    "every_lane_its_own_slot": (
        [(s, 64 + s % 64) for s in range(LANES)], [0, 32, 64],
        [n for n in range(LANES) if n % 32]),
}


def _step(layout, dtype, seed=0):
    rng = np.random.default_rng(seed)
    n_pages = 1 + SLOTS * PAGES
    shape = (4, n_pages, PAGE, DIM)
    pool = KVPool(*(jnp.asarray(rng.standard_normal(shape), dtype)
                    for _ in range(2)),
                  kc=jnp.asarray(rng.standard_normal(shape[:2] + (DIM,)),
                                 dtype), heads=1)
    tables = 1 + rng.permutation(SLOTS * PAGES).reshape(SLOTS, PAGES)
    slots, positions = np.zeros((2, LANES), np.int32)
    live = np.arange(LANES) < len(layout)
    if layout:
        slots[live], positions[live] = np.transpose(layout)
    q = jnp.asarray(rng.standard_normal((LANES, HEADS, DIM)), dtype)
    return (q, pool, LAYERS, jnp.asarray(tables, jnp.int32),
            jnp.asarray(slots), jnp.asarray(positions), jnp.asarray(live))


@pytest.mark.parametrize("dtype", list(TOL))
@pytest.mark.parametrize("case", list(LAYOUTS))
def test_a_shared_fetch_scores_what_the_lanes_own_fetches_score(
        case, dtype, monkeypatch):
    layout, main, stray = LAYOUTS[case]
    args = _step(layout, dtype)
    slots, positions, live = args[4:]
    # the rule, over jnp where the step follows it and numpy where the
    # host counts it
    for xp in (jnp, np):
        got_main, got = SP.main_slots(xp.asarray(slots), xp.asarray(live),
                                      xp)
        assert np.flatnonzero(np.asarray(got)).tolist() == list(stray)
        assert all(want in (None, int(m)) for m, want in zip(got_main, main))
        assert int(SP.stray_batches(got, xp)) == max(
            1, -(-len(stray) // SP.STRAY_TILE))
    probs = SP.lane_probs(*args, SC)
    o = SP.paged_sparse_attention(*args, SC)
    # every live lane a stray: the per-lane form alone
    monkeypatch.setattr(SP, "main_slots", lambda s, live, *a: (
        s[::SP.LANE_TILE], live))
    own = SP.lane_probs(*args, SC)
    o_own = SP.paged_sparse_attention(*args, SC)
    # a dead lane's answer is nobody's
    at = np.flatnonzero(np.asarray(live))
    probs, own = np.asarray(probs)[at], np.asarray(own)[at]
    assert probs.shape == (len(at), KV_HEADS, PAGES)
    assert np.isfinite(np.asarray(o, np.float32)).all()
    assert np.abs(probs - own).max(initial=0) <= \
        (1e-6 if dtype == "float32" else 1e-5)
    pos = positions[at]
    for a, b in zip(SA.select_blocks(jnp.asarray(probs), pos, SC),
                    SA.select_blocks(jnp.asarray(own), pos, SC)):
        assert (np.asarray(a) == np.asarray(b)).all()
    diff = np.asarray(o, np.float32)[at] - np.asarray(o_own, np.float32)[at]
    assert np.abs(diff).max(initial=0) <= TOL[dtype]
