"""The selection through the pool (serve/sparse_paged.py).

The selector's scores (PR 55): every stretch of lanes scores ONE fetch
of its main sequence's compressed keys in one product, and the stray
lanes — those of another sequence — their own gathered copy, a stretch
of them a trip; held to the per-lane form alone (every live lane made a
stray) on the same pool and tables, over the lane layouts the scheduler
can pack.

The selected blocks (PR 57): the LIST form — a work list made of the
selection (kernels/paged_ragged_v2.build_select_list), one masked call
of the paged kernel a key/value head and group of lanes, through the
Pallas interpreter — held to the per-lane twin on `o`, over those
layouts and the ones that stress a list, at kv-blocks of one, two and
four selection blocks; the list over numpy against the list the device
builds, and its length against its proven bound.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.kernels import paged_ragged_v2 as K
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
    o, _ = SP.paged_sparse_attention(*args, SC, **_geometry(SELECT_PAGES))
    # every live lane a stray: the per-lane form alone
    monkeypatch.setattr(SP, "main_slots", lambda s, live, *a: (
        s[::SP.LANE_TILE], live))
    own = SP.lane_probs(*args, SC)
    o_own, _ = SP.paged_sparse_attention(*args, SC,
                                         **_geometry(SELECT_PAGES))
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


# ------------------------------------------------- the selected blocks
SELECT_PAGES = SC.block_size // PAGE            # pages a selection block
BLOCKS = PAGES // SELECT_PAGES                  # ... a table row holds
# kv-blocks of one, two and four selection blocks
BLOCK_PAGES = (SELECT_PAGES, 2 * SELECT_PAGES, 4 * SELECT_PAGES)
# layouts that stress a list
LIST_LAYOUTS = {
    "run_ends_mid_tile": _chunk(6, 70, 45),
    "chunk_astride_dense_len": _chunk(2, 40, 50),
    "nothing_past_dense_len": _chunk(1, 0, 40) + [(9, 50), (11, 63)],
    "decode_lane_alone_in_the_last_tile": [(0, 3)] * 0 + _chunk(3, 0, 64)
    + [(5, 127)],
    "a_tile_of_one_lane_runs": [(s, 64 + 2 * s) for s in range(32)],
}


def _geometry(block_pages, lanes=LANES, changes=None):
    """`attend_selected`'s keywords at a kv-block."""
    tiles, bound = K.select_call_tiles(
        lanes, PAGES, block_pages, SELECT_PAGES, SC.topk,
        slot_changes=changes)
    return {"block_pages": block_pages, "call_lanes": tiles * K.Q_ROWS,
            "max_items": bound}


def _selection(args):
    positions = args[5]
    blocks, chosen = SA.select_blocks(SP.lane_probs(*args, SC), positions,
                                      SC)
    return blocks, chosen


def _walk(args, blocks, chosen, block_pages):
    """A numpy walk of the lists a layer's calls are given -> (their
    grid steps, the selection blocks their items fetch)."""
    _, _, _, tables, slots, positions, live = (np.asarray(a) for a in args)
    geometry = _geometry(block_pages)
    lanes = geometry["call_lanes"]
    rows, slots, tables, blocks, chosen = (
        SP.whole_calls(np, a, lanes) for a in (
            live & (positions >= SC.dense_len), slots, tables[slots],
            np.asarray(blocks), np.asarray(chosen)))
    items = fetched = 0
    for lo in range(0, len(rows), lanes):
        cut = slice(lo, lo + lanes)
        for j in range(KV_HEADS):
            *_, total, real = K._select_arrays(
                np, blocks[cut, j], chosen[cut, j], rows[cut], slots[cut],
                tables[cut], block_pages=block_pages,
                select_pages=SELECT_PAGES, q_rows=K.Q_ROWS, max_items=None)
            assert total <= geometry["max_items"]
            items += int(total)
            fetched += int(real) * (block_pages // SELECT_PAGES)
    return items, fetched


def _held_to_the_twin(args, blocks, chosen, dtype):
    """The list form at every kv-block against the per-lane twin."""
    positions, live = (np.asarray(a) for a in args[5:])
    at = np.flatnonzero(live & (positions >= SC.dense_len))
    for block_pages in BLOCK_PAGES:
        kw = _geometry(block_pages)
        want, counted = SP.attend_selected(*args, blocks, chosen, SC,
                                           impl="jnp", **kw)
        got, walked = SP.attend_selected(*args, blocks, chosen, SC,
                                         impl="pallas_interpret", **kw)
        got, want = (np.asarray(o, np.float32) for o in (got, want))
        # a lane that selects nothing comes out finite: nobody reads it,
        # and no NaN reaches a later reduction
        assert np.isfinite(got).all()
        assert np.abs(got[at] - want[at]).max(initial=0) <= TOL[dtype]
        # what the device counted of its lists is the numpy walk's, and
        # the twin's count of the lists it never builds
        assert tuple(np.asarray(walked)) == tuple(np.asarray(counted)) \
            == _walk(args, blocks, chosen, block_pages)


@pytest.mark.parametrize("dtype", list(TOL))
@pytest.mark.parametrize("case", list(LAYOUTS) + list(LIST_LAYOUTS))
def test_the_list_form_attends_what_the_per_lane_twin_attends(case, dtype):
    layout = LAYOUTS[case][0] if case in LAYOUTS else LIST_LAYOUTS[case]
    args = _step(layout, dtype)
    _held_to_the_twin(args, *_selection(args), dtype)


def _chosen_by_hand(pick):
    """A 64-lane chunk past dense_len whose lane n chose `pick(n)`."""
    args = _step(_chunk(3, 64, 64), "float32", seed=1)
    blocks = np.zeros((LANES, KV_HEADS, SC.topk), np.int32)
    chosen = np.zeros((LANES, KV_HEADS, SC.topk), bool)
    for n in range(64):
        for j in range(KV_HEADS):
            blocks[n, j] = pick(n, j)
            chosen[n, j] = True
    return args, jnp.asarray(blocks), jnp.asarray(chosen)


@pytest.mark.parametrize("case,pick", [
    # one fetch serves thirty-two rows
    ("every_row_of_a_tile_the_same_blocks", lambda n, j: [0, 3, 4 + j, 7]),
    # every row's bits in words of their own
    ("every_row_different_blocks",
     lambda n, j: (n + j + np.arange(4) * (1 + n % 2)) % BLOCKS),
    # a row's four choices in ONE kv-block of four
    ("one_kv_block_a_row", lambda n, j: 4 * (n % 2) + np.arange(4)),
])
def test_the_list_form_on_selections_made_by_hand(case, pick):
    _held_to_the_twin(*_chosen_by_hand(pick), "float32")


def test_the_step_through_the_list_is_the_step_through_the_twin():
    """`paged_sparse_attention` whole, both forms: the same selection
    (made by the same code before either), the same `o` where a lane
    selects, the same counts."""
    args = _step(LAYOUTS["decode_then_chunk"][0], "float32")
    kw = _geometry(BLOCK_PAGES[1])
    want, counted = SP.paged_sparse_attention(*args, SC, **kw)
    got, walked = SP.paged_sparse_attention(
        *args, SC, impl="pallas_interpret", **kw)
    at = np.flatnonzero(np.asarray(args[6])
                        & (np.asarray(args[5]) >= SC.dense_len))
    assert len(at) and np.abs(
        np.asarray(got)[at] - np.asarray(want)[at]).max() <= TOL["float32"]
    assert (np.asarray(walked) == np.asarray(counted)).all()


@pytest.mark.parametrize("block_pages", BLOCK_PAGES)
@pytest.mark.parametrize("case", ["decode_then_chunk", "two_chunks_meet",
                                  "every_lane_its_own_slot",
                                  "nothing_live"])
def test_the_list_over_numpy_is_the_list_the_device_builds(case,
                                                           block_pages):
    args = _step(LAYOUTS[case][0], "float32")
    blocks, chosen = (np.asarray(a)[:, 0] for a in _selection(args))
    _, _, _, tables, slots, positions, live = (np.asarray(a) for a in args)
    rows = live & (positions >= SC.dense_len)
    bound = K.max_select_items(LANES, PAGES, block_pages, SC.topk)
    host = K._select_arrays(
        np, blocks, chosen, rows, slots, tables[slots],
        block_pages=block_pages, select_pages=SELECT_PAGES,
        q_rows=K.Q_ROWS, max_items=None)
    work = K.build_select_list(
        *(jnp.asarray(a) for a in (blocks, chosen, rows, slots,
                                   tables[slots], positions + 1)),
        block_pages=block_pages, select_pages=SELECT_PAGES,
        max_items=bound)
    n = int(host[5])
    s_words = block_pages // SELECT_PAGES
    assert int(work.count) == n <= bound
    assert work.tile.shape == (bound + 1,)
    device = (work.tile, work.blk, work.meta,
              work.pages.reshape(-1, block_pages),
              work.masks.reshape(-1, s_words))
    for name, mine, theirs in zip(("tile", "blk", "meta", "pages", "masks"),
                                  host, device):
        theirs = np.asarray(theirs)
        assert (theirs[:n] == mine).all(), name
        # past the list's end: the last item again (the blocks already
        # resident), with every flag clear
        last = mine[-1] & 0xFFFF if name == "meta" else mine[-1]
        assert (theirs[n:] == last).all(), name
    # every tile's output is written: one first and one last item each
    meta, tile = host[2], host[0]
    tiles = -(-LANES // K.Q_ROWS)
    for flag in (K._FIRST, K._LAST):
        assert sorted(tile[(meta & flag) != 0]) == list(range(tiles))
    assert (np.diff(tile) >= 0).all()


@pytest.mark.parametrize("seed", range(4))
def test_a_random_selection_list_stays_inside_its_proven_bound(seed):
    rng = np.random.default_rng(seed)
    lanes, changes = 96, 7
    # runs cut at random lanes, as many as the caller's bound allows
    cuts = np.sort(rng.choice(np.arange(1, lanes), changes, replace=False))
    slots = np.searchsorted(cuts, np.arange(lanes), side="right")
    blocks = np.stack([rng.permutation(BLOCKS)[:SC.topk]
                       for _ in range(lanes)]).astype(np.int32)
    chosen = rng.random((lanes, SC.topk)) < 0.9
    tables = 1 + rng.permutation(SLOTS * PAGES).reshape(SLOTS, PAGES)
    for block_pages in BLOCK_PAGES:
        *_, total, real = K._select_arrays(
            np, blocks, chosen, np.ones(lanes, bool), slots,
            tables[slots], block_pages=block_pages,
            select_pages=SELECT_PAGES, q_rows=K.Q_ROWS, max_items=None)
        assert real <= total <= K.max_select_items(
            lanes, PAGES, block_pages, SC.topk, slot_changes=changes)


def test_a_selection_list_at_its_bound():
    """Every lane a run of its own, every one of its topk choices in a
    kv-block of its own: rows x topk items, the bound."""
    lanes = 64
    blocks = np.tile(np.arange(SC.topk, dtype=np.int32) * 2, (lanes, 1))
    tables = 1 + np.arange(lanes * PAGES).reshape(lanes, PAGES)
    *_, total, real = K._select_arrays(
        np, blocks, np.ones((lanes, SC.topk), bool), np.ones(lanes, bool),
        np.arange(lanes), tables, block_pages=SELECT_PAGES,
        select_pages=SELECT_PAGES, q_rows=K.Q_ROWS, max_items=None)
    assert total == real == lanes * SC.topk == K.max_select_items(
        lanes, PAGES, SELECT_PAGES, SC.topk)
    # ... and one run a tile that chose every kv-block: runs x kv-blocks
    blocks = np.tile(np.arange(SC.topk, dtype=np.int32), (lanes, 1)) \
        + SC.topk * (np.arange(lanes)[:, None] % 2)
    *_, total, _ = K._select_arrays(
        np, blocks, np.ones((lanes, SC.topk), bool), np.ones(lanes, bool),
        np.zeros(lanes, np.int32), tables, block_pages=SELECT_PAGES,
        select_pages=SELECT_PAGES, q_rows=K.Q_ROWS, max_items=None)
    assert total == 2 * BLOCKS == K.max_select_items(
        lanes, PAGES, SELECT_PAGES, SC.topk, slot_changes=0)


def test_the_calls_of_a_step_are_cut_where_their_lists_fit_smem():
    # the served geometry: 544 lanes, 4,096 pages of 16, blocks of 64
    for block_pages, calls in ((4, 3), (8, 5), (16, 9)):
        tiles, bound = K.select_call_tiles(544, 4096, block_pages, 4, 64,
                                           slot_changes=32)
        assert -(-17 // tiles) == calls
        words = 3 + block_pages + block_pages // 4
        assert (bound + 1) * words <= K.SMEM_LIST_WORDS
        assert bound == K.max_select_items(
            tiles * K.Q_ROWS, 4096, block_pages, 64, slot_changes=32)
