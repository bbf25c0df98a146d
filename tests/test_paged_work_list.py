"""The paged kernel's work decomposition (kernels/paged_ragged_v2.py).

  * the LIST against a brute-force walk: every (lane, live kv-block)
    is covered by exactly one item, a tile's first and last items carry
    the flags that start and emit its online softmax, a live page slot
    names the page table's page, the entries past the end repeat the
    last item and do nothing; an idle step is one item a tile; the
    bound `max_work_items` is reached by the worst arrays it admits and
    never passed by a plan the scheduler makes.
  * the KERNEL (Pallas interpreter) against the jnp twin over the lane
    layouts the engine packs — decode lanes with an inactive tail, a
    chunk crossing kv-blocks and a tile boundary, a chunk with draft
    lanes, sequences in neighbouring lanes, lanes of one slot whose
    lengths do not rise by one — for blocks of 1, 2 and 8 pages, f32 /
    int8 / fp8 pages and H*D of 256 and 512, at the tolerances
    tests/test_kv_quant.py states (2e-6 for a format against its own
    jnp path).
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flexflow_tpu.kernels.paged_ragged_v2 import (_FIRST, _LAST, _LIVE,
                                                  Q_ROWS, build_work_list,
                                                  max_work_items,
                                                  paged_attention_ragged_v2,
                                                  quantize_kv_rows,
                                                  work_items)

PS = 4          # page size
PP = 20         # table columns: sequences of up to 80 tokens
SEQS = 6


def _table(rng):
    pt = np.zeros((SEQS, PP), np.int32)
    pt[:] = 1 + rng.permutation(SEQS * PP).reshape(SEQS, PP)
    return pt


# ----------------------------------------------------------- lane layouts
def _decode_tail():
    """Five decode lanes of five sequences, then inactive lanes."""
    lens = [9, 33, 1, 80, 17]
    return list(range(1, 6)), lens, 3 * Q_ROWS


def _chunk_across_tiles():
    """One chunk of sequence 2 at positions 30..30+Q_ROWS+9: several
    kv-blocks, and it crosses a tile boundary."""
    n = Q_ROWS + 10
    return [2] * n, list(range(31, 31 + n)), 2 * Q_ROWS


def _chunk_and_drafts():
    """A chunk of sequence 1, a decode lane of sequence 3 with four
    draft lanes after it, a decode lane of sequence 4."""
    slots = [1] * 11 + [3] * 5 + [4]
    lens = list(range(20, 31)) + list(range(41, 46)) + [7]
    return slots, lens, 2 * Q_ROWS


def _neighbours():
    """Two sequences lane by lane, then back to the first."""
    return [1, 2, 1, 2, 2, 1], [5, 9, 6, 10, 11, 7], Q_ROWS


def _not_rising():
    """Lanes of one slot with equal, falling and jumping lengths, and
    a live lane of slot 0 next to the inactive lanes of slot 0."""
    slots = [5] * 7 + [0] * 2
    lens = [40, 40, 13, 77, 2, 2, 50, 30, 1]
    return slots, lens, Q_ROWS + 3          # not a whole tile


def _one_lane_rows():
    """One-lane runs at the first, middle and last rows of a tile, a
    chunk between them, then an empty tile, then a lone decode lane."""
    slots = [1, 2, 3] + [4] * 20 + [5, 1, 2, 3, 5, 1, 2, 3, 5]
    lens = [70, 3, 41] + list(range(11, 31)) + [9, 80, 17, 33, 64, 2, 55,
                                                26, 79]
    assert len(slots) == Q_ROWS
    slots += [0] * (Q_ROWS + 7) + [2]
    lens += [1] * (Q_ROWS + 7) + [61]
    return slots, lens, 3 * Q_ROWS


LAYOUTS = {"decode_tail": _decode_tail, "chunk_across_tiles":
           _chunk_across_tiles, "chunk_and_drafts": _chunk_and_drafts,
           "neighbours": _neighbours, "not_rising": _not_rising,
           "one_lane_rows": _one_lane_rows}


def _lanes(name):
    slots, lens, width = LAYOUTS[name]()
    live = len(slots)
    slots = np.array(slots + [0] * (width - live), np.int32)
    lens = np.array(lens + [1] * (width - live), np.int32)
    changes = int(np.sum(slots[1:] != slots[:-1]))
    return slots, lens, live, changes


# ------------------------------------------------------- the list itself
def _brute_items(lens, slots, pt, bp, qb):
    """Tile by tile, run by run, block by block: (tile, lo, hi, blk,
    [the page a slot must hold, None where it sees nothing])."""
    pad = -len(lens) % qb
    lens = list(lens) + [1] * pad
    slots = list(slots) + [0] * pad
    items = []
    for tile in range(len(lens) // qb):
        lo = 0
        while lo < qb:
            hi = lo + 1
            while hi < qb and slots[tile * qb + hi] == slots[tile * qb + lo]:
                hi += 1
            longest = max(lens[tile * qb + lo:tile * qb + hi])
            for blk in range(-(-longest // (bp * PS))):
                cols = [blk * bp + i for i in range(bp)]
                items.append((tile, lo, hi, blk, [
                    int(pt[slots[tile * qb + lo], c])
                    if c * PS < longest else None for c in cols]))
            lo = hi
    return items


@pytest.mark.parametrize("bp", [1, 2, 8])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_work_list_equals_a_brute_force_walk(layout, bp):
    slots, lens, live, changes = _lanes(layout)
    pt = _table(np.random.RandomState(bp))
    bound = max_work_items(len(slots), PP, bp, Q_ROWS, changes)
    work = build_work_list(jnp.asarray(pt), jnp.asarray(slots),
                           jnp.asarray(lens), page_size=PS,
                           block_pages=bp, max_items=bound)
    tile, blk, meta = (np.asarray(x) for x in (work.tile, work.blk,
                                               work.meta))
    pages = np.asarray(work.pages).reshape(-1, bp)
    assert len(tile) == len(blk) == len(meta) == len(pages) == bound + 1
    want = _brute_items(lens, slots, pt, bp, Q_ROWS)
    assert len(want) <= bound
    held = [0] * bp
    for w, (t, lo, hi, b, pg) in enumerate(want):
        assert (tile[w], blk[w]) == (t, b)
        assert (meta[w] & 0xFF, (meta[w] >> 8) & 0xFF) == (lo, hi)
        assert meta[w] & _LIVE
        # the tile's first item starts its softmax, its last emits it
        assert bool(meta[w] & _FIRST) == (lo == 0 and b == 0)
        assert bool(meta[w] & _LAST) == (
            w + 1 == len(want) or want[w + 1][0] != t)
        # a live slot names its page; a dead one keeps what it held
        held = [h if p is None else p for h, p in zip(held, pg)]
        assert list(pages[w]) == held
    # past the end: the last item again, every flag clear
    for w in range(len(want), bound + 1):
        assert (tile[w], blk[w]) == (want[-1][0], want[-1][3])
        assert meta[w] & (_FIRST | _LAST | _LIVE) == 0
        assert list(pages[w]) == held
    # every (lane, live block) lies in exactly one item
    seen = {}
    for t, lo, hi, b, _ in want:
        for r in range(lo, hi):
            lane = t * Q_ROWS + r
            if lane < len(lens) and b * bp * PS < lens[lane]:
                seen[(lane, b)] = seen.get((lane, b), 0) + 1
    assert seen == {(lane, b): 1 for lane in range(len(lens))
                    for b in range(-(-int(lens[lane]) // (bp * PS)))}
    # the host's counters walk the same list
    got = work_items(lens, slots, pt, page_size=PS, block_kv_pages=bp,
                     max_items=bound, live_lanes=live)
    mine = [(t, lo, hi) for t, lo, hi, _, _ in want
            if t * Q_ROWS + lo < live]
    assert got["grid"] == bound and got["total"] == len(want)
    assert got["items"] == len(mine)
    assert got["rows"] == sum(min(hi, live - t * Q_ROWS) - lo
                              for t, lo, hi in mine)
    # the items the one-lane body takes, on a call that holds it (four
    # query heads a key/value head) and on one that does not
    assert got["short_items"] == 0
    grouped = work_items(lens, slots, pt, page_size=PS, block_kv_pages=bp,
                         max_items=bound, live_lanes=live, group=4)
    assert grouped["short_items"] == sum(hi - lo == 1
                                         for _, lo, hi in mine)
    assert {k: v for k, v in grouped.items() if k != "short_items"} == \
        {k: v for k, v in got.items() if k != "short_items"}


def test_rows_per_item_says_how_often_lanes_share():
    pt = _table(np.random.RandomState(0))
    slots, lens, live, _ = _lanes("decode_tail")
    got = work_items(lens, slots, pt, page_size=PS, block_kv_pages=2,
                     live_lanes=live)
    assert got["rows"] == got["items"] > live      # one row an item
    slots, lens, live, _ = _lanes("chunk_across_tiles")
    got = work_items(lens, slots, pt, page_size=PS, block_kv_pages=2,
                     live_lanes=live)
    assert got["rows"] / got["items"] > Q_ROWS / 2


def test_an_idle_step_is_one_item_a_tile_on_the_sink_page():
    pt = np.zeros((SEQS, PP), np.int32)
    width = 3 * Q_ROWS
    got = work_items(np.ones(width, np.int32), np.zeros(width, np.int32),
                     pt, page_size=PS, block_kv_pages=2, live_lanes=0)
    assert got["total"] == 3 and got["page_fetches"] == 2
    assert got["items"] == got["rows"] == 0


def _random_lanes(kind, rng, width=3 * Q_ROWS):
    """Lane arrays as `_pack` lays them: chunks one after another, a
    slot each, then the inactive lanes on slot 0."""
    top = PP * PS
    chunks = {"decode_only": [1] * 5, "one_long_chunk": [Q_ROWS + 13],
              "mixed": [17, 1, 1, 6, 1], "all_inactive": []}[kind]
    slots, lens = [], []
    for slot, n in zip(rng.permutation(SEQS - 1) + 1, chunks):
        end = rng.randint(n, top + 1)
        slots += [slot] * n
        lens += range(end - n + 1, end + 1)
    live = len(slots)
    slots = np.array(slots + [0] * (width - live), np.int32)
    lens = np.array(lens + [1] * (width - live), np.int32)
    return slots, lens, live


@pytest.mark.parametrize("window", [0, 24])
@pytest.mark.parametrize("kind", ["decode_only", "one_long_chunk", "mixed",
                                  "all_inactive"])
def test_the_list_s_count_is_the_items_the_host_counts(kind, window):
    """`WorkList.count`, the grid's length on the device, is
    `work_items(...)["total"]`, what `_pack` checks against the bound
    and reports as walked: the live lanes' items and one a tile of the
    inactive ones."""
    tiles = 3
    for seed in range(4):
        rng = np.random.RandomState(seed)
        pt = _table(rng)
        slots, lens, live = _random_lanes(kind, rng)
        for bp in (1, 2, 8):
            got = work_items(lens, slots, pt, page_size=PS,
                             block_kv_pages=bp, live_lanes=live,
                             window=window)
            work = build_work_list(
                jnp.asarray(pt), jnp.asarray(slots), jnp.asarray(lens),
                page_size=PS, block_pages=bp, window=window)
            count = int(work.count)
            assert work.count.dtype == jnp.int32 and work.count.shape == ()
            assert count == got["total"] <= got["grid"]
            assert tiles <= count and got["items"] <= count
            assert count - got["items"] <= tiles
            meta = np.asarray(work.meta)
            assert (meta[:count] & _LIVE).all()
            assert not (meta[count:] & _LIVE).any()
            assert kind != "all_inactive" or count == tiles


def test_the_count_never_passes_the_caller_s_bound():
    """A bound too short for the arrays loses the tail (`_pack` raises
    before that); the grid still ends inside the arrays."""
    rng = np.random.RandomState(0)
    pt = _table(rng)
    slots, lens, live = _random_lanes("mixed", rng)
    total = work_items(lens, slots, pt, page_size=PS, block_kv_pages=1,
                       live_lanes=live)["total"]
    for bound, want in ((total - 3, total - 3), (total, total),
                        (total + 5, total)):
        work = build_work_list(jnp.asarray(pt), jnp.asarray(slots),
                               jnp.asarray(lens), page_size=PS,
                               block_pages=1, max_items=bound)
        assert int(work.count) == want
        assert work.tile.shape[0] == bound + 1


@pytest.mark.parametrize("bp", [1, 3, 8])
def test_the_bound_is_reached_and_not_passed(bp):
    """`max_work_items` for lanes whose slot changes `c` times: the
    arrays that change slot c times, away from the tile boundaries,
    with every lane at the full table, make exactly that many items;
    and with no bound given every lane may be a run of its own."""
    pt = _table(np.random.RandomState(1))
    width, c = 2 * Q_ROWS, 5
    slots = np.zeros(width, np.int32)
    for i, at in enumerate((2, 5, 7, Q_ROWS + 1, Q_ROWS + 4)):
        slots[at:] = i + 1
    lens = np.full(width, PP * PS, np.int32)
    nb = -(-PP // bp)
    bound = max_work_items(width, PP, bp, Q_ROWS, c)
    assert bound == (2 + c) * nb
    assert work_items(lens, slots, pt, page_size=PS,
                      block_kv_pages=bp)["total"] == bound
    every = (np.arange(width) % SEQS).astype(np.int32)
    free = max_work_items(width, PP, bp, Q_ROWS)
    assert free == width * nb == work_items(
        lens, every, pt, page_size=PS, block_kv_pages=bp)["total"]


def test_no_plan_passes_the_engine_s_bound():
    """A session whose plans mix chunks, decode lanes and draft lanes
    over every slot: `_pack` checks each plan's items against
    `attn_max_items` (it raises if one passes), and the slot changes
    the bound's proof counts stay at or under max_seqs."""
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.models.transformer import build_transformer_lm
    from flexflow_tpu.serve import ServeEngine
    from flexflow_tpu.serve.engine import ServeSession

    cfg = FFConfig(batch_size=1, kv_page_size=4, kv_num_pages=65,
                   serve_max_seqs=4, serve_prefill_budget=12,
                   serve_spec_decode=True)
    lm = build_transformer_lm(cfg, vocab_size=61, max_seq_len=64,
                              hidden=32, num_heads=4, num_layers=1,
                              ff_dim=64)
    eng = ServeEngine(lm, use_pallas=False)
    seen = []
    pack = ServeSession._pack

    def spy(self, plan):
        out = pack(self, plan)
        slots = out[0][5]
        seen.append((out[-1], int(np.sum(slots[1:] != slots[:-1]))))
        return out

    ServeSession._pack = spy
    try:
        rng = np.random.RandomState(3)
        # repeated tokens: the prompt-lookup drafter finds drafts
        prompts = [list(rng.randint(1, 5, size=n))
                   for n in (30, 3, 17, 40, 9, 25, 2, 33)]
        eng.generate(prompts, 12)
    finally:
        ServeSession._pack = pack
    assert len(seen) > 10
    assert max(w["total"] for w, _ in seen) <= eng.attn_max_items
    assert max(c for _, c in seen) <= eng.cache_cfg.max_seqs
    assert any(w["rows"] > w["items"] for w, _ in seen)     # a chunk
    assert any(w["rows"] == w["items"] > 0 for w, _ in seen)  # decodes


# ------------------------- the step's fixed shape against its live work
def _count_engine(kind, **kw):
    """A small engine of each kind of `geometry.attn_calls`: every layer on
    the one list, or a hybrid model whose window layers walk a second
    one (speculation off: an emitter is one lane)."""
    from flexflow_tpu.config import CompMode, FFConfig
    from flexflow_tpu.serve import ServeEngine

    cfg = FFConfig(batch_size=1, seed=5, kv_page_size=4, kv_num_pages=65,
                   serve_max_seqs=4, serve_prefill_budget=12,
                   serve_spec_decode=False, serve_prefix_cache=False)
    if kind == "hybrid":
        from flexflow_tpu.models.phi4flash import build_phi4flash_lm
        lm = build_phi4flash_lm(cfg, vocab_size=61, max_seq_len=64,
                                hidden=32, num_heads=4, num_kv_heads=2,
                                num_layers=8, ff_dim=48, window=8)
    else:
        from flexflow_tpu.models.transformer import build_transformer_lm
        lm = build_transformer_lm(cfg, vocab_size=61, max_seq_len=64,
                                  hidden=32, num_heads=4, num_layers=3,
                                  ff_dim=64)
    lm.compile(comp_mode=CompMode.INFERENCE)
    return ServeEngine(lm, **kw)


@pytest.mark.parametrize("kind", ["opt", "hybrid"])
def test_every_step_counts_its_fixed_shape_against_its_live_work(
        kind, monkeypatch):
    """`_pack` has each list walked once (mixers.step_counts, as before
    the counts existed) and what `work_items` answered summed over the
    calls that walk it; the `dispatch` span carries the same numbers."""
    from flexflow_tpu.serve import engine as E
    from flexflow_tpu.serve import mixers as M
    from flexflow_tpu.utils.telemetry import Telemetry

    tel = Telemetry()
    eng = _count_engine(kind, use_pallas=False, telemetry=tel)
    full_calls, window_calls = eng.geometry.attn_calls
    assert (full_calls, window_calls) == \
        ((3, 0) if kind == "opt" else (2, 2))       # 1 full + 1 cross
    walked = []
    real = M.work_items
    monkeypatch.setattr(
        M, "work_items",
        lambda *a, **kw: walked.append(real(*a, **kw)) or walked[-1])
    rng = np.random.RandomState(3)
    steps, packed = [], []
    with E.ServeSession(eng) as s:
        for n in (30, 3, 17, 40, 9, 25, 2, 33):
            s.submit(list(rng.randint(1, 61, size=n)), 10)
        while s.has_work():
            del walked[:]
            ev = s.step()
            if walked:      # the call packed a step: the next to land
                packed.append(list(walked))
            if ev is None or not ev.dispatched:
                continue
            lists = packed[ev.step_index]   # `_pack` adds keys, changes none
            assert len(lists) == 1 + bool(window_calls)
            calls = [full_calls, window_calls][:len(lists)]
            grids = [eng.attn_max_items, eng.window_max_items]
            # a call walks its list's own length, under the bound
            assert [w["grid"] for w in lists] == grids[:len(lists)]
            bound = sum(n * g for n, g in zip(calls, grids))
            assert ev.grid_steps <= bound
            for key, item in (("grid_steps", "total"),
                              ("live_steps", "items"),
                              ("short_steps", "short_items"),
                              ("live_rows", "rows")):
                assert getattr(ev, key) == sum(
                    n * w[item] for n, w in zip(calls, lists)), key
            assert (ev.attn_items, ev.attn_rows) == (
                lists[0]["items"], lists[0]["rows"])    # ONE call's
            assert 0 < ev.live_steps <= ev.grid_steps
            # what is walked and not live: an item a tile of inactive
            # lanes, a call
            tiles = -(-eng.mixed_width // Q_ROWS)
            assert ev.grid_steps - ev.live_steps <= sum(calls) * tiles
            assert 0 <= ev.short_steps <= ev.live_steps
            assert 0 < ev.live_rows <= ev.live_steps * Q_ROWS
            assert ev.lanes == eng.head_rows < eng.mixed_width
            assert ev.emitters == len(ev.emit_lanes) <= ev.lanes
            assert ev.emit_lanes == list(range(ev.emitters))
            assert ev.topv.shape == (ev.lanes, eng.topk_cap)
            steps.append({k: getattr(ev, k) for k in E.LIVE_COUNTS})
        assert s.stats_dict()["attn_steps"] == {
            "live": sum(st["live_steps"] for st in steps),
            "short": sum(st["short_steps"] for st in steps)}
    assert len(steps) > 10
    assert min(st["grid_steps"] for st in steps) < bound / 2
    # decode lanes are one-lane runs: the body they take is in a call of
    # four query heads a key/value head and in no call of one
    assert any(st["short_steps"] for st in steps) == (kind == "hybrid")
    assert any(st["live_rows"] > st["live_steps"] for st in steps)  # chunk
    assert any(st["emitters"] == 0 for st in steps)     # mid-prompt chunk
    spans = [e[6] for e in tel.events
             if e[0] == "X" and e[2] == "dispatch"]
    assert [{k: a[k] for k in E.LIVE_COUNTS} for a in spans] == steps
    assert not any("ssm_runs" in a for a in spans)
    assert ("state_bytes" in spans[0]) == (kind == "hybrid")


def test_the_row_fill_metrics_scale_by_the_kernel_s_rows_an_item():
    """`attn_row_fill.*` reads live_rows / live_steps: its files' scale
    is 100 over the rows an item has room for."""
    import glob
    import json
    import os
    here = os.path.dirname(os.path.abspath(__file__))
    files = glob.glob(os.path.join(
        here, "..", "benchmark", "metrics", "attn_row_fill.*.json"))
    assert len(files) == 5      # chat, docqa, olmoe, phi; cmda (PR 43)
    for path in files:
        with open(path) as f:
            args = json.load(f)["args"]
        assert (args["num"], args["den"]) == ("live_rows", "live_steps")
        assert args["scale"] * Q_ROWS == 100.0


@pytest.mark.parametrize("kind", ["opt", "hybrid"])
def test_the_window_list_s_calls_have_a_name_of_their_own(kind):
    """The device trace tells the two lists' calls apart by the
    kernel's name; a model with one list lowers with the one name."""
    import re

    import jax
    eng = _count_engine(kind, interpret=True)
    c = eng.cache_cfg
    z = np.zeros((eng.mixed_width,), np.int32)
    pts = np.zeros((c.max_seqs, c.pages_per_seq), np.int32)
    text = jax.jit(eng._mixed_impl).lower(
        eng._step_params, eng._device_pool(), z, z, z, z, pts, z, z + 1,
        z[:eng.head_rows], z - 1, z[:eng.head_rows]
    ).as_text(debug_info=True)
    names = set(re.findall(r"paged_ragged_v2\w*", text))
    assert names == ({"paged_ragged_v2", "paged_ragged_v2_window"}
                     if kind == "hybrid" else {"paged_ragged_v2"})


@pytest.mark.parametrize("hq,h,d,conds", [
    (4, 4, 64, 3),      # one group, two heads a slab (OPT's)
    (2, 2, 128, 3),     # one group, one head a slab (OLMoE's)
    (8, 2, 128, 5),     # four query heads a key/value head (Phi's)
])
def test_the_one_lane_body_is_in_a_call_by_its_shapes_alone(hq, h, d, conds):
    """A call whose whole-tile product has under SHORT_MIN_ROWS rows
    traces the kernel it always traced: init, accumulate, emit, and no
    second body (the cells of one group are held to the program they
    had); four query heads a key/value head add the one-lane body."""
    import jax

    from flexflow_tpu.kernels import paged_ragged_v2 as K
    assert K.has_short_body(hq // h) == (conds == 5)
    q = jnp.zeros((Q_ROWS, hq, d), jnp.bfloat16)
    kp = jnp.zeros((1 + SEQS * PP, PS, h, d), jnp.bfloat16)
    slots, lens = jnp.zeros(Q_ROWS, jnp.int32), jnp.ones(Q_ROWS, jnp.int32)
    pt = jnp.asarray(_table(np.random.RandomState(0)))
    work = build_work_list(pt, slots, lens, page_size=PS, block_pages=2)

    def text(**kw):
        return str(jax.make_jaxpr(lambda q, kp, work: K._ragged_v2_pallas(
            q, kp, kp, work, 0.125, True, **kw))(q, kp, work))

    by_rule = str(jax.make_jaxpr(
        lambda q, kp, work: paged_attention_ragged_v2(
            q, kp, kp, pt, slots, lens, scale=0.125, work=work,
            interpret=True))(q, kp, work))
    assert by_rule == text(short=conds == 5) != text(short=conds != 5)
    assert by_rule.count("cond[") == conds
    assert text() == text(short=False)      # a caller sets nothing


# -------------------------------------------- the kernel on those layouts
def _pools(rng, h, d, fmt):
    num_pages = 1 + SEQS * PP
    kp = rng.randn(num_pages, PS, h, d).astype(np.float32)
    vp = rng.randn(num_pages, PS, h, d).astype(np.float32)
    kp[0] = vp[0] = 0.0                   # the sink page
    kp, vp = jnp.asarray(kp), jnp.asarray(vp)
    if fmt == "float32":
        return kp, vp, {}
    dtype = jnp.int8 if fmt == "int8" else jnp.float8_e4m3fn
    kq, ks = quantize_kv_rows(kp, dtype)
    vq, vs = quantize_kv_rows(vp, dtype)
    return kq, vq, {"k_scales": ks, "v_scales": vs}


@pytest.mark.parametrize("fmt,h,d,bp", [
    ("float32", 4, 64, 1), ("float32", 4, 64, 2), ("float32", 4, 64, 8),
    ("float32", 8, 64, 2), ("float32", 4, 128, 2),
    ("int8", 4, 64, 2), ("int8", 8, 64, 8),
    ("float8_e4m3", 4, 64, 2), ("float8_e4m3", 4, 128, 1)])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_kernel_matches_the_jnp_twin_on_every_layout(layout, fmt, h, d,
                                                     bp):
    slots, lens, live, changes = _lanes(layout)
    rng = np.random.RandomState(len(layout) + h + d + bp)
    pt = jnp.asarray(_table(rng))
    kp, vp, scales = _pools(rng, h, d, fmt)
    q = jnp.asarray(rng.randn(len(slots), h, d).astype(np.float32))
    slots, lens = jnp.asarray(slots), jnp.asarray(lens)
    ref = paged_attention_ragged_v2(q, kp, vp, pt, slots, lens,
                                 use_pallas=False, **scales)
    # once on the caller's proven bound, once on the kernel's own
    work = build_work_list(
        pt, slots, lens, page_size=PS, block_pages=bp,
        max_items=max_work_items(len(slots), PP, bp, Q_ROWS, changes))
    for kw in ({"work": work}, {"block_kv": bp * PS}):
        if "block_kv" in kw and (bp == 1 or fmt != "float32"):
            continue              # the long grid, interpreted: f32 only
        out = np.asarray(paged_attention_ragged_v2(
            q, kp, vp, pt, slots, lens, interpret=True, **kw, **scales))
        assert np.isfinite(out).all()   # the inactive lanes' rows too
        np.testing.assert_allclose(out, np.asarray(ref), rtol=2e-6,
                                   atol=2e-6)


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5),
                                       ("bfloat16", 2e-2)])
@pytest.mark.parametrize("window", [0, 24])
def test_one_lane_items_on_their_own_rows_equal_the_twin(window, dtype,
                                                         tol):
    """Phi-4-mini-flash's served heads — 40 query heads over 10
    key/value heads of 128 — on the layout whose first tile holds
    one-lane runs at its first rows (the first starts the tile's
    softmax), a chunk's run, more one-lane runs and one at its last row
    (whose last block emits the tile), whose second tile is empty and
    whose third holds a lone decode lane: the one-lane body and the
    whole-tile body give the twin's answer, and each other's."""
    from flexflow_tpu.kernels import paged_ragged_v2 as K
    hq, h, d, bp = 40, 10, 128, 2
    slots, lens, live, _ = _lanes("one_lane_rows")
    rng = np.random.RandomState(window)
    pt = _table(rng)
    counts = work_items(lens, slots, pt, page_size=PS, block_kv_pages=bp,
                        live_lanes=live, window=window, group=hq // h)
    assert 0 < counts["short_items"] < counts["items"]
    kp, vp, _ = _pools(rng, h, d, "float32")
    kp, vp = kp.astype(dtype), vp.astype(dtype)
    q = jnp.asarray(rng.randn(len(slots), hq, d), dtype)
    pt, slots, lens = jnp.asarray(pt), jnp.asarray(slots), jnp.asarray(lens)
    twin = np.asarray(paged_attention_ragged_v2(
        q, kp, vp, pt, slots, lens, use_pallas=False, window=window,
        scale=0.3), np.float32)
    work = build_work_list(pt, slots, lens, page_size=PS, block_pages=bp,
                           window=window)
    whole, short = (np.asarray(K._ragged_v2_pallas(
        q, kp, vp, work, 0.3, True, window=window, short=s), np.float32)
        for s in (False, True))
    assert np.isfinite(short).all()         # the empty tile's rows too
    np.testing.assert_allclose(short[:live], twin[:live], atol=tol, rtol=0)
    np.testing.assert_allclose(whole[:live], twin[:live], atol=tol, rtol=0)
    # the same products summed in the same order
    np.testing.assert_allclose(short, whole, atol=1e-6, rtol=0)
    by_rule = np.asarray(paged_attention_ragged_v2(
        q, kp, vp, pt, slots, lens, work=work, interpret=True,
        window=window, scale=0.3), np.float32)
    np.testing.assert_array_equal(by_rule, short)


@pytest.mark.parametrize("fmt,hq,h,d,window,tol,layout", [
    ("float32", 4, 4, 64, 0, 2e-6, "one_lane_rows"),
    ("float32", 8, 2, 128, 24, 2e-6, "chunk_and_drafts"),
    ("bfloat16", 4, 4, 64, 0, 2e-2, "one_lane_rows"),
    ("bfloat16", 8, 2, 128, 0, 2e-2, "chunk_and_drafts"),
    ("bfloat16", 8, 2, 128, 24, 2e-2, "one_lane_rows"),
    ("bfloat16", 16, 4, 128, 0, 2e-2, "one_lane_rows"),
    ("int8", 4, 4, 64, 0, 2e-6, "neighbours"),
    ("int8", 8, 2, 64, 24, 2e-6, "neighbours"),
    ("float8_e4m3", 4, 4, 64, 0, 2e-6, "neighbours"),
    ("float8_e4m3", 8, 2, 64, 24, 2e-6, "neighbours")])
def test_rows_of_many_layers_read_by_a_base_equal_the_layer_s_own_call(
        fmt, hq, h, d, window, tol, layout):
    """A head-packed pool's leaf holds the pages of all its layers as
    rows (KVPool.layer): the call on the whole leaf with `page_base` =
    layer * pages gives, for EVERY layer of three, exactly what the call
    on that layer's own (page, slot, head, dim) slice gives — the jnp
    twin against itself and the kernel against itself (the same
    products in the same order; only where a block comes from differs)
    — and the kernel with a base agrees with the twin like any other
    call. One query head a key/value head and several (with the
    one-lane body at 16 / 4), a window's list, and bf16, int8 and fp8
    pages."""
    layers, bp = 3, 2
    slots, lens, live, _ = _lanes(layout)
    rng = np.random.RandomState(hq + d + window)
    pt = jnp.asarray(_table(rng))
    quant = fmt in ("int8", "float8_e4m3")
    act = jnp.float32 if quant else jnp.dtype(fmt)
    each = [_pools(rng, h, d, fmt if quant else "float32")
            for _ in range(layers)]
    kl, vl = (jnp.stack([e[i] for e in each]) for i in (0, 1))
    if not quant:
        kl, vl = kl.astype(act), vl.astype(act)
    sl = {n: jnp.stack([e[2][n] for e in each]) for n in each[0][2]}
    pages = kl.shape[1]
    rows = lambda a: a.reshape((-1,) + a.shape[2:])
    kr, vr = (rows(a).reshape(layers * pages, PS, h * d) for a in (kl, vl))
    sr = {n: rows(a) for n, a in sl.items()}
    q = jnp.asarray(rng.randn(len(slots), hq, d), act)
    slots, lens = jnp.asarray(slots), jnp.asarray(lens)
    work = build_work_list(pt, slots, lens, page_size=PS, block_pages=bp,
                           window=window)
    outs = []
    for i in range(layers):
        base = jnp.int32(i * pages)
        for kw in ({"use_pallas": False}, {"interpret": True, "work": work}):
            own = paged_attention_ragged_v2(
                q, kl[i], vl[i], pt, slots, lens, window=window, **kw,
                **{n: a[i] for n, a in sl.items()})
            based = paged_attention_ragged_v2(
                q, kr, vr, pt, slots, lens, window=window,
                page_base=base, **kw, **sr)
            np.testing.assert_array_equal(np.asarray(based, np.float32),
                                          np.asarray(own, np.float32))
            outs.append(np.asarray(based, np.float32))
        np.testing.assert_allclose(outs[-1][:live], outs[-2][:live],
                                   atol=tol, rtol=0)
    # the layers hold different pages: a base that named another
    # layer's rows would have given another layer's answer
    assert np.abs(outs[1] - outs[3]).max() > 0.1
    # a packed layer's own rows need no base
    alone = paged_attention_ragged_v2(
        q, kr[:pages], vr[:pages], pt, slots, lens, window=window,
        interpret=True, work=work, **{n: a[:pages] for n, a in sr.items()})
    np.testing.assert_array_equal(np.asarray(alone, np.float32), outs[1])


def test_a_call_with_no_base_lowers_to_the_program_it_was():
    """`page_base=None` is the call of before: four scalar-prefetch
    operands and the pages' index maps with no add; a base is a fifth,
    traced — two layers of one leaf share the nested jit's one trace."""
    h, d, bp = 4, 64, 2
    slots, lens, _, _ = _lanes("decode_tail")
    rng = np.random.RandomState(0)
    pt = jnp.asarray(_table(rng))
    kp, vp, _ = _pools(rng, h, d, "float32")
    q = jnp.asarray(rng.randn(len(slots), h, d), jnp.float32)
    slots, lens = jnp.asarray(slots), jnp.asarray(lens)
    work = build_work_list(pt, slots, lens, page_size=PS, block_pages=bp)

    def call(kp, vp, **kw):
        return jax.make_jaxpr(lambda *a: paged_attention_ragged_v2(
            q, *a, pt, slots, lens, work=work, interpret=True, **kw))(kp, vp)

    def prefetched(jaxpr):
        (eqn,) = _pallas_calls(jaxpr.jaxpr)
        return eqn.params["grid_mapping"].num_index_operands

    assert prefetched(call(kp, vp)) == 4
    assert str(call(kp, vp)) == str(call(kp, vp, page_base=None))
    kr, vr = (jnp.concatenate([a, a]).reshape(-1, PS, h * d)
              for a in (kp, vp))
    both = jax.make_jaxpr(lambda *a: [paged_attention_ragged_v2(
        q, *a, pt, slots, lens, work=work, interpret=True,
        page_base=jnp.int32(layer * kp.shape[0])) for layer in (0, 1)])(
        kr, vr)
    traces = [e.params["jaxpr"] for e in both.jaxpr.eqns
              if e.params.get("name") == "_ragged_v2_pallas"]
    assert len(traces) == 2 and traces[0] is traces[1]
    assert prefetched(traces[0]) == 5


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                yield from _pallas_calls(inner)


@pytest.mark.parametrize("interpret", [False, True])
def test_the_compiled_call_s_grid_bound_is_the_traced_count(interpret):
    """Mosaic takes the grid's length as an operand (`work.count`); the
    interpreter takes no traced bound and keeps the arrays' length."""
    import jax
    from flexflow_tpu.kernels import paged_ragged_v2 as K
    q = jnp.zeros((Q_ROWS, 4, 64), jnp.bfloat16)
    kp = jnp.zeros((1 + SEQS * PP, PS, 4, 64), jnp.bfloat16)
    slots, lens = jnp.zeros(Q_ROWS, jnp.int32), jnp.ones(Q_ROWS, jnp.int32)
    pt = jnp.asarray(_table(np.random.RandomState(0)))
    work = build_work_list(pt, slots, lens, page_size=PS, block_pages=2)
    call, = _pallas_calls(jax.make_jaxpr(
        lambda q, kp, work: K._ragged_v2_pallas(
            q, kp, kp, work, 0.125, interpret))(q, kp, work).jaxpr)
    grid = call.params["grid_mapping"]
    assert grid.num_dynamic_grid_bounds == (0 if interpret else 1)
    if interpret:
        assert grid.grid == (work.tile.shape[0] - 1,)


def _cut(work):
    """The list cut to its own length (and the one entry past it that
    the pipeline evaluates): through the interpreter, whose grid is the
    arrays' length, the walk the compiled kernel takes from `count`."""
    n = int(work.count) + 1
    return dataclasses.replace(
        work, tile=work.tile[:n], blk=work.blk[:n], meta=work.meta[:n],
        pages=work.pages[:n * work.block_pages])


@pytest.mark.parametrize("hq,h,d,fmt,window", [
    (4, 4, 64, "float32", 0),       # one group: OPT's and OLMoE's form
    (40, 10, 128, "float32", 0),    # group 4: the one-lane body too
    (40, 10, 128, "float32", 24),   # and the window's list
    (8, 4, 64, "float32", 24),      # group 2 under a window, one body
    (4, 4, 64, "int8", 0)])         # quantized pages with their scales
@pytest.mark.parametrize("layout", ["one_lane_rows", "chunk_and_drafts"])
def test_the_walk_to_count_equals_the_walk_of_the_whole_bound(
        layout, hq, h, d, fmt, window):
    """The entries past `count` do nothing: the kernel over the list cut
    to `count` entries gives, bit for bit, what it gives over the
    bound's whole static grid."""
    from flexflow_tpu.kernels import paged_ragged_v2 as K
    bp = 2
    slots, lens, live, changes = _lanes(layout)
    rng = np.random.RandomState(hq + window)
    pt = jnp.asarray(_table(rng))
    kp, vp, scales = _pools(rng, h, d, fmt)
    q = jnp.asarray(rng.randn(len(slots), hq, d).astype(np.float32))
    slots, lens = jnp.asarray(slots), jnp.asarray(lens)
    bound = max_work_items(len(slots), PP, bp, Q_ROWS, changes)
    work = build_work_list(pt, slots, lens, page_size=PS, block_pages=bp,
                           max_items=bound, window=window)
    assert int(work.count) < bound == work.tile.shape[0] - 1
    whole, cut = (np.asarray(K._ragged_v2_pallas(
        q, kp, vp, w, 0.3, True, window=window,
        short=K.has_short_body(hq // h), **scales))
        for w in (work, _cut(work)))
    assert np.isfinite(cut).all()
    np.testing.assert_array_equal(cut, whole)
    twin = np.asarray(paged_attention_ragged_v2(
        q, kp, vp, pt, slots, lens, use_pallas=False, window=window,
        scale=0.3, **scales))
    np.testing.assert_allclose(cut[:live], twin[:live], atol=1e-5, rtol=0)


def test_a_list_too_long_for_smem_is_split_by_lanes(monkeypatch):
    """With no bound from the caller the list is one run a lane; where
    that would pass the SMEM budget the lanes go in several calls, and
    the answer is the same."""
    from flexflow_tpu.kernels import paged_ragged_v2 as k
    rng = np.random.RandomState(9)
    pt = jnp.asarray(_table(rng))
    kp, vp, _ = _pools(rng, 4, 64, "float32")
    lanes = 3 * Q_ROWS + 5
    slots = jnp.asarray(rng.randint(0, SEQS, size=lanes).astype(np.int32))
    lens = jnp.asarray(rng.randint(1, PP * PS + 1,
                                   size=lanes).astype(np.int32))
    q = jnp.asarray(rng.randn(lanes, 4, 64).astype(np.float32))
    ref = paged_attention_ragged_v2(q, kp, vp, pt, slots, lens,
                                 use_pallas=False)
    calls = []
    real = k._ragged_v2_pallas
    monkeypatch.setattr(k, "_ragged_v2_pallas",
                        lambda q, *a, **kw: calls.append(q.shape[0])
                        or real(q, *a, **kw))
    # one tile of lanes a call: 32 lanes x 3 blocks x (3 + 8) words
    monkeypatch.setattr(k, "SMEM_LIST_WORDS",
                        k.max_work_items(Q_ROWS, PP, 8) * 11)
    out = paged_attention_ragged_v2(q, kp, vp, pt, slots, lens,
                                 interpret=True, block_kv=8 * PS)
    assert calls == [Q_ROWS, Q_ROWS, Q_ROWS, 5]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
def test_the_sharded_engine_builds_its_list_inside_shard_map(kv_dtype):
    """tensor_parallel=2 with the interpreted kernel: the work list is
    made per device inside shard_map, each device's call runs its own
    heads over it, and the tokens equal the jnp engine's."""
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.models.transformer import build_transformer_lm
    from flexflow_tpu.serve import ServeEngine

    cfg = FFConfig(batch_size=1, kv_page_size=4, kv_num_pages=65,
                   kv_dtype=kv_dtype, serve_max_seqs=4,
                   serve_prefill_budget=32, serve_spec_decode=True)
    lm = build_transformer_lm(cfg, vocab_size=61, max_seq_len=64,
                              hidden=32, num_heads=4, num_layers=2,
                              ff_dim=72)
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(1, 61, size=n)) for n in (5, 20, 27, 9, 3)]
    ref = ServeEngine(lm, use_pallas=False).generate(prompts, 6)
    eng = ServeEngine(lm, tensor_parallel=2, interpret=True)
    assert eng.generate(prompts, 6) == ref
