"""MiniCPM-SALA's language model through the serve engine (PR 45): the
op graph, the engine through pages, compressed keys and state slots,
the lightning recurrence in its three forms, the selection by hand,
the dense/sparse switch's independence of chunking, a re-admitted
sequence's zero state, what the description refuses, the older
descriptions' pools and programs — against
benchmark/lib/reference_sala.py, at a small size with seeded random
weights.
"""

import copy
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from lib import sala_cell  # noqa: E402
from lib import reference_sala as R  # noqa: E402

from flexflow_tpu.config import CompMode, FFConfig  # noqa: E402
from flexflow_tpu.models.minicpm_sala import (LIGHTNING, LINEAR,  # noqa: E402
                                              MINICPM4, SPARSE,
                                              build_minicpm_sala_lm,
                                              mixer_kinds)
from flexflow_tpu.ops import linear_attention as LA  # noqa: E402
from flexflow_tpu.ops import sparse_attention as SA  # noqa: E402
from flexflow_tpu.ops import ssm  # noqa: E402
from flexflow_tpu.serve import ServeEngine  # noqa: E402
from flexflow_tpu.serve.arch import MiniCPMSala, describe  # noqa: E402
from flexflow_tpu.serve.kv_cache import (HybridPool, HybridSpec,  # noqa: E402
                                         KVCacheConfig, KVPool)
from flexflow_tpu.kernels.paged_ragged_v2 import select_counts  # noqa: E402
from flexflow_tpu.serve.sparse_paged import (LANE_TILE,  # noqa: E402
                                             STRAY_TILE, main_slots)

VOCAB, HIDDEN, HEADS, KV_HEADS, HEAD_DIM = 128, 64, 8, 2, 16
LIN_HEADS, LIN_DIM, FF = 4, 16, 96
TYPES = [MINICPM4] + [LIGHTNING] * 7 + [MINICPM4] * 2   # 10 published
KEPT = [0, 1, 3, 8]                       # sparse, linear, linear, sparse
SIZES = dict(kernel_size=16, kernel_stride=8, block_size=16, topk=4,
             init_blocks=1, window_size=32, dense_len=64)
SC = SA.SparseConfig(**SIZES)
PAGE, BUDGET, SEQS = 8, 24, 4
CONF = {"vocab_size": VOCAB, "hidden_size": HIDDEN,
        "num_hidden_layers": len(KEPT), "mixer_types": TYPES,
        "layers_kept": KEPT, "sparse_config": SIZES, "rope_theta": 10000,
        "rms_norm_eps": 1e-6, "scale_emb": 12, "scale_depth": 1.4,
        "dim_model_base": 16, "max_position_embeddings": 256,
        "system": {"compute_dtype": "float32"}}
F32_TOL = 1e-4


def _lm(max_seq_len=256, qk_init=1.0, **cfg):
    base = dict(batch_size=1, seed=5, kv_page_size=PAGE, kv_num_pages=129,
                serve_max_seqs=SEQS, serve_prefill_budget=BUDGET,
                serve_spec_decode=False, serve_prefix_cache=False)
    base.update(cfg)
    lm = build_minicpm_sala_lm(
        FFConfig(**base), vocab_size=VOCAB, max_seq_len=max_seq_len,
        hidden=HIDDEN, num_heads=HEADS, num_kv_heads=KV_HEADS,
        head_dim=HEAD_DIM, lightning_heads=LIN_HEADS,
        lightning_head_dim=LIN_DIM, ff_dim=FF, mixer_types=TYPES,
        layers_kept=KEPT, sparse=SC, dim_model_base=16,
        sparse_qk_norm_init=qk_init)
    lm.compile(comp_mode=CompMode.INFERENCE)
    return lm


@pytest.fixture(scope="module")
def engine():
    eng = ServeEngine(_lm(), interpret=True)
    eng.warmup()
    return eng


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(1, VOCAB, n).tolist()


def test_mixer_types_become_the_engine_s_mixer_kinds():
    assert mixer_kinds(TYPES, KEPT) == [SPARSE, LINEAR, LINEAR, SPARSE]
    assert mixer_kinds(TYPES)[-2:] == [SPARSE, SPARSE]
    with pytest.raises(ValueError, match="mamba"):
        mixer_kinds(["mamba"])


def test_graph_forward_equals_the_reference():
    """Past dense_len, with more visible blocks than topk."""
    lm = _lm()
    toks = _tokens(200, 3)
    arr = np.zeros((1, 256), np.int32)
    arr[0, :200] = toks
    eng = ServeEngine(lm, interpret=True)
    got = np.asarray(eng.arch.forward_logits(eng.params, jnp.asarray(arr)))
    want = sala_cell.reference_logits(CONF, selector_dtype=None)(
        eng.params, toks, list(range(200)))
    assert np.abs(got[:200] - want).max() < F32_TOL
    assert 0.7 < want.std() < 1.4        # the head's 1 / (hidden / base)


# ---- the engine through pages, compressed keys and state slots
CASES = {
    "one_chunk": [[17]],
    "several_chunks": [[3 * BUDGET + 5]],
    "across_dense_len": [[SIZES["dense_len"] - 9]],     # crossed decoding
    "long_selection": [[190]],            # 12 visible blocks, topk 4
    "two_together": [[70, 131]],
    "one_after_another": [[40], [9]],     # the slot is re-admitted
}


@pytest.mark.parametrize("case", list(CASES))
def test_engine_through_the_cache_equals_the_reference(engine, case):
    groups = [[_tokens(n, 11 + n) for n in group] for group in CASES[case]]
    rows, stats = sala_cell.logits_through_cache(engine, CONF, groups, 14)
    for r in rows:
        assert r["new"] == 14 and r["worst_gap"] < F32_TOL, r
        assert r["logit_abs_err"] < F32_TOL, r
    assert stats["nonfinite_logit_steps"] == 0
    assert engine.compile_counts()["mixed"] == 1
    engine.cache.check_invariants(engine.pool)


def test_a_variant_below_the_stated_precision_fails_the_tolerance():
    """bf16 states, bf16 pages + compressed keys: each moves the logits
    past the f32 tolerance the cases above hold."""
    lm = _lm()
    groups = [[_tokens(150, 2)]]
    for variant in ("bf16_state", "bf16_pages"):
        cfg = copy.copy(lm.config)
        if variant == "bf16_pages":
            cfg.kv_dtype = "bfloat16"
        eng = ServeEngine(lm, interpret=True, config=cfg)
        if variant == "bf16_state":
            pool = eng._device_pool()
            eng.pool = dataclasses.replace(
                pool, state=pool.state.astype(jnp.bfloat16))
        rows, _ = sala_cell.logits_through_cache(eng, CONF, groups, 8)
        assert rows[0]["logit_abs_err"] > 10 * F32_TOL, (variant, rows[0])
        eng.close()


def test_peaked_qk_scales_make_a_wrong_selected_block_count():
    """What the configuration's `init.sparse_qk_norm` is for: with the
    sparse layers' q_norm and k_norm at 2 the engine still equals the
    reference, and a wrong selected block (check_sala_logits.py's
    plant) moves the logits over twice as far as at unit
    scales, where attention is a near-even mean."""
    import check_sala_logits
    groups = [[_tokens(190, 4)]]
    moved = {}
    for init in (1.0, 2.0):
        eng = ServeEngine(_lm(qk_init=init), interpret=True)
        sp, lin = eng.params["layer0_sparse"], eng.params["layer1_linear"]
        assert np.all(np.asarray(sp["q_norm"]) == init)
        assert np.all(np.asarray(sp["k_norm"]) == init)
        assert np.all(np.asarray(lin["q_norm"]) == 1.0)
        rows, _ = sala_cell.logits_through_cache(eng, CONF, groups, 8)
        assert rows[0]["logit_abs_err"] < F32_TOL, (init, rows[0])
        rows, _ = sala_cell.logits_through_cache(
            eng, CONF, groups, 8,
            check_sala_logits.plant_wrong_selected_block(eng))
        moved[init] = rows[0]["logit_rms_err"]
        eng.close()
    assert moved[2.0] > 2 * moved[1.0] > 30 * F32_TOL, moved


# ---- lightning: three forms of one recurrence
def _qkv(t, seed, heads=LIN_HEADS, d=LIN_DIM):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal((t, heads, d)),
                             jnp.float32) for _ in range(3))


def test_lightning_chunk_form_equals_the_recurrence():
    q, k, v = _qkv(77, 0)
    g = LA.decay_rates(LIN_HEADS, 3, 10)
    want = LA.lightning_recurrent(q, k, v, g)
    for chunk in (8, 64, 77):
        got = LA.lightning_chunked(q[None], k[None], v[None], g, chunk)[0]
        assert np.abs(np.asarray(got - want)).max() < 1e-4, chunk


def test_decays_are_the_slopes_times_the_layer_factor():
    g = np.asarray(LA.decay_rates(32, 0, 32))
    assert np.allclose(g[[0, 31]], [2 ** -0.25, 2 ** -8], rtol=1e-5)
    last = np.asarray(LA.decay_rates(32, 31, 32))
    assert np.allclose(last, g * 1e-5 / (1 + 1e-5), rtol=1e-3)


def test_segmented_lightning_resumes_and_restarts_by_slot():
    """Two runs in one step (a chunk that resumes slot 2 at position 5,
    a sequence that starts in slot 0), dead lanes behind them: each
    equals the recurrence over its own sequence, and the slabs hold
    each slot's state after its run."""
    g = LA.decay_rates(LIN_HEADS, 1, 10)
    d = LIN_DIM
    q, k, v = _qkv(5 + 6 + 4, 1)
    first = LA.lightning_recurrent(q[:5], k[:5], v[:5], g)      # earlier
    state = jnp.zeros((4, d, LIN_HEADS * d), jnp.float32)
    state = state.at[0].set(7.0)        # a finished sequence's leftovers
    lanes = lambda a, b: jnp.concatenate([a, b, jnp.zeros(3, a.dtype)])
    slots = lanes(jnp.full(5, 2), jnp.full(4, 0))
    step1_pos = lanes(jnp.arange(5), jnp.arange(4))
    live = jnp.arange(12) < 9
    pad3 = lambda a: jnp.concatenate([a, jnp.zeros((3,) + a.shape[1:])])
    rows = lambda a: pad3(jnp.concatenate([a[:5], a[11:15]]))
    starts = ssm.run_starts(slots, step1_pos)
    o1, state = LA.segmented_lightning(
        rows(q), rows(k), rows(v), g, state, slots, step1_pos, live,
        starts, ssm.run_offsets(starts))
    assert np.abs(np.asarray(o1[:5] - first)).max() < 1e-4
    other = LA.lightning_recurrent(q[11:], k[11:], v[11:], g)
    assert np.abs(np.asarray(o1[5:9] - other)).max() < 1e-4     # from zero
    # the second step resumes slot 2 at position 5
    slots = jnp.full(6, 2)
    pos = jnp.arange(5, 11)
    starts = ssm.run_starts(slots, pos)
    o2, state2 = LA.segmented_lightning(
        q[5:11], k[5:11], v[5:11], g, state, slots, pos,
        jnp.ones(6, bool), starts, ssm.run_offsets(starts))
    whole = LA.lightning_recurrent(q[:11], k[:11], v[:11], g)
    assert np.abs(np.asarray(o2 - whole[5:])).max() < 1e-4
    assert np.array_equal(state2[0], state[0])      # untouched slots stay
    assert np.array_equal(state2[3], state[3])


# ---- the selection, by hand
def test_the_selection_on_a_ten_block_example():
    """Blocks of 16 tokens = 2 strides of 8; topk 4, init 1, 2 local
    blocks. A query in block 9: blocks 0, 8, 9 are forced; the other
    place goes to the best block score, which is the maximum over a
    block's two strides AND the stride before it (the max-pool's
    padding of one)."""
    probs = np.zeros((3, 1, 20), np.float32)
    probs[0, 0, 9] = 0.5        # stride 9: overlaps blocks 4 AND 5 (9 = 2*5-1)
    probs[0, 0, 6] = 0.4        # stride 6: block 3
    probs[1, 0, 13] = 0.9       # stride 13: blocks 6 and 7; block 6 wins ties
    probs[2, 0, 19] = 0.9       # not visible to a query in block 2
    pos = jnp.asarray([9 * 16 + 3, 9 * 16 + 15, 2 * 16 + 1])
    blocks, chosen = SA.select_blocks(jnp.asarray(probs), pos, SC)
    picked = [sorted(np.asarray(blocks[i, 0])[np.asarray(chosen[i, 0])]
                     .tolist()) for i in range(3)]
    assert picked[0] == [0, 4, 8, 9]     # stride 9 scores block 4 first
    assert picked[1] == [0, 6, 8, 9]
    assert picked[2] == [0, 1, 2]        # bt < topk: all of them, no more
    # the reference's own selector decides the same
    kc = np.zeros((20, 1, 4), np.float32)
    want = R._selected(jnp.zeros((3, 1, 4)), jnp.asarray(kc), pos, SIZES, 1)
    assert np.asarray(want)[2, 0, :4].tolist() == [True, True, True, False]


def test_compressed_keys_are_means_of_two_strides():
    k = jnp.asarray(np.random.default_rng(0).standard_normal((40, 2, 4)),
                    jnp.float32)
    kc = np.asarray(SA.compress_keys(k, SC))
    assert kc.shape == (5, 2, 4)
    assert np.allclose(kc[1], np.asarray(k[8:24]).mean(0), atol=1e-6)
    assert np.allclose(kc[3], np.asarray(k[24:40]).mean(0), atol=1e-6)


def test_the_dense_sparse_switch_does_not_depend_on_the_chunking():
    """One prompt crossing dense_len inside a chunk, served at two
    prefill budgets: the same logits."""
    prompt = _tokens(SIZES["dense_len"] + 21, 4)
    outs = []
    for budget in (BUDGET, 40):
        eng = ServeEngine(_lm(serve_prefill_budget=budget), interpret=True)
        rows, _ = sala_cell.logits_through_cache(eng, CONF, [[prompt]], 6)
        outs.append(rows[0])
        eng.close()
    assert outs[0]["prefill_chunks"] != outs[1]["prefill_chunks"]
    for r in outs:
        assert r["logit_abs_err"] < F32_TOL


# ---- the description, the pool, the counters
def test_describe_reads_the_fifth_shape(engine):
    arch = describe(engine.model)
    assert isinstance(arch, MiniCPMSala) and arch.kind == "minicpm_sala"
    assert arch.kinds == [SPARSE, LINEAR, LINEAR, SPARSE]
    # the model's geometry; a paged call a key/value head
    assert (arch.kv_heads, arch.paged_layers) == (KV_HEADS, 2)
    assert engine.geometry.attn_calls == (2 * KV_HEADS, 0)
    assert arch.selector_dim == HEAD_DIM
    assert arch.hybrid_spec(24) == HybridSpec(
        window_layers=0, window=0, chunk=24, state_layers=2,
        state_shape=(LIN_DIM, LIN_HEADS * LIN_DIM))
    # a kept layer's decays are those of its PUBLISHED index
    assert np.allclose(arch.decays[2], LA.decay_rates(LIN_HEADS, 3, 10))


def test_the_pool_holds_pages_selector_rows_and_states_and_no_ring(engine):
    c = engine.cache_cfg
    pool = engine._device_pool()
    assert isinstance(pool, HybridPool)
    assert pool.window is None and pool.tail is None
    # the pool's own layout: a key/value head's pages a pool layer
    assert c.split_heads and (c.num_layers, c.num_heads) == (2, KV_HEADS)
    assert c.head_layers(1) == [2, 3] and c.layer_heads == 1
    assert pool.full.k.shape == (4, c.num_pages, PAGE, HEAD_DIM)
    assert pool.full.kc.shape == (4, c.num_pages, HEAD_DIM)
    assert pool.state.shape == (2, SEQS + 1, LIN_DIM, LIN_HEADS * LIN_DIM)
    assert pool.state.dtype == jnp.float32
    kv = 2 * 2 * KV_HEADS * HEAD_DIM * 4    # K and V, 2 layers, f32
    sel = 2 * KV_HEADS * HEAD_DIM * 4       # a compressed key a page
    assert c.cache_bytes_per_token == kv + sel // PAGE
    assert c.constant_bytes_per_seq == 2 * LIN_HEADS * LIN_DIM**2 * 4
    assert c.ring_pages == 0 and c.ring_page_bytes == 0
    assert engine.scan_impl == "jnp" and engine.dense_pages == 8
    report = engine.cache.pool_report()
    assert report["cache_bytes_per_token"] == c.cache_bytes_per_token
    assert report["cache_bytes_constant_per_seq"] == \
        c.constant_bytes_per_seq


def test_published_widths_give_the_issue_s_bytes():
    spec = HybridSpec(window_layers=0, window=0, chunk=512,
                      state_layers=12, state_shape=(128, 4096))
    c = KVCacheConfig(num_layers=4, num_heads=2, head_dim=128,
                      page_size=16, num_pages=32769, max_seqs=32,
                      max_seq_len=65536, kv_dtype="bfloat16", hybrid=spec,
                      packed_heads=True, selector_dim=128, split_heads=True)
    assert (c.pool_layers, c.layer_heads) == (8, 1)
    assert c.cache_bytes_per_token == 4224
    assert c.constant_bytes_per_seq == 24 * 2**20


def test_the_step_counts_what_was_selected(engine):
    seen = []
    groups = [[_tokens(150, 9)]]
    sala_cell.logits_through_cache(
        engine, CONF, groups, 4, on_step=lambda s, ev: seen.append(ev))
    evs = [ev for ev in seen if ev.dispatched]
    dense_len, block = SIZES["dense_len"], SIZES["block_size"]
    past = [p for p in range(150 + 3) if p >= dense_len]
    visible = sum(p // block + 1 for p in past) * KV_HEADS * 2
    selected = sum(min(4, p // block + 1) for p in past) * KV_HEADS * 2
    assert sum(ev.sparse_lanes for ev in evs) == len(past)
    assert sum(ev.blocks_visible for ev in evs) == visible
    assert sum(ev.blocks_selected for ev in evs) == selected
    # what the device MOVES of the selected blocks it counts itself, as
    # it walks the lists made of the selection (PR 57): `select_items`
    # the grid steps of the selection's calls over both sparse layers
    # and heads, `select_block_fetches` the selection blocks those
    # items fetch, `selected_kv_bytes` their K and V. At this geometry
    # one kv-block holds a whole table row, so an item is a run with a
    # lane that selects, whatever it chose, and the walk over numpy
    # needs no scores: every such lane on block 0
    c = engine.cache_cfg
    heads = 2 * KV_HEADS
    g = engine.geometry
    assert g.select_block_pages == c.pages_per_seq
    words = c.pages_per_seq * PAGE // block
    assert g.select_call_lanes >= engine.mixed_width      # one call

    def walk(ev):
        width = engine.mixed_width
        slots, positions = np.zeros((2, width), np.int32)
        lanes = [(ch.req.slot, p) for ch in ev.plan.chunks
                 for p in range(ch.start, ch.end)]
        slots[:len(lanes)], positions[:len(lanes)] = np.transpose(lanes)
        rows = (np.arange(width) < len(lanes)) & (positions >= dense_len)
        items, real = select_counts(
            np, np.zeros((width, 1), np.int32), np.ones((width, 1), bool),
            rows, slots, num_blocks=words, mask_words=words)
        return heads * int(items), heads * int(real) * words

    for ev in evs:
        assert (ev.select_items, ev.select_block_fetches) == walk(ev)
        assert ev.selected_kv_bytes == ev.select_block_fetches * 2 \
            * block * HEAD_DIM * 4
    # a step with no lane past dense_len walks the sink items alone: one
    # a tile, head and sparse layer, and moves nothing
    early = [ev for ev in evs if not ev.sparse_lanes]
    assert early and {(ev.select_items, ev.selected_kv_bytes)
                      for ev in early} == {(heads, 0)}
    assert any(ev.select_block_fetches for ev in evs)
    # of the compressed keys every stretch fetches ONE copy of its main
    # sequence's strides and the stray lanes — those of another
    # sequence — a copy each, a stretch of them a trip, the first trip
    # standing in the program whatever is live (PR 55): one sequence
    # here, so no lane strays, its chunks' and its decode lane's alike
    tiles = -(-engine.mixed_width // LANE_TILE)
    assert {(ev.score_tiles, ev.score_shared_tiles) for ev in evs} == {
        (tiles, tiles)}
    table = heads * c.pages_per_seq * HEAD_DIM * 4
    assert {ev.selector_bytes for ev in evs} == {
        (tiles + STRAY_TILE) * table}
    # two sequences together: the lanes of the one with fewer lanes in
    # a stretch stray, and the host's count is the rule the device
    # follows (the one helper, over numpy there and jax.numpy here)
    session = engine.start_session()
    for n in (70, 90):
        session.submit(_tokens(n, n), 8)
    strayed = both = 0
    while session.has_work():
        ev = session.step()
        if not ev.dispatched:
            continue
        rids = [ch.req.rid for ch in ev.plan.chunks
                for _ in range(ch.start, ch.end)]
        width = engine.mixed_width
        slots = np.zeros(width, np.int32)
        slots[:len(rids)] = rids
        _, stray = main_slots(jnp.asarray(slots),
                              jnp.arange(width) < len(rids))
        fewer = min(rids.count(r) for r in set(rids)) \
            if len(set(rids)) > 1 else 0
        assert tiles == 1 and int(stray.sum()) == fewer
        assert (ev.score_tiles, ev.score_shared_tiles) == (1, fewer == 0)
        assert ev.selector_bytes == (
            1 + STRAY_TILE * max(1, -(-fewer // STRAY_TILE))) * table
        # two sequences' lanes past dense_len: an item each
        assert (ev.select_items, ev.select_block_fetches) == walk(ev)
        both = max(both, ev.select_block_fetches // words)
        strayed += fewer
    session.close()
    assert strayed and both == 2 * heads
    # the paged calls' fetches alone are `kv_bytes_read`
    assert all(ev.kv_bytes_read == ev.full_kv_bytes for ev in evs)
    # a state in and a state out for every run and layer
    assert all(ev.state_bytes == 2 * len(ev.plan.chunks) * 2 * LIN_HEADS
               * LIN_DIM**2 * 4 for ev in evs)
    # the paged calls walk the lanes under dense_len alone
    late = [ev for ev in evs if min(c.start for c in ev.plan.chunks)
            >= dense_len]
    page = 2 * PAGE * KV_HEADS * HEAD_DIM * 4          # K and V, a layer
    assert late and all(
        ev.full_kv_bytes <= 2 * page * (engine.attn_block_pages + 1)
        for ev in late), [ev.full_kv_bytes for ev in late]


@pytest.mark.parametrize("kwargs,cfg,message", [
    (dict(tensor_parallel=2), {}, "refuses tp"),
    ({}, dict(adapter_rank=4), "refuses adapters"),
    ({}, dict(serve_spec_decode=True), "refuses speculation"),
    ({}, dict(serve_prefix_cache=True), "refuses prefix_cache"),
])
def test_what_minicpm_sala_is_not_served_on_raises_by_name(kwargs, cfg,
                                                          message):
    with pytest.raises(NotImplementedError, match=message):
        ServeEngine(_lm(**cfg), **kwargs)


def test_the_handoff_and_the_host_tier_are_refused_by_name(engine):
    with pytest.raises(NotImplementedError, match="refuses handoff"):
        engine.arch.refuse(handoff=True)
    with pytest.raises(NotImplementedError, match="refuses host_tier"):
        engine.arch.refuse(host_tier=True)


# ---- the older descriptions keep their pools and their programs
def test_a_pool_without_a_selector_flattens_to_the_leaves_it_had():
    c = KVCacheConfig(num_layers=2, num_heads=2, head_dim=8, page_size=4,
                      num_pages=9, max_seqs=2, max_seq_len=16)
    assert len(jax.tree.leaves(KVPool.alloc(c))) == 2
    q = dataclasses.replace(c, kv_dtype="int8")
    assert len(jax.tree.leaves(KVPool.alloc(q))) == 4
    s = dataclasses.replace(c, selector_dim=16)
    assert len(jax.tree.leaves(KVPool.alloc(s))) == 3
    assert s.page_bytes == c.page_bytes + 2 * 16 * 4
    ring = HybridSpec(window_layers=1, window=8, chunk=4, state_layers=1,
                      state_shape=(4, 8), tail_shape=(3, 8))
    h = dataclasses.replace(c, hybrid=ring, packed_heads=True)
    pool = HybridPool.alloc(h)
    assert pool.window is not None and pool.tail is not None
    assert len(jax.tree.leaves(pool)) == 6
    pool.check_geometry(h)


@pytest.mark.parametrize("which", ["olmoe", "phi4flash", "cmdaplus"])
def test_the_older_descriptions_steps_have_nothing_of_the_selection(which):
    """Their engines walk every lane's pages (no dense_pages), their
    pools have no selector rows, and the traced step names no scope of
    the two new mixers."""
    mod = __import__(f"test_{which}")
    eng = ServeEngine(mod._lm(), interpret=True)
    assert eng.dense_pages == 0 and eng.arch.selector_dim == 0
    pool = eng._device_pool()
    full = pool.full if isinstance(pool, HybridPool) else pool
    assert full.kc is None
    c = eng.cache_cfg
    lane = jnp.zeros((eng.mixed_width,), jnp.int32)
    text = str(jax.make_jaxpr(eng._mixed_impl)(
        eng._step_params, pool, lane, lane, lane, lane,
        jnp.zeros((c.max_seqs, c.pages_per_seq), jnp.int32), lane,
        lane + 1, jnp.zeros((eng.head_rows,), jnp.int32), lane - 1,
        jnp.zeros((eng.head_rows,), jnp.int32)))
    assert "sparse_" not in text and "linear_" not in text
    eng.close()
