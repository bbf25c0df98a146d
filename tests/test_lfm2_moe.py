"""LFM2-MoE's language model through the serve engine (PR 58): the op
graph, the engine through pages AND tails, the gated short convolution's
two forms against each other and the reference, the router with and
without its selection bias, the bias, the gate order, the per-head norm
and the dense layers each shown to matter, a pool whose slots are tails
alone, the step's counters, what the description refuses — against
benchmark/lib/reference_lfm2moe.py, at a small size with seeded random
weights.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from lib import (conv_counts, lfm2moe_cell, moe_counts,  # noqa: E402
                 reference_lfm2moe)

from flexflow_tpu import SGDOptimizer  # noqa: E402
from flexflow_tpu.config import CompMode, FFConfig  # noqa: E402
from flexflow_tpu.models.lfm2_moe import (CONV,  # noqa: E402
                                          build_lfm2_moe_lm)
from flexflow_tpu.models.phi4flash import FULL  # noqa: E402
from flexflow_tpu.ops import short_conv as SC  # noqa: E402
from flexflow_tpu.ops import ssm  # noqa: E402
from flexflow_tpu.ops.moe import route_top_k  # noqa: E402
from flexflow_tpu.serve import ServeEngine  # noqa: E402
from flexflow_tpu.serve.arch import LFM2MoE, describe  # noqa: E402
from flexflow_tpu.serve.kv_cache import (HybridPool, HybridSpec,  # noqa: E402
                                         KVCacheConfig)

VOCAB, HIDDEN, HEADS, KV_HEADS, FF = 128, 32, 4, 2, 48
EXPERTS, TOP_K, EXPERT_FF = 8, 2, 16
# two dense layers, then one whole period and a half: attention in the
# first routing layer as published
LAYER_TYPES = ["conv", "conv", "full_attention", "conv", "conv", "conv"]
PAGE, BUDGET, SEQS = 8, 24, 4
STDS = {"table": 1.0, "conv_in": 0.18, "conv_out": 0.07, "wq": 0.2,
        "wk": 0.2, "wv": 0.18, "wo": 0.09, "gate_up": 0.18, "down": 0.085,
        "router": 0.18, "expert_in": 0.18, "expert_out": 0.12}
CONF = {"vocab_size": VOCAB, "hidden_size": HIDDEN,
        "num_hidden_layers": len(LAYER_TYPES), "layer_types": LAYER_TYPES,
        "num_dense_layers": 2, "num_attention_heads": HEADS,
        "num_key_value_heads": KV_HEADS, "num_experts_per_tok": TOP_K,
        "rope_parameters": {"rope_theta": 1e6, "rope_type": "default"},
        "norm_eps": 1e-5, "max_position_embeddings": 256}
F32_TOL = 1e-3      # f32 engine against the f32 reference, logits of up to 4:
                    # rounding alone


def _lm(max_seq_len=256, **cfg):
    base = dict(batch_size=1, seed=5, kv_page_size=PAGE, kv_num_pages=129,
                serve_max_seqs=SEQS, serve_prefill_budget=BUDGET,
                serve_spec_decode=False, serve_prefix_cache=False)
    base.update(cfg)
    lm = build_lfm2_moe_lm(
        FFConfig(**base), vocab_size=VOCAB, max_seq_len=max_seq_len,
        hidden=HIDDEN, layer_types=LAYER_TYPES, num_dense_layers=2,
        num_heads=HEADS, num_kv_heads=KV_HEADS, ff_dim=FF,
        num_experts=EXPERTS, experts_per_token=TOP_K, expert_dim=EXPERT_FF,
        norm_init=(0.5, 1.5), final_norm_init=(0.09, 0.27),
        qk_norm_init=(2.0, 3.1),
        tap_init=(0.3, 0.7, "signed"), expert_bias_std=0.08, stds=STDS)
    lm.compile(comp_mode=CompMode.INFERENCE)
    return lm


@pytest.fixture(scope="module")
def engine():
    eng = ServeEngine(_lm(), interpret=True)
    eng.warmup()
    return eng


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(1, VOCAB, n).tolist()


def test_graph_forward_equals_the_reference(engine):
    toks = _tokens(200, 3)
    arr = np.zeros((1, 256), np.int32)
    arr[0, :200] = toks
    got = np.asarray(engine.arch.forward_logits(engine.params,
                                                jnp.asarray(arr)))
    want = lfm2moe_cell.reference_logits(CONF)(
        engine.params, toks, list(range(200)))
    assert np.abs(got[:200] - want).max() < F32_TOL
    assert 0.3 < want.std() < 3.0


# ---- the engine through pages AND tails
CASES = {
    "one_chunk": [[17]],
    "several_chunks": [[3 * BUDGET + 5]],
    "a_chunk_that_ends_one_token_into_the_next": [[BUDGET + 1]],
    "one_after_another": [[40], [9]],     # the slot is freed and used again
    "a_first_run_shorter_than_the_taps": [[1]],
    "a_chunk_beside_decode_lanes": [[5, 7], [60]],
}


@pytest.mark.parametrize("case", list(CASES))
def test_engine_through_the_cache_equals_the_reference(engine, case):
    """Chunked prefill, then decoding through pages AND tails. The
    tolerance is f32 rounding: an f32 engine against the f32 reference
    differs by the order of its sums alone."""
    groups = [[_tokens(n, 11 + n) for n in group] for group in CASES[case]]
    rows, stats = lfm2moe_cell.logits_through_cache(
        engine, CONF, groups, 10)
    for r in rows:
        assert r["new"] == 10 and r["worst_gap"] < F32_TOL, r
        assert r["logit_abs_err"] < F32_TOL, r
    assert stats["nonfinite_logit_steps"] == 0
    assert stats["experts"]["dropped"] == 0
    assert engine.compile_counts()["mixed"] == 1
    engine.cache.check_invariants(engine.pool)


def test_a_preemption_and_its_replay_serve_the_same_logits():
    """Too few pages for three sequences: one is preempted and replayed
    from its prompt, its slot's tails started over."""
    eng = ServeEngine(_lm(max_seq_len=128, kv_num_pages=18), interpret=True)
    groups = [[_tokens(40, 21), _tokens(44, 22), _tokens(36, 23)]]
    rows, stats = lfm2moe_cell.logits_through_cache(eng, CONF, groups, 24)
    assert stats["preemptions"] > 0
    for r in rows:
        assert r["new"] == 24 and r["logit_abs_err"] < F32_TOL, r
    eng.close()


# ---- the bias, the gate order, the per-head norm, the dense layers MATTER
CONTROLS = {
    "b_and_c_exchanged": {"swap_gates": True},
    "a_silu_after_the_taps": {"silu_after": True},
    "the_qk_norm_over_the_whole_projection": {"whole_norm": True},
    "the_bias_left_out": {"bias": "none"},
    "the_bias_in_the_weights_too": {"bias": "weights"},
    "softmax_scores": {"score": "softmax"},
    "the_weights_not_renormalised": {"renorm": False},
    "the_dense_layers_given_an_expert_layer": "dense_as_experts",
}


@pytest.mark.parametrize("control", list(CONTROLS))
def test_the_control_moves_the_logits_past_the_tolerance(engine, control):
    """The reference with ONE line of the equations replaced, against
    the engine's logits through the cache: past thirty times the
    tolerance the sound pair is held to (the bias in the weights too
    moves them least: it is a tenth of a score)."""
    toks = _tokens(70, 31)
    rows, _ = lfm2moe_cell.logits_through_cache(engine, CONF, [[toks]], 4)
    assert rows[0]["logit_abs_err"] < F32_TOL
    kw = CONTROLS[control]
    if kw == "dense_as_experts":
        # the first routing layer's experts where the dense layers stood
        kw = {"dense_as_experts": lfm2moe_cell.published_params(
            engine.params, CONF)["layers"][2]}
    at = list(range(60, 70))
    moved_to = lfm2moe_cell.reference_logits(CONF, **kw)(
        engine.params, toks, at)
    sound = lfm2moe_cell.reference_logits(CONF)(engine.params, toks, at)
    moved = np.abs(moved_to - sound).max()
    floor = 10 if control == "the_bias_in_the_weights_too" else 30
    assert moved > floor * F32_TOL, (control, moved)


def test_the_bias_changes_which_experts_a_token_takes(engine):
    """... for some tokens and not for all: else it is not tested."""
    got = lfm2moe_cell.router_readings(engine.params, CONF, _tokens(200, 4))
    assert 0.1 < got["bias_changes_choice_share"] < 0.7, got
    assert 0.5 < got["router_logit_std"] < 2.0, got


# ---- the gated short convolution: two forms of one definition
def _conv_params(seed, e=HIDDEN, taps=3, dtype=jnp.float32):
    r = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(r.standard_normal(s) * 0.3, dtype)
    return {"w_in": f(e, 3 * e), "conv_w": f(taps, e), "w_out": f(e, e)}


def _lanes(runs, t, slots):
    """runs: (slot, first position, lanes, live lanes of them) one after
    another from lane 0; the lanes behind them are dead (slot 0, position
    0, as ServeSession._pack leaves them). -> the lane arrays and what
    step_lanes makes of them."""
    lane_slots, positions, live = (np.zeros(t, np.int32) for _ in range(3))
    at = 0
    for slot, pos0, n, n_live in runs:
        lane_slots[at:at + n] = slot
        positions[at:at + n] = np.arange(pos0, pos0 + n)
        live[at:at + n_live] = 1
        at += n
    live = jnp.asarray(live.astype(bool))
    lane_slots, positions = jnp.asarray(lane_slots), jnp.asarray(positions)
    starts = ssm.run_starts(lane_slots, positions)
    wslots = ssm.run_write_slots(starts, live, lane_slots, slots)
    return (lane_slots, positions, ssm.run_offsets(starts),
            ssm.run_tail_lanes(wslots, slots))


def _whole(p, h):
    return np.asarray(SC.whole(p, h[None])[0])


def _reference(p, h):
    """lib/reference_lfm2moe's convolution on the op's leaves."""
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference_lfm2moe._short_conv(
            {"in_proj": p["w_in"], "conv": p["conv_w"],
             "out_proj": p["w_out"]}, h))


def _steps(p, h_of, steps, slots=3, width=16):
    """Serve `steps` (each a list of runs) over a tail slab from zeros:
    -> {slot: the rows the segmented form gave it, in order}, the slab."""
    tail = jnp.zeros((slots + 1, 2 * HIDDEN), jnp.float32)
    out = {}
    for runs in steps:
        lanes = _lanes(runs, width, slots)
        h = np.zeros((width, HIDDEN), np.float32)
        at = 0
        for slot, pos0, n, _ in runs:
            h[at:at + n] = h_of[slot][pos0:pos0 + n]
            at += n
        b, c, z = SC.project(p, jnp.asarray(h))
        y, tail = SC.segmented(p, b, c, z, tail, *lanes)
        y = np.asarray(SC.out_project(p, y))
        at = 0
        for slot, pos0, n, n_live in runs:
            out.setdefault(slot, []).append(y[at:at + n_live])
            at += n
    return {s: np.concatenate(rows) for s, rows in out.items()}, tail


RUNS = {
    # (slot, first position, lanes, live lanes)
    "a_run_resumed_past_position_0": [[(0, 0, 7, 7)], [(0, 7, 5, 5)]],
    "a_first_run_of_one_token": [[(1, 0, 1, 1)], [(1, 1, 1, 1)],
                                 [(1, 2, 6, 6)]],
    "a_chunk_that_ends_one_token_into_a_sequence":
        [[(0, 0, 9, 9), (2, 0, 1, 1)], [(2, 1, 8, 8), (0, 9, 1, 1)]],
    "dead_lanes_behind_the_live":
        [[(0, 0, 6, 4)], [(0, 4, 6, 6)]],
    "decode_lanes_of_three_sequences":
        [[(0, 0, 4, 4), (1, 0, 3, 3), (2, 0, 5, 5)],
         [(0, 4, 1, 1), (1, 3, 1, 1), (2, 5, 1, 1)],
         [(2, 6, 1, 1), (0, 5, 1, 1), (1, 4, 1, 1)]],
}


@pytest.mark.parametrize("case", list(RUNS))
def test_the_segmented_form_equals_the_whole_and_the_reference(case):
    p = _conv_params(3)
    r = np.random.default_rng(5)
    h_of = {s: r.standard_normal((16, HIDDEN)).astype(np.float32)
            for s in range(3)}
    got, _ = _steps(p, h_of, RUNS[case])
    for slot, rows in got.items():
        n = len(rows)
        want = _whole(p, jnp.asarray(h_of[slot]))[:n]
        assert np.abs(rows - want).max() < 1e-5, (case, slot)
        assert np.abs(want - _reference(p, jnp.asarray(h_of[slot]))[:n]
                      ).max() < 1e-4, (case, slot)


def test_a_slot_freed_and_used_again_starts_from_zeros():
    """The second sequence in slot 0 starts at position 0: it reads
    zeros, not the first one's tail; and the tail that is kept is the
    PRODUCT B * z of the last two live tokens, the sink row untouched."""
    p = _conv_params(4)
    r = np.random.default_rng(6)
    first = r.standard_normal((16, HIDDEN)).astype(np.float32)
    second = r.standard_normal((16, HIDDEN)).astype(np.float32)
    got, tail = _steps(p, {0: first}, [[(0, 0, 8, 8)]])
    again, tail2 = _steps(p, {0: second}, [[(0, 0, 5, 5)]])
    assert np.abs(again[0] - _whole(p, jnp.asarray(second))[:5]).max() < 1e-5
    b, _, z = SC.project(p, jnp.asarray(first))
    u = np.asarray(SC.gate_in(b, z))
    assert np.array_equal(np.asarray(tail[0]).reshape(2, HIDDEN), u[6:8])
    assert not np.asarray(tail[3]).any()


def test_the_rounding_does_not_depend_on_where_a_step_cuts():
    """bf16 activations: the product a later token's taps read is the
    same bits from this step's lanes or from the slot's tail."""
    p = _conv_params(7, dtype=jnp.bfloat16)
    r = np.random.default_rng(8)
    h = jnp.asarray(r.standard_normal((12, HIDDEN)), jnp.bfloat16)

    def serve(cuts):
        tail = jnp.zeros((2, 2 * HIDDEN), jnp.bfloat16)
        rows, at = [], 0
        for n in cuts:
            lanes = _lanes([(0, at, n, n)], 12, 1)
            hh = jnp.zeros((12, HIDDEN), jnp.bfloat16).at[:n].set(
                h[at:at + n])
            b, c, z = SC.project(p, hh)
            y, tail = SC.segmented(p, b, c, z, tail, *lanes)
            rows.append(np.asarray(y[:n], np.float32))
            at += n
        return np.concatenate(rows)

    assert np.array_equal(serve([12]), serve([5, 1, 1, 5]))


# ---- the router with and without its bias
def _plain_top_k(scores, k):
    return np.sort(np.argsort(-scores, axis=1, kind="stable")[:, :k], axis=1)


def test_the_biased_router_against_a_plain_top_k():
    r = np.random.default_rng(2)
    tokens = jnp.asarray(r.standard_normal((50, HIDDEN)), jnp.float32)
    gate = jnp.asarray(r.standard_normal((HIDDEN, EXPERTS)) * 0.3,
                       jnp.float32)
    bias = jnp.asarray(r.standard_normal(EXPERTS) * 0.3, jnp.float32)
    probs, vals, assign = route_top_k(tokens, gate, 2, True, "sigmoid", bias)
    s = 1 / (1 + np.exp(-np.asarray(tokens) @ np.asarray(gate)))
    want = _plain_top_k(s + np.asarray(bias), 2)
    assert np.array_equal(np.sort(np.asarray(assign), axis=1), want)
    # the bias chooses and never weighs: the weights are the SCORES of
    # the chosen, renormalised with 1e-6 in the divisor
    chosen = np.take_along_axis(s, np.asarray(assign), axis=1)
    assert np.allclose(np.asarray(vals), chosen / (
        chosen.sum(axis=1, keepdims=True) + 1e-6), atol=1e-6)
    # ... and it changes the choice for some tokens
    assert (want != _plain_top_k(s, 2)).any()
    assert np.allclose(np.asarray(probs), s, atol=1e-6)


@pytest.mark.parametrize("score,norm", [("softmax", False),
                                        ("softmax", True),
                                        ("sigmoid", True)])
def test_the_older_routers_are_as_they_were(score, norm):
    """OLMoE's, Qwen3-Next's and Command A+'s: no bias, the k largest
    scores as they are or over their sum."""
    r = np.random.default_rng(3)
    tokens = jnp.asarray(r.standard_normal((20, HIDDEN)), jnp.float32)
    gate = jnp.asarray(r.standard_normal((HIDDEN, EXPERTS)), jnp.float32)
    _, vals, assign = route_top_k(tokens, gate, 2, norm, score)
    logits = np.asarray(tokens) @ np.asarray(gate)
    s = 1 / (1 + np.exp(-logits)) if score == "sigmoid" else \
        np.exp(logits) / np.exp(logits).sum(axis=1, keepdims=True)
    assert np.array_equal(np.sort(np.asarray(assign), axis=1),
                          _plain_top_k(s, 2))
    chosen = np.take_along_axis(s, np.asarray(assign), axis=1)
    if norm:
        chosen = chosen / chosen.sum(axis=1, keepdims=True)
    assert np.allclose(np.asarray(vals), chosen, atol=1e-5)


def test_a_training_step_leaves_the_bias_alone():
    """The bias enters the choice alone: its gradient is exactly 0."""
    # the table is the head too: its rows are updated whole
    cfg = FFConfig(batch_size=2, seed=3, sparse_embedding_updates=False)
    lm = build_lfm2_moe_lm(
        cfg, vocab_size=VOCAB, max_seq_len=16, hidden=HIDDEN,
        layer_types=["conv", "full_attention"], num_dense_layers=1,
        num_heads=HEADS, num_kv_heads=KV_HEADS, ff_dim=FF,
        num_experts=EXPERTS, experts_per_token=TOP_K, expert_dim=EXPERT_FF,
        expert_bias_std=0.08)
    lm.compile(optimizer=SGDOptimizer(lr=0.1),
               loss_type="sparse_categorical_crossentropy", metrics=[])
    before = jax.tree.map(np.asarray, lm.state.params)
    assert before["layer1_moe"]["expert_bias"].dtype == np.float32
    assert before["layer1_moe"]["expert_bias"].any()
    toks = np.random.default_rng(0).integers(0, VOCAB, (2, 16)).astype(
        np.int32)
    lm.train_batch({"tokens": toks, "label": np.roll(toks, -1, axis=1),
                    "positions": np.broadcast_to(
                        np.arange(16, dtype=np.int32), (2, 16)).copy()})
    after = jax.tree.map(np.asarray, lm.state.params)
    assert np.array_equal(after["layer1_moe"]["expert_bias"],
                          before["layer1_moe"]["expert_bias"])
    assert not np.array_equal(after["layer1_moe"]["gate"],
                              before["layer1_moe"]["gate"])
    assert not np.array_equal(after["layer0_conv"]["conv_w"],
                              before["layer0_conv"]["conv_w"])


def test_the_bias_leaf_stays_f32_beside_bf16_masters():
    lm = _lm(compute_dtype="bfloat16", param_dtype="bfloat16",
             kv_dtype="bfloat16")
    p = lm.state.params
    assert p["layer2_moe"]["expert_bias"].dtype == jnp.float32
    assert p["layer2_moe"]["wg"].dtype == jnp.bfloat16
    assert p["layer0_conv"]["w_in"].dtype == jnp.bfloat16
    eng = ServeEngine(lm, interpret=True)
    assert eng._device_pool().tail.dtype == jnp.bfloat16
    eng.close()


# ---- the description, the pool, the counters
def test_describe_reads_the_ninth_shape(engine):
    arch = describe(engine.model)
    assert isinstance(arch, LFM2MoE) and arch.kind == "lfm2_moe"
    assert [arch.mixer(i) for i in range(6)] == [
        CONV, CONV, FULL, CONV, CONV, CONV]
    assert not arch.parallel_block and not arch.post_norm
    assert (arch.kv_heads, arch.kv_head_dim, arch.paged_layers) == (
        KV_HEADS, HIDDEN // HEADS, 1)
    assert arch.conv_layers == [0, 1, 3, 4, 5] and arch.full_layers == [2]
    assert arch.dense_layers == 2 and arch.moe_layers == [2, 3, 4, 5]
    assert (arch.experts, arch.experts_per_token, arch.experts_held,
            arch.score, arch.norm_topk, arch.expert_bias) == (
        EXPERTS, TOP_K, None, "sigmoid", True, True)
    assert engine.geometry.attn_calls == (1, 0)
    assert engine.geometry.conv_layers == 5
    assert engine.geometry.scan_impl is None
    assert arch.hybrid_spec(24) == HybridSpec(
        window_layers=0, window=0, chunk=24, tail_layers=5,
        tail_shape=(2, HIDDEN), tail_dtype="float32")
    assert {k: v for k, v in engine.boot_stats.items() if "conv" in k} == {
        "conv_tail_layout": "layers_by_slots_by_flat_rows",
        "conv_tail_shape": (5, SEQS + 1, 2 * HIDDEN),
        "conv_tail_slot_bytes": 2 * HIDDEN * 4}


def test_the_pool_holds_tails_and_no_state(engine):
    c = engine.cache_cfg
    pool = engine._device_pool()
    assert isinstance(pool, HybridPool)
    assert pool.window is None and pool.state is None
    assert pool.tail.shape == (5, SEQS + 1, 2 * HIDDEN)
    assert pool.full.k.shape == (1, c.num_pages, PAGE,
                                 KV_HEADS * (HIDDEN // HEADS))
    assert len(jax.tree.leaves(pool)) == 3      # K, V, the tails
    assert all(leaf.size for leaf in jax.tree.leaves(pool))
    assert c.constant_bytes_per_seq == conv_counts.tail_bytes_per_seq(
        5, HIDDEN, itemsize=4)
    pool.check_geometry(c)
    with pytest.raises(AssertionError, match="pool leaf state"):
        HybridPool(pool.full, None, jnp.zeros((1, 1)), pool.tail
                   ).check_geometry(c)
    with pytest.raises(AssertionError, match="pool leaf tail"):
        HybridPool(pool.full, None, None, pool.tail[:4]).check_geometry(c)


STATEFUL = {
    # the four models whose slots hold a state: their leaves as before
    "phi4flash": (dict(window_layers=2, window=16, state_layers=3,
                       state_shape=(16, 64), tail_shape=(3, 64)),
                  (3, 5, 16, 64), (3, 5, 192)),
    "qwen3_next": (dict(window_layers=0, window=0, state_layers=3,
                        state_shape=(64, 32), tail_shape=(3, 96)),
                   (3, 5, 64, 32), (3, 5, 288)),
    "falcon_h1": (dict(window_layers=0, window=0, state_layers=2,
                       state_shape=(16, 32), tail_shape=(3, 96)),
                  (2, 5, 16, 32), (2, 5, 288)),
    "minicpm_sala": (dict(window_layers=0, window=0, state_layers=2,
                          state_shape=(8, 32)),
                     (2, 5, 8, 32), None),
}


@pytest.mark.parametrize("name", list(STATEFUL))
def test_the_stateful_pools_are_as_they_were(name):
    kw, state, tail = STATEFUL[name]
    spec = HybridSpec(chunk=16, **kw)
    c = KVCacheConfig(num_layers=1, num_heads=2, head_dim=8, page_size=8,
                      num_pages=17, max_seqs=4, max_seq_len=64,
                      kv_dtype="float32", hybrid=spec, packed_heads=True)
    pool = jax.eval_shape(lambda: HybridPool.alloc(c))
    assert (pool.state.shape, pool.state.dtype) == (state, jnp.float32)
    if tail is None:
        assert pool.tail is None and spec.tails == 0
    else:
        assert (pool.tail.shape, pool.tail.dtype) == (tail, jnp.bfloat16)
        assert spec.tails == spec.state_layers
    rows = kw["tail_shape"][0] * kw["tail_shape"][1] * 2 \
        if "tail_shape" in kw else 0
    assert spec.state_bytes == kw["state_layers"] * (
        kw["state_shape"][0] * kw["state_shape"][1] * 4 + rows)


def test_a_tail_without_a_state_is_one_or_the_other():
    with pytest.raises(ValueError, match="WITHOUT a state"):
        HybridSpec(window_layers=0, window=0, chunk=8, state_layers=1,
                   state_shape=(4, 4), tail_shape=(2, 4), tail_layers=2)
    with pytest.raises(ValueError, match="WITHOUT a state"):
        HybridSpec(window_layers=0, window=0, chunk=8, tail_layers=2)


def test_published_widths_give_the_issue_s_bytes():
    spec = HybridSpec(window_layers=0, window=0, chunk=512, tail_layers=8,
                      tail_shape=(2, 2048))
    c = KVCacheConfig(num_layers=2, num_heads=8, head_dim=64,
                      page_size=16, num_pages=32769, max_seqs=256,
                      max_seq_len=4096, kv_dtype="bfloat16", hybrid=spec,
                      packed_heads=True)
    assert c.cache_bytes_per_token == 4096 and c.pages_per_seq == 256
    assert c.constant_bytes_per_seq == 65536 \
        == conv_counts.tail_bytes_per_seq(8, 2048)
    assert spec.tail_bytes == 8192
    pool = jax.eval_shape(lambda: HybridPool.alloc(c))
    assert pool.state is None and pool.window is None
    assert (pool.tail.shape, pool.tail.dtype) == ((8, 257, 4096),
                                                  jnp.bfloat16)
    # 257 rows x 8 layers x 8,192 B = 16 MiB; 32,768 pages = 2 GiB
    assert pool.tail.size * 2 == 257 * 8 * 8192
    assert (c.num_pages - 1) * c.page_bytes == 2 << 30
    # a step of 160 decode lanes and a chunk moves 21 MB of tails
    assert conv_counts.step_tail_bytes(161, 8, 2048) == 2 * 161 * 65536
    assert conv_counts.proj_bytes(2048) == 4 * 2048 * 2048 * 2
    assert conv_counts.lane_flops(2048) == 2 * 2048 * 6144 + 2048 \
        + 6 * 2048 + 2048 + 2 * 2048 * 2048
    # one expert's three matrices, as the counters price a touched one
    assert moe_counts.step_work(np.ones((8, 64)), 2048, 1536, 2)[
        "weight_bytes"] == 512 * 3 * 2048 * 1536 * 2


def test_the_step_counts_its_tails_its_pages_and_its_routing_layers(engine):
    seen = []
    lfm2moe_cell.logits_through_cache(
        engine, CONF, [[_tokens(60, 9), _tokens(20, 10)]], 4,
        on_step=lambda s, ev: seen.append(ev))
    evs = [ev for ev in seen if ev.dispatched]
    # a tail in and a tail out, a run and a layer: the program's counter
    # against benchmark/lib/conv_counts.py
    assert all(ev.state_bytes == conv_counts.step_tail_bytes(
        len(ev.plan.chunks), 5, HIDDEN, itemsize=4) for ev in evs)
    assert all(ev.ssm_runs == len(ev.plan.chunks) for ev in evs)
    assert all(ev.kv_bytes_read == ev.full_kv_bytes > 0 for ev in evs)
    assert all((ev.paged_calls, ev.paged_calls_in_place) == (1, 1)
               for ev in evs)
    live = [ev.plan.num_prefill_lanes + ev.plan.num_decode_lanes
            for ev in evs]
    assert [ev.conv_lanes for ev in evs] == [5 * n for n in live]
    # the counts are over the layers that ROUTE: 4 of 6 here
    for ev, n in zip(evs, live):
        assert ev.expert_counts.shape == (4, EXPERTS)
        work = moe_counts.step_work(ev.expert_counts, HIDDEN, EXPERT_FF, 4)
        assert ev.expert_slots == work["slots"] == n * TOP_K * 4
        assert ev.experts_touched == work["touched"]
        assert ev.expert_bytes == work["weight_bytes"]
        assert ev.expert_dropped == 0
    assert {"state_bytes", "full_kv_bytes", "conv_lanes",
            "paged_calls_in_place"} <= set(engine.geometry.counted)
    assert "ssd_lanes" not in engine.geometry.counted


def test_the_traced_step_keeps_the_scopes_the_readers_know(engine):
    c = engine.cache_cfg
    lane = jnp.zeros((engine.mixed_width,), jnp.int32)
    rows = jnp.zeros((engine.head_rows,), jnp.int32)
    text = jax.jit(engine._mixed_impl).lower(
        engine._step_params, engine._device_pool(), lane, lane, lane, lane,
        jnp.zeros((c.max_seqs, c.pages_per_seq), jnp.int32), lane,
        lane + 1, rows, lane - 1, rows).as_text(debug_info=True)
    for name in ("ln", "conv_proj", "short_conv", "conv_out", "ffn"):
        assert f"serve_step/layer1/{name}/" in text, name
    for name in ("ln", "qkv", "kv_write", "attn", "attn_out", "router",
                 "moe_dispatch", "experts", "moe_combine"):
        assert f"serve_step/layer2/{name}/" in text, name
    for name in ("conv_proj", "short_conv", "conv_out", "router", "experts"):
        assert f"serve_step/layer3/{name}/" in text, name
    assert "serve_step/layer2/ffn/" not in text
    assert "serve_step/layer0/router/" not in text
    assert "ssm_" not in text and "post_norm" not in text


@pytest.mark.parametrize("kwargs,cfg,message", [
    (dict(tensor_parallel=2), {}, "refuses tp"),
    ({}, dict(adapter_rank=4), "refuses adapters"),
    ({}, dict(serve_spec_decode=True), "refuses speculation"),
    ({}, dict(serve_prefix_cache=True), "refuses prefix_cache"),
])
def test_what_lfm2_moe_is_not_served_on_raises_by_name(kwargs, cfg, message):
    with pytest.raises(NotImplementedError, match=message):
        ServeEngine(_lm(**cfg), **kwargs)


def test_the_handoff_and_the_host_tier_are_refused_by_name(engine):
    assert set(LFM2MoE.refused) == {"tp", "adapters", "speculation",
                                    "prefix_cache", "host_tier", "handoff"}
    with pytest.raises(NotImplementedError, match="refuses handoff"):
        engine.arch.refuse(handoff=True)
    with pytest.raises(NotImplementedError, match="refuses host_tier"):
        engine.arch.refuse(host_tier=True)


def test_the_reference_imports_nothing_of_the_program():
    src = open(reference_lfm2moe.__file__).read()
    assert "flexflow_tpu" not in src.split('"""', 2)[2]
    assert "default_matmul_precision(\"highest\")" in src
