"""Command A+'s language model through the serve engine (PR 43): the op
graph, the engine through pages and rings (no state), the parallel
block, the sigmoid router, the interleaved rotary, one share of an
expert-parallel layer against the uncut layer, the paged kernel at 16
query heads a key/value head, what the description refuses — all
against benchmark/lib/reference_cmdaplus.py, at a small size with
seeded random weights.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from lib import cmdaplus_cell  # noqa: E402
from lib import reference_cmdaplus as R  # noqa: E402

from flexflow_tpu.config import CompMode, FFConfig  # noqa: E402
from flexflow_tpu.kernels.paged_ragged_v2 import (  # noqa: E402
    has_short_body, paged_attention_ragged_v2)
from flexflow_tpu.models.cmdaplus import (GLOBAL, SLIDING,  # noqa: E402
                                          build_cmdaplus_lm, mixer_kinds)
from flexflow_tpu.ops import moe  # noqa: E402
from flexflow_tpu.ops.common import rotary  # noqa: E402
from flexflow_tpu.serve import ServeEngine  # noqa: E402
from flexflow_tpu.serve.arch import CommandAPlus, describe  # noqa: E402
from flexflow_tpu.serve.kv_cache import HybridPool, HybridSpec  # noqa: E402

VOCAB, HIDDEN, HEADS, KV_HEADS, HEAD_DIM, WINDOW = 128, 64, 8, 2, 16, 16
ROUTER, HELD, PER_TOKEN, SHARED, FF = 16, (4, 4), 2, 2, 32
TYPES = [SLIDING] * 3 + [GLOBAL]
PAGE, BUDGET, SEQS = 8, 24, 4
CONF = {"vocab_size": VOCAB, "hidden_size": HIDDEN,
        "num_attention_heads": HEADS, "num_key_value_heads": KV_HEADS,
        "head_dim": HEAD_DIM, "num_hidden_layers": 4, "layer_types": TYPES,
        "sliding_window": WINDOW, "rope_theta": 50000,
        "num_experts": HELD[1], "experts_first": HELD[0],
        "router_width": ROUTER, "num_experts_per_tok": PER_TOKEN,
        "num_shared_experts": SHARED, "intermediate_size": FF,
        "layer_norm_eps": 1e-5, "logit_scale": 1,
        "max_position_embeddings": 128,
        "system": {"compute_dtype": "float32"}}
REF = dict(layer_types=TYPES, window=WINDOW, theta=50000.0,
           experts_per_token=PER_TOKEN, held=HELD, shared=SHARED)
F32_TOL = 1e-4


def _lm(max_seq_len=128, held=HELD, **cfg):
    base = dict(batch_size=1, seed=5, kv_page_size=PAGE, kv_num_pages=65,
                serve_max_seqs=SEQS, serve_prefill_budget=BUDGET,
                serve_spec_decode=False, serve_prefix_cache=False)
    base.update(cfg)
    lm = build_cmdaplus_lm(
        FFConfig(**base), vocab_size=VOCAB, max_seq_len=max_seq_len,
        hidden=HIDDEN, num_heads=HEADS, num_kv_heads=KV_HEADS,
        head_dim=HEAD_DIM, layer_types=TYPES, window=WINDOW,
        num_experts=ROUTER, experts_per_token=PER_TOKEN, expert_dim=FF,
        shared_experts=SHARED, experts_held=held)
    lm.compile(comp_mode=CompMode.INFERENCE)
    return lm


@pytest.fixture(scope="module")
def engine():
    eng = ServeEngine(_lm(), interpret=True)
    eng.warmup()
    return eng


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(1, VOCAB, n).tolist()


def _graph_logits(lm, toks):
    pos = jnp.arange(toks.shape[1], dtype=jnp.int32)[None]
    values, _ = lm.executor.forward_values(
        lm.state.params, {}, {"tokens": toks, "positions": pos},
        training=False, rng=None)
    return values[lm.ops[-1].outputs[0].uid][0]


# ------------------------------------------------------ (a) the op graph
def test_layer_types_become_the_engine_s_mixer_kinds():
    assert "".join(k[0] for k in mixer_kinds(TYPES * 8)) == "wwwf" * 8
    with pytest.raises(ValueError, match="chunked_attention"):
        mixer_kinds(["chunked_attention"])


def test_graph_forward_equals_the_reference():
    lm = _lm(max_seq_len=48)
    toks = jnp.asarray([_tokens(48, 1)], jnp.int32)
    ref = R.logits_at(lm.state.params, toks, jnp.arange(48), **REF)
    assert float(ref.std()) > 0.5
    np.testing.assert_allclose(_graph_logits(lm, toks), ref, atol=F32_TOL,
                               rtol=0)


def test_the_weights_are_the_share_s_and_nothing_has_a_bias():
    p = _lm(max_seq_len=16).state.params
    assert "lm_head" not in p                       # tied
    assert set(p["layer0_ln"]) == set(p["final_ln"]) == {"scale"}
    assert p["layer0_attn"]["wq"].shape == (HIDDEN, HEADS, HEAD_DIM)
    assert p["layer0_attn"]["wk"].shape == (HIDDEN, KV_HEADS, HEAD_DIM)
    assert p["layer0_attn"]["wo"].shape == (HEADS, HEAD_DIM, HIDDEN)
    m = p["layer0_moe"]
    assert m["gate"].shape == (HIDDEN, ROUTER)      # the router stays whole
    assert m["wg"].shape == (HELD[1], HIDDEN, FF)   # the held experts alone
    assert m["sg"].shape == (HIDDEN, SHARED * FF)
    assert m["sd"].shape == (SHARED * FF, HIDDEN)


def test_the_parallel_block_is_one_norm():
    """A SEQUENTIAL block over the same weights — the experts reading
    the norm of x + a — is another function: the comparison that passes
    above fails for it."""
    lm = _lm(max_seq_len=32)
    p = lm.state.params
    toks = jnp.asarray([_tokens(32, 2)], jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = R._f32(jnp.take(p["tok_embed"]["kernel"], toks[0], axis=0))
        for i, kind in enumerate(TYPES):
            w = p[f"layer{i}_ln"]["scale"]
            a = R._attention(p[f"layer{i}_attn"], R._ln(x, w, 1e-5),
                             WINDOW if kind == SLIDING else 0, 50000.0)
            x = x + a
            x = x + R._experts(p[f"layer{i}_moe"], R._ln(x, w, 1e-5),
                               PER_TOKEN, HELD, SHARED)
        seq = R._ln(x, p["final_ln"]["scale"], 1e-5) \
            @ R._f32(p["tok_embed"]["kernel"]).T
    got = _graph_logits(lm, toks)
    assert float(jnp.abs(got - seq).max()) > 100 * F32_TOL


# ------------------------- (b) the engine through pages and rings
def _serve(eng, groups, max_new):
    return cmdaplus_cell.logits_through_cache(eng, CONF, groups, max_new)


CASES = {
    "one prompt whole": ([[_tokens(20, 11)]], 6),
    "a prompt past the window in 3 chunks": ([[_tokens(60, 12)]], 6),
    "two prompts' chunks beside decode lanes":
        ([[_tokens(9, 13)], [_tokens(40, 14), _tokens(33, 15)]], 10),
    "40+ tokens decoded across the window and a page":
        ([[_tokens(10, 16)]], 44),
}


@pytest.mark.parametrize("case", list(CASES))
def test_engine_through_the_cache_equals_the_reference(engine, case):
    groups, new = CASES[case]
    rows, stats = _serve(engine, groups, new)
    for r in rows:
        assert r["new"] == new
        assert r["logit_abs_err"] < F32_TOL, r
        assert r["worst_gap"] < F32_TOL, r
    if "3 chunks" in case:
        assert rows[0]["prefill_chunks"] == 3
    assert stats["nonfinite_logit_steps"] == 0
    assert stats["experts"]["dropped"] == 0
    assert engine.compile_counts()["mixed"] == 1
    engine.cache.check_invariants(engine.pool)


@pytest.mark.parametrize("tampered", [False, True])
def test_the_cell_judges_the_logits_its_own_steps_emitted(engine, tampered):
    """lib/cmdaplus_cell.Loop keeps the top-k logits of every token the
    served steps emitted, and check_serving compares THOSE (no second
    pass): sound they equal the reference's, moved by 0.3 they fail
    the limit on the root mean square."""
    from lib import serving, traffic_gen
    from lib.spans import Spans
    loop = cmdaplus_cell.Loop(engine, Spans())
    for i, (n, new) in enumerate([(9, 7), (40, 12)]):
        loop.submit(serving.Rec(traffic_gen.Req(
            index=i, due_s=0.0, prompt=_tokens(n, 40 + i), max_new=new,
            tenant=0), None))
    while loop.session.has_work():
        loop.step()
    picks = [r for r in loop.check_records() if r["done"]]
    assert [len(r["tokens"]) for r in picks] == [7, 12]
    assert sum(len(rids) for rids, _, _ in loop.logit_steps) == 19
    assert len(loop.count_steps) == len(loop.expert_steps) > 12

    def top_logits(rid):
        v, i = loop.top_logits(rid)
        return (v + 0.3 if tampered else v), i

    found = cmdaplus_cell.check_serving(engine.params, CONF, picks, 16,
                                        top_logits)
    loop.close()
    assert found["positions"] == 19 and found["worst_gap"] < F32_TOL
    why = cmdaplus_cell.verdict(found, {"logit_margin": 0.5,
                                        "logit_rms": 0.05})
    if tampered:
        assert abs(found["logit_rms_err"] - 0.3) < 1e-3 and len(why) == 1
    else:
        assert found["logit_max_abs_err"] < F32_TOL and not why


def test_the_step_counts_held_and_routed_slots_and_lanes_past_the_window(
        engine):
    session = engine.start_session()
    session.submit(_tokens(30, 21), 4)
    evs = []
    while session.has_work():
        ev = session.step()
        if ev is not None and ev.dispatched:
            evs.append(ev)
    stats = session.stats_dict()
    session.close()
    first = evs[0]                          # a 24-token chunk
    assert first.expert_counts.shape == (4, HELD[1])
    assert first.expert_slots == 24 * PER_TOKEN * 4
    assert first.slots_held == int(first.expert_counts.sum())
    assert 0 < first.slots_held < first.expert_slots
    assert first.expert_dropped == 0
    assert first.shared_bytes == 4 * SHARED * 3 * HIDDEN * FF * 4
    assert first.lanes_past_window == 24 - WINDOW
    assert evs[-1].lanes_past_window == 1   # a decode lane at 30+ tokens
    assert first.state_bytes == first.ssm_runs == 0
    assert stats["cache_bytes_per_token"] == 2 * KV_HEADS * HEAD_DIM * 4
    assert stats["experts"]["counts"].shape == (4, HELD[1])


def test_the_pool_holds_rings_and_pages_and_no_state(engine):
    c = engine.cache_cfg
    assert c.hybrid == HybridSpec(window_layers=3, window=WINDOW,
                                  chunk=BUDGET)
    assert (c.num_layers, c.hybrid.state_bytes) == (1, 0)
    pool = engine._device_pool()
    assert pool.state is None and pool.tail is None
    assert all(leaf.size for leaf in jax.tree.leaves(pool))
    assert engine.scan_impl is None
    # rings: the 3 window layers; pages: the ONE full layer
    assert pool.window.k.shape[0] == 3 and pool.full.k.shape[0] == 1
    want = jax.eval_shape(lambda: HybridPool.alloc(c))
    assert jax.tree.structure(want) == jax.tree.structure(pool)


def test_a_depth_of_two_periods_indexes_two_full_layers():
    lm = build_cmdaplus_lm(
        FFConfig(batch_size=1, seed=5, kv_page_size=PAGE, kv_num_pages=33,
                 serve_max_seqs=2, serve_prefill_budget=16,
                 serve_spec_decode=False, serve_prefix_cache=False),
        vocab_size=VOCAB, max_seq_len=64, hidden=HIDDEN, num_heads=HEADS,
        num_kv_heads=KV_HEADS, head_dim=HEAD_DIM,
        layer_types=[SLIDING, GLOBAL] * 2, window=WINDOW,
        num_experts=ROUTER, experts_per_token=PER_TOKEN, expert_dim=FF,
        shared_experts=SHARED, experts_held=HELD)
    lm.compile(comp_mode=CompMode.INFERENCE)
    eng = ServeEngine(lm, interpret=True)
    assert eng.arch.full_layers == [1, 3] and eng.geometry.attn_calls == (2, 2)
    assert eng.cache_cfg.num_layers == 2
    conf = dict(CONF, layer_types=[SLIDING, GLOBAL] * 2,
                max_position_embeddings=64)
    rows, _ = cmdaplus_cell.logits_through_cache(
        eng, conf, [[_tokens(37, 31)]], 5)
    assert rows[0]["logit_abs_err"] < F32_TOL, rows
    eng.close()


# ------------------------------------------ (c) the pieces, by hand
def test_sigmoid_routing_by_hand():
    """Two tokens, four experts, the identity as the router: scores are
    the sigmoids of the token's own entries, the two largest are kept
    and renormalised over the two."""
    x = jnp.asarray([[2.0, -1.0, 0.0, 1.0], [-3.0, 0.5, 0.4, -0.2]])
    scores, vals, ids = moe.route_top_k(x, jnp.eye(4), 2, True, "sigmoid")
    sig = 1 / (1 + np.exp(-np.asarray(x)))
    np.testing.assert_allclose(scores, sig, rtol=1e-6)
    assert ids.tolist() == [[0, 3], [1, 2]]
    np.testing.assert_allclose(
        vals, [[sig[0, 0], sig[0, 3]] / (sig[0, 0] + sig[0, 3]),
               [sig[1, 1], sig[1, 2]] / (sig[1, 1] + sig[1, 2])], rtol=1e-6)
    # the softmax router of the same logits keeps another weight
    _, soft, _ = moe.route_top_k(x, jnp.eye(4), 2, True)
    assert abs(float(soft[0, 0]) - float(vals[0, 0])) > 0.05
    with pytest.raises(ValueError, match="no scoring function"):
        moe.route_top_k(x, jnp.eye(4), 2, True, "tanh")


def test_interleaved_rotary_by_hand():
    """One head of 4 dims at position 3: pairs (x0, x1) and (x2, x3)
    turn by 3 and 3 * theta^-0.5; the half-rotation pairs (x0, x2) and
    (x1, x3) instead."""
    x = jnp.asarray([[[1.0, 2.0, 3.0, 4.0]]])          # (1 token, 1, 4)
    theta = 100.0
    a, b = 3.0, 3.0 * theta ** -0.5
    want = [1 * np.cos(a) - 2 * np.sin(a), 2 * np.cos(a) + 1 * np.sin(a),
            3 * np.cos(b) - 4 * np.sin(b), 4 * np.cos(b) + 3 * np.sin(b)]
    got = rotary(x, jnp.asarray([3]), theta, interleaved=True)
    np.testing.assert_allclose(got[0, 0], want, rtol=1e-6)
    half = rotary(x, jnp.asarray([3]), theta)
    np.testing.assert_allclose(
        half[0, 0], [1 * np.cos(a) - 3 * np.sin(a),
                     2 * np.cos(b) - 4 * np.sin(b),
                     3 * np.cos(a) + 1 * np.sin(a),
                     4 * np.cos(b) + 2 * np.sin(b)], rtol=1e-6)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """ONE layer, its 16 experts whole in the reference; the program's
    share j holds experts 2j, 2j + 1. The eight shares' routed parts,
    the shared term ONCE and the attention ONCE add up to the uncut
    layer's x + a + f."""
    rng = np.random.default_rng(7)
    n, d, f, e = 24, HIDDEN, FF, ROUTER

    def w(*shape):
        return jnp.asarray(rng.standard_normal(shape) * shape[-2] ** -0.5,
                           jnp.float32)

    p = {"gate": w(d, e), "wg": w(e, d, f), "wu": w(e, d, f),
         "wd": w(e, f, d), "sg": w(d, SHARED * f), "su": w(d, SHARED * f),
         "sd": w(SHARED * f, d)}
    attn = {"wq": w(d, HEADS, HEAD_DIM), "wk": w(d, KV_HEADS, HEAD_DIM),
            "wv": w(d, KV_HEADS, HEAD_DIM), "wo": w(HEADS, HEAD_DIM, d) / 4}
    x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        h = R._ln(x, jnp.ones((d,)), 1e-5)
        a = R._attention(attn, h, WINDOW, 50000.0)
        uncut = x + a + R._experts(p, h, PER_TOKEN, (0, e), SHARED)
        _, vals, ids = moe.route_top_k(h, p["gate"], PER_TOKEN, True,
                                       "sigmoid")
        total, held_slots = x + a, 0
        for j in range(8):
            first = 2 * j
            rows, order, counts = moe.dropless_dispatch(
                h, ids, e, held=(first, 2))
            ys = moe.grouped_ffn(
                rows, counts, p["wg"][first:first + 2],
                p["wu"][first:first + 2], p["wd"][first:first + 2], "silu",
                use_pallas=False)
            total = total + moe.dropless_combine(ys, order, vals)
            held_slots += int(counts.sum())
        total = total + moe.shared_ffn(h, p["sg"], p["su"], p["sd"], "silu",
                                       SHARED)
    assert held_slots == n * PER_TOKEN      # every slot held exactly once
    np.testing.assert_allclose(total, uncut, atol=F32_TOL, rtol=0)


def test_a_share_s_dispatch_counts_the_held_experts_alone():
    tokens = jnp.arange(12, dtype=jnp.float32).reshape(6, 2)
    assign = jnp.asarray([[0, 5], [5, 6], [7, 1], [4, 5], [6, 6], [2, 3]])
    live = jnp.asarray([True, True, True, True, False, True])
    rows, order, counts = moe.dropless_dispatch(tokens, assign, 8, live,
                                                held=(4, 3))
    assert counts.tolist() == [1, 3, 1]     # experts 4, 5, 6; lane 4 dead
    # held slots first, by expert: slots 6 (4), 1, 2, 7 (5), 3 (6)
    assert order[:5].tolist() == [6, 1, 2, 7, 3]
    np.testing.assert_array_equal(rows[:5, 0], [6, 0, 2, 6, 2])
    # no share, no change: the program OLMoE had
    old = jax.make_jaxpr(lambda t, a, m: _old_dispatch(t, a, 8, m))(
        tokens, assign, live)
    new = jax.make_jaxpr(lambda t, a, m: moe.dropless_dispatch(t, a, 8, m))(
        tokens, assign, live)
    assert str(old) == str(new)


def _old_dispatch(tokens, assign, n_experts, live):
    """ops/moe.py::dropless_dispatch as PR 26 wrote it."""
    k = assign.shape[1]
    flat = assign.reshape(-1)
    flat = jnp.where(jnp.repeat(live, k), flat, n_experts)
    order = jnp.argsort(flat)
    counts = jnp.zeros((n_experts,), jnp.int32).at[flat].add(1, mode="drop")
    return jnp.take(tokens, order // k, axis=0), order, counts


# ------------------------------------------ (d) what the model refuses
def test_describe_reads_the_fourth_shape():
    arch = describe(_lm(max_seq_len=16))
    assert isinstance(arch, CommandAPlus)
    assert "".join(k[0] for k in arch.kinds) == "wwwf"
    assert arch.parallel_block and not arch.differential
    assert (arch.kv_heads, arch.kv_head_dim, arch.paged_layers) == \
        (KV_HEADS, HEAD_DIM, 1)
    assert (arch.experts, arch.experts_held) == (ROUTER, HELD)
    assert [t > 0 for t, _ in arch.rope] == [True, True, True, False]
    assert all(inter for t, inter in arch.rope if t > 0)


@pytest.mark.parametrize("kwargs,cfg,message", [
    ({"tensor_parallel": 2}, {}, "refuses tp"),
    ({}, {"adapter_rank": 4}, "refuses adapters"),
    ({}, {"serve_spec_decode": True}, "refuses speculation.*ring"),
    ({}, {"serve_prefix_cache": True}, "refuses prefix_cache.*window"),
    ({"prefix_cache": True}, {"host_tier_mb": 8.0},
     "refuses prefix_cache"),
])
def test_what_command_a_plus_is_not_served_on_raises_by_name(kwargs, cfg,
                                                             message):
    with pytest.raises(NotImplementedError, match=message):
        ServeEngine(_lm(max_seq_len=32, **cfg), interpret=True, **kwargs)


def test_the_handoff_and_the_host_tier_are_refused_by_name(engine):
    with pytest.raises(NotImplementedError, match="refuses handoff"):
        engine.export_kv(0, [1, 2, 3])
    with pytest.raises(NotImplementedError, match="refuses host_tier"):
        engine.arch.refuse(host_tier=True)


def test_a_grouped_or_windowed_attention_op_refuses_what_it_lacks():
    from flexflow_tpu.model import FFModel
    ff = FFModel(FFConfig(batch_size=1))
    x = ff.create_tensor((1, 8, 32), name="x")
    with pytest.raises(ValueError, match="do not divide"):
        ff.multihead_attention(x, x, x, 32, 4, causal=True, num_kv_heads=3)
    with pytest.raises(ValueError, match="plain causal"):
        ff.multihead_attention(x, x, x, 32, 4, causal=False, window=4)
    with pytest.raises(ValueError, match="dropless layer's"):
        ff.moe_ffn(x, 4, 2, 16, score="sigmoid")


# ----------------- (e) the paged kernel at 16 query heads a key/value head
@pytest.mark.parametrize("window", [0, 24])
def test_kernel_equals_its_jnp_twin_at_group_16(window):
    """The served heads — 128 query heads over 8 key/value heads of 128
    — with the full layer's list and under a (scaled-down) window; a
    decode lane takes the one-lane body on its 16 rows."""
    hq, h, d = 128, 8, 128
    assert has_short_body(hq // h)
    rng = np.random.default_rng(160 + window)
    ps, npg, slots_n, pp, t = 8, 40, 3, 12, 40
    kp = jnp.asarray(rng.standard_normal((npg, ps, h, d)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((npg, ps, h, d)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((t, hq, d)), jnp.float32)
    pt = np.stack([rng.permutation(np.arange(1, npg))[:pp]
                   for _ in range(slots_n)]).astype(np.int32)
    slots, lens = np.zeros(t, np.int32), np.ones(t, np.int32)
    slots[:20], lens[:20] = 1, np.arange(41, 61)     # a chunk
    slots[20], lens[20] = 2, 90                      # decode lanes
    slots[21], lens[21] = 0, 5
    args = (q, kp, vp, jnp.asarray(pt), jnp.asarray(slots),
            jnp.asarray(lens))
    twin = paged_attention_ragged_v2(*args, use_pallas=False, window=window)
    got = paged_attention_ragged_v2(*args, use_pallas=True, interpret=True,
                                    window=window)
    np.testing.assert_allclose(got[:22], twin[:22], atol=2e-5, rtol=0)
