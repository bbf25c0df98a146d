"""OLMoE through the op graph and the serve engine, against the plain
reference (benchmark/lib/reference_olmoe.py), at the rehearsal size:
hidden 64, 4 heads of 16, 8 experts of width 32, 2 a token, 2 layers
(ISSUE 26).

Tolerances, and why: in f32 the program and the reference differ only
in the order of sums (a fused qkv matmul, the sorted grouped matmul
against the dense mask, the paged kernel's blocks against one
softmax), measured here at 2e-6 on logits of deviation 0.85; 2e-4
leaves two orders of room and is a thousandth of what a wrong
position, page, expert or weight would give (a lane one position off
differs by about one deviation). In bf16 (the served precision) the
measured worst is 0.09 at deviation 0.47; the limit 0.3 there is what
the chip check's configuration states at its size, and the f32 cases
carry the proof of the mathematics.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from lib import moe_counts, olmoe_cell  # noqa: E402
from lib import reference_olmoe as R  # noqa: E402

from flexflow_tpu.config import CompMode, FFConfig  # noqa: E402
from flexflow_tpu.models.olmoe import build_olmoe_lm  # noqa: E402
from flexflow_tpu.ops.moe import (dropless_combine,  # noqa: E402
                                  dropless_dispatch, grouped_ffn,
                                  route_top_k)
from flexflow_tpu.serve import ServeEngine  # noqa: E402

VOCAB, HIDDEN, HEADS, LAYERS, EXPERTS, TOPK, WIDTH = 128, 64, 4, 2, 8, 2, 32
CONF = {"vocab_size": VOCAB, "hidden_size": HIDDEN,
        "num_attention_heads": HEADS, "num_hidden_layers": LAYERS,
        "num_experts": EXPERTS, "num_experts_per_tok": TOPK,
        "intermediate_size": WIDTH, "max_position_embeddings": 256,
        "rope_theta": 10000, "rms_norm_eps": 1e-5,
        "norm_topk_prob": False}
F32_TOL = 2e-4


def _lm(max_seq_len=256, **cfg):
    cfg = FFConfig(batch_size=1, seed=5, kv_page_size=16, kv_num_pages=65,
                   serve_max_seqs=4, serve_prefill_budget=32,
                   serve_spec_decode=False, **cfg)
    lm = build_olmoe_lm(cfg, vocab_size=VOCAB, max_seq_len=max_seq_len,
                        hidden=HIDDEN, num_heads=HEADS, num_layers=LAYERS,
                        num_experts=EXPERTS, experts_per_token=TOPK,
                        expert_dim=WIDTH)
    lm.compile(comp_mode=CompMode.INFERENCE)
    return lm


@pytest.fixture(scope="module")
def engine():
    eng = ServeEngine(_lm(), interpret=True)
    eng.warmup()
    return eng


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(1, VOCAB, n).tolist()


# ------------------------------------------------------ the op graph
def _graph_logits(lm, params, tokens):
    s = tokens.shape[1]
    values, _ = lm.executor.forward_values(
        params, {}, {"tokens": tokens,
                     "positions": jnp.arange(s, dtype=jnp.int32)[None]},
        training=False, rng=None)
    return values[lm.ops[-1].outputs[0].uid][0]


def test_graph_forward_equals_the_reference():
    lm = _lm(max_seq_len=24)
    toks = jnp.asarray([_tokens(24, 1)], jnp.int32)
    got = _graph_logits(lm, lm.state.params, toks)
    ref = R.logits_at(lm.state.params, toks, jnp.arange(24), LAYERS,
                      experts_per_token=TOPK)
    assert float(ref.std()) > 0.3
    np.testing.assert_allclose(got, ref, atol=F32_TOL, rtol=0)


def test_graph_gradient_equals_the_reference_s():
    """jax.grad through rms_norm, rotary attention with QK-norm and the
    dropless gated MoEFFN (sort, ragged_dot, unsort) against jax.grad
    of the reference's dense-mask expert loop."""
    lm = _lm(max_seq_len=24)
    toks = jnp.asarray([_tokens(24, 2)], jnp.int32)
    w = jnp.asarray(np.random.default_rng(3).standard_normal((24, VOCAB)),
                    jnp.float32)
    g_sys = jax.grad(lambda p: jnp.sum(_graph_logits(lm, p, toks) * w))(
        lm.state.params)
    g_ref = jax.grad(lambda p: jnp.sum(R.logits_at(
        p, toks, jnp.arange(24), LAYERS, experts_per_token=TOPK) * w))(
        lm.state.params)
    for op, ws in g_ref.items():
        for name, ref in ws.items():
            scale = float(jnp.max(jnp.abs(ref))) + 1e-6
            np.testing.assert_allclose(
                g_sys[op][name] / scale, ref / scale, atol=1e-4, rtol=0,
                err_msg=f"{op}/{name}")
    assert float(jnp.max(jnp.abs(g_ref["layer0_moe"]["wg"]))) > 0


def test_top_k_weights_are_not_renormalised():
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.standard_normal((10, HIDDEN)), jnp.float32)
    gate = jnp.asarray(rng.standard_normal((HIDDEN, EXPERTS)),
                       jnp.float32) * 0.05
    probs, vals, idx = route_top_k(h, gate, TOPK, norm_topk=False)
    assert float(jnp.max(jnp.sum(vals, -1))) < 1.0
    np.testing.assert_allclose(
        vals, jnp.take_along_axis(probs, idx, axis=1), rtol=0, atol=0)
    _, normed, _ = route_top_k(h, gate, TOPK, norm_topk=True)
    np.testing.assert_allclose(jnp.sum(normed, -1), 1.0, atol=1e-6)
    _, ref_vals, ref_idx = R.router({"gate": gate}, h, TOPK)
    np.testing.assert_array_equal(idx, ref_idx)
    np.testing.assert_allclose(vals, ref_vals, atol=1e-6)


def test_lanes_that_are_not_live_change_no_expert_s_count():
    rng = np.random.default_rng(1)
    n = 12
    h = jnp.asarray(rng.standard_normal((n, HIDDEN)), jnp.float32)
    assign = jnp.asarray(rng.integers(0, EXPERTS, (n, TOPK)), jnp.int32)
    live = jnp.asarray(np.arange(n) % 3 != 0)
    _, _, all_counts = dropless_dispatch(h, assign, EXPERTS)
    rows, order, counts = dropless_dispatch(h, assign, EXPERTS, live)
    assert int(all_counts.sum()) == n * TOPK
    assert int(counts.sum()) == int(live.sum()) * TOPK
    expect = np.bincount(np.asarray(assign)[np.asarray(live)].ravel(),
                         minlength=EXPERTS)
    np.testing.assert_array_equal(counts, expect)
    # a dead lane's garbage reaches no expert and no live lane's output
    w = [jnp.asarray(rng.standard_normal(s), jnp.float32) * 0.1
         for s in ((EXPERTS, HIDDEN, WIDTH), (EXPERTS, HIDDEN, WIDTH),
                   (EXPERTS, WIDTH, HIDDEN))]
    gate_vals = jnp.full((n, TOPK), 0.25, jnp.float32)
    out = dropless_combine(grouped_ffn(rows, counts, *w, "silu"), order,
                           gate_vals)
    poisoned = jnp.where(live[:, None], h, 1e30)
    rows2, order2, counts2 = dropless_dispatch(poisoned, assign, EXPERTS,
                                               live)
    out2 = dropless_combine(grouped_ffn(rows2, counts2, *w, "silu"),
                            order2, gate_vals)
    np.testing.assert_array_equal(out[np.asarray(live)],
                                  out2[np.asarray(live)])
    np.testing.assert_array_equal(out2[~np.asarray(live)], 0.0)


def test_moe_ffn_flops_price_k_of_the_experts_not_a_capacity():
    lm = _lm(max_seq_len=24)
    op = next(o for o in lm.ops if o.name == "layer0_moe")
    n = 24
    gate = 2.0 * n * HIDDEN * EXPERTS
    assert op.flops() == gate + n * TOPK * 2.0 * 3 * HIDDEN * WIDTH
    assert op.capacity == n * TOPK // EXPERTS      # the mean load
    # the capacity layer keeps its price: two matmuls over E x capacity
    from flexflow_tpu.models.moe import build_moe_fused
    old = next(o for o in build_moe_fused(FFConfig(batch_size=8)).ops
               if o.op_type == "moe_ffn")
    per_row = 2.0 * (old.in_dim * old.hidden_dim
                     + old.hidden_dim * old.out_dim)
    assert old.flops() == (
        2.0 * old.n_tokens * old.in_dim * old.num_experts
        + old.num_experts * old.capacity * per_row
        + 2.0 * old.n_tokens * old.k * old.num_experts * old.capacity)
    # resident bytes: every expert's three matrices, whatever is active
    assert op.weight_bytes() == 4 * (
        HIDDEN * EXPERTS + 3 * EXPERTS * HIDDEN * WIDTH)


# ------------------------------------------- the engine, through the cache
PROMPTS = {
    # one chunk; several chunks (budget 32); a second prompt that finds
    # the first one's 32-token prefix in the cache
    "one_chunk": [_tokens(20, 10)],
    "several_chunks": [_tokens(75, 11)],
    "prefix_hit": [_tokens(32, 12) + _tokens(9, 13),
                   _tokens(32, 12) + _tokens(14, 14)],
}


@pytest.mark.parametrize("case", sorted(PROMPTS))
def test_prefill_then_decode_through_the_cache_equals_the_reference(
        engine, case):
    rows, stats = olmoe_cell.logits_through_cache(
        engine, CONF, PROMPTS[case], 12)
    last = rows[-1]
    assert last["new"] == 12 and last["logit_std"] > 0.3
    if case == "several_chunks":
        assert last["prefill_chunks"] >= 3
    if case == "prefix_hit":
        assert last["hit_tokens"] == 32 and rows[0]["hit_tokens"] == 0
    else:
        assert last["hit_tokens"] == 0
    for r in rows:
        assert r["logit_abs_err"] <= F32_TOL, r
        assert r["worst_gap"] <= F32_TOL, r
    assert stats["experts"]["dropped"] == 0


def test_bf16_engine_stays_inside_the_served_tolerance():
    eng = ServeEngine(_lm(compute_dtype="bfloat16", param_dtype="bfloat16",
                          kv_dtype="bfloat16"), interpret=True)
    assert eng.params["layer0_moe"]["wg"].dtype == jnp.bfloat16
    rows, _ = olmoe_cell.logits_through_cache(
        eng, CONF, [_tokens(40, 15)], 8)
    assert rows[0]["logit_abs_err"] <= 0.3, rows
    eng.close()


def test_every_token_on_the_same_two_experts_is_still_exact():
    """A router of zeros gives every expert the same probability, and
    top-k then takes experts 0 and 1 for EVERY token: 8 times a
    capacity layer's mean load on each. Dropless: the logits still
    equal the reference's, and the engine's counts say where they
    went."""
    lm = _lm()
    params = lm.state.params
    for i in range(LAYERS):
        params[f"layer{i}_moe"]["gate"] = jnp.zeros_like(
            params[f"layer{i}_moe"]["gate"])
    eng = ServeEngine(lm, interpret=True)
    rows, stats = olmoe_cell.logits_through_cache(
        eng, CONF, [_tokens(50, 16)], 6)
    assert rows[0]["logit_abs_err"] <= F32_TOL, rows
    counts = stats["experts"]["counts"]
    lanes = 50 + 5                     # the prompt, then 5 decode lanes
    np.testing.assert_array_equal(counts[:, :2], lanes)
    np.testing.assert_array_equal(counts[:, 2:], 0)
    assert stats["experts"]["slots"] == lanes * TOPK * LAYERS
    assert stats["experts"]["dropped"] == 0
    eng.close()


def test_step_events_count_the_live_lanes_experts(engine):
    session = engine.start_session()
    session.submit(_tokens(21, 17), 3)
    session.submit(_tokens(5, 18), 2)
    seen = []
    while session.has_work():
        ev = session.step()
        if ev is not None and ev.dispatched:
            seen.append(ev)
    session.close()
    first = seen[0]
    assert first.expert_slots == (21 + 5) * TOPK * LAYERS
    assert first.expert_counts.shape == (LAYERS, EXPERTS)
    assert int(first.expert_counts.sum()) == first.expert_slots
    assert first.expert_dropped == 0
    assert first.experts_touched == int((first.expert_counts > 0).sum())
    assert first.expert_bytes == first.experts_touched \
        * 3 * HIDDEN * WIDTH * 4
    assert first.expert_load_max == int(first.expert_counts.max())
    # a decode-only step: two live lanes of 72, and the 70 others
    # route nowhere
    assert seen[1].expert_slots == 2 * TOPK * LAYERS
    # the logits a reference check reads: the fetched arrays (a row
    # for each lane the head ran over: the emitters', lanes 20 and 25
    # of the first step, then padding), and the row each emitted token
    # came from
    for ev in (first, seen[1]):
        assert len(ev.emit_lanes) == len(ev.emitted) == 2
        assert ev.topv.shape == ev.topi.shape \
            == (engine.head_rows, engine.topk_cap)
        assert ev.lanes == engine.head_rows == engine.cache_cfg.max_seqs
    assert first.emit_lanes == [0, 1] and seen[1].emit_lanes == [0, 1]


def test_mixed_step_lowers_with_the_expert_scopes(engine):
    c = engine.cache_cfg
    z = np.zeros((engine.mixed_width,), np.int32)
    pts = np.zeros((c.max_seqs, c.pages_per_seq), np.int32)
    text = jax.jit(engine._mixed_impl).lower(
        engine._step_params, engine._device_pool(), z, z, z, z, pts, z,
        z + 1, z[:engine.head_rows], z - 1, z[:engine.head_rows]
    ).as_text(debug_info=True)
    for path in ("serve_step/embed/", "serve_step/layer0/ln/",
                 "serve_step/layer0/qkv/", "serve_step/layer1/kv_write/",
                 "serve_step/layer1/attn/", "serve_step/layer0/attn_out/",
                 "serve_step/layer0/router/",
                 "serve_step/layer1/moe_dispatch/",
                 "serve_step/layer1/experts/",
                 "serve_step/layer0/moe_combine/", "serve_step/head/",
                 "serve_step/sample/"):
        assert path in text, path
    assert "/ffn/" not in text
    # an f32 engine takes the kernel's twin, three grouped matmuls
    # (tests/test_grouped_ffn_kernel.py lowers the step on the kernel)
    assert engine.expert_impl == "ragged_dot"
    assert "serve_step/layer0/experts/ragged_dot" in text


# ------------------------------------------------ what OLMoE refuses
@pytest.mark.parametrize("kwargs,cfg,message", [
    ({"tensor_parallel": 2}, {}, "tensor-parallel"),
    ({}, {"adapter_rank": 4}, "adapter"),
    ({}, {"serve_mesh": "auto"}, "serve_mesh='auto'"),
])
def test_what_olmoe_is_not_served_on_raises_by_name(kwargs, cfg, message):
    with pytest.raises(NotImplementedError, match=message):
        ServeEngine(_lm(**cfg), interpret=True, **kwargs)


def test_a_model_of_neither_shape_is_refused():
    from flexflow_tpu.models.moe import build_moe_fused
    with pytest.raises(ValueError, match="neither.*tok_embed"):
        ServeEngine(build_moe_fused(FFConfig(batch_size=4)))


# --------------------- the paged kernel at OLMoE's head shape: 16 x 128
def test_paged_kernel_at_16_heads_of_128_equals_the_jnp_twin():
    from flexflow_tpu.kernels.paged_ragged_v2 import \
        paged_attention_ragged_v2
    from flexflow_tpu.kernels.paged_ragged_v2 import (Q_ROWS,
                                                      build_work_list,
                                                      max_work_items)
    rng = np.random.RandomState(7)
    ps, pp, seqs, h, d = 16, 4, 3, 16, 128
    pt = np.zeros((seqs, pp), np.int32)
    pt[:] = 1 + rng.permutation(seqs * pp).reshape(seqs, pp)
    kp = rng.randn(1 + seqs * pp, ps, h, d).astype(np.float32)
    vp = rng.randn(1 + seqs * pp, ps, h, d).astype(np.float32)
    kp[0] = vp[0] = 0.0
    # a chunk of 20 lanes of sequence 0 at positions 30..49, decode
    # lanes of sequences 1 and 2, then inactive lanes on slot 0
    slots = np.array([0] * 20 + [1, 2] + [0] * 10, np.int32)
    lens = np.array(list(range(31, 51)) + [57, 9] + [1] * 10, np.int32)
    q = jnp.asarray(rng.randn(len(slots), h, d).astype(np.float32))
    args = (q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(pt),
            jnp.asarray(slots), jnp.asarray(lens))
    ref = paged_attention_ragged_v2(*args, use_pallas=False)
    work = build_work_list(
        args[3], args[4], args[5], page_size=ps, block_pages=2,
        max_items=max_work_items(len(slots), pp, 2, Q_ROWS, 3))
    out = paged_attention_ragged_v2(*args, interpret=True, work=work)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(out, ref, rtol=2e-6, atol=2e-6)


# --------------------------------------------------- the benchmark's part
def test_moe_counts_on_hand_made_counts():
    counts = np.array([[3, 0, 5, 0], [0, 0, 0, 8]])
    w = moe_counts.step_work(counts, hidden=10, width=7, itemsize=2)
    assert w["slots"] == 16 and w["touched"] == 3
    assert w["flops"] == 2.0 * 16 * 3 * 10 * 7
    assert w["weight_bytes"] == 3 * 3 * 10 * 7 * 2
    idle = moe_counts.step_work(np.zeros((2, 4), int), 10, 7, 2)
    assert idle["touched"] == 0 and idle["flops"] == 0.0


def test_logit_errors_on_hand_made_steps():
    """What `logit_rms` limits: the engine's top-k logits minus the
    reference's logits of the same tokens, over the emitting lanes."""
    class Req:
        def __init__(self, rid):
            self.rid = rid

    class Ev:
        emitted = [(Req(7), 1), (Req(9), 2)]    # the second speculated
        emit_lanes = [4, 1]
        topv = np.arange(12, dtype=np.float32).reshape(6, 2)
        topi = np.array([[0, 1], [2, 0], [1, 2], [0, 0], [2, 1], [1, 1]])
    rids, v, i = olmoe_cell.emitted_logits(Ev)
    assert rids == [7, 9, 9]
    np.testing.assert_array_equal(v, Ev.topv[[4, 1, 2]])
    np.testing.assert_array_equal(i, Ev.topi[[4, 1, 2]])
    ref = np.array([[8.0, 9.0, 9.5], [2.0, 0.0, 2.0], [0.0, 4.0, 5.5]])
    err = olmoe_cell.logit_errors(v, i, ref)
    np.testing.assert_array_equal(err, [[-1.5, 0.0], [0.0, 1.0],
                                        [0.0, -0.5]])
    assert olmoe_cell.rms([err, err]) == pytest.approx(
        np.sqrt((1.5 ** 2 + 1.0 + 0.25) / 6))


def test_named_op_reader_on_hand_made_intervals(monkeypatch):
    """`expert_hbm_share.olmoe`: counted bytes over the device seconds
    of operations found by NAME, inside whole steps only."""
    from lib import program_trace as P
    from readers import named_op_hbm_share as reader
    gmm = "%ragged-dot-none.3 = f32[8,4] custom-call(...)"
    trace = {
        "bench": [P.Span("window", 0.0, 10.0, {})],
        "phases": [P.Span("serve_step", 1.0, 5.0, {}),
                   P.Span("emit", 4.5, 4.9, {"expert_bytes": 819e9}),
                   # a step cut by the window's end: not counted
                   P.Span("serve_step", 8.0, 11.0, {}),
                   P.Span("emit", 10.5, 10.9, {"expert_bytes": 5e12})],
        "devices": {0: [P.Op(gmm, 1.5, 2.5, "", 0.0, 0.0),
                        P.Op("%fusion.1 = ...", 2.5, 3.0, "", 0.0, 0.0),
                        P.Op(gmm, 3.0, 4.0, "", 0.0, 0.0),
                        P.Op(gmm, 8.5, 9.5, "", 0.0, 0.0)]},
    }
    monkeypatch.setattr(P, "of_run", lambda run: trace)
    run = {"device_kind": "TPU v5 lite"}
    args = dict(root="serve_step", span="emit", arg="expert_bytes")
    # 819e9 bytes over 2 s of grouped matmul = half of 819 GB/s
    assert reader.read(run, ops="ragged-dot-none", scale=100.0,
                       **args) == pytest.approx(50.0)
    assert reader.read(run, ops="no-such-kernel", **args) is None
    assert reader.read(run, ops="ragged", root="serve_step", span="emit",
                       arg="kv_bytes") is None
    monkeypatch.setattr(P, "of_run", lambda run: None)
    assert reader.read(run, ops="ragged-dot-none", **args) is None


def test_the_configuration_holds_the_catalog_row_s_widths():
    conf = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "olmoe-1b-7b-1chip.json")))
    published = {"hidden_size": 2048, "num_attention_heads": 16,
                 "num_key_value_heads": 16, "intermediate_size": 1024,
                 "num_experts": 64, "num_experts_per_tok": 8,
                 "vocab_size": 50304, "max_position_embeddings": 4096,
                 "rope_theta": 10000, "rms_norm_eps": 1e-5,
                 "norm_topk_prob": False, "hidden_act": "silu"}
    assert {k: conf[k] for k in published} == published
    assert conf["reduced"] == ["num_hidden_layers"] or conf["reduced"] == [
        "num_hidden_layers", "kv_num_pages"]
    assert conf["system"]["param_dtype"] == "bfloat16"


def test_the_cell_s_cpu_rehearsal_ends_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "olmoe-chat", "--seed", "3200000077", "--seconds",
         "3", "--trace", "0", "--rehearse-cpu"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 10 and last["metrics"] == {}
    numbers = json.loads(next(
        ln for ln in out.stdout.splitlines()
        if ln.startswith("# numbers: "))[len("# numbers: "):])
    assert numbers["expert_dropped"] == 0 and numbers["expert_slots"] > 0
    assert numbers["expert_load_max_over_mean"] >= 1.0
    assert numbers["compiles_in_window"] == 0
