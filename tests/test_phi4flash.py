"""Phi-4-mini-flash-reasoning through the serve engine (PR 32): the op
graph, the engine through pages, rings and state slots, the grouped
identity of differential attention, the paged kernel at grouped heads
under a window, what the description refuses, and the benchmark cell's
CPU rehearsal — all against benchmark/lib/reference_phi4flash.py, at a
small size with seeded random weights.
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

from lib import phi4flash_cell, ssm_counts  # noqa: E402
from lib import reference_phi4flash as R  # noqa: E402

from flexflow_tpu.config import CompMode, FFConfig  # noqa: E402
from flexflow_tpu.kernels.paged_ragged_v2 import (  # noqa: E402
    paged_attention_ragged_v2, work_items)
from flexflow_tpu.models.phi4flash import (build_phi4flash_lm,  # noqa: E402
                                           mixer_kinds)
from flexflow_tpu.ops import diff_attention as DA  # noqa: E402
from flexflow_tpu.serve import ServeEngine  # noqa: E402
from flexflow_tpu.serve.arch import Phi4Flash, describe  # noqa: E402

VOCAB, HIDDEN, HEADS, KV_HEADS, LAYERS, FF, WINDOW = 128, 64, 8, 4, 8, 96, 16
PAGE, BUDGET, SEQS = 8, 24, 4
CONF = {"vocab_size": VOCAB, "hidden_size": HIDDEN,
        "num_attention_heads": HEADS, "num_key_value_heads": KV_HEADS,
        "num_hidden_layers": LAYERS, "intermediate_size": FF,
        "sliding_window": WINDOW, "max_position_embeddings": 128,
        "layer_norm_eps": 1e-5}
F32_TOL = 1e-4


def _lm(max_seq_len=128, **cfg):
    base = dict(batch_size=1, seed=5, kv_page_size=PAGE, kv_num_pages=65,
                serve_max_seqs=SEQS, serve_prefill_budget=BUDGET,
                serve_spec_decode=False, serve_prefix_cache=False)
    base.update(cfg)
    lm = build_phi4flash_lm(
        FFConfig(**base), vocab_size=VOCAB, max_seq_len=max_seq_len,
        hidden=HIDDEN, num_heads=HEADS, num_kv_heads=KV_HEADS,
        num_layers=LAYERS, ff_dim=FF, window=WINDOW)
    lm.compile(comp_mode=CompMode.INFERENCE)
    return lm


@pytest.fixture(scope="module")
def engine():
    eng = ServeEngine(_lm(), interpret=True)
    eng.warmup()
    return eng


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(1, VOCAB, n).tolist()


# ------------------------------------------------------ (a) the op graph
def test_layer_pattern_is_the_published_one():
    kinds = mixer_kinds(32)
    assert "".join(k[0] for k in kinds) == "sw" * 8 + "sf" + "gc" * 7
    assert [kinds.count(k) for k in ("ssm", "window", "full", "gmu",
                                     "cross")] == [9, 8, 1, 7, 7]
    with pytest.raises(ValueError, match="multiple of 4"):
        mixer_kinds(6)


def test_graph_forward_equals_the_reference():
    lm = _lm(max_seq_len=48)
    toks = jnp.asarray([_tokens(48, 1)], jnp.int32)
    values, _ = lm.executor.forward_values(
        lm.state.params, {}, {"tokens": toks}, training=False, rng=None)
    got = values[lm.ops[-1].outputs[0].uid][0]
    ref = R.logits_at(lm.state.params, toks, jnp.arange(48), LAYERS, WINDOW)
    assert float(ref.std()) > 0.5
    np.testing.assert_allclose(got, ref, atol=F32_TOL, rtol=0)


def test_the_head_is_tied_and_the_cross_layers_have_no_kv_weights():
    lm = _lm(max_seq_len=16)
    p = lm.state.params
    assert "lm_head" not in p
    assert {"wk", "wv"} <= set(p["layer5_attn"])          # the full layer
    assert not {"wk", "wv"} & set(p["layer7_attn"])       # a cross layer
    assert p["layer7_attn"]["wo"].shape == (HEADS // 2, 2 * HIDDEN // HEADS,
                                            HIDDEN)


# ------------------------------- (b) the engine through the three caches
def _serve(eng, groups, max_new, on_step=None):
    return phi4flash_cell.logits_through_cache(eng, CONF, groups, max_new,
                                               on_step=on_step)


CASES = {
    "one prompt whole": ([[_tokens(20, 11)]], 6),
    "the same prompt in 3 chunks": ([[_tokens(60, 12)]], 6),
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
    if "chunks" in case:
        assert max(r["prefill_chunks"] for r in rows) >= 2
    if "3 chunks" in case:
        assert rows[0]["prefill_chunks"] == 3
    assert stats["nonfinite_logit_steps"] == 0
    assert engine.compile_counts()["mixed"] == 1
    engine.cache.check_invariants(engine.pool)


def test_a_preempted_request_restarts_from_zero_state(engine):
    """Preemption drops the pages and (num_computed = 0) recomputes the
    whole context: the re-admitted request reads neither its old state
    nor its old window keys, and its logits stay the reference's."""
    preempted = []

    def preempt_once(session, ev):
        running = [r for r in session.sched.running.values()
                   if r.out_tokens]
        if not preempted and running and len(running[0].out_tokens) >= 3:
            session.sched._preempt(running[0])
            preempted.append(running[0].rid)

    rows, stats = _serve(engine, [[_tokens(30, 21), _tokens(12, 22)]], 8,
                         on_step=preempt_once)
    assert preempted and stats["preemptions"] >= 1
    for r in rows:
        assert r["logit_abs_err"] < F32_TOL, r
    engine.cache.check_invariants(engine.pool)


# --------------------------------------------- (c) the grouped identity
def test_one_grouped_call_equals_the_four_products():
    """40 query / 20 key-value heads read as 40 query heads over 10
    key/value heads of 128, group 4, zero-padded queries — against the
    published form's four products A11, A12, A21, A22."""
    rng = np.random.default_rng(0)
    s, h, hk, d = 12, 8, 4, 16
    q = jnp.asarray(rng.standard_normal((s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((s, hk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((s, hk, d)), jnp.float32)
    q2, k2, v2 = DA.grouped_qkv(q, k, v)
    assert q2.shape == (s, h, 2 * d) and k2.shape == (s, hk // 2, 2 * d)
    group = h // (hk // 2)
    causal = np.tril(np.ones((s, s), bool))
    sc = jnp.einsum("qmgd,kmd->mgqk", q2.reshape(s, hk // 2, group, 2 * d),
                    k2) / np.sqrt(d)
    pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    o = jnp.einsum("mgqk,kmd->qmgd", pr, v2).reshape(s, h, 2 * d)
    a1, a2 = DA.split_grouped(o)

    def probs(qj, kj):
        return jax.nn.softmax(jnp.where(
            causal, (qj @ kj.T) / np.sqrt(d), -jnp.inf), axis=-1)

    g = h // hk
    for j in range(h // 2):
        m = j // g
        p1 = probs(q[:, 2 * j], k[:, 2 * m])
        p2 = probs(q[:, 2 * j + 1], k[:, 2 * m + 1])
        v1, v2_ = v[:, 2 * m], v[:, 2 * m + 1]
        np.testing.assert_allclose(
            a1[:, j], jnp.concatenate([p1 @ v1, p1 @ v2_], -1), atol=1e-5)
        np.testing.assert_allclose(
            a2[:, j], jnp.concatenate([p2 @ v1, p2 @ v2_], -1), atol=1e-5)


# ------------------------------- (d) what a slot holds, and holds no more
def test_cache_bytes_a_token_and_a_sequence(engine):
    c = engine.cache_cfg
    d = HIDDEN // HEADS
    assert c.cache_bytes_per_token == ssm_counts.kv_bytes_per_token(
        KV_HEADS // 2, 2 * d, 4) == 2 * KV_HEADS * d * 4
    ring = ssm_counts.ring_bytes_per_seq(
        2, WINDOW, BUDGET, PAGE, KV_HEADS // 2, 2 * d, 4)
    state = ssm_counts.state_bytes_per_seq(3, 2 * HIDDEN, 16, 4, 4)
    assert c.constant_bytes_per_seq == ring + state
    assert c.ring_pages * PAGE <= WINDOW + BUDGET + PAGE
    leaves = sum(a.size * a.dtype.itemsize
                 for a in jax.tree.leaves(engine._device_pool()))
    assert leaves == c.pool_bytes
    # at the published widths: 5,120 B a token, and no more than the
    # issue's constant part a sequence
    assert ssm_counts.kv_bytes_per_token(10, 128) == 5120
    assert ssm_counts.ring_bytes_per_seq(8, 512, 512, 16, 10, 128) \
        == 8 * (512 + 512 + 16) * 5120
    assert ssm_counts.state_bytes_per_seq(9, 5120, 16, 4) == 9 * 358400


def test_no_slot_holds_window_keys_behind_the_ring_and_invariants_hold(
        engine):
    c = engine.cache_cfg
    steps = []

    def each_step(session, ev):
        engine.cache.check_invariants(engine.pool)
        if ev.dispatched:
            steps.append((ev.state_bytes, ev.ssm_runs, ev.window_kv_bytes,
                          ev.full_kv_bytes, len(ev.plan.chunks)))
            # the window layers' list fetches no page wholly behind any
            # lane's window: at most the ring's pages a run
            ring = work_items(
                np.asarray([ch.end for ch in ev.plan.chunks]),
                np.asarray([ch.req.slot for ch in ev.plan.chunks]),
                engine.geometry.rings, page_size=PAGE,
                block_kv_pages=engine.attn_block_pages, window=WINDOW)
            assert ring["page_fetches"] <= len(ev.plan.chunks) * (
                c.ring_pages + engine.attn_block_pages)

    rows, stats = _serve(engine, [[_tokens(70, 31), _tokens(45, 32)]], 30,
                         on_step=each_step)
    assert all(r["logit_abs_err"] < F32_TOL for r in rows)
    assert stats["cache_bytes_per_token"] == c.cache_bytes_per_token
    assert stats["cache_bytes_constant_per_seq"] == c.constant_bytes_per_seq
    for state_bytes, runs, win, full, chunks in steps:
        assert runs == chunks
        assert state_bytes == ssm_counts.scan_step_bytes(
            runs, 3, 2 * HIDDEN, 16, 4, 4)
        assert win > 0 and full > 0
    # the full layer's pages are read by its own call and the one cross
    # layer's: twice one call's fetches
    assert all(full % 2 == 0 for *_, full, _ in steps)


def test_a_slot_s_next_owner_reads_none_of_its_state(engine):
    """Fill the state and tail slabs (and both sink rows) with NaN: a
    sequence admitted at position 0 starts from zeros, whatever the
    slot held."""
    import dataclasses
    pool = engine._device_pool()
    engine.pool = dataclasses.replace(
        pool, state=jnp.full_like(pool.state, jnp.nan),
        tail=jnp.full_like(pool.tail, jnp.nan))
    rows, stats = _serve(engine, [[_tokens(30, 41)], [_tokens(7, 42)]], 5)
    assert stats["nonfinite_logit_steps"] == 0
    assert all(r["logit_abs_err"] < F32_TOL for r in rows)
    # what the sinks took is never read back
    engine.pool = dataclasses.replace(
        engine.pool, state=jnp.nan_to_num(engine.pool.state),
        tail=jnp.nan_to_num(engine.pool.tail))


# ------------------------------------------ (e) what the model refuses
def test_describe_reads_the_third_shape():
    arch = describe(_lm(max_seq_len=16))
    assert isinstance(arch, Phi4Flash)
    assert "".join(k[0] for k in arch.kinds) == "swswsfgc"
    assert (arch.kv_heads, arch.kv_head_dim, arch.paged_layers) == \
        (KV_HEADS // 2, 2 * HIDDEN // HEADS, 1)
    assert len(arch.window_layers) + 1 == 3       # layers that write K/V


@pytest.mark.parametrize("kwargs,cfg,message", [
    ({"tensor_parallel": 2}, {}, "refuses tp"),
    ({}, {"adapter_rank": 4}, "refuses adapters"),
    ({}, {"serve_spec_decode": True}, "refuses speculation.*snapshot"),
    ({}, {"serve_prefix_cache": True}, "refuses prefix_cache.*state at"),
    ({"prefix_cache": True}, {"host_tier_mb": 8.0},
     "refuses prefix_cache"),
    ({}, {"serve_mesh": "auto"}, "serve_mesh='auto'"),
])
def test_what_phi4flash_is_not_served_on_raises_by_name(kwargs, cfg,
                                                        message):
    with pytest.raises(NotImplementedError, match=message):
        ServeEngine(_lm(max_seq_len=32, **cfg), interpret=True, **kwargs)


def test_the_handoff_and_the_host_tier_are_refused_by_name(engine):
    with pytest.raises(NotImplementedError, match="refuses handoff"):
        engine.export_kv(0, [1, 2, 3])
    with pytest.raises(NotImplementedError, match="refuses host_tier"):
        engine.arch.refuse(host_tier=True)


def test_one_refuse_signature_for_every_description():
    import inspect
    from flexflow_tpu.serve.arch import SHAPES
    sigs = {str(inspect.signature(cls.refuse)) for cls in SHAPES}
    assert len(sigs) == 1
    assert set(inspect.signature(SHAPES[0].refuse).parameters) == {
        "self", "tp", "adapters", "speculation", "prefix_cache",
        "host_tier", "handoff"}


# -------------------- (f) the paged kernel: grouped heads under a window
@pytest.mark.parametrize("hq,h,d,window", [
    (40, 10, 128, 16),    # the served heads: ten slabs, four query heads
    (40, 10, 128, 0),     # a key/value head (the one-lane body is in)
    (8, 2, 128, 16),      # group 4 under a window: the served shape
    (8, 2, 128, 0),       # group 4, the full layer
    (8, 4, 64, 24),       # group 2, two heads a slab
    (4, 4, 64, 0),        # group 1, no window: what the kernel always did
])
def test_kernel_equals_its_jnp_twin_at_grouped_heads_under_a_window(
        hq, h, d, window):
    rng = np.random.default_rng(hq * 100 + window)
    ps, npg, slots_n, pp, t = 8, 40, 3, 12, 70
    kp = jnp.asarray(rng.standard_normal((npg, ps, h, d)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((npg, ps, h, d)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((t, hq, d)), jnp.float32)
    pt = np.stack([rng.permutation(np.arange(1, npg))[:pp]
                   for _ in range(slots_n)]).astype(np.int32)
    slots, lens = np.zeros(t, np.int32), np.ones(t, np.int32)
    slots[:40], lens[:40] = 1, np.arange(31, 71)     # a chunk
    slots[40], lens[40] = 2, 90                      # decode lanes
    slots[41], lens[41] = 0, 5
    args = (q, kp, vp, jnp.asarray(pt), jnp.asarray(slots),
            jnp.asarray(lens))
    twin = paged_attention_ragged_v2(*args, use_pallas=False, window=window,
                                     scale=0.3)
    kern = paged_attention_ragged_v2(*args, interpret=True, block_kv=16,
                                     window=window, scale=0.3)
    np.testing.assert_allclose(kern[:42], twin[:42], atol=1e-5, rtol=0)
    # and the twin is plain attention over the lane's visible keys
    for lane in (10, 40):
        n, sl = lens[lane], slots[lane]
        keys = np.asarray(kp)[pt[sl]].reshape(-1, h, d)
        vals = np.asarray(vp)[pt[sl]].reshape(-1, h, d)
        lo = max(0, n - window) if window else 0
        for head in (0, hq - 1):
            kh = head // (hq // h)
            sc = keys[lo:n, kh] @ np.asarray(q)[lane, head] * 0.3
            pr = np.exp(sc - sc.max())
            np.testing.assert_allclose(
                twin[lane, head], (pr / pr.sum()) @ vals[lo:n, kh],
                atol=2e-5)


def test_a_window_s_work_list_skips_the_blocks_behind_it():
    pt = np.arange(1, 65, dtype=np.int32).reshape(1, 64)
    slots, lens = np.ones(32, np.int32), np.full(32, 1, np.int32)
    slots[0], lens[0] = 0, 500                          # one decode lane
    pt = np.concatenate([pt, pt])
    full = work_items(lens, slots, pt, page_size=8, block_kv_pages=2)
    win = work_items(lens, slots, pt, page_size=8, block_kv_pages=2,
                     window=64)
    assert full["total"] - win["total"] == (500 - 64) // 16
    assert win["page_fetches"] < full["page_fetches"] // 4


# --------------------------------------- the scopes the metrics read
def test_the_step_names_every_mixer_s_phases(engine):
    c, t = engine.cache_cfg, engine.mixed_width
    z = jnp.zeros((t,), jnp.int32)
    text = jax.jit(engine._mixed_impl).lower(
        engine.params, engine._device_pool(), z, z, z, z,
        jnp.zeros((c.max_seqs, c.pages_per_seq), jnp.int32), z,
        jnp.ones((t,), jnp.int32), z[:engine.head_rows], z - 1,
        z[:engine.head_rows]).as_text(debug_info=True)
    for path in ("serve_step/layer0/ssm_proj/", "serve_step/layer0/ssm_conv/",
                 "serve_step/layer4/ssm_scan/", "serve_step/layer1/kv_write/",
                 "serve_step/layer1/attn/", "serve_step/layer1/diff_norm/",
                 "serve_step/layer5/kv_write/", "serve_step/layer6/gmu/",
                 "serve_step/layer7/attn/", "serve_step/layer7/ffn/",
                 "serve_step/head/", "serve_step/sample/"):
        assert path in text, path
    assert "serve_step/layer7/kv_write" not in text     # cross: no write
    assert "serve_step/layer0/attn/" not in text


# ------------------------------------------------ (g) the benchmark cell
def test_the_cell_rehearses_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "phi4flash-reason", "--rehearse-cpu", "--seconds",
         "3"], capture_output=True, text=True, env=env, cwd=ROOT,
        timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["metrics"] == {} and last["rehearsal"] is True
