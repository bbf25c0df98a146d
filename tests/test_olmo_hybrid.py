"""Olmo-Hybrid's language model through the serve engine (PR 52): the op
graph, the engine through pages, state slots and convolution tails, the
gated delta rule's three forms with beta past 1, the lane kernel at a
state whose heads lie in pairs on the lanes (and still at Qwen3-Next's
shape, traced as it was), the post-norm block's scopes, what the
description refuses, the older descriptions' programs — against
benchmark/lib/reference_olmohybrid.py, at a small size with seeded
random weights.
"""

import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from lib import delta_counts, olmohybrid_cell  # noqa: E402

from flexflow_tpu.config import CompMode, FFConfig  # noqa: E402
from flexflow_tpu.kernels import gated_delta_scan as K  # noqa: E402
from flexflow_tpu.models.olmo_hybrid import (DELTA, FULL,  # noqa: E402
                                             build_olmo_hybrid_lm)
from flexflow_tpu.ops import gated_delta as GD  # noqa: E402
from flexflow_tpu.ops import ssm  # noqa: E402
from flexflow_tpu.serve import ServeEngine  # noqa: E402
from flexflow_tpu.serve.arch import OlmoHybrid, describe  # noqa: E402
from flexflow_tpu.serve.kv_cache import (HybridPool, HybridSpec,  # noqa: E402
                                         KVCacheConfig)

VOCAB, HIDDEN, HEADS, FF = 128, 64, 4, 96
# six heads (no multiple of 8) of 24 x 64: Dk != Dv, Dk under the tile,
# two heads side by side fill the 128 lanes — the kernel's pair path
LIN_HEADS, DK, DV = 6, 24, 64
PAGE, BUDGET, SEQS = 8, 24, 4
INIT = {"post_norm": [0.25, 0.45], "final_norm": [0.5, 1.5],
        "qk_norm": [2.5, 3.0], "delta_norm": [0.5, 1.5],
        "dt": [0.001, 0.1]}
TYPES = ["linear_attention"] * 3 + ["full_attention"]
CONF = {"vocab_size": VOCAB, "hidden_size": HIDDEN, "num_hidden_layers": 4,
        "layer_types": TYPES * 8, "num_attention_heads": HEADS,
        "linear_num_value_heads": LIN_HEADS, "linear_key_head_dim": DK,
        "linear_value_head_dim": DV, "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None}, "rms_norm_eps": 1e-6,
        "max_position_embeddings": 256}
F32_TOL = 2e-4


def _lm(max_seq_len=256, rope_theta=None, **cfg):
    base = dict(batch_size=1, seed=5, kv_page_size=PAGE, kv_num_pages=129,
                serve_max_seqs=SEQS, serve_prefill_budget=BUDGET,
                serve_spec_decode=False, serve_prefix_cache=False)
    base.update(cfg)
    lm = build_olmo_hybrid_lm(
        FFConfig(**base), vocab_size=VOCAB, max_seq_len=max_seq_len,
        hidden=HIDDEN, num_layers=4, num_heads=HEADS, ff_dim=FF,
        rope_theta=rope_theta, key_heads=LIN_HEADS, value_heads=LIN_HEADS,
        key_dim=DK, value_dim=DV, post_norm_init=INIT["post_norm"],
        final_norm_init=INIT["final_norm"], qk_norm_init=INIT["qk_norm"],
        delta_norm_init=INIT["delta_norm"], dt_range=INIT["dt"],
        init_std=0.08)
    lm.compile(comp_mode=CompMode.INFERENCE)
    return lm


@pytest.fixture(scope="module")
def engine():
    eng = ServeEngine(_lm(), interpret=True)
    eng.warmup()
    return eng


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(1, VOCAB, n).tolist()


@pytest.mark.parametrize("theta", [None, 500000.0])
def test_graph_forward_equals_the_reference(theta):
    """No rotation (the row's null) and the half-split one the builder's
    argument also takes."""
    eng = ServeEngine(_lm(rope_theta=theta), interpret=True)
    toks = _tokens(200, 3)
    arr = np.zeros((1, 256), np.int32)
    arr[0, :200] = toks
    got = np.asarray(eng.arch.forward_logits(eng.params, jnp.asarray(arr)))
    conf = dict(CONF, rope_parameters={"rope_theta": theta})
    want = olmohybrid_cell.reference_logits(conf)(
        eng.params, toks, list(range(200)))
    assert np.abs(got[:200] - want).max() < F32_TOL
    assert 0.7 < want.std() < 1.4       # the head's unit deviation
    eng.close()


# ---- the engine through pages, state slots and tails
CASES = {
    "one_chunk": [[17]],
    "several_chunks_and_a_block_of_the_chunk_form": [[3 * BUDGET + 5, 190]],
    "one_after_another": [[40], [9]],     # the slot is re-admitted
    "a_prompt_shorter_than_the_taps": [[2]],
}


@pytest.mark.parametrize("case", list(CASES))
def test_engine_through_the_cache_equals_the_reference(engine, case):
    assert engine.geometry.delta_impl == "pallas_interpret"
    groups = [[_tokens(n, 11 + n) for n in group] for group in CASES[case]]
    rows, stats = olmohybrid_cell.logits_through_cache(
        engine, CONF, groups, 10)
    for r in rows:
        assert r["new"] == 10 and r["worst_gap"] < F32_TOL, r
        assert r["logit_abs_err"] < F32_TOL, r
    assert stats["nonfinite_logit_steps"] == 0
    assert engine.compile_counts()["mixed"] == 1
    engine.cache.check_invariants(engine.pool)


def test_the_twin_serves_the_same_logits():
    """The jnp engine (the twin on the paired slab) and a wider step
    whose first block is one run in the chunk form."""
    eng = ServeEngine(_lm(serve_prefill_budget=80))
    assert eng.geometry.delta_impl == "jnp"
    rows, _ = olmohybrid_cell.logits_through_cache(
        eng, CONF, [[_tokens(150, 7), _tokens(33, 8)]], 6)
    for r in rows:
        assert r["logit_abs_err"] < F32_TOL, r
    eng.close()


# ---- the gated delta rule: three forms of one recurrence, beta in (0, 2)
def _inputs(t, seed, h, dk, dv):
    """q, k unit (q over sqrt(Dk)), v, g <= 0 and beta = 2 sigmoid(b)
    with b drawn wide: a third of it past 1.5."""
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    return (unit(f(t, h, dk)) / np.sqrt(dk), unit(f(t, h, dk)), f(t, h, dv),
            -jnp.exp(f(t, h) - 2.0), 2.0 * jax.nn.sigmoid(2.0 * f(t, h)))


def _lanes(runs, t, slots):
    """runs: (slot, first position, lanes) from lane 0, dead lanes
    behind -> the lane arrays as `step_lanes` makes them."""
    slot_of, pos = np.zeros(t, np.int32), np.zeros(t, np.int32)
    n = 0
    for slot, p0, k in runs:
        slot_of[n:n + k] = slot
        pos[n:n + k] = np.arange(p0, p0 + k)
        n += k
    live = jnp.arange(t) < n
    slot_of, pos = jnp.asarray(slot_of), jnp.asarray(pos)
    starts = ssm.run_starts(slot_of, pos)
    return (slot_of, pos, live, starts,
            ssm.run_write_slots(starts, live, slot_of, slots), jnp.int32(n))


SHAPES = [(4, 16, 8),           # tests/test_qwen3_next.py's: pack 1
          (LIN_HEADS, DK, DV),  # this file's: pairs, 6 heads of 24 x 64
          (30, 96, 192)]        # Olmo-Hybrid's published heads


@pytest.mark.parametrize("h,dk,dv", SHAPES)
def test_the_three_forms_agree_with_beta_past_one(h, dk, dv):
    """`recurrent`, `chunked` and `segmented` (a sequence cut into two
    steps beside a decode lane, lanes and a chunk-form block) with beta
    in (0, 2), at Dk != Dv and a head count that is no multiple of 8;
    the slab in the layout `state_shape` gives it."""
    n = 150
    x = _inputs(n, 0, h, dk, dv)
    assert float(jnp.mean(x[4] > 1.5)) > 0.2 and float(x[4].max()) > 1.9
    want, s_want = GD.recurrent(*x)
    got = GD.chunked(*(a[None] for a in x))[0]
    assert np.abs(np.asarray(got - want)).max() < 2e-5
    slots, t = 3, 128
    state = jnp.full((slots + 1,) + GD.state_shape(h, dk, dv), 7.0)
    out = []
    for first, k in ((0, 100), (100, 50)):
        lanes = _lanes([(2, first, k)], t, slots)
        part = tuple(jnp.concatenate([a[first:first + k], jnp.zeros(
            (t - k,) + a.shape[1:])]) for a in x)
        o, state = jax.jit(GD.segmented)(*part, state, *lanes)
        out.append(np.asarray(o[:k]))
    assert np.abs(np.concatenate(out) - np.asarray(want)).max() < 2e-5
    pack = GD.state_pack(h, dv)
    rows = np.asarray(state[2]).reshape(h // pack, dk, pack, dv)
    assert np.abs(np.moveaxis(rows, 2, 1).reshape(h, dk, dv)
                  - np.asarray(s_want)).max() < 2e-5
    assert np.all(np.asarray(state[1]) == 7.0)      # untouched slots stay


def test_the_inverse_by_halves_holds_past_beta_one():
    """T = (I + tril(beta K K^T D, -1))^-1 with beta up to 2 and keys
    that repeat (the worst conditioning the rule meets)."""
    rng = np.random.default_rng(0)
    k = rng.standard_normal((3, 64, 24))
    k[:, 1::2] = k[:, ::2] + 0.05 * rng.standard_normal((3, 32, 24))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    m = np.tril(2.0 * np.einsum("hid,hjd->hij", k, k), -1) + np.eye(64)
    got = GD._unit_lower_inverse(jnp.asarray(m, jnp.float32))
    err = np.abs(np.asarray(got, np.float64) @ m - np.eye(64)).max()
    assert err < 1e-3 * np.abs(np.linalg.inv(m)).max()


# ---- the lane kernel where heads lie in pairs
RUNS = {
    "decode lanes beside a chunk":
        [(0, 9, 1), (3, 100, 1), (1, 33, 1), (2, 20, 64 + 40)],
    "lanes, a chunk-form block, lanes": [(3, 40, 1), (1, 11, 62 + 64 + 9)],
    "a sequence that starts inside the step": [(2, 7, 9), (0, 0, 13)],
    "no live lane": [],
}


def _kernel_and_twin(h, dk, dv, runs, t, slots, layers=2, layer=1):
    x = _inputs(t, 1, h, dk, dv)
    slab = jnp.asarray(np.random.default_rng(2).standard_normal(
        (layers, slots + 1) + GD.state_shape(h, dk, dv)), jnp.float32)
    lanes = _lanes(runs, t, slots)
    slot_of, pos, live, starts, _, n = lanes
    o0, s0 = jax.jit(lambda: GD.segmented(*x, slab, *lanes, layer=layer))()
    plan = GD.lane_plan(slot_of, pos, live, starts, n)
    o1, s1 = jax.jit(lambda: K.gated_delta_scan(
        *x, slab, layer, slot_of, pos, plan, interpret=True))()
    return map(np.asarray, (o0, s0, o1, s1, slab)), int(n)


@pytest.mark.parametrize("case", list(RUNS))
def test_the_kernel_equals_its_twin_on_a_paired_slab(case):
    (o0, s0, o1, s1, slab), n = _kernel_and_twin(
        LIN_HEADS, DK, DV, RUNS[case], 192, 4)
    np.testing.assert_allclose(o1[:n], o0[:n], atol=1e-5, rtol=0)
    np.testing.assert_allclose(s1[1, :4], s0[1, :4], atol=1e-5, rtol=0)
    # the other layer, and every slot no run ended in, stay as they were
    np.testing.assert_array_equal(s1[0], slab[0])
    for slot in set(range(4)) - {s for s, _, _ in RUNS[case]}:
        np.testing.assert_array_equal(s1[1, slot], slab[1, slot])


def test_the_kernel_equals_its_twin_at_the_published_heads():
    """30 heads of 96 x 192: q's heads at sublane 32 of the tile, the
    transposed tile's first 96 rows, 15 pairs of 384 lanes."""
    assert K.supported(544, 30, 96, 192)
    (o0, s0, o1, s1, _), n = _kernel_and_twin(
        30, 96, 192, [(0, 9, 1), (1, 4, 3), (2, 0, 2)], 64, 3)
    np.testing.assert_allclose(o1[:n], o0[:n], atol=1e-5, rtol=0)
    np.testing.assert_allclose(s1[1, :3], s0[1, :3], atol=1e-5, rtol=0)


def test_the_kernel_at_qwen3_next_s_shape_traces_as_it_did():
    """Where the static shape is Qwen3-Next's (576 lanes, 32 heads of
    128 x 128) the widened kernel and its wrapper trace to the jaxpr
    they traced to at PR 51 (hashed at the parent commit and here, under
    this suite's conftest — f32 products, eight host devices; a plain
    process reads adf9c64682cd3e25 at both; a PR that changes the kernel
    on purpose re-pins it and measures `qwen3next-longchat`)."""
    t, rows, h, dk, dv, layers = 576, 65, 32, 128, 128, 6
    f = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    lane = jax.ShapeDtypeStruct((t,), jnp.int32)
    flag = jax.ShapeDtypeStruct((t,), jnp.bool_)

    def call(q, k, v, g, beta, slab, slots, pos, live, starts, n):
        plan = GD.lane_plan(slots, pos, live, starts, n)
        return K.gated_delta_scan(q, k, v, g, beta, slab, 4, slots, pos,
                                  plan)

    jaxpr = jax.make_jaxpr(call)(
        f(t, h, dk), f(t, h, dk), f(t, h, dv), f(t, h), f(t, h),
        f(layers, rows, h * dk, dv), lane, lane, flag, flag,
        jax.ShapeDtypeStruct((), jnp.int32))
    assert GD.state_shape(h, dk, dv) == (h * dk, dv)
    assert hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16] \
        == "3c0422ac67c0b803"


# ---- the description, the pool, the counters
def test_describe_reads_the_seventh_shape(engine):
    arch = describe(engine.model)
    assert isinstance(arch, OlmoHybrid) and arch.kind == "olmo_hybrid"
    assert arch.kinds == [DELTA, DELTA, DELTA, FULL] and arch.post_norm
    assert (arch.kv_heads, arch.kv_head_dim, arch.paged_layers) == (
        HEADS, HIDDEN // HEADS, 1)
    assert engine.geometry.attn_calls == (1, 0) and arch.experts == 0
    assert arch.rope_theta == 0 and arch.delta.beta_scale == 2.0
    channels = 2 * LIN_HEADS * DK + LIN_HEADS * DV
    assert arch.hybrid_spec(24) == HybridSpec(
        window_layers=0, window=0, chunk=24, state_layers=3,
        state_shape=(LIN_HEADS // 2 * DK, 2 * DV),
        tail_shape=(3, channels), tail_dtype="float32")
    x = jnp.ones((3, HIDDEN))
    assert arch.norm1(engine.params, 0, x) is x     # nothing before
    fp = engine._program_fingerprint()
    assert fp["delta_impl"] == "pallas_interpret"
    assert fp["arch"] == "olmo_hybrid" and "delta_state_layout" not in fp
    assert {k: v for k, v in engine.boot_stats.items() if "delta" in k} == {
        "delta_impl": "pallas_interpret",
        "delta_state_layout": "head_pairs",
        "delta_state_shape": (LIN_HEADS // 2 * DK, 2 * DV),
        "delta_state_slot_bytes": LIN_HEADS * DK * DV * 4}


def test_the_pool_holds_pages_states_and_tails_and_no_ring(engine):
    c = engine.cache_cfg
    pool = engine._device_pool()
    assert isinstance(pool, HybridPool) and pool.window is None
    channels = 2 * LIN_HEADS * DK + LIN_HEADS * DV
    assert pool.state.shape == (3, SEQS + 1, LIN_HEADS // 2 * DK, 2 * DV)
    assert pool.tail.shape == (3, SEQS + 1, 3 * channels)
    assert pool.full.k.shape == (1, c.num_pages, PAGE, HIDDEN)
    assert c.constant_bytes_per_seq == delta_counts.state_bytes_per_seq(
        3, LIN_HEADS, LIN_HEADS, DK, DV, tail_itemsize=4)


def test_published_widths_give_the_issue_s_bytes():
    assert GD.state_pack(30, 192) == 2 and GD.state_pack(32, 128) == 1
    assert GD.state_shape(30, 96, 192) == (1440, 384)
    assert GD.state_layout(30, 96, 192) == {
        "delta_state_layout": "head_pairs",
        "delta_state_shape": (1440, 384),
        "delta_state_slot_bytes": 2211840}      # 2.11 MiB: nothing padded
    # a head after another would hold a third more: 192 lanes tile to 256
    assert 4 * 30 * 96 * 256 == 2949120
    spec = HybridSpec(window_layers=0, window=0, chunk=512, state_layers=12,
                      state_shape=GD.state_shape(30, 96, 192),
                      tail_shape=(3, delta_counts.channels(30, 30, 96, 192)))
    c = KVCacheConfig(num_layers=4, num_heads=30, head_dim=128,
                      page_size=16, num_pages=4916, max_seqs=32,
                      max_seq_len=32768, kv_dtype="bfloat16", hybrid=spec,
                      packed_heads=True)
    assert c.cache_bytes_per_token == 61440
    assert c.constant_bytes_per_seq == 12 * (2211840 + 69120) \
        == delta_counts.state_bytes_per_seq(12, 30, 30, 96, 192)
    assert c.pages_per_seq == 2048
    assert delta_counts.scan_step_bytes(20, 12, 30, 30, 96, 192) \
        == 2 * 20 * 12 * 2280960                # 1.09 GB a decode step


def test_the_step_counts_its_states_and_its_pages(engine):
    seen = []
    olmohybrid_cell.logits_through_cache(
        engine, CONF, [[_tokens(60, 9), _tokens(20, 10)]], 4,
        on_step=lambda s, ev: seen.append(ev))
    evs = [ev for ev in seen if ev.dispatched]
    # a state and a tail in, a state and a tail out, a run and a layer:
    # the program's counter against benchmark/lib/delta_counts.py
    assert all(ev.state_bytes == delta_counts.scan_step_bytes(
        len(ev.plan.chunks), 3, LIN_HEADS, LIN_HEADS, DK, DV,
        tail_itemsize=4) for ev in evs)
    assert all(ev.kv_bytes_read == ev.full_kv_bytes > 0 for ev in evs)
    assert all((ev.paged_calls, ev.paged_calls_in_place) == (1, 1)
               for ev in evs)
    assert all(ev.delta_lanes + 64 * ev.delta_chunk_blocks
               >= ev.plan.num_prefill_lanes + ev.plan.num_decode_lanes
               - 63 * ev.delta_chunk_blocks for ev in evs)
    assert {"state_bytes", "full_kv_bytes", "delta_lanes",
            "delta_chunk_blocks", "paged_calls_in_place"} <= set(
        engine.geometry.counted)


def test_the_traced_step_names_the_post_norm_scope(engine):
    c = engine.cache_cfg
    lane = jnp.zeros((engine.mixed_width,), jnp.int32)
    rows = jnp.zeros((engine.head_rows,), jnp.int32)
    text = jax.jit(engine._mixed_impl).lower(
        engine._step_params, engine._device_pool(), lane, lane, lane, lane,
        jnp.zeros((c.max_seqs, c.pages_per_seq), jnp.int32), lane,
        lane + 1, rows, lane - 1, rows).as_text(debug_info=True)
    for name in ("delta_proj", "delta_conv", "delta_scan", "qkv",
                 "kv_write", "attn", "attn_out", "ffn", "post_norm"):
        assert f"/layer3/{name}" in text or f"/layer0/{name}" in text, name
    # a block's two post-norms, directly under the layer; nothing under
    # `ln` (no norm stands before a sub-layer) and no gate
    assert text.count("serve_step/layer0/post_norm/") > 4
    assert "/ln/" not in text and "attn_gate" not in text


@pytest.mark.parametrize("kwargs,cfg,message", [
    (dict(tensor_parallel=2), {}, "refuses tp"),
    ({}, dict(adapter_rank=4), "refuses adapters"),
    ({}, dict(serve_spec_decode=True), "refuses speculation"),
    ({}, dict(serve_prefix_cache=True), "refuses prefix_cache"),
])
def test_what_olmo_hybrid_is_not_served_on_raises_by_name(kwargs, cfg,
                                                         message):
    with pytest.raises(NotImplementedError, match=message):
        ServeEngine(_lm(**cfg), **kwargs)


def test_the_handoff_and_the_host_tier_are_refused_by_name(engine):
    with pytest.raises(NotImplementedError, match="refuses handoff"):
        engine.arch.refuse(handoff=True)
    with pytest.raises(NotImplementedError, match="refuses host_tier"):
        engine.arch.refuse(host_tier=True)


# ---- the six older descriptions keep their programs
OLDER = {"kv_quant": "transformer_lm", "olmoe": "olmoe",
         "phi4flash": "phi4flash", "cmdaplus": "command_a_plus",
         "minicpm_sala": "minicpm_sala", "qwen3_next": "qwen3_next"}


@pytest.mark.parametrize("which", list(OLDER))
def test_the_older_descriptions_keep_their_programs(which):
    """The engine `tests/test_<which>._lm()` builds: its description is
    still its own, its traced step names no `post_norm` and still its
    `ln`, its fingerprint has the keys it had (the slab's layout is in
    `boot_stats` alone, and only where a delta layer is), and a delta
    layer's slab keeps the rows it had. The lowered step's text of the
    six configurations at their rehearsal sizes was hashed at the parent
    commit and at this one, jnp and interpreted: the same twelve
    (PERF.md section 6, PR 52)."""
    mod = __import__(f"test_{which}")
    eng = ServeEngine(mod._lm(), interpret=True)
    assert eng.arch.kind == OLDER[which] and not eng.arch.post_norm
    c = eng.cache_cfg
    lane = jnp.zeros((eng.mixed_width,), jnp.int32)
    rows = jnp.zeros((eng.head_rows,), jnp.int32)
    text = jax.jit(eng._mixed_impl).lower(
        eng._step_params, eng._device_pool(), lane, lane, lane, lane,
        jnp.zeros((c.max_seqs, c.pages_per_seq), jnp.int32), lane,
        lane + 1, rows, lane - 1, rows).as_text(debug_info=True)
    assert "post_norm" not in text and "/ln/" in text
    fp = eng._program_fingerprint()
    tail = ["attn_impl", "scan_impl", "expert_impl"]
    if which == "qwen3_next":
        d = eng.arch.delta
        assert list(fp)[-4:] == tail + ["delta_impl"]
        assert c.hybrid.state_shape == (d.value_heads * d.key_dim,
                                        d.value_dim)
        assert eng.geometry.delta_state["delta_state_layout"] == "heads"
    else:
        assert list(fp)[-3:] == tail and eng.geometry.delta_state == {}
    eng.close()
