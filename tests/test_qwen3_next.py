"""Qwen3-Next's language model through the serve engine (PR 49): the op
graph, the engine through pages, state slots and convolution tails, the
gated delta rule in its three forms, the paged kernel at a head of 256,
the expert layer's four shares against the uncut layer, what the
description refuses, the older descriptions' programs — against
benchmark/lib/reference_qwen3next.py, at a small size with seeded random
weights.
"""

import copy
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from lib import qwen3next_cell  # noqa: E402
from lib import reference_qwen3next as R  # noqa: E402

from flexflow_tpu.config import CompMode, FFConfig  # noqa: E402
from flexflow_tpu.kernels import paged_ragged_v2 as pr  # noqa: E402
from flexflow_tpu.models.phi4flash import FULL  # noqa: E402
from flexflow_tpu.models.qwen3_next import (DELTA,  # noqa: E402
                                            build_qwen3_next_lm,
                                            layer_types, mixer_kinds)
from flexflow_tpu.ops import gated_delta as GD  # noqa: E402
from flexflow_tpu.ops import ssm  # noqa: E402
from flexflow_tpu.serve import ServeEngine  # noqa: E402
from flexflow_tpu.serve.arch import Qwen3Next, describe  # noqa: E402
from flexflow_tpu.serve.kv_cache import HybridPool, HybridSpec  # noqa: E402

VOCAB, HIDDEN, HEADS, KV_HEADS, HEAD_DIM = 128, 64, 8, 2, 32
KEY_HEADS, VALUE_HEADS, KEY_DIM, VALUE_DIM = 2, 4, 16, 16
EXPERTS, TOPK, HELD, FF = 16, 3, (4, 8), 32
PAGE, BUDGET, SEQS = 8, 24, 4
INIT = {"norm": [0.25, 0.75, "signed"], "qk_norm": [0.75, 1.25],
        "delta_norm": [0.5, 1.5], "dt": [0.001, 0.1]}
CONF = {"vocab_size": VOCAB, "hidden_size": HIDDEN, "num_hidden_layers": 4,
        "full_attention_interval": 4, "head_dim": HEAD_DIM,
        "partial_rotary_factor": 0.25, "rope_theta": 10000000,
        "linear_num_key_heads": KEY_HEADS,
        "linear_num_value_heads": VALUE_HEADS, "num_experts_per_tok": TOPK,
        "num_experts": HELD[1], "experts_first": HELD[0],
        "rms_norm_eps": 1e-6, "max_position_embeddings": 256,
        "system": {"compute_dtype": "float32"}}
F32_TOL = 2e-4


def _lm(max_seq_len=256, held=HELD, **cfg):
    base = dict(batch_size=1, seed=5, kv_page_size=PAGE, kv_num_pages=129,
                serve_max_seqs=SEQS, serve_prefill_budget=BUDGET,
                serve_spec_decode=False, serve_prefix_cache=False)
    base.update(cfg)
    lm = build_qwen3_next_lm(
        FFConfig(**base), vocab_size=VOCAB, max_seq_len=max_seq_len,
        hidden=HIDDEN, num_layers=4, num_heads=HEADS,
        num_kv_heads=KV_HEADS, head_dim=HEAD_DIM, key_heads=KEY_HEADS,
        value_heads=VALUE_HEADS, key_dim=KEY_DIM, value_dim=VALUE_DIM,
        num_experts=EXPERTS, experts_per_token=TOPK, expert_dim=FF,
        shared_expert_dim=FF, experts_held=held, norm_init=INIT["norm"],
        qk_norm_init=INIT["qk_norm"], delta_norm_init=INIT["delta_norm"],
        dt_range=INIT["dt"])
    lm.compile(comp_mode=CompMode.INFERENCE)
    return lm


@pytest.fixture(scope="module")
def engine():
    eng = ServeEngine(_lm(), interpret=True)
    eng.warmup()
    return eng


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(1, VOCAB, n).tolist()


def test_the_layer_pattern_becomes_the_engine_s_mixer_kinds():
    types = layer_types(8, 4)
    assert types == (["linear_attention"] * 3 + ["full_attention"]) * 2
    assert mixer_kinds(types)[:4] == [DELTA, DELTA, DELTA, FULL]
    with pytest.raises(ValueError, match="mamba"):
        mixer_kinds(["mamba"])


def test_graph_forward_equals_the_reference():
    lm = _lm()
    toks = _tokens(200, 3)
    arr = np.zeros((1, 256), np.int32)
    arr[0, :200] = toks
    eng = ServeEngine(lm, interpret=True)
    got = np.asarray(eng.arch.forward_logits(eng.params, jnp.asarray(arr)))
    want = qwen3next_cell.reference_logits(CONF)(
        eng.params, toks, list(range(200)))
    assert np.abs(got[:200] - want).max() < F32_TOL
    assert 0.7 < want.std() < 1.4       # the head's unit deviation


# ---- the engine through pages, state slots and tails
CASES = {
    "one_chunk": [[17]],
    "several_chunks": [[3 * BUDGET + 5]],
    "a_block_of_the_chunk_form": [[190]],
    "two_together": [[70, 131]],
    "one_after_another": [[40], [9]],     # the slot is re-admitted
    "a_prompt_shorter_than_the_taps": [[2]],
}


@pytest.mark.parametrize("case", list(CASES))
def test_engine_through_the_cache_equals_the_reference(engine, case):
    groups = [[_tokens(n, 11 + n) for n in group] for group in CASES[case]]
    rows, stats = qwen3next_cell.logits_through_cache(
        engine, CONF, groups, 14)
    for r in rows:
        assert r["new"] == 14 and r["worst_gap"] < F32_TOL, r
        assert r["logit_abs_err"] < F32_TOL, r
    assert stats["nonfinite_logit_steps"] == 0
    assert engine.compile_counts()["mixed"] == 1
    engine.cache.check_invariants(engine.pool)


def test_the_chunk_form_runs_where_a_run_fills_a_block():
    """A wider step (80 prefill lanes): its first block of 64 lanes is
    ONE run and takes the WY form; the result is the reference's."""
    eng = ServeEngine(_lm(serve_prefill_budget=80), interpret=True)
    rows, _ = qwen3next_cell.logits_through_cache(
        eng, CONF, [[_tokens(150, 7), _tokens(33, 8)]], 6)
    for r in rows:
        assert r["logit_abs_err"] < F32_TOL, r
    eng.close()


@pytest.mark.parametrize("variant", ["bf16_pages", "bf16_state"])
def test_a_variant_below_the_stated_precision_fails_the_tolerance(variant):
    import dataclasses
    lm = _lm()
    cfg = copy.copy(lm.config)
    if variant == "bf16_pages":
        cfg.kv_dtype = "bfloat16"
    eng = ServeEngine(lm, interpret=True, config=cfg)
    if variant == "bf16_state":
        pool = eng._device_pool()
        rounded = pool.state.astype(jnp.bfloat16).astype(jnp.float32)
        eng.pool = dataclasses.replace(pool, state=rounded)

        def on_step(session, ev):       # the state rounded after a step
            p = eng.pool
            eng.pool = dataclasses.replace(p, state=p.state.astype(
                jnp.bfloat16).astype(jnp.float32))
    else:
        on_step = None
    rows, _ = qwen3next_cell.logits_through_cache(
        eng, CONF, [[_tokens(150, 2)]], 8, on_step)
    assert rows[0]["logit_abs_err"] > 10 * F32_TOL, (variant, rows[0])
    eng.close()


# ---- the gated delta rule: three forms of one recurrence
H, DK, DV = 4, 16, 8


def _inputs(t, seed):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    return (unit(f(t, H, DK)) / 4.0, unit(f(t, H, DK)), f(t, H, DV),
            -jnp.exp(f(t, H) - 2.0), jax.nn.sigmoid(f(t, H)))


@pytest.mark.parametrize("length,chunk", [(77, 64), (150, 64), (64, 64),
                                          (150, 16), (5, 64)])
def test_the_chunk_form_equals_the_recurrence(length, chunk):
    x = _inputs(length, 0)
    want, _ = GD.recurrent(*x)
    got = GD.chunked(*(a[None] for a in x), chunk=chunk)[0]
    assert np.abs(np.asarray(got - want)).max() < 1e-5


def test_the_inverse_of_a_unit_lower_triangle_by_halves():
    rng = np.random.default_rng(0)
    m = np.tril(0.1 * rng.standard_normal((3, 64, 64)), -1) + np.eye(64)
    got = GD._unit_lower_inverse(jnp.asarray(m, jnp.float32))
    assert np.abs(np.asarray(got) @ m - np.eye(64)).max() < 1e-5


def _step(state, parts, width, slots):
    """One serving step's lanes from `parts` [(sequence arrays, slot,
    first position, tokens)], dead lanes behind them."""
    cols = [[] for _ in range(5)]
    slot_of, pos = [], []
    for seq, slot, first, n in parts:
        for col, a in zip(cols, seq):
            col.append(a[first:first + n])
        slot_of += [slot] * n
        pos += list(range(first, first + n))
    live = len(slot_of)
    dead = width - live
    arrs = [jnp.concatenate(c + [jnp.zeros((dead,) + c[0].shape[1:])])
            for c in cols]
    slot_of = jnp.asarray(slot_of + [0] * dead, jnp.int32)
    pos = jnp.asarray(pos + [0] * dead, jnp.int32)
    alive = jnp.arange(width) < live
    starts = ssm.run_starts(slot_of, pos)
    wslots = ssm.run_write_slots(starts, alive, slot_of, slots)
    o, state = jax.jit(GD.segmented)(
        *arrs, state, slot_of, pos, alive, starts, wslots, jnp.int32(live))
    return np.asarray(o[:live]), state


@pytest.mark.parametrize("cut", list(range(1, 40, 3)) + [64, 100, 149])
def test_a_run_split_at_any_lane_of_a_step_equals_the_recurrence(cut):
    """A sequence of 150 tokens served as two steps cut at `cut`: the
    second resumes from the slot's state, whatever the cut."""
    a = _inputs(150, 1)
    want = np.asarray(GD.recurrent(*a)[0])
    state = jnp.full((4, H * DK, DV), 7.0)      # a finished run's leftovers
    o1, state = _step(state, [(a, 2, 0, cut)], 192, 3)
    o2, state = _step(state, [(a, 2, cut, 150 - cut)], 192, 3)
    assert np.abs(np.concatenate([o1, o2]) - want).max() < 1e-5
    assert np.all(np.asarray(state[1]) == 7.0)      # untouched slots stay


def test_decode_lanes_and_a_chunk_in_one_step_and_a_readmitted_slot():
    """A chunk of one sequence beside one-lane runs of two others, then
    slot 0 re-admitted at position 0 over what the first one left."""
    a, b, c = _inputs(150, 1), _inputs(40, 2), _inputs(30, 3)
    wa, wb, wc = (np.asarray(GD.recurrent(*x)[0]) for x in (a, b, c))
    state = jnp.zeros((4, H * DK, DV))
    _, state = _step(state, [(b, 1, 0, 30), (c, 0, 0, 29)], 128, 3)
    o, state = _step(state, [(a, 2, 0, 100), (b, 1, 30, 1), (c, 0, 29, 1)],
                     128, 3)
    assert np.abs(o[:100] - wa[:100]).max() < 1e-5
    assert np.abs(o[100] - wb[30]).max() < 1e-5
    assert np.abs(o[101] - wc[29]).max() < 1e-5
    # sequence c is done; slot 0 takes sequence b anew, from zero
    o, state = _step(state, [(a, 2, 100, 50), (b, 0, 0, 20)], 128, 3)
    assert np.abs(o[:50] - wa[100:]).max() < 1e-5
    assert np.abs(o[50:] - wb[:20]).max() < 1e-5


# ---- the paged kernel at a head of 256
@pytest.mark.parametrize("heads,kv_heads,dim", [(16, 2, 256), (8, 1, 256)])
def test_the_paged_kernel_at_a_wide_head_equals_the_gather(heads, kv_heads,
                                                           dim):
    """8 query heads a key/value head of 256: a slab is a whole head of
    two 128-lane tiles (`_slab_geometry` gives one head a slab)."""
    assert pr._slab_geometry(kv_heads, dim) == (1, dim)
    rng = np.random.default_rng(0)
    t, slots, pages, ps = 40, 3, 17, 8
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    q, kp, vp = f(t, heads, dim), f(pages, ps, kv_heads, dim), \
        f(pages, ps, kv_heads, dim)
    tables = jnp.asarray(rng.permutation(np.arange(1, 16)).reshape(3, 5),
                         jnp.int32)
    lane_slots = jnp.asarray([0] * 24 + [1] * 8 + [2] * 8, jnp.int32)
    lens = jnp.asarray(list(range(10, 34)) + list(range(1, 9))
                       + [40, 33, 7, 1, 1, 1, 1, 1], jnp.int32)
    want = pr._ragged_jnp(q, kp, vp, tables, lane_slots, lens, 1 / 16.0)
    got = pr.paged_attention_ragged_v2(
        q, kp, vp, tables, lane_slots, lens, scale=1 / 16.0, block_kv=16,
        interpret=True)
    assert np.abs(np.asarray(got - want)).max() < 1e-4


# ---- the expert layer: a share of four
def test_four_shares_of_the_expert_layer_add_up_to_the_uncut_layer():
    """The graph op at experts_held = (4 j, 4), j = 0 .. 3, over the same
    router and the same 16 experts' weights: the four routed parts and
    the gated shared expert's term, counted ONCE, are the reference's
    uncut layer; and each share equals the reference's share."""
    whole = ServeEngine(_lm(held=None), interpret=True)
    p = dict(whole.params["layer0_moe"])
    h = jnp.asarray(np.random.default_rng(0).standard_normal((50, HIDDEN)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut = np.asarray(R._experts(p, h, TOPK, (0, EXPERTS)))
        shared = uncut - np.asarray(
            R._experts(p, h, TOPK, (0, EXPERTS), shared=False))
    op = next(o for o in whole.model.ops if o.name == "layer0_moe")
    total = np.zeros_like(uncut)
    for first in range(0, EXPERTS, 4):
        share = {k: v[first:first + 4] if k in ("wg", "wu", "wd") else v
                 for k, v in p.items()}
        held_op = copy.copy(op)
        held_op.experts_held = (first, 4)
        got = np.asarray(held_op.forward(
            share, [h], types.SimpleNamespace(training=False))[0])
        with jax.default_matmul_precision("highest"):
            want = np.asarray(R._experts(share, h, TOPK, (first, 4)))
        assert np.abs(got - want).max() < F32_TOL
        total += got - shared
    assert np.abs(total + shared - uncut).max() < F32_TOL
    assert np.abs(shared).max() > 0.01      # the gated shared expert counts
    whole.close()


# ---- the description, the pool, the counters
def test_describe_reads_the_sixth_shape(engine):
    arch = describe(engine.model)
    assert isinstance(arch, Qwen3Next) and arch.kind == "qwen3_next"
    assert arch.kinds == [DELTA, DELTA, DELTA, FULL]
    assert (arch.kv_heads, arch.kv_head_dim, arch.paged_layers) == (
        KV_HEADS, HEAD_DIM, 1)
    assert engine.geometry.attn_calls == (1, 0)
    assert (arch.experts, arch.experts_held) == (EXPERTS, HELD)
    channels = 2 * KEY_HEADS * KEY_DIM + VALUE_HEADS * VALUE_DIM
    assert arch.hybrid_spec(24) == HybridSpec(
        window_layers=0, window=0, chunk=24, state_layers=3,
        state_shape=(VALUE_HEADS * KEY_DIM, VALUE_DIM),
        tail_shape=(3, channels), tail_dtype="float32")
    # a key dimension of 16 is not the kernel's tile: the twin, even on
    # the interpreted paged kernel (tests/test_gated_delta_kernel.py
    # builds the engine that resolves kernels/gated_delta_scan.py)
    assert (engine.geometry.delta_impl, engine.scan_impl) == ("jnp", "jnp")
    fp = engine._program_fingerprint()
    assert fp["delta_impl"] == "jnp" and fp["arch"] == "qwen3_next"


def test_the_pool_holds_pages_states_and_tails_and_no_ring(engine):
    c = engine.cache_cfg
    pool = engine._device_pool()
    assert isinstance(pool, HybridPool) and pool.window is None
    channels = 2 * KEY_HEADS * KEY_DIM + VALUE_HEADS * VALUE_DIM
    assert pool.state.shape == (3, SEQS + 1, VALUE_HEADS * KEY_DIM,
                                VALUE_DIM)
    assert pool.tail.shape == (3, SEQS + 1, 3 * channels)
    assert pool.full.k.shape == (1, c.num_pages, PAGE, KV_HEADS * HEAD_DIM)
    state = KEY_DIM * VALUE_HEADS * VALUE_DIM * 4 + 3 * channels * 4
    assert c.constant_bytes_per_seq == 3 * state


def test_published_widths_give_the_issue_s_bytes():
    from flexflow_tpu.serve.kv_cache import KVCacheConfig
    spec = HybridSpec(window_layers=0, window=0, chunk=512, state_layers=6,
                      state_shape=(4096, 128), tail_shape=(3, 8192))
    c = KVCacheConfig(num_layers=2, num_heads=2, head_dim=256,
                      page_size=16, num_pages=49153, max_seqs=64,
                      max_seq_len=32768, kv_dtype="bfloat16", hybrid=spec,
                      packed_heads=True)
    assert c.cache_bytes_per_token == 4096
    assert c.constant_bytes_per_seq == 6 * (2 * 2**20 + 48 * 2**10)
    assert c.num_pages * c.page_bytes == 49153 * 65536     # 3.0 GiB


def test_the_step_counts_its_states_its_pages_and_its_experts(engine):
    seen = []
    qwen3next_cell.logits_through_cache(
        engine, CONF, [[_tokens(60, 9), _tokens(20, 10)]], 4,
        on_step=lambda s, ev: seen.append(ev))
    evs = [ev for ev in seen if ev.dispatched]
    channels = 2 * KEY_HEADS * KEY_DIM + VALUE_HEADS * VALUE_DIM
    state = KEY_DIM * VALUE_HEADS * VALUE_DIM * 4 + 3 * channels * 4
    # a state and a tail in, a state and a tail out, a run and a layer
    assert all(ev.state_bytes == 2 * len(ev.plan.chunks) * 3 * state
               for ev in evs)
    assert all(ev.kv_bytes_read == ev.full_kv_bytes > 0 for ev in evs)
    assert all(ev.expert_counts.shape == (4, HELD[1]) for ev in evs)
    assert all(0 < ev.slots_held < ev.expert_slots for ev in evs)
    assert all(ev.shared_bytes == 4 * 3 * HIDDEN * FF * 4 for ev in evs)
    assert all(ev.expert_dropped == 0 for ev in evs)


def test_the_traced_step_names_the_new_scopes(engine):
    c = engine.cache_cfg
    lane = jnp.zeros((engine.mixed_width,), jnp.int32)
    rows = jnp.zeros((engine.head_rows,), jnp.int32)
    text = jax.jit(engine._mixed_impl).lower(
        engine._step_params, engine._device_pool(), lane, lane, lane, lane,
        jnp.zeros((c.max_seqs, c.pages_per_seq), jnp.int32), lane,
        lane + 1, rows, lane - 1, rows).as_text(debug_info=True)
    for name in ("delta_proj", "delta_conv", "delta_scan", "attn_gate",
                 "router", "moe_dispatch", "experts", "shared_experts",
                 "moe_combine"):
        assert f"/{name}" in text, name


@pytest.mark.parametrize("kwargs,cfg,message", [
    (dict(tensor_parallel=2), {}, "refuses tp"),
    ({}, dict(adapter_rank=4), "refuses adapters"),
    ({}, dict(serve_spec_decode=True), "refuses speculation"),
    ({}, dict(serve_prefix_cache=True), "refuses prefix_cache"),
])
def test_what_qwen3_next_is_not_served_on_raises_by_name(kwargs, cfg,
                                                        message):
    with pytest.raises(NotImplementedError, match=message):
        ServeEngine(_lm(**cfg), **kwargs)


def test_the_handoff_and_the_host_tier_are_refused_by_name(engine):
    with pytest.raises(NotImplementedError, match="refuses handoff"):
        engine.arch.refuse(handoff=True)
    with pytest.raises(NotImplementedError, match="refuses host_tier"):
        engine.arch.refuse(host_tier=True)


# ---- the five older descriptions keep their programs
OLDER = {"kv_quant": "transformer_lm", "olmoe": "olmoe",
         "phi4flash": "phi4flash", "cmdaplus": "command_a_plus",
         "minicpm_sala": "minicpm_sala"}


@pytest.mark.parametrize("which", list(OLDER))
def test_the_older_descriptions_keep_their_programs(which):
    """The engine `tests/test_<which>._lm()` builds: its traced step
    names no scope of the new mixer or of the gate, its fingerprint has
    the keys it had and no `delta_impl`, and its description is still
    its own (Qwen3Next's names are a superset of OLMoE's). The lowered
    step's text was hashed at the parent commit and at this one, jnp
    and interpreted: the same for all five (PERF.md section 6, PR 49)."""
    mod = __import__(f"test_{which}")
    eng = ServeEngine(mod._lm(), interpret=True)
    assert eng.arch.kind == OLDER[which] and not eng.arch.output_gate
    c = eng.cache_cfg
    lane = jnp.zeros((eng.mixed_width,), jnp.int32)
    rows = jnp.zeros((eng.head_rows,), jnp.int32)
    text = jax.jit(eng._mixed_impl).lower(
        eng._step_params, eng._device_pool(), lane, lane, lane, lane,
        jnp.zeros((c.max_seqs, c.pages_per_seq), jnp.int32), lane,
        lane + 1, rows, lane - 1, rows).as_text(debug_info=True)
    # scope paths, not the file names a cached trace may carry
    assert "/delta_" not in text and "attn_gate" not in text
    fp = eng._program_fingerprint()
    assert "delta_impl" not in fp and eng.geometry.delta_impl is None
    assert list(fp)[-3:] == ["attn_impl", "scan_impl", "expert_impl"]
    assert "delta_impl" not in eng.boot_stats if eng.boot_stats else True
    eng.close()
