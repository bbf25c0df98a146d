"""Falcon-H1's language model through the serve engine (PR 56): the op
graph, the engine through pages AND state slots in every layer, Mamba-2's
three forms and its lane kernel against its twin, the multipliers, the
group mapping and the per-group norm each shown to matter, the step's
counters, what the description refuses — against
benchmark/lib/reference_falconh1.py, at a small size with seeded random
weights.
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from lib import falconh1_cell, reference_falconh1, ssd_counts  # noqa: E402

from flexflow_tpu.config import CompMode, FFConfig  # noqa: E402
from flexflow_tpu.kernels import ssd_scan as K  # noqa: E402
from flexflow_tpu.kernels import ssm_scan  # noqa: E402
from flexflow_tpu.models.falcon_h1 import (SSD_ATTN,  # noqa: E402
                                           build_falcon_h1_lm)
from flexflow_tpu.ops import gated_delta as GD  # noqa: E402
from flexflow_tpu.ops import ssd as SD  # noqa: E402
from flexflow_tpu.ops import ssm  # noqa: E402
from flexflow_tpu.serve import ServeEngine  # noqa: E402
from flexflow_tpu.serve.arch import FalconH1, describe  # noqa: E402
from flexflow_tpu.serve.kv_cache import (HybridPool, HybridSpec,  # noqa: E402
                                         KVCacheConfig)

VOCAB, HIDDEN, HEADS, KV_HEADS, HEAD_DIM, FF = 128, 32, 4, 2, 8, 48
# four heads in two groups of two: the head-to-group mapping is at work
SSM_HEADS, SSM_P, GROUPS, N = 4, 8, 2, 16
DIMS = SD.Dims(SSM_HEADS, SSM_P, GROUPS, N)
PAGE, BUDGET, SEQS = 8, 24, 4
MULT = {"embedding_multiplier": 2.0, "lm_head_multiplier": 0.5,
        "ssm_in_multiplier": 0.5,
        "ssm_multipliers": (0.7, 0.5, 0.9, 1.5, 0.8),
        "ssm_out_multiplier": 0.6, "attention_in_multiplier": 1.25,
        "attention_out_multiplier": 0.7, "key_multiplier": 0.5,
        "mlp_multipliers": (0.8, 0.9)}
STDS = {"table": 0.5, "ssm_in": 1.0, "ssm_out": 0.3, "wq": 1.5, "wk": 1.5,
        "wv": 0.2, "wo": 0.5, "gate_up": 0.3, "down": 0.5, "head": 0.4}
CONF = {"vocab_size": VOCAB, "hidden_size": HIDDEN, "num_hidden_layers": 2,
        "num_attention_heads": HEADS, "num_key_value_heads": KV_HEADS,
        "mamba_n_heads": SSM_HEADS, "mamba_n_groups": GROUPS,
        "mamba_d_state": N, "rope_theta": 1e11, "rms_norm_eps": 1e-5,
        "max_position_embeddings": 256,
        **{k: list(v) if isinstance(v, tuple) else v
           for k, v in MULT.items()}}
F32_TOL = 1e-3      # f32 engine against the f32 reference, logits of up to 4:
                    # rounding alone


def _lm(max_seq_len=256, mult=None, **cfg):
    base = dict(batch_size=1, seed=5, kv_page_size=PAGE, kv_num_pages=129,
                serve_max_seqs=SEQS, serve_prefill_budget=BUDGET,
                serve_spec_decode=False, serve_prefix_cache=False)
    base.update(cfg)
    lm = build_falcon_h1_lm(
        FFConfig(**base), vocab_size=VOCAB, max_seq_len=max_seq_len,
        hidden=HIDDEN, num_layers=2, num_heads=HEADS,
        num_kv_heads=KV_HEADS, head_dim=HEAD_DIM, ff_dim=FF,
        ssm_heads=SSM_HEADS, ssm_head_dim=SSM_P, ssm_groups=GROUPS,
        ssm_state=N, norm_init=(0.5, 1.5), stds=STDS,
        **(MULT if mult is None else mult))
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
    want = falconh1_cell.reference_logits(CONF)(
        engine.params, toks, list(range(200)))
    assert np.abs(got[:200] - want).max() < F32_TOL
    assert 0.3 < want.std() < 3.0


# ---- the engine through pages AND state slots and tails, every layer
CASES = {
    "one_chunk": [[17]],
    "several_chunks_and_a_block_of_the_chunk_form": [[3 * BUDGET + 5, 190]],
    "one_after_another": [[40], [9]],     # the slot is freed and used again
    "a_first_run_shorter_than_the_taps": [[2]],
    "a_chunk_beside_decode_lanes": [[5, 7], [60]],
}


@pytest.mark.parametrize("case", list(CASES))
def test_engine_through_the_cache_equals_the_reference(engine, case):
    """The tolerance is f32 rounding: an f32 engine against the f32
    reference differs by the order of its sums alone."""
    assert engine.scan_impl == "jnp"    # N 16: the kernel's twin
    groups = [[_tokens(n, 11 + n) for n in group] for group in CASES[case]]
    rows, stats = falconh1_cell.logits_through_cache(
        engine, CONF, groups, 10)
    for r in rows:
        assert r["new"] == 10 and r["worst_gap"] < F32_TOL, r
        assert r["logit_abs_err"] < F32_TOL, r
    assert stats["nonfinite_logit_steps"] == 0
    assert engine.compile_counts()["mixed"] == 1
    engine.cache.check_invariants(engine.pool)


def test_a_preemption_and_its_replay_serve_the_same_logits():
    """Too few pages for three sequences: one is preempted and replayed
    from its prompt, its slot's state and tail started over."""
    eng = ServeEngine(_lm(max_seq_len=128, kv_num_pages=18), interpret=True)
    groups = [[_tokens(40, 21), _tokens(44, 22), _tokens(36, 23)]]
    rows, stats = falconh1_cell.logits_through_cache(eng, CONF, groups, 24)
    assert stats["preemptions"] > 0
    for r in rows:
        assert r["new"] == 24 and r["logit_abs_err"] < F32_TOL, r
    eng.close()


def test_a_wider_step_takes_the_chunk_form():
    """A step of 80 prefill lanes: its first block is one run in the
    chunk form (64 lanes), beside a second sequence's lanes."""
    eng = ServeEngine(_lm(serve_prefill_budget=80))
    seen = []
    rows, _ = falconh1_cell.logits_through_cache(
        eng, CONF, [[_tokens(150, 7), _tokens(33, 8)]], 6,
        on_step=lambda s, ev: seen.append(ev))
    for r in rows:
        assert r["logit_abs_err"] < F32_TOL, r
    assert sum(ev.ssd_chunk_blocks for ev in seen if ev.dispatched) >= 2
    eng.close()


# ---- each multiplier, the group mapping and the per-group norm MATTER
CONTROLS = {
    **{key: {key: 1.0} for key in (
        "embedding_multiplier", "lm_head_multiplier", "ssm_in_multiplier",
        "ssm_out_multiplier", "attention_in_multiplier",
        "attention_out_multiplier", "key_multiplier")},
    **{f"ssm_multipliers_{j}": {"ssm_multipliers": tuple(
        1.0 if i == j else m for i, m in enumerate(MULT["ssm_multipliers"]))}
       for j in range(5)},
    **{f"mlp_multipliers_{j}": {"mlp_multipliers": tuple(
        1.0 if i == j else m for i, m in enumerate(MULT["mlp_multipliers"]))}
       for j in range(2)},
    "every_head_on_group_0": {"group_of": [0] * SSM_HEADS},
    "the_norm_over_all_channels": {"whole_norm": True},
}


@pytest.mark.parametrize("control", list(CONTROLS))
def test_the_control_moves_the_logits_past_the_tolerance(engine, control):
    """The reference with ONE multiplier left at 1, every head reading
    group 0, or the gated norm over all channels at once, against the
    engine's logits through the cache: past thirty times the
    tolerance the sound pair is held to."""
    toks = _tokens(70, 31)
    rows, _ = falconh1_cell.logits_through_cache(engine, CONF, [[toks]], 4)
    assert rows[0]["logit_abs_err"] < F32_TOL
    change = CONTROLS[control]
    kw = {k: v for k, v in change.items() if k in ("group_of", "whole_norm")}
    mult = {**falconh1_cell.multipliers(CONF),
            **{k: v for k, v in change.items() if k not in kw}}
    faulty = falconh1_cell.reference_logits(CONF, mult=mult, **kw)
    sound = falconh1_cell.reference_logits(CONF)
    at = list(range(60, 70))
    moved = np.abs(faulty(engine.params, toks, at)
                   - sound(engine.params, toks, at)).max()
    assert moved > 30 * F32_TOL, (control, moved)


# ---- Mamba-2: three forms of one recurrence
def _inputs(t, seed, h=SSM_HEADS, p=SSM_P, g=GROUPS, n=N):
    r = np.random.default_rng(seed)
    f = lambda a: jnp.asarray(a, jnp.float32)
    return (f(r.standard_normal((t, h, p))), f(r.standard_normal((t, g, n))),
            f(r.standard_normal((t, g, n))),
            f(-np.exp(r.uniform(-5, 0.5, (t, h)))))


def _lanes(runs, t):
    """runs: (slot, first position, lanes, live lanes of them) one after
    another from lane 0."""
    slots, pos = np.zeros(t, np.int32), np.zeros(t, np.int32)
    live = np.zeros(t, bool)
    n = 0
    for slot, p0, k, alive in runs:
        slots[n:n + k] = slot
        pos[n:n + k] = np.arange(p0, p0 + k)
        live[n:n + alive] = True
        n += k
    last = int(np.flatnonzero(live).max()) + 1 if live.any() else 0
    slots, pos, live = map(jnp.asarray, (slots, pos, live))
    starts = ssm.run_starts(slots, pos)
    return slots, pos, GD.lane_plan(slots, pos, live, starts, last)


def _naive(v, b, c, la, state):
    """The recurrence written out a head at a time, in numpy f64: head
    j on group j // (H / G), its state (P, N)."""
    v, b, c, la = (np.asarray(a, np.float64) for a in (v, b, c, la))
    t, h, p = v.shape
    g = b.shape[1]
    s = np.zeros((h, p, b.shape[2])) if state is None else np.asarray(
        state, np.float64).transpose(1, 2, 0)           # (N,H,P)->(H,P,N)
    ys = np.zeros((t, h, p))
    for i in range(t):
        for j in range(h):
            grp = j // (h // g)
            s[j] = np.exp(la[i, j]) * s[j] + np.outer(v[i, j], b[i, grp])
            ys[i, j] = s[j] @ c[i, grp]
    return ys, s.transpose(2, 0, 1)


def test_the_recurrence_is_the_definition_with_groups():
    v, b, c, la = _inputs(40, 1)
    s0 = jnp.asarray(np.random.default_rng(2).standard_normal(
        (N, SSM_HEADS, SSM_P)), jnp.float32)
    y, s = SD.recurrent(v, b, c, la, s0)
    want_y, want_s = _naive(v, b, c, la, s0)
    np.testing.assert_allclose(y, want_y, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(s, want_s, atol=1e-4, rtol=1e-4)


def test_the_chunk_form_equals_the_recurrence():
    v, b, c, la = _inputs(150, 3)      # two whole blocks and a part
    y, _ = SD.recurrent(v, b, c, la)
    got = SD.chunked(v[None], b[None], c[None], la[None])[0]
    np.testing.assert_allclose(got, y, atol=2e-4, rtol=2e-4)


RUNS = {
    "decode_lanes_a_fresh_one_and_dead_lanes": (
        [(0, 9, 1, 1), (1, 0, 1, 1), (2, 77, 1, 1), (3, 4, 5, 0)], 64),
    "a_run_resumed_past_0_lanes_blocks_lanes": (
        [(1, 5, 1, 1), (2, 30, 20 + 64 + 9, 20 + 64 + 9), (0, 3, 1, 1)],
        192),
    "a_first_run_shorter_than_the_taps_and_a_chunk": (
        [(3, 0, 2, 2), (0, 0, 62 + 64, 62 + 64)], 128),
    "dead_lanes_behind_the_live_in_a_chunk_block": (
        [(2, 10, 40, 33)], 64),
}


@pytest.mark.parametrize("case", list(RUNS))
def test_the_segmented_form_equals_the_recurrence(case):
    runs, t = RUNS[case]
    v, b, c, la = _inputs(t, len(case))
    slab = jnp.asarray(np.random.default_rng(4).standard_normal(
        (2, 5, N, SSM_HEADS * SSM_P)), jnp.float32)
    slots, pos, plan = _lanes(runs, t)
    y, out = jax.jit(lambda *a: SD.segmented(*a, 1, slots, pos, plan))(
        v, b, c, la, slab)
    n = 0
    touched = set()
    for slot, p0, k, alive in runs:
        if alive:
            s0 = slab[1, slot].reshape(N, SSM_HEADS, SSM_P) if p0 else None
            rows = slice(n, n + alive)
            want_y, want_s = SD.recurrent(v[rows], b[rows], c[rows],
                                          la[rows], s0)
            np.testing.assert_allclose(y[rows], want_y, atol=2e-4,
                                       rtol=2e-4)
            np.testing.assert_allclose(
                out[1, slot], want_s.reshape(N, -1), atol=2e-4, rtol=2e-4)
            touched.add(slot)
        assert not np.asarray(y[n + alive:n + k]).any()
        n += k
    # the other layer, the slots no run ends in and the sink stay
    np.testing.assert_array_equal(out[0], slab[0])
    for slot in set(range(5)) - touched:
        np.testing.assert_array_equal(out[1, slot], slab[1, slot])


# ---- the lane kernel (interpreted) against its twin
def test_the_kernel_equals_its_twin():
    """At a shape the kernel takes (N and P of 128, four heads in two
    groups): decode lanes, a fresh sequence, lanes on either side of a
    chunk-form block, dead lanes — the same bits as the twin's, and
    nothing else of the slab written."""
    h, p, g, n, t = 4, 128, 2, 128, 128
    assert K.supported(t, h, p, g, n)
    v, b, c, la = _inputs(t, 9, h, p, g, n)
    slab = jax.random.normal(jax.random.key(1), (2, 7, n, h * p))
    slots, pos, plan = _lanes([(1, 5, 70, 70), (2, 9, 1, 1), (3, 0, 1, 1),
                               (4, 0, 3, 3), (5, 8, 4, 0)], t)
    y0, s0 = jax.jit(lambda *a: SD.segmented(*a, 1, slots, pos, plan))(
        v, b, c, la, slab)
    y1, s1 = jax.jit(lambda *a: SD.segmented(
        *a, 1, slots, pos, plan, lane_pass=functools.partial(
            K.lane_pass, interpret=True)))(v, b, c, la, slab)
    np.testing.assert_allclose(y1, y0, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(s1, s0, atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(s1[0], slab[0])
    np.testing.assert_array_equal(s1[1, 5:], slab[1, 5:])


def test_the_kernel_takes_the_published_shape_and_no_small_one():
    assert K.supported(608, 32, 128, 2, 256)
    assert not K.supported(40, SSM_HEADS, SSM_P, GROUPS, N)
    # three states of 16 MiB would pass the kernel's VMEM
    assert not K.supported(608, 128, 128, 2, 256)


@pytest.mark.parametrize("rows,d_state,d_inner,takes", [
    (65, 16, 5120, True),       # Phi-4-mini-flash as served: unchanged
    (97, 256, 4096, False),     # Falcon-H1's slab: 25 MB a 128-wide block
    (9, 16, 128, True),
])
def test_the_mamba_1_kernel_prices_its_vmem(rows, d_state, d_inner, takes):
    """kernels/ssm_scan.py keeps its column of EVERY slot's state in
    VMEM: `supported` answers from the block's bytes, so that
    `Geometry.scan_impl` falls to the twin before Mosaic refuses."""
    assert ssm_scan.supported(576, d_state, d_inner, rows=rows) is takes
    if takes:
        assert ssm_scan.supported(576, d_state, d_inner)


# ---- the description, the pool, the counters
def test_describe_reads_the_eighth_shape(engine):
    arch = describe(engine.model)
    assert isinstance(arch, FalconH1) and arch.kind == "falcon_h1"
    assert [arch.mixer(i) for i in range(2)] == [SSD_ATTN] * 2
    assert not arch.parallel_block and not arch.post_norm
    assert (arch.kv_heads, arch.kv_head_dim, arch.paged_layers) == (
        KV_HEADS, HEAD_DIM, 2)
    assert arch.full_layers == arch.ssd_layers == [0, 1]
    assert engine.geometry.attn_calls == (2, 0) and engine.geometry.ssd
    assert arch.hybrid_spec(24) == HybridSpec(
        window_layers=0, window=0, chunk=24, state_layers=2,
        state_shape=(N, SSM_HEADS * SSM_P), tail_shape=(3, DIMS.channels),
        tail_dtype="float32")
    for key in ("embedding_multiplier", "lm_head_multiplier",
                "attention_in_multiplier", "attention_out_multiplier",
                "key_multiplier", "mlp_multipliers"):
        assert getattr(arch, key) == MULT[key], key
    assert (arch.ssd.in_multiplier, arch.ssd.multipliers,
            arch.ssd.out_multiplier) == (0.5, MULT["ssm_multipliers"], 0.6)
    assert {k: v for k, v in engine.boot_stats.items() if "ssd" in k} == {
        "ssd_state_layout": "state_rows_by_head_lanes",
        "ssd_state_shape": (N, SSM_HEADS * SSM_P),
        "ssd_state_slot_bytes": 4 * N * SSM_HEADS * SSM_P}
    assert engine.boot_stats["scan_impl"] == "jnp"


def test_the_pool_holds_pages_and_states_in_every_layer(engine):
    c = engine.cache_cfg
    pool = engine._device_pool()
    assert isinstance(pool, HybridPool) and pool.window is None
    assert pool.state.shape == (2, SEQS + 1, N, SSM_HEADS * SSM_P)
    assert pool.tail.shape == (2, SEQS + 1, 3 * DIMS.channels)
    assert pool.full.k.shape == (2, c.num_pages, PAGE, KV_HEADS * HEAD_DIM)
    assert c.constant_bytes_per_seq == ssd_counts.state_bytes_per_seq(
        2, SSM_HEADS, SSM_P, GROUPS, N, tail_itemsize=4)


def test_published_widths_give_the_issue_s_bytes():
    d = SD.Dims(32, 128, 2, 256)
    assert (d.in_width, d.channels, d.state_shape) == (
        9248, 5120, (256, 4096))
    assert ssd_counts.channels(32, 128, 2, 256) == 5120
    spec = HybridSpec(window_layers=0, window=0, chunk=512, state_layers=6,
                      state_shape=d.state_shape, tail_shape=(3, d.channels))
    c = KVCacheConfig(num_layers=6, num_heads=4, head_dim=128,
                      page_size=16, num_pages=8193, max_seqs=96,
                      max_seq_len=8192, kv_dtype="bfloat16", hybrid=spec,
                      packed_heads=True)
    assert c.cache_bytes_per_token == 12288 and c.pages_per_seq == 512
    assert c.constant_bytes_per_seq == 6 * (4194304 + 30720) \
        == ssd_counts.state_bytes_per_seq(6, 32, 128, 2, 256)
    # a decode step of 77 sequences moves 3.9 GB of state
    assert ssd_counts.scan_step_bytes(77, 6, 32, 128, 2, 256) \
        == 2 * 77 * 6 * 4225024
    assert ssd_counts.lane_flops(32, 128, 256) == 5 * 1048576
    assert ssd_counts.chunk_block_flops(32, 128, 2, 256) \
        == 2 * 2 * 64 * 64 * 256 + 32 * (2 * 64 * 64 * 128
                                         + 4 * 64 * 256 * 128)


def test_the_step_counts_its_states_and_its_pages(engine):
    seen = []
    falconh1_cell.logits_through_cache(
        engine, CONF, [[_tokens(60, 9), _tokens(20, 10)]], 4,
        on_step=lambda s, ev: seen.append(ev))
    evs = [ev for ev in seen if ev.dispatched]
    # a state and a tail in, a state and a tail out, a run and a layer:
    # the program's counter against benchmark/lib/ssd_counts.py
    assert all(ev.state_bytes == ssd_counts.scan_step_bytes(
        len(ev.plan.chunks), 2, SSM_HEADS, SSM_P, GROUPS, N,
        tail_itemsize=4) for ev in evs)
    assert all(ev.ssm_runs == len(ev.plan.chunks) for ev in evs)
    assert all(ev.kv_bytes_read == ev.full_kv_bytes > 0 for ev in evs)
    assert all((ev.paged_calls, ev.paged_calls_in_place) == (2, 2)
               for ev in evs)
    # every live lane goes lane by lane here (24 prefill lanes a step:
    # no block holds 16 of one run... but the first does: count both)
    assert all(ev.ssd_lanes + 64 * ev.ssd_chunk_blocks
               >= ev.plan.num_prefill_lanes + ev.plan.num_decode_lanes
               - 63 * ev.ssd_chunk_blocks for ev in evs)
    assert all(ev.delta_lanes == ev.delta_chunk_blocks == 0 for ev in evs)
    assert {"state_bytes", "full_kv_bytes", "ssd_lanes",
            "ssd_chunk_blocks", "paged_calls_in_place"} <= set(
        engine.geometry.counted)
    assert "delta_lanes" not in engine.geometry.counted


def test_the_traced_step_keeps_the_scopes_the_readers_know(engine):
    c = engine.cache_cfg
    lane = jnp.zeros((engine.mixed_width,), jnp.int32)
    rows = jnp.zeros((engine.head_rows,), jnp.int32)
    text = jax.jit(engine._mixed_impl).lower(
        engine._step_params, engine._device_pool(), lane, lane, lane, lane,
        jnp.zeros((c.max_seqs, c.pages_per_seq), jnp.int32), lane,
        lane + 1, rows, lane - 1, rows).as_text(debug_info=True)
    for name in ("ln", "ssm_proj", "ssm_conv", "ssm_scan", "qkv",
                 "kv_write", "attn", "attn_out", "residual", "ffn"):
        assert f"serve_step/layer1/{name}/" in text, name
    assert "post_norm" not in text and "delta_" not in text
    # the in-projection's output is held as computed (at the published
    # size XLA otherwise recomputes it for each of its seven readers)
    assert text.count("stablehlo.optimization_barrier") == 2    # one a layer


@pytest.mark.parametrize("kwargs,cfg,message", [
    (dict(tensor_parallel=2), {}, "refuses tp"),
    ({}, dict(adapter_rank=4), "refuses adapters"),
    ({}, dict(serve_spec_decode=True), "refuses speculation"),
    ({}, dict(serve_prefix_cache=True), "refuses prefix_cache"),
])
def test_what_falcon_h1_is_not_served_on_raises_by_name(kwargs, cfg,
                                                        message):
    with pytest.raises(NotImplementedError, match=message):
        ServeEngine(_lm(**cfg), **kwargs)


def test_the_handoff_and_the_host_tier_are_refused_by_name(engine):
    assert set(FalconH1.refused) == {"tp", "adapters", "speculation",
                                     "prefix_cache", "host_tier", "handoff"}
    with pytest.raises(NotImplementedError, match="refuses handoff"):
        engine.arch.refuse(handoff=True)
    with pytest.raises(NotImplementedError, match="refuses host_tier"):
        engine.arch.refuse(host_tier=True)


def test_the_reference_imports_nothing_of_the_program():
    src = open(reference_falconh1.__file__).read()
    assert "flexflow_tpu" not in src.split('"""', 2)[2]
    assert "default_matmul_precision(\"highest\")" in src
