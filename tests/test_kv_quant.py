"""Ragged paged attention v2 + int8 quantized KV pages (PR 8).

Layers:
  * kernel — v2's jnp fallback is BIT-identical to the v1 kernel on
    fp32 across random ragged mixes; the Pallas v2 form (interpret
    mode) agrees at f32 tolerance for every kv-block shape; int8
    dequant attention is bounded-error vs f32 with both
    implementations agreeing; the quantizer's row properties and the
    autotune-by-shape table behave.
  * engine — int8 serving holds greedy token parity with the no-cache
    reference on the base workload, and is TOKEN-IDENTICAL to itself
    through chunking, prefix hits, preemption, speculation and
    rollback (per-row write-local scales make quantized content
    execution-path invariant); scale bookkeeping survives the stress
    interleavings (check_invariants + check_kv_scales).
  * sizing — kv_pool_mb byte budgets derive pages from the configured
    kv_dtype itemsize (never a hardcoded 4), and the auto-tuned
    grad_bucket_mb satellite resolves identically in the executor and
    the simulator with explicit values authoritative.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flexflow_tpu.config import FFConfig
from flexflow_tpu.kernels.paged_ragged_v2 import (
    Q_ROWS,
    _BLOCK_KV_TABLE,
    _ragged_jnp,
    choose_block_kv,
    dequantize_kv,
    paged_attention_ragged_v2,
    quantize_kv_rows,
    ragged_dispatch_passes,
    register_block_kv,
)
from flexflow_tpu.models.transformer import build_transformer_lm
from flexflow_tpu.serve import ServeEngine
from flexflow_tpu.serve.kv_cache import (KVCacheConfig, KVPool,
                                         PagedKVCache)


# --------------------------------------------------------------- helpers
def _ragged_setup(batch, seed, page_size=4, pages_per_seq=6, h=4, d=8):
    """Random ragged K/V histories scattered into pages (the
    tests/test_serve_v2.py layout)."""
    rng = np.random.RandomState(seed)
    max_len = pages_per_seq * page_size
    num_pages = 1 + batch * pages_per_seq
    lens = rng.randint(1, max_len + 1, size=batch)
    k_pages = np.zeros((num_pages, page_size, h, d), np.float32)
    v_pages = np.zeros((num_pages, page_size, h, d), np.float32)
    table = np.zeros((batch, pages_per_seq), np.int32)
    pool = list(rng.permutation(np.arange(1, num_pages)))
    for b, L in enumerate(lens):
        for i in range(-(-int(L) // page_size)):
            p = int(pool.pop())
            table[b, i] = p
            k_pages[p] = rng.randn(page_size, h, d)
            v_pages[p] = rng.randn(page_size, h, d)
    slots, poss = [], []
    for s, L in enumerate(lens):
        picks = {int(L) - 1} | {int(p) for p in
                                rng.randint(0, int(L), size=3)}
        for p in sorted(picks):
            slots.append(s)
            poss.append(p)
    q = rng.randn(len(slots), h, d).astype(np.float32)
    return (jnp.asarray(q), jnp.asarray(k_pages), jnp.asarray(v_pages),
            jnp.asarray(table), jnp.asarray(np.asarray(slots, np.int32)),
            jnp.asarray(np.asarray(poss, np.int32) + 1))


def _lm(kv_dtype="float32", *, page_size=4, pool_pages=None,
        kv_pool_mb=0.0, budget=32, max_seqs=4, max_seq_len=64,
        spec=True, **cfg_kw):
    cfg = FFConfig(
        batch_size=1, kv_page_size=page_size,
        kv_num_pages=pool_pages or (1 + 16 * max_seqs),
        kv_pool_mb=kv_pool_mb, kv_dtype=kv_dtype,
        serve_max_seqs=max_seqs, serve_prefill_budget=budget,
        serve_spec_decode=spec, **cfg_kw)
    return build_transformer_lm(cfg, vocab_size=61,
                                max_seq_len=max_seq_len, hidden=32,
                                num_heads=4, num_layers=2, ff_dim=64)


def _prompts(rng, n, lo=4, hi=28):
    return [list(rng.randint(1, 61, size=rng.randint(lo, hi)))
            for _ in range(n)]


# ----------------------------------------------- kernel v2 bit-equality
@pytest.mark.parametrize("seed", [0, 1, 2, 7])
def test_ragged_v2_jnp_bit_identical_to_v1(seed):
    """fp32 acceptance: the kernel's jnp twin is bit-for-bit the
    contiguous full-prefill attention (what v1 was held to, the oracle
    tests/test_serve.py holds) across random ragged (slot, position)
    mixes: the page and slot indirection is pure data movement."""
    from test_serve import _full_prefill_attention
    q, kp, vp, table, slots, lens = _ragged_setup(3 + seed % 3, seed)
    scale = 1.0 / np.sqrt(q.shape[-1])
    out = _ragged_jnp(q, kp, vp, table, slots, lens, scale)
    # each lane's history laid out contiguously, gathered on the host
    lane_pages = np.asarray(table)[np.asarray(slots)]        # (T, pp)
    k_full, v_full = (
        np.asarray(a)[lane_pages].reshape(len(slots), -1, *a.shape[2:])
        for a in (kp, vp))
    ref = _full_prefill_attention(q, jnp.asarray(k_full),
                                  jnp.asarray(v_full), lens, scale)
    assert out.dtype == ref.dtype
    assert np.array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("block_kv", [4, 8, 12, 24])
def test_ragged_v2_pallas_interpret_matches_jnp(block_kv):
    """The work-list Pallas kernel agrees with the fallback at f32
    tolerance for every kv-block shape (whole pages, ragged tails,
    whole-table blocks)."""
    q, kp, vp, table, slots, lens = _ragged_setup(3, 60)
    ref = paged_attention_ragged_v2(q, kp, vp, table, slots, lens,
                                 use_pallas=False)
    out = paged_attention_ragged_v2(q, kp, vp, table, slots, lens,
                                 interpret=True, block_kv=block_kv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-6, atol=2e-6)


def test_ragged_v2_int8_bounded_error_and_path_agreement():
    """int8 pages: attention output error vs the f32 pages is bounded
    per element (the relaxed exactness gate's atol half), and the
    Pallas and jnp dequant paths agree at f32 tolerance."""
    q, kp, vp, table, slots, lens = _ragged_setup(4, 11)
    kq, ks = quantize_kv_rows(kp)
    vq, vs = quantize_kv_rows(vp)
    f32 = paged_attention_ragged_v2(q, kp, vp, table, slots, lens,
                                 use_pallas=False)
    int8 = paged_attention_ragged_v2(q, kq, vq, table, slots, lens,
                                  use_pallas=False, k_scales=ks,
                                  v_scales=vs)
    # bound: the output is a convex combination of dequantized V rows
    # (each within scale/2 of its f32 row) with softmax weights whose
    # perturbation is driven by the K rows' bounded error — at randn
    # scale the measured error is ~1e-2; 0.05 catches a mis-indexed
    # scale or stale page (O(1) error) with wide margin
    err = np.abs(np.asarray(int8) - np.asarray(f32)).max()
    assert err < 0.05, f"int8 attention error {err} exceeds the bound"
    assert err > 0, "int8 path suspiciously exact (not quantizing?)"
    pal = paged_attention_ragged_v2(q, kq, vq, table, slots, lens,
                                 interpret=True, block_kv=8,
                                 k_scales=ks, v_scales=vs)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(int8),
                               rtol=2e-6, atol=2e-6)


def test_quantize_rows_properties():
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(5, 4, 8).astype(np.float32) * 3.0)
    qv, sc = quantize_kv_rows(x)
    assert qv.dtype == jnp.int8 and sc.shape == (5, 4)
    # roundtrip error is within half a quantization step per element
    err = np.abs(np.asarray(dequantize_kv(qv, sc)) - np.asarray(x))
    assert np.all(err <= np.asarray(sc)[..., None] / 2 + 1e-7)
    # the row amax is representable exactly at |q| = 127
    assert np.abs(np.asarray(qv)).max() == 127
    # all-zero rows: scale 0, content 0, dequant reproduces zero
    zq, zs = quantize_kv_rows(jnp.zeros((2, 3, 8)))
    assert np.all(np.asarray(zs) == 0) and np.all(np.asarray(zq) == 0)
    assert np.all(np.asarray(dequantize_kv(zq, zs)) == 0)


def test_quantize_rows_fp8_reuses_scale_machinery():
    """fp8 (e4m3) pages ride the int8 per-row machinery verbatim: same
    scale shape, rows scaled to the format's max finite (448), all-zero
    rows exact, roundtrip error within the format's relative step at
    amax scale — and NEVER a NaN/inf from the saturating cast."""
    import ml_dtypes
    rng = np.random.RandomState(9)
    x = jnp.asarray(rng.randn(5, 4, 8).astype(np.float32) * 3.0)
    qv, sc = quantize_kv_rows(x, jnp.float8_e4m3fn)
    assert qv.dtype == jnp.dtype(ml_dtypes.float8_e4m3fn)
    assert sc.shape == (5, 4)
    deq = np.asarray(dequantize_kv(qv, sc))
    assert np.all(np.isfinite(deq))
    # e4m3's 3-bit mantissa: relative step 2^-3 at the top binade;
    # absolute error per element <= scale * 448 * 2^-4 = amax/16
    err = np.abs(deq - np.asarray(x))
    amax = np.abs(np.asarray(x)).max(axis=-1)
    assert np.all(err <= amax[..., None] / 16 + 1e-7)
    zq, zs = quantize_kv_rows(jnp.zeros((2, 3, 8)), jnp.float8_e4m3fn)
    assert np.all(np.asarray(zs) == 0)
    assert np.all(np.asarray(dequantize_kv(zq, zs)) == 0)


def test_fp8_attention_bounded_error():
    """fp8 pages through the ragged kernel: bounded per-element
    attention error vs f32 pages (coarser than int8 — e4m3 rounds at
    amax/16 vs amax/254 — but still far below the O(1) error of a
    mis-indexed scale)."""
    q, kp, vp, table, slots, lens = _ragged_setup(4, 21)
    kq, ks = quantize_kv_rows(kp, jnp.float8_e4m3fn)
    vq, vs = quantize_kv_rows(vp, jnp.float8_e4m3fn)
    f32 = paged_attention_ragged_v2(q, kp, vp, table, slots, lens,
                                 use_pallas=False)
    fp8 = paged_attention_ragged_v2(q, kq, vq, table, slots, lens,
                                 use_pallas=False, k_scales=ks,
                                 v_scales=vs)
    err = np.abs(np.asarray(fp8) - np.asarray(f32)).max()
    assert 0 < err < 0.25, f"fp8 attention error {err} out of bounds"


def test_choose_block_kv_table_and_dispatch_accounting():
    got = choose_block_kv(16, 16, 8, 64, 4)
    assert got % 16 == 0 and 16 <= got <= 16 * 16
    # int8 pages move 1/4 the bytes -> larger blocks to hit the same
    # DMA target
    assert choose_block_kv(16, 16, 8, 64, 1) >= got
    # a registered (measured) entry overrides the analytic pick
    register_block_kv(16, 8, 64, 4, 16, 48)
    try:
        assert choose_block_kv(16, 16, 8, 64, 4) == 48
    finally:
        _BLOCK_KV_TABLE.pop((16, 8, 64, 4, 16), None)
    # v2's grid: a run of lanes per (tile, slot change), one item per
    # kv-block of four pages — a lane its own run when the caller
    # bounds no slot changes
    tiles = -(-24 // Q_ROWS)
    assert ragged_dispatch_passes(24, 16, 4) == {
        "v1": 24 * 16, "v2": tiles * Q_ROWS * 4}
    assert ragged_dispatch_passes(24, 16, 4, slot_changes=3) == {
        "v1": 24 * 16, "v2": (tiles + 3) * 4}


# ------------------------------------------------------- engine parity
def test_int8_greedy_parity_base_workload():
    """The acceptance gate: int8 pages keep greedy token parity with
    the no-cache f32 reference on the (seeded, short) base workload —
    exactly, except at tie-margin argmax flips
    (ServeEngine.assert_token_parity, the same gate ci.sh runs) —
    with zero recompiles after warmup. The on_step audit inspects the
    live scale arrays while sequences are resident."""
    eng = ServeEngine(_lm("int8"))
    counts = eng.warmup()
    rng = np.random.RandomState(0)
    prompts = _prompts(rng, 8)
    out = eng.generate(prompts, 6,
                       on_step=lambda s: eng.check_kv_scales())
    eng.assert_token_parity(prompts, out,
                            eng.generate_reference(prompts, 6),
                            min_exact_frac=0.75)
    assert eng.compile_counts() == counts
    eng.check_kv_scales()
    eng.cache.check_invariants()


def test_int8_invariant_through_chunking_prefix_preempt_spec_rollback():
    """The quantized-parity stress: per-row write-local scales make
    the quantized content a pure function of (tokens, positions), so
    the SAME requests must decode token-identically no matter how the
    execution path slices them — different chunk budgets, prefix-cache
    hits on a warm engine, page pressure driving preemption, and
    speculation whose rejected drafts roll pages back."""
    rng = np.random.RandomState(1)
    prompts = _prompts(rng, 8, lo=6, hi=30)
    # ample pool, no speculation: the baseline stream
    eng_a = ServeEngine(_lm("int8", spec=False), spec_tokens=0)
    eng_a.warmup()
    base = eng_a.generate(prompts, 8)

    # different chunking (budget 8 vs 32) + speculation on (drafts on
    # random text are mostly rejected -> rollbacks every spec step)
    eng_b = ServeEngine(_lm("int8", budget=8), spec_tokens=3)
    eng_b.warmup()
    assert eng_b.generate(prompts, 8) == base
    # warm second pass: prefix hits attach previously committed
    # quantized pages instead of recomputing them
    out2 = eng_b.generate(prompts, 8)
    assert out2 == base
    assert eng_b.last_stats["prefix_hit_tokens"] > 0

    # tight pool: watermark blocking + preemption churn under the same
    # requests — still the same tokens
    eng_c = ServeEngine(_lm("int8", pool_pages=1 + 30, budget=16),
                        spec_tokens=2)
    eng_c.warmup()
    # audit the live scale rows mid-run, at peak residency — this is
    # the interleaving (preemption + rollback churn) most likely to
    # reuse a page slot without rewriting its scale
    assert eng_c.generate(
        prompts, 8, on_step=lambda s: eng_c.check_kv_scales()) == base
    for eng in (eng_a, eng_b, eng_c):
        eng.check_kv_scales()   # post-run: prefix-cache-parked pages
        eng.cache.check_invariants()


def test_int8_kv_stress_interleavings():
    """Scale bookkeeping through adversarial interleavings: repeated
    mixed batches over one warm engine (prefix attach/evict churn)
    under a pool small enough to preempt, with speculation rolling
    back pages, invariant-checked after every step."""
    eng = ServeEngine(_lm("int8", pool_pages=1 + 40, budget=12),
                      spec_tokens=3)
    eng.warmup()
    rng = np.random.RandomState(7)
    streams = {}
    for round_i in range(3):
        prompts = _prompts(rng, 6, lo=4, hi=24)

        def on_step(i):
            eng.cache.check_invariants()
            eng.check_kv_scales()   # live rows: residency + scales

        out = eng.generate(prompts, 6, on_step=on_step)
        eng.check_kv_scales()
        key = tuple(tuple(p) for p in prompts)
        # a replayed prompt set (same engine, different pool history)
        # must reproduce its stream exactly
        if key in streams:
            assert streams[key] == out
        streams[key] = out
    assert eng.last_stats["compile_counts"]["mixed"] == 1


def test_bf16_pages_run_and_report():
    eng = ServeEngine(_lm("bfloat16"))
    eng.warmup()
    assert not eng.kv_exact   # f32 activations round into bf16 pages
    rng = np.random.RandomState(5)
    prompts = _prompts(rng, 4)
    out = eng.generate(prompts, 4)
    assert all(len(o) == 4 for o in out)
    pool = eng.last_stats["kv_pool"]
    assert pool["kv_dtype"] == "bfloat16"
    assert pool["bytes_per_page"] == pool["pool_bytes"] // (
        eng.cache_cfg.num_pages)
    assert pool["page_ratio_vs_f32"] == 2.0


# ------------------------------------------------- sizing / bookkeeping
def test_kv_pool_mb_sizes_pages_from_itemsize():
    """The hardcoded-4 fix: an equal byte budget yields page counts in
    the ratio of the per-page byte costs — f32 at 4 B/elem, bf16 at 2,
    int8 at 1 (+ its f32 scale rows) — so every page-fraction knob
    (watermark, ladder rungs) sees the larger effective pool."""
    def cfg_for(dtype):
        c = FFConfig(kv_page_size=8, kv_pool_mb=0.5, kv_dtype=dtype)
        return KVCacheConfig.from_ff(c, num_layers=2, num_heads=4,
                                     head_dim=8, max_seq_len=128)
    f32, bf16, int8 = (cfg_for(d) for d in ("float32", "bfloat16",
                                            "int8"))
    d = 8
    assert f32.page_bytes == 2 * 2 * 8 * 4 * d * 4
    assert bf16.page_bytes == f32.page_bytes // 2
    assert int8.page_bytes == 2 * 2 * 8 * 4 * (d + 4)  # values + scales
    assert int8.effective_page_ratio == pytest.approx(4 * d / (d + 4))
    assert int8.effective_page_ratio >= 1.9   # the capacity acceptance
    # equal budget -> proportionally more pages (floor rounding aside)
    assert bf16.usable_pages >= 2 * f32.usable_pages - 2
    assert int8.usable_pages >= int(1.9 * f32.usable_pages)
    # pool bytes never exceed the budget
    for c in (f32, bf16, int8):
        assert c.num_pages * c.page_bytes <= 0.5 * (1 << 20) \
            + c.page_bytes


def test_scale_meta_wired_into_check_invariants():
    cfg = KVCacheConfig(num_layers=1, num_heads=2, head_dim=4,
                        page_size=4, num_pages=7, max_seqs=2,
                        max_seq_len=16, kv_dtype="int8")
    cache = PagedKVCache(cfg)
    cache.check_invariants()   # host bookkeeping alone: ok
    pool = KVPool.alloc(cfg)
    cache.check_invariants(pool)
    # geometry drift must be caught
    drifted = dataclasses.replace(pool, k_scale=pool.k_scale[:, :3])
    with pytest.raises(AssertionError, match="k_scale"):
        cache.check_invariants(drifted)
    with pytest.raises(AssertionError, match="v_scale.*float32"):
        cache.check_invariants(dataclasses.replace(
            pool, v_scale=pool.v_scale.astype(jnp.bfloat16)))
    # a lossless pool must not carry scale bookkeeping, and gets none
    plain_cfg = KVCacheConfig(
        num_layers=1, num_heads=2, head_dim=4, page_size=4,
        num_pages=7, max_seqs=2, max_seq_len=16)
    plain, plain_pool = PagedKVCache(plain_cfg), KVPool.alloc(plain_cfg)
    assert plain_pool.k_scale is None and plain_pool.v_scale is None
    plain.check_invariants(plain_pool)
    with pytest.raises(AssertionError, match="carries scale arrays"):
        plain.check_invariants(dataclasses.replace(
            plain_pool, k_scale=pool.k_scale, v_scale=pool.v_scale))


def test_kv_pool_stats_and_serve_report_line():
    from flexflow_tpu.utils.profiling import serve_report
    eng = ServeEngine(_lm("int8"))
    eng.warmup()
    rng = np.random.RandomState(2)
    eng.generate(_prompts(rng, 3), 3)
    pool = eng.last_stats["kv_pool"]
    for key in ("kv_dtype", "bytes_per_page", "effective_pages",
                "pool_bytes", "occupancy", "page_ratio_vs_f32",
                "pages_saved_vs_f32", "attn_block_kv",
                "attn_dispatch_passes"):
        assert key in pool, key
    assert pool["kv_dtype"] == "int8" and not pool["kv_exact"]
    dp = pool["attn_dispatch_passes"]
    assert dp["v1"] > dp["v2"] > 0
    # v2 is the most the kernel's grid walks: the engine's bound a step
    assert dp["v2"] % eng.attn_max_items == 0
    report = serve_report(eng.last_stats)
    assert "kv pool: int8 pages" in report
    assert "ragged kernel v2" in report


def test_serve_attn_block_kv_knob():
    lm = _lm("float32", serve_attn_block_kv=8)
    eng = ServeEngine(lm)
    assert eng.attn_block_kv == 8
    rng = np.random.RandomState(4)
    prompts = _prompts(rng, 3)
    eng.warmup()
    out = eng.generate(prompts, 4)
    # fp32 + explicit block shape: still bit-exact vs the reference
    assert out == eng.generate_reference(prompts, 4)


def test_kv_cli_flags():
    cfg = FFConfig(argv=["--kv-dtype", "int8", "--kv-pool-mb", "2.5",
                         "--serve-attn-block-kv", "32"])
    assert cfg.kv_dtype == "int8"
    assert cfg.kv_pool_mb == 2.5
    assert cfg.serve_attn_block_kv == 32
    with pytest.raises(ValueError, match="kv_dtype"):
        FFConfig(kv_dtype="fp8")
    with pytest.raises(ValueError, match="kv_pool_mb"):
        FFConfig(kv_pool_mb=-1)
    with pytest.raises(ValueError, match="serve_attn_block_kv"):
        FFConfig(serve_attn_block_kv=-2)


# ------------------------------------------- auto grad_bucket_mb (PR 7)
def test_auto_grad_bucket_mb_resolution():
    """The ROADMAP leftover: an unset grad_bucket_mb auto-tunes from
    the machine model, identically in the executor and the simulator,
    with explicit values authoritative and the RESOLVED value folded
    into the cost-cache fingerprint."""
    from flexflow_tpu import SGDOptimizer, make_mesh
    from flexflow_tpu.core.overlap import resolve_bucket_mb
    from flexflow_tpu.models.transformer import build_transformer
    from flexflow_tpu.search.simulator import Simulator

    cfg = FFConfig(batch_size=8)
    assert cfg.grad_bucket_mb is None          # the new default
    ff = build_transformer(cfg, batch_size=8, seq_len=16, hidden=32,
                           num_heads=4, num_layers=2, ff_dim=64,
                           num_classes=10)
    mesh = make_mesh((4, 2), ("data", "model"))
    auto = resolve_bucket_mb(cfg, ff, mesh=mesh)
    assert auto > 0
    # deterministic, and 0 (monolithic) without a data axis to sync
    assert resolve_bucket_mb(cfg, ff, mesh=mesh) == auto
    assert resolve_bucket_mb(cfg, ff, mesh=None) == 0.0
    # explicit values are authoritative, including 0
    cfg.grad_bucket_mb = 0.0
    assert resolve_bucket_mb(cfg, ff, mesh=mesh) == 0.0
    cfg.grad_bucket_mb = 9.5
    assert resolve_bucket_mb(cfg, ff, mesh=mesh) == 9.5
    cfg.grad_bucket_mb = None
    ff.compile(optimizer=SGDOptimizer(lr=0.05), mesh=mesh)
    assert ff.executor._grad_bucket_mb == auto
    sim = Simulator(ff, mesh)
    assert sim.bucket_mb == auto
    # the fingerprint sees the RESOLVED value, not the None sentinel
    assert sim.overlap_sig() == (True, auto)
