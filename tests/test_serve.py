"""flexflow_tpu.serve: paged KV-cache, continuous batching, ServeEngine.

Three layers of coverage, mirroring the subsystem's layering:
  * kernel — paged decode attention equals full-prefill attention
    BIT-FOR-BIT on CPU at ragged batch sizes {1, 3, 8} (the page
    indirection must be exact, not approximately right), and the
    Pallas kernel (interpret mode) agrees with the jnp fallback.
  * scheduler — property-style invariants over a randomized workload:
    no page leaks after eviction, the waiting queue drains, the
    prefill token budget is never exceeded.
  * engine — generate() on a ragged batch produces tokens identical
    to the naive no-cache greedy-decode reference, with ZERO
    recompiles after warmup.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flexflow_tpu.config import FFConfig
from flexflow_tpu.kernels.paged_ragged_v2 import (
    _ragged_jnp,
    paged_attention_ragged_v2,
)
from flexflow_tpu.serve.kv_cache import KVCacheConfig, PagedKVCache
from flexflow_tpu.serve.scheduler import ContinuousBatchingScheduler


# --------------------------------------------------------------- helpers
def _ragged_setup(batch, seed, page_size=4, pages_per_seq=6):
    """Random ragged K/V histories scattered into pages. Returns
    (q, k_pages, v_pages, page_table, seq_lens, k_full, v_full) where
    k_full/v_full are the same histories laid out contiguously (padded
    with zeros), the layout full-prefill attention reads."""
    rng = np.random.RandomState(seed)
    h, d = 4, 8
    max_len = pages_per_seq * page_size
    num_pages = 1 + batch * pages_per_seq
    lens = rng.randint(1, max_len + 1, size=batch)
    k_pages = np.zeros((num_pages, page_size, h, d), np.float32)
    v_pages = np.zeros((num_pages, page_size, h, d), np.float32)
    table = np.zeros((batch, pages_per_seq), np.int32)
    k_full = np.zeros((batch, max_len, h, d), np.float32)
    v_full = np.zeros((batch, max_len, h, d), np.float32)
    # shuffled pool: page tables are deliberately non-contiguous
    pool = list(rng.permutation(np.arange(1, num_pages)))
    for b, L in enumerate(lens):
        k_full[b, :L] = rng.randn(L, h, d)
        v_full[b, :L] = rng.randn(L, h, d)
        for i in range(-(-int(L) // page_size)):
            p = int(pool.pop())
            table[b, i] = p
            chunk = slice(i * page_size, min((i + 1) * page_size, int(L)))
            n = chunk.stop - chunk.start
            k_pages[p, :n] = k_full[b, chunk]
            v_pages[p, :n] = v_full[b, chunk]
    q = rng.randn(batch, h, d).astype(np.float32)
    return (jnp.asarray(q), jnp.asarray(k_pages), jnp.asarray(v_pages),
            jnp.asarray(table), jnp.asarray(lens.astype(np.int32)),
            jnp.asarray(k_full), jnp.asarray(v_full))


def _full_prefill_attention(q, k_full, v_full, seq_lens, scale):
    """The attention a full prefill computes at the last position, on
    the CONTIGUOUS layout, with the exact op sequence of the paged
    path (dot_general dims, divide-after-matmul) so equality is
    bitwise when the page indirection is exact."""
    b, t, h, d = k_full.shape
    s = jax.lax.dot_general(
        q, k_full, (((2,), (3,)), ((0, 1), (0, 2))),
        preferred_element_type=jnp.float32) * scale
    pos = jax.lax.broadcasted_iota(jnp.int32, (b, 1, t), 2)
    s = jnp.where(pos < seq_lens[:, None, None], s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jax.lax.dot_general(
        p, v_full.astype(jnp.float32), (((2,), (1,)), ((0, 1), (0, 2))),
        preferred_element_type=jnp.float32)
    return (o / l).astype(q.dtype)


# ------------------------------------------------------- kernel parity
@pytest.mark.parametrize("batch", [1, 3, 8])
def test_paged_decode_bitwise_vs_full_prefill(batch):
    q, kp, vp, table, lens, k_full, v_full = _ragged_setup(batch, batch)
    scale = 1.0 / np.sqrt(q.shape[-1])
    # a decode step: one lane per sequence, lane b reads table row b
    out = paged_attention_ragged_v2(
        q, kp, vp, table, jnp.arange(batch, dtype=jnp.int32), lens,
        scale=scale, use_pallas=False)
    ref = _full_prefill_attention(q, k_full, v_full, lens, scale)
    assert out.dtype == ref.dtype
    # bit-for-bit: the page table is pure indirection, zero numerics
    assert np.array_equal(np.asarray(out), np.asarray(ref)), (
        np.abs(np.asarray(out) - np.asarray(ref)).max())


@pytest.mark.parametrize("batch", [1, 3])
def test_paged_decode_pallas_interpret_matches_jnp(batch):
    q, kp, vp, table, lens, _, _ = _ragged_setup(batch, 100 + batch)
    scale = 1.0 / np.sqrt(q.shape[-1])
    slots = jnp.arange(batch, dtype=jnp.int32)
    ref = _ragged_jnp(q, kp, vp, table, slots, lens, scale)
    out = paged_attention_ragged_v2(q, kp, vp, table, slots, lens,
                                    scale=scale, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-6, atol=2e-6)


# --------------------------------------------------------- kv cache
def test_kv_cache_alloc_free_cycle():
    cfg = KVCacheConfig(num_layers=1, num_heads=2, head_dim=4,
                        page_size=4, num_pages=9, max_seqs=2,
                        max_seq_len=16)
    cache = PagedKVCache(cfg)
    assert cache.free_pages == 8
    s0 = cache.alloc_slot()
    cache.ensure_capacity(s0, 5)       # 2 pages, on demand
    cache.advance(s0, 5)
    s1 = cache.alloc_slot()
    cache.ensure_capacity(s1, 3)       # 1 page — no worst-case reserve
    cache.advance(s1, 3)
    cache.check_invariants()
    assert cache.free_pages == 5
    assert cache.free_slots == 0
    # append across a page boundary allocates exactly when crossed
    assert cache.append_token(s0) == 5
    assert cache.append_token(s0) == 6
    assert cache.append_token(s0) == 7
    assert cache.free_pages == 5       # page 2 still has room
    assert cache.append_token(s0) == 8  # crosses into a third page
    assert cache.free_pages == 4
    cache.check_invariants()
    cache.free_slot(s0)
    cache.check_invariants()
    assert cache.free_pages == 7
    cache.free_slot(s1)
    assert cache.free_pages == 8
    assert cache.free_slots == 2


def test_kv_cache_exhaustion_recovers():
    cfg = KVCacheConfig(num_layers=1, num_heads=2, head_dim=4,
                        page_size=4, num_pages=5, max_seqs=2,
                        max_seq_len=16)
    cache = PagedKVCache(cfg)
    s0 = cache.alloc_slot()
    cache.ensure_capacity(s0, 16)      # the whole pool (4 pages)
    cache.advance(s0, 16)
    s1 = cache.alloc_slot()
    with pytest.raises(RuntimeError):  # pool dry: scheduler must preempt
        cache.ensure_capacity(s1, 1)
    with pytest.raises(ValueError):    # past the page-table ceiling
        cache.ensure_capacity(s0, 17)
    cache.free_slot(s0)                # frees admit again
    cache.check_invariants()
    assert cache.ensure_capacity(s1, 4) == 1
    cache.free_slot(s1)
    assert cache.free_pages == cfg.usable_pages


# --------------------------------------------------------- scheduler
def _drive_step(sched, cache, plan):
    """What the engine does with a plan, minus the device work:
    bookkeeping first (chunk_dispatched, chunk_landed), then
    emissions."""
    for ch in plan.chunks:
        sched.chunk_dispatched(ch)
        sched.chunk_landed(ch)
    for ch in plan.chunks:
        if ch.emits:
            ch.req.out_tokens.append(0)
            if ch.req.is_done():
                sched.finish(ch.req)


def test_scheduler_invariants_random_workload():
    """Drive the scheduler host-side (no device work): FCFS admission
    under the token budget, chunked prefill progress, eviction +
    backfill, and page accounting hold for every step of a randomized
    ragged workload."""
    rng = np.random.RandomState(7)
    cfg = KVCacheConfig(num_layers=1, num_heads=2, head_dim=4,
                        page_size=4, num_pages=33, max_seqs=3,
                        max_seq_len=32)
    cache = PagedKVCache(cfg)
    budget = 12
    sched = ContinuousBatchingScheduler(cache, prefill_token_budget=budget)
    reqs = [sched.submit(list(rng.randint(0, 50, size=rng.randint(1, 20))),
                         int(rng.randint(1, 12)))
            for _ in range(20)]
    admitted_order = []
    steps = 0
    while sched.has_work():
        steps += 1
        assert steps < 2000, "scheduler wedged"
        plan = sched.schedule()
        assert plan.chunks, "a step with work must plan chunks"
        # chunked prefill: prefill lanes never exceed the budget, and
        # decode lanes (one per running sequence) never wait on them
        assert plan.num_prefill_lanes <= budget
        assert plan.num_decode_lanes <= cfg.max_seqs
        admitted_order += [r.rid for r in plan.admitted]
        _drive_step(sched, cache, plan)
        cache.check_invariants()
    # queue drained, every request ran to completion, FCFS order held
    # (this pool never fills, so no preemption re-admissions)
    assert not sched.waiting and not sched.running
    assert sched.stats["preemptions"] == 0
    assert admitted_order == sorted(admitted_order)
    assert all(len(r.out_tokens) == r.max_new_tokens for r in reqs)
    # eviction returned every page (hashed ones park reclaimable)
    assert cache.free_pages == cfg.usable_pages
    assert cache.free_slots == cfg.max_seqs


# --------------------------------------------------------- engine e2e
@pytest.fixture(scope="module")
def lm_engine():
    from flexflow_tpu.models.transformer import build_transformer_lm
    from flexflow_tpu.serve import ServeEngine
    cfg = FFConfig(batch_size=1, kv_page_size=8, kv_num_pages=73,
                   serve_max_seqs=8, serve_prefill_budget=48)
    ff = build_transformer_lm(cfg, vocab_size=89, max_seq_len=64,
                              hidden=32, num_heads=4, num_layers=2,
                              ff_dim=64)
    eng = ServeEngine(ff)
    eng.warmup()
    return eng


@pytest.mark.parametrize("batch", [1, 3, 8])
def test_generate_matches_nocache_reference(lm_engine, batch):
    """Ragged prompts, ragged max-new-tokens: continuous-batched paged
    decoding must produce the exact token streams of the naive
    re-forward-everything reference, without compiling anything new
    after warmup."""
    rng = np.random.RandomState(batch)
    prompts = [list(rng.randint(1, 89, size=rng.randint(1, 30)))
               for _ in range(batch)]
    max_new = [int(rng.randint(1, 10)) for _ in range(batch)]
    before = lm_engine.compile_counts()
    out = lm_engine.generate(prompts, max_new)
    assert lm_engine.compile_counts() == before, "serving recompiled"
    ref = lm_engine.generate_reference(prompts, max_new)
    assert out == ref
    assert [len(o) for o in out] == max_new
    stats = lm_engine.last_stats
    assert stats["total_new_tokens"] == sum(max_new)
    assert stats["tokens_per_sec"] > 0


def test_generate_more_requests_than_slots(lm_engine):
    """12 requests through 8 slots: the waiting queue must drain via
    finished-sequence eviction + backfill, still matching the
    reference."""
    rng = np.random.RandomState(42)
    prompts = [list(rng.randint(1, 89, size=rng.randint(1, 24)))
               for _ in range(12)]
    out = lm_engine.generate(prompts, 5)
    ref = lm_engine.generate_reference(prompts, 5)
    assert out == ref


def test_eos_stops_early(lm_engine):
    """Pick the token the model actually emits first as EOS: the
    request must finish at that point, shorter than max_new."""
    prompts = [[5, 6, 7]]
    free = lm_engine.generate(prompts, 8)
    eos = free[0][0]
    out = lm_engine.generate(prompts, 8, eos_token=eos)
    assert out[0] == [eos]


def test_serve_report_renders(lm_engine):
    from flexflow_tpu.utils.profiling import serve_report
    lm_engine.generate([[1, 2, 3], [4]], 4)
    rep = serve_report(lm_engine.last_stats)
    assert "tok/s" in rep and "p99" in rep
    assert "mixed=1" in rep  # ONE serving program compiled, ever
    assert "prefix" in rep and "preemptions" in rep
