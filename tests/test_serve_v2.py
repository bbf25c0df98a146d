"""Serve throughput v2: prefix caching, chunked prefill, on-demand
paged allocation, preemption, and sampling.

Layered like tests/test_serve.py:
  * kernel — paged_attention_ragged_v2 (the mixed-step kernel) equals
    full-prefill attention BIT-FOR-BIT per lane on CPU, and its Pallas
    form (interpret mode) agrees with the jnp fallback.
  * cache — refcounted sharing, commit/match/evict life cycle, and a
    property test driving random submit/decode/finish/preempt traffic
    against check_invariants.
  * engine — prefix-cached, chunked, preempted generation stays
    token-for-token identical to the no-cache greedy reference with
    zero recompiles; sampling is seeded and reproducible.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flexflow_tpu.config import FFConfig
from flexflow_tpu.kernels.paged_ragged_v2 import paged_attention_ragged_v2
from flexflow_tpu.serve import (
    ContinuousBatchingScheduler,
    KVCacheConfig,
    PagedKVCache,
    prefix_page_keys,
)


# --------------------------------------------------------------- helpers
def _ragged_setup(batch, seed, page_size=4, pages_per_seq=6):
    """Random ragged K/V histories scattered into pages (same layout as
    tests/test_serve.py) plus the contiguous copies full-prefill
    attention reads."""
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
    return k_pages, v_pages, table, lens, k_full, v_full


def _lanes_for(lens, rng, lanes_per_seq=3):
    """Random (slot, position) lanes — several per sequence, the mixed
    step's shape — always including each sequence's last position."""
    slots, poss = [], []
    for s, L in enumerate(lens):
        picks = {int(L) - 1} | {int(p) for p in
                                rng.randint(0, int(L), size=lanes_per_seq)}
        for p in sorted(picks):
            slots.append(s)
            poss.append(p)
    return np.asarray(slots, np.int32), np.asarray(poss, np.int32)


def _full_prefill_attention(q, k_full, v_full, seq_lens, scale):
    """Last-position attention on the CONTIGUOUS layout with the exact
    op sequence of the paged path (dot_general dims,
    divide-after-matmul) so equality is bitwise when the page
    indirection is exact. Copied from tests/test_serve.py — per-lane
    here: each 'batch' row is one lane."""
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


# ------------------------------------------------- ragged kernel parity
@pytest.mark.parametrize("batch", [1, 3, 8])
def test_paged_ragged_bitwise_vs_full_prefill(batch):
    """Every lane — an arbitrary (sequence, position) query — must
    equal full-prefill attention at that position bit-for-bit: the
    slot indirection and per-lane masking are pure data movement."""
    rng = np.random.RandomState(10 + batch)
    kp, vp, table, lens, k_full, v_full = _ragged_setup(batch, batch)
    slots, poss = _lanes_for(lens, rng)
    t = len(slots)
    q = rng.randn(t, 4, 8).astype(np.float32)
    scale = 1.0 / np.sqrt(q.shape[-1])
    out = paged_attention_ragged_v2(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(table), jnp.asarray(slots), jnp.asarray(poss + 1),
        scale=scale, use_pallas=False)
    ref = _full_prefill_attention(
        jnp.asarray(q), jnp.asarray(k_full[slots]),
        jnp.asarray(v_full[slots]), jnp.asarray(poss + 1), scale)
    assert out.dtype == ref.dtype
    assert np.array_equal(np.asarray(out), np.asarray(ref)), (
        np.abs(np.asarray(out) - np.asarray(ref)).max())


@pytest.mark.parametrize("batch", [1, 3])
def test_paged_ragged_pallas_interpret_matches_jnp(batch):
    rng = np.random.RandomState(60 + batch)
    kp, vp, table, lens, _, _ = _ragged_setup(batch, 200 + batch)
    slots, poss = _lanes_for(lens, rng)
    t = len(slots)
    q = rng.randn(t, 4, 8).astype(np.float32)
    scale = 1.0 / np.sqrt(q.shape[-1])
    ref = paged_attention_ragged_v2(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(table), jnp.asarray(slots), jnp.asarray(poss + 1),
        scale=scale, use_pallas=False)
    out = paged_attention_ragged_v2(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(table), jnp.asarray(slots), jnp.asarray(poss + 1),
        scale=scale, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-6, atol=2e-6)


# --------------------------------------------------- prefix cache (host)
def test_kv_cache_prefix_share_lifecycle():
    """Commit -> match -> attach (refcount 2) -> free one owner (page
    survives) -> free both (page parks in the LRU, still matchable) ->
    pool pressure evicts it (hash dropped)."""
    cfg = KVCacheConfig(num_layers=1, num_heads=2, head_dim=4,
                        page_size=4, num_pages=7, max_seqs=3,
                        max_seq_len=24)
    cache = PagedKVCache(cfg)
    tokens = list(range(100, 108))          # 2 full pages
    keys = prefix_page_keys(tokens, 4, 2)
    s0 = cache.alloc_slot()
    cache.ensure_capacity(s0, 8)
    cache.advance(s0, 8)
    assert cache.match_prefix(keys) == []   # nothing committed yet
    cache.commit_page(s0, 0, keys[0])
    cache.commit_page(s0, 1, keys[1])
    pages = cache.match_prefix(keys)
    assert len(pages) == 2
    s1 = cache.alloc_slot()
    cache.attach_prefix(s1, pages, 8)
    cache.check_invariants()
    assert cache.ref(pages[0]) == 2
    assert cache.free_pages == 4
    cache.free_slot(s0)                     # shared pages survive
    cache.check_invariants()
    assert cache.ref(pages[0]) == 1
    assert cache.match_prefix(keys) == pages
    cache.free_slot(s1)                     # refcount 0: parked, not freed
    cache.check_invariants()
    assert cache.match_prefix(keys) == pages
    assert cache.free_pages == cfg.usable_pages  # still reclaimable
    # pool pressure evicts parked pages and drops their hashes
    s2 = cache.alloc_slot()
    cache.ensure_capacity(s2, 24)           # all 6 usable pages
    cache.check_invariants()
    assert cache.match_prefix(keys) == []
    assert cache.stats["prefix_evictions"] >= 2


def test_kv_pool_stress_property():
    """Random submit/chunk/decode/finish/preempt traffic against
    check_invariants: refcounts sum correctly, no page leaks or
    double-frees, exhaustion preempts and later admits again. Prompts
    draw from a few shared prefixes so the run exercises real sharing,
    and the pool is sized to force preemptions."""
    rng = np.random.RandomState(11)
    cfg = KVCacheConfig(num_layers=1, num_heads=2, head_dim=4,
                        page_size=4, num_pages=17, max_seqs=4,
                        max_seq_len=40)
    cache = PagedKVCache(cfg)
    sched = ContinuousBatchingScheduler(cache, prefill_token_budget=16)
    prefixes = [list(rng.randint(0, 9, size=12)) for _ in range(3)]
    reqs = []
    steps = 0
    while sched.has_work() or len(reqs) < 40:
        steps += 1
        assert steps < 5000, "stress driver wedged"
        if len(reqs) < 40 and rng.rand() < 0.4:
            pre = prefixes[rng.randint(len(prefixes))]
            prompt = pre + list(rng.randint(0, 9,
                                            size=rng.randint(1, 8)))
            reqs.append(sched.submit(prompt, int(rng.randint(1, 14))))
        if not sched.has_work():
            continue
        plan = sched.schedule()
        assert plan.chunks
        for ch in plan.chunks:
            sched.chunk_dispatched(ch)
            sched.chunk_landed(ch)
        for ch in plan.chunks:
            if ch.emits:
                ch.req.out_tokens.append(int(rng.randint(0, 9)))
                if ch.req.is_done():
                    sched.finish(ch.req)
        cache.check_invariants()
    assert all(len(r.out_tokens) == r.max_new_tokens for r in reqs)
    assert cache.free_pages == cfg.usable_pages
    assert cache.free_slots == cfg.max_seqs
    # the pool is tight enough to preempt and the prompts share
    # prefixes — both paths must actually have run
    assert sched.stats["preemptions"] > 0
    assert sched.stats["prefix_hit_tokens"] > 0
    assert cache.stats["prefix_evictions"] >= 0  # counter sane


def test_kv_pool_stress_with_rollback():
    """The stress property test with SPECULATION in the traffic:
    random drafts ride on decode chunks, a simulated verifier accepts
    random prefixes (so complete_spec_chunk advances + rolls back every
    step), and gratuitous ensure_capacity/rollback pairs are
    interleaved — refcount partition, hash bijection and the
    hashed-page coverage rule (no rolled-back page is
    prefix-matchable) must hold at every quiescent point."""
    from flexflow_tpu.serve import Drafter

    rng = np.random.RandomState(23)

    class RandomDrafter(Drafter):
        def draft(self, tokens, k):
            n = int(rng.randint(0, k + 1))
            return [int(t) for t in rng.randint(0, 9, size=n)]

    cfg = KVCacheConfig(num_layers=1, num_heads=2, head_dim=4,
                        page_size=4, num_pages=17, max_seqs=4,
                        max_seq_len=40)
    cache = PagedKVCache(cfg)
    sched = ContinuousBatchingScheduler(cache, prefill_token_budget=16,
                                        spec_tokens=3,
                                        drafter=RandomDrafter())
    prefixes = [list(rng.randint(0, 9, size=12)) for _ in range(3)]
    reqs = []
    steps = 0
    while sched.has_work() or len(reqs) < 40:
        steps += 1
        assert steps < 5000, "stress driver wedged"
        if len(reqs) < 40 and rng.rand() < 0.4:
            pre = prefixes[rng.randint(len(prefixes))]
            prompt = pre + list(rng.randint(0, 9,
                                            size=rng.randint(1, 8)))
            reqs.append(sched.submit(prompt, int(rng.randint(1, 14))))
        if not sched.has_work():
            continue
        plan = sched.schedule()
        assert plan.chunks
        for ch in plan.chunks:
            if not ch.draft_tokens:
                sched.chunk_dispatched(ch)
                sched.chunk_landed(ch)
        for ch in plan.chunks:
            if ch.draft_tokens:
                # simulated verification: the engine's emit_spec rules
                req, k = ch.req, len(ch.draft_tokens)
                matched = 0
                for j in range(k + 1):
                    if j < k and rng.rand() < 0.6:
                        tok = ch.draft_tokens[j]
                    else:
                        tok = int(rng.randint(0, 9))
                    req.out_tokens.append(tok)
                    ok = j < k and tok == ch.draft_tokens[j]
                    if ok:
                        matched += 1
                    if req.is_done() or not ok:
                        break
                sched.complete_spec_chunk(ch, matched)
                if req.is_done():
                    sched.finish(req)
            elif ch.emits:
                ch.req.out_tokens.append(int(rng.randint(0, 9)))
                if ch.req.is_done():
                    sched.finish(ch.req)
        # gratuitous speculative mapping rolled straight back: a
        # no-op for residency, never for the allocator's books
        if sched.running and rng.rand() < 0.3:
            req = list(sched.running.values())[
                rng.randint(len(sched.running))]
            cur = int(cache.seq_lens[req.slot])
            if cur > 0:
                room = cfg.pages_per_seq * cfg.page_size
                ahead = min(cur + int(rng.randint(1, 6)), room)
                if cache.pages_to_extend(req.slot, ahead) \
                        <= len(cache._free) + len(cache._lru):
                    cache.ensure_capacity(req.slot, ahead)
                    cache.rollback(req.slot, max(cur, req.num_computed))
        cache.check_invariants()
    assert all(len(r.out_tokens) >= r.max_new_tokens
               or (r.eos_token is not None) for r in reqs)
    assert cache.free_pages == cfg.usable_pages
    assert cache.free_slots == cfg.max_seqs
    assert sched.stats["spec_drafted_tokens"] > 0
    assert sched.stats["spec_accepted_tokens"] > 0
    assert cache.stats["rollback_pages"] > 0
    assert sched.stats["preemptions"] > 0
    assert sched.stats["prefix_hit_tokens"] > 0


def test_scheduler_many_slots_fast_partition():
    """Satellite regression for the O(n^2) membership scan: with many
    slots the prefill/decode partition must stay correct (sets, not
    identity scans over a list)."""
    n = 128
    cfg = KVCacheConfig(num_layers=1, num_heads=2, head_dim=4,
                        page_size=4, num_pages=1 + 2 * n, max_seqs=n,
                        max_seq_len=8)
    cache = PagedKVCache(cfg)
    sched = ContinuousBatchingScheduler(cache, prefill_token_budget=4 * n)
    for i in range(n):
        sched.submit([i % 7 + 1, i % 5 + 1], 3)
    plan = sched.schedule()
    assert len(plan.admitted) == n
    assert plan.num_prefill_lanes == 2 * n and plan.num_decode_lanes == 0
    for ch in plan.chunks:
        sched.chunk_dispatched(ch)
        sched.chunk_landed(ch)
        ch.req.out_tokens.append(0)
    plan2 = sched.schedule()
    # every slot decodes; the partition is exact and disjoint
    assert plan2.num_decode_lanes == n and plan2.num_prefill_lanes == 0
    assert set(r.rid for r in plan2.decodes) == set(range(n))
    assert not plan2.prefills


# --------------------------------------------------------- engine e2e
@pytest.fixture(scope="module")
def lm():
    from flexflow_tpu.models.transformer import build_transformer_lm
    cfg = FFConfig(batch_size=1, kv_page_size=8, kv_num_pages=73,
                   serve_max_seqs=8, serve_prefill_budget=48)
    return build_transformer_lm(cfg, vocab_size=89, max_seq_len=64,
                                hidden=32, num_heads=4, num_layers=2,
                                ff_dim=64)


@pytest.fixture(scope="module")
def v2_engine(lm):
    from flexflow_tpu.serve import ServeEngine
    eng = ServeEngine(lm)
    eng.warmup()
    return eng


def _shared_prompts(rng, n, prefix_len=24, tail=4, vocab=89):
    prefix = list(rng.randint(1, vocab, size=prefix_len))
    return [prefix + list(rng.randint(1, vocab, size=tail))
            for _ in range(n)]


def test_prefix_cache_exact_with_hits(v2_engine):
    """A shared-prefix batch must hit the cache HARD (>= 2x fewer
    prefill tokens) and still match the no-cache reference token for
    token, without compiling anything."""
    rng = np.random.RandomState(1)
    prompts = _shared_prompts(rng, 6)
    before = v2_engine.compile_counts()
    out = v2_engine.generate(prompts, 5)
    assert v2_engine.compile_counts() == before, "serving recompiled"
    assert out == v2_engine.generate_reference(prompts, 5)
    st = v2_engine.last_stats
    assert st["prefix_hit_tokens"] > 0
    assert st["prompt_tokens_total"] >= 2 * st["prefill_tokens_computed"]


def test_prefix_cache_persists_across_generates(v2_engine):
    """The cache outlives generate(): a repeated prompt re-prefills
    only its tail (the partial last page + final token)."""
    rng = np.random.RandomState(2)
    prompts = [list(rng.randint(1, 89, size=27))]
    first = v2_engine.generate(prompts, 4)
    computed_first = v2_engine.last_stats["prefill_tokens_computed"]
    again = v2_engine.generate(prompts, 4)
    st = v2_engine.last_stats
    assert again == first
    assert st["prefix_hit_tokens"] >= 16   # two full pages of 8
    assert st["prefill_tokens_computed"] < computed_first


def test_prefix_cache_off_still_exact(lm):
    from flexflow_tpu.serve import ServeEngine
    eng = ServeEngine(lm, prefix_cache=False)
    eng.warmup()
    rng = np.random.RandomState(3)
    prompts = _shared_prompts(rng, 4)
    out = eng.generate(prompts, 4)
    assert out == eng.generate_reference(prompts, 4)
    st = eng.last_stats
    assert st["prefix_hit_tokens"] == 0
    assert st["prefill_tokens_computed"] == st["prompt_tokens_total"]


def test_chunked_prefill_long_prompt_exact():
    """A prompt longer than the whole prefill budget must chunk across
    steps (no oversized-bucket escape) and still match the reference,
    with decode lanes of other requests interleaved."""
    from flexflow_tpu.models.transformer import build_transformer_lm
    from flexflow_tpu.serve import ServeEngine
    cfg = FFConfig(batch_size=1, kv_page_size=8, kv_num_pages=49,
                   serve_max_seqs=4, serve_prefill_budget=16)
    ff = build_transformer_lm(cfg, vocab_size=61, max_seq_len=96,
                              hidden=32, num_heads=4, num_layers=2,
                              ff_dim=64)
    eng = ServeEngine(ff)
    eng.warmup()
    rng = np.random.RandomState(5)
    prompts = [list(rng.randint(1, 61, size=70)),   # >> budget of 16
               list(rng.randint(1, 61, size=5)),
               list(rng.randint(1, 61, size=40))]
    before = eng.compile_counts()
    out = eng.generate(prompts, [6, 12, 6])
    assert eng.compile_counts() == before
    assert out == eng.generate_reference(prompts, [6, 12, 6])
    # the 70-token prompt needed ceil(70/16) = 5 chunked steps minimum
    assert eng.last_stats["steps"] >= 5


def test_preemption_exact_and_counted():
    """A pool too small for the whole batch must preempt (youngest
    first), resume via the prefix cache, and still produce the exact
    reference streams."""
    from flexflow_tpu.models.transformer import build_transformer_lm
    from flexflow_tpu.serve import ServeEngine
    cfg = FFConfig(batch_size=1, kv_page_size=4, kv_num_pages=14,
                   serve_max_seqs=4, serve_prefill_budget=16)
    ff = build_transformer_lm(cfg, vocab_size=61, max_seq_len=48,
                              hidden=32, num_heads=4, num_layers=2,
                              ff_dim=64)
    eng = ServeEngine(ff)
    eng.warmup()
    rng = np.random.RandomState(7)
    prompts = [list(rng.randint(1, 61, size=rng.randint(8, 20)))
               for _ in range(4)]
    max_new = [int(rng.randint(8, 16)) for _ in range(4)]
    out = eng.generate(prompts, max_new)
    assert out == eng.generate_reference(prompts, max_new)
    assert eng.last_stats["preemptions"] > 0
    assert any(r["preemptions"] > 0
               for r in eng.last_stats["requests"])


def test_unaligned_max_seq_len_reference_not_nan_poisoned():
    """Regression: with max_seq_len NOT page-aligned (40 over 16-token
    pages) the bucket ladder used to round up past the learned
    positions (48 > 40), and jnp.take's "fill" OOB default made the
    padded position rows NaN — which poisoned every attended lane
    through 0 * NaN in the p.v product, so generate_reference emitted
    argmax-of-all-NaN (token 0) while the paged engine was right.
    Buckets now cap at max_seq_len and embeds clip, so decoding right
    up to the cap stays exact."""
    from flexflow_tpu.models.transformer import build_transformer_lm
    cfg = FFConfig(batch_size=1, kv_page_size=16, kv_num_pages=25,
                   serve_max_seqs=2, serve_prefill_budget=16)
    ff = build_transformer_lm(cfg, vocab_size=61, max_seq_len=40,
                              hidden=32, num_heads=4, num_layers=2,
                              ff_dim=64)
    from flexflow_tpu.serve import ServeEngine
    eng = ServeEngine(ff)
    assert eng.buckets[-1] == 40
    eng.warmup()
    rng = np.random.RandomState(31)
    prompts = [list(rng.randint(1, 61, size=16)),
               list(rng.randint(1, 61, size=7))]
    out = eng.generate(prompts, [24, 33])   # both reach the 40 cap
    ref = eng.generate_reference(prompts, [24, 33])
    assert out == ref
    assert [len(o) for o in out] == [24, 33]  # ran to the cap, no eos


# --------------------------------------------------------- sampling
def test_sampling_seeded_reproducible(v2_engine):
    rng = np.random.RandomState(21)
    prompts = [list(rng.randint(1, 89, size=rng.randint(2, 12)))
               for _ in range(3)]
    a = v2_engine.generate(prompts, 8, temperature=0.9, top_k=16,
                           sample_seed=42)
    b = v2_engine.generate(prompts, 8, temperature=0.9, top_k=16,
                           sample_seed=42)
    c = v2_engine.generate(prompts, 8, temperature=0.9, top_k=16,
                           sample_seed=43)
    assert a == b, "fixed seed must reproduce the streams exactly"
    assert a != c, "a different seed should diverge (vanishingly rare)"
    # sampling must not break the zero-recompile contract: the top-k
    # head is part of the one mixed program
    assert v2_engine.compile_counts()["mixed"] == 1


def test_sampling_topk1_is_greedy(v2_engine):
    """top_k=1 at any temperature is argmax — an exactness bridge
    between the sampling path and the greedy parity tests."""
    prompts = [[5, 6, 7], [11, 3]]
    greedy = v2_engine.generate(prompts, 6)
    sampled = v2_engine.generate(prompts, 6, temperature=1.7, top_k=1)
    assert sampled == greedy


def test_sampling_per_request_and_validation(v2_engine):
    prompts = [[5, 6, 7], [11, 3]]
    greedy = v2_engine.generate(prompts, 6)
    mixed = v2_engine.generate(prompts, 6, temperature=[0.0, 0.8],
                               top_k=[None, 8], sample_seed=1)
    assert mixed[0] == greedy[0], "temperature 0 lane stays greedy"
    with pytest.raises(ValueError):
        v2_engine.generate(prompts, 2, temperature=0.5,
                           top_k=v2_engine.topk_cap + 1)
    with pytest.raises(ValueError):
        v2_engine.generate(prompts, 2, temperature=-0.1)
