"""Speculative decoding: multi-token verified decode in the mixed
program, with KV rollback and adaptive drafting.

Layered like the other serve suites:
  * drafter — prompt-lookup n-gram proposals (recency vs continuation
    fullness) and the adaptive draft-length controller (windowed
    acceptance rate, auto-disable, probe recovery), pure host units.
  * cache — rollback: page release past the verified boundary, hash
    hygiene (a rolled-back page is never prefix-matchable), invariants.
  * engine — speculative generation stays token-for-token identical to
    the no-cache greedy reference on repetitive AND adversarial
    workloads (speculation changes dispatch count, never tokens),
    through eos, preemption and sampling; k=0 degenerates to the plain
    engine; zero recompiles after warmup; and the compile-event counter
    (the anti-vacuous zero-recompile gate) sees a forced new program.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from flexflow_tpu.config import CompMode, FFConfig
from flexflow_tpu.serve import (
    DraftControl,
    Drafter,
    KVCacheConfig,
    KVPool,
    PagedKVCache,
    PromptLookupDrafter,
    ServeEngine,
    prefix_page_keys,
)


# --------------------------------------------------------------- drafter
def test_prompt_lookup_basic_ngram():
    d = PromptLookupDrafter()
    # trailing [5, 6] last occurred earlier followed by 7, 8
    assert d.draft([5, 6, 7, 8, 1, 5, 6], 2) == [7, 8]
    # no earlier occurrence of anything -> no draft
    assert d.draft([1, 2, 3], 2) == []
    assert d.draft([1, 2, 3], 0) == []


def test_prompt_lookup_prefers_full_continuation():
    """On a constant run the nearest match clips its continuation at
    the end of history; an earlier occurrence must supply all k."""
    d = PromptLookupDrafter()
    assert d.draft([7] * 10, 4) == [7, 7, 7, 7]
    # periodic text: the full period is proposed, not a 1-token stub
    assert d.draft([1, 2, 3, 1, 2, 3, 1, 2, 3], 3) == [1, 2, 3]


def test_prompt_lookup_recency_wins_among_full():
    """Two occurrences can both supply k tokens: the most recent one's
    continuation is proposed (generated text drifts)."""
    d = PromptLookupDrafter(max_ngram=2)
    #         [9,1]->2        [9,1]->4 (more recent), both full
    ctx = [9, 1, 2, 0, 0, 9, 1, 4, 0, 9, 1]
    assert d.draft(ctx, 1) == [4]


def test_draft_control_adapts_and_disables():
    c = DraftControl(k_max=4, window=4, disable_below=0.25,
                     probe_every=8)
    assert c.next_k() == 4          # optimistic start
    for _ in range(4):
        c.record(4, 0)              # nothing ever accepted
    assert c.disabled
    # adversarial steady state: every drafted token is rejected; most
    # steps draft 0 and re-measure phases only ever risk 1-token drafts
    drafted = 0
    ks = []
    for _ in range(32):
        k = c.next_k()
        ks.append(k)
        if k:
            c.record(k, 0)
            drafted += k
    assert ks.count(0) >= len(ks) // 2
    assert max(ks) <= 1
    assert drafted <= 16            # vs 32 * k_max = 128 at full tilt


def test_draft_control_probe_recovers():
    c = DraftControl(k_max=4, window=4, disable_below=0.25,
                     probe_every=2)
    for _ in range(4):
        c.record(4, 0)
    assert c.disabled
    # a probe fires, its fresh measurement fully accepts -> re-enabled
    while c.next_k() == 0:
        pass
    c.record(1, 1)
    assert not c.disabled
    assert c.next_k() == 4          # rate 1.0 over the fresh window


def test_draft_control_scales_with_rate():
    c = DraftControl(k_max=8, window=4)
    c.record(8, 8)
    assert c.next_k() == 8
    c2 = DraftControl(k_max=8, window=4)
    for _ in range(4):
        c2.record(8, 2)             # rate 0.25 -> ceil(8 * 1.5 * .25)
    assert 1 <= c2.next_k() <= 4


# --------------------------------------------------------------- rollback
def _cache():
    cfg = KVCacheConfig(num_layers=1, num_heads=2, head_dim=4,
                        page_size=4, num_pages=9, max_seqs=2,
                        max_seq_len=24)
    return PagedKVCache(cfg)


def test_rollback_frees_speculative_tail():
    cache = _cache()
    s = cache.alloc_slot()
    cache.ensure_capacity(s, 6)
    cache.advance(s, 6)
    free0 = cache.free_pages
    # map two pages ahead for 8 drafted tokens, then reject them all
    cache.ensure_capacity(s, 14)
    assert cache.free_pages == free0 - 2
    released = cache.rollback(s, 6)
    assert released == 2
    assert cache.free_pages == free0
    assert cache.mapped_pages(s) == 2   # ceil(6/4)
    assert int(cache.seq_lens[s]) == 6
    cache.check_invariants()
    # partial acceptance: keep one of the two speculative pages
    cache.ensure_capacity(s, 14)
    cache.advance(s, 9)
    assert cache.rollback(s, 9) == 1
    cache.check_invariants()
    cache.free_slot(s)
    cache.check_invariants()


def test_rollback_never_leaves_tail_matchable():
    """A hashed page past (or straddling) the rollback boundary must
    leave the prefix registry — matching it later would hand a new
    prompt unverified K/V."""
    cache = _cache()
    tokens = list(range(100, 108))
    keys = prefix_page_keys(tokens, 4, 2)
    s = cache.alloc_slot()
    cache.ensure_capacity(s, 8)
    cache.advance(s, 8)
    cache.commit_page(s, 0, keys[0])
    cache.commit_page(s, 1, keys[1])
    assert len(cache.match_prefix(keys)) == 2
    # rewind past page 1 entirely: its hash must drop with it
    cache.rollback(s, 4)
    assert len(cache.match_prefix(keys)) == 1
    cache.check_invariants()
    # re-grow, recommit, then rewind INTO page 1 (boundary mid-page):
    # the page stays mapped but its full-content hash now overclaims
    cache.ensure_capacity(s, 8)
    cache.advance(s, 8)
    cache.commit_page(s, 1, keys[1])
    cache.rollback(s, 6)
    assert len(cache.match_prefix(keys)) == 1
    cache.check_invariants()
    cache.free_slot(s)
    cache.check_invariants()


def test_rollback_shared_page_survives_for_other_owner():
    cache = _cache()
    tokens = list(range(50, 58))
    keys = prefix_page_keys(tokens, 4, 2)
    s0 = cache.alloc_slot()
    cache.ensure_capacity(s0, 8)
    cache.advance(s0, 8)
    cache.commit_page(s0, 0, keys[0])
    cache.commit_page(s0, 1, keys[1])
    pages = cache.match_prefix(keys)
    s1 = cache.alloc_slot()
    cache.attach_prefix(s1, pages, 8)
    # owner 1 rolls back; owner 0 still fully covers both pages, so
    # they stay mapped, hashed and matchable
    cache.rollback(s1, 4)
    assert cache.ref(pages[1]) == 1
    assert cache.match_prefix(keys) == pages
    cache.check_invariants()
    cache.free_slot(s0)
    cache.free_slot(s1)
    cache.check_invariants()


# --------------------------------------------------------------- engines
@pytest.fixture(scope="module")
def lm():
    from flexflow_tpu.models.transformer import build_transformer_lm
    cfg = FFConfig(batch_size=1, kv_page_size=8, kv_num_pages=97,
                   serve_max_seqs=4, serve_prefill_budget=64)
    return build_transformer_lm(cfg, vocab_size=89, max_seq_len=192,
                                hidden=32, num_heads=4, num_layers=2,
                                ff_dim=64)


@pytest.fixture(scope="module")
def echo_lm():
    """The bench's repetitive-text generator: residual writers zeroed,
    head tied to token embeddings — greedy decode echoes the trailing
    token (see tools/serve_bench._make_echo_lm)."""
    from flexflow_tpu.models.transformer import build_transformer_lm
    cfg = FFConfig(batch_size=1, kv_page_size=8, kv_num_pages=97,
                   serve_max_seqs=4, serve_prefill_budget=64)
    ff = build_transformer_lm(cfg, vocab_size=89, max_seq_len=192,
                              hidden=32, num_heads=4, num_layers=2,
                              ff_dim=64)
    ff.compile(comp_mode=CompMode.INFERENCE)
    p = ff.state.params
    for i in range(2):
        attn = p[f"layer{i}_attn"]
        attn["wo"] = jnp.zeros_like(attn["wo"])
        attn["bo"] = jnp.zeros_like(attn["bo"])
        ff2 = p[f"layer{i}_ff2"]
        ff2["kernel"] = jnp.zeros_like(ff2["kernel"])
        ff2["bias"] = jnp.zeros_like(ff2["bias"])
    p["pos_embed"]["kernel"] = p["pos_embed"]["kernel"] * 0.15
    p["lm_head"]["kernel"] = 4.0 * p["tok_embed"]["kernel"].T
    p["lm_head"]["bias"] = jnp.zeros_like(p["lm_head"]["bias"])
    return ff


@pytest.fixture(scope="module")
def spec_engine(lm):
    eng = ServeEngine(lm, spec_tokens=6)
    eng.warmup()
    return eng


def test_spec_exact_on_repetitive_and_reduces_steps(echo_lm):
    """The headline contract: on repetitive text the speculative
    engine dispatches FAR fewer decode steps for the bit-identical
    token streams, compiling nothing after warmup."""
    eng = ServeEngine(echo_lm, spec_tokens=6)
    eng.warmup()
    base = ServeEngine(echo_lm, spec_tokens=0)
    base.warmup()
    rng = np.random.RandomState(3)
    prompts = [list(rng.randint(1, 89, size=rng.randint(4, 12)))
               for _ in range(4)]
    before = eng.compile_counts()
    out = eng.generate(prompts, 32)
    assert eng.compile_counts() == before, "speculation recompiled"
    ref = eng.generate_reference(prompts, 32)
    assert out == ref
    assert base.generate(prompts, 32) == ref
    st = eng.last_stats
    assert st["spec_accepted_tokens"] > 0
    assert st["decode_steps"] * 2 <= base.last_stats["decode_steps"]
    assert st["steps_per_decode_token"] < 0.6


def test_spec_exact_on_adversarial_and_autodisables(lm):
    """A drafter that is always wrong costs correctness nothing, and
    the windowed acceptance rate drives every request's draft length
    to 0 (speculation pays for itself or turns itself off)."""
    class WrongDrafter(Drafter):
        def draft(self, tokens, k):
            return [(tokens[-1] + 37) % 89 or 1] * k

    eng = ServeEngine(lm, spec_tokens=6, drafter=WrongDrafter())
    eng.warmup()
    rng = np.random.RandomState(5)
    prompts = [list(rng.randint(1, 89, size=rng.randint(4, 24)))
               for _ in range(4)]
    out = eng.generate(prompts, 48)
    assert out == eng.generate_reference(prompts, 48)
    st = eng.last_stats
    # (the +37 shift can collide with the true argmax only by accident;
    # what matters is that almost everything was rejected)
    assert st["spec_acceptance"] <= 0.1
    # auto-disable: after the first windows fill, steps mostly draft 0,
    # so drafted tokens stay FAR below steps * k_max
    assert st["spec_drafted_tokens"] < 0.3 * 6 * st["decode_steps"] * 4


def test_spec_natural_text_exact(spec_engine):
    """Random-weight LM, mixed ragged prompts: partially-accepted
    drafts, rejections and rollbacks — outputs stay the reference's."""
    rng = np.random.RandomState(11)
    prompts = [list(rng.randint(1, 89, size=rng.randint(2, 40)))
               for _ in range(6)]
    max_new = [int(rng.randint(1, 32)) for _ in range(6)]
    before = spec_engine.compile_counts()
    out = spec_engine.generate(prompts, max_new)
    assert spec_engine.compile_counts() == before
    assert out == spec_engine.generate_reference(prompts, max_new)
    assert spec_engine.cache.stats["rollback_pages"] >= 0


def test_spec_eos_inside_draft_exact(spec_engine):
    """EOS emitted from an ACCEPTED draft must stop the stream exactly
    where sequential decode would — accepted-after-eos tokens drop."""
    rng = np.random.RandomState(13)
    prompts = [[7, 7, 7, 7, 7, 7], list(rng.randint(1, 89, size=9))]
    ref_free = spec_engine.generate_reference(prompts, 12)
    eos = ref_free[0][min(2, len(ref_free[0]) - 1)]
    out = spec_engine.generate(prompts, 12, eos_token=eos)
    assert out == spec_engine.generate_reference(prompts, 12,
                                                 eos_token=eos)


def test_spec_k0_is_todays_engine(lm):
    """An engine with spec_tokens=0 and a spec-ENABLED engine whose
    drafter never proposes are the SAME engine: every decode chunk
    carries zero drafts, so token streams, step counts and stats all
    match bit-for-bit (speculation off == speculation inert)."""
    class NeverDrafter(Drafter):
        def draft(self, tokens, k):
            return []

    e_k0 = ServeEngine(lm, spec_tokens=0)
    e_k0.warmup()
    e_inert = ServeEngine(lm, spec_tokens=6, drafter=NeverDrafter())
    e_inert.warmup()
    rng = np.random.RandomState(17)
    prompts = [list(rng.randint(1, 89, size=rng.randint(2, 30)))
               for _ in range(5)]
    a = e_k0.generate(prompts, 16)
    b = e_inert.generate(prompts, 16)
    assert a == b
    sa, sb = e_k0.last_stats, e_inert.last_stats
    assert sa["steps"] == sb["steps"]
    assert sa["decode_steps"] == sb["decode_steps"]
    assert sa["spec_drafted_tokens"] == sb["spec_drafted_tokens"] == 0
    assert sa["steps_per_decode_token"] == sb["steps_per_decode_token"] \
        == 1.0


def test_no_spec_decode_config_resolves_to_zero():
    """--no-spec-decode / serve_spec_decode=False must reach the
    engine: spec_tokens resolves to 0 (no manual override), and the
    engine still serves exactly."""
    from flexflow_tpu.models.transformer import build_transformer_lm
    cfg = FFConfig(batch_size=1, kv_page_size=8, kv_num_pages=49,
                   serve_max_seqs=4, serve_prefill_budget=32,
                   argv=["--no-spec-decode"])
    assert cfg.serve_spec_decode is False
    ff = build_transformer_lm(cfg, vocab_size=61, max_seq_len=64,
                              hidden=32, num_heads=4, num_layers=2,
                              ff_dim=64)
    eng = ServeEngine(ff)
    assert eng.spec_tokens == 0
    eng.warmup()
    prompts = [[5, 6, 7, 5, 6, 7], [11, 3]]
    out = eng.generate(prompts, 6)
    assert out == eng.generate_reference(prompts, 6)
    st = eng.last_stats
    assert st["spec_drafted_tokens"] == 0
    assert st["steps_per_decode_token"] == 1.0
    # and the dial itself: serve_spec_tokens=0 with the switch ON
    cfg2 = FFConfig(argv=["--spec-tokens", "0"])
    assert cfg2.serve_spec_decode and cfg2.serve_spec_tokens == 0


def test_spec_preempt_resume_mid_speculation():
    """A pool too small for the batch preempts while speculation is
    active; resumed requests keep drafting and the streams still equal
    the reference's."""
    from flexflow_tpu.models.transformer import build_transformer_lm
    cfg = FFConfig(batch_size=1, kv_page_size=4, kv_num_pages=14,
                   serve_max_seqs=4, serve_prefill_budget=16)
    ff = build_transformer_lm(cfg, vocab_size=61, max_seq_len=48,
                              hidden=32, num_heads=4, num_layers=2,
                              ff_dim=64)
    eng = ServeEngine(ff, spec_tokens=4)
    eng.warmup()
    rng = np.random.RandomState(7)
    prompts = [list(rng.randint(1, 61, size=rng.randint(8, 20)))
               for _ in range(4)]
    max_new = [int(rng.randint(8, 16)) for _ in range(4)]
    out = eng.generate(prompts, max_new)
    assert out == eng.generate_reference(prompts, max_new)
    st = eng.last_stats
    assert st["preemptions"] > 0
    assert st["spec_drafted_tokens"] > 0


def test_spec_topk1_sampling_speculates_exact(spec_engine):
    """top_k=1 sampling is deterministic (the drawn sample IS the top
    logit), so it speculates under the verify-against-the-drawn-sample
    rule and matches both greedy and its own non-speculative run."""
    prompts = [[7] * 8, [5, 6, 7, 5, 6, 7, 5, 6]]
    greedy = spec_engine.generate(prompts, 10)
    sampled = spec_engine.generate(prompts, 10, temperature=1.3, top_k=1)
    assert sampled == greedy
    # temperature>0 with top_k>1 must NOT speculate (k=0 this PR)
    spec_engine.generate(prompts, 6, temperature=0.8, top_k=8,
                         sample_seed=3)
    assert spec_engine.last_stats["spec_drafted_tokens"] == 0


def test_spec_zero_recompiles_after_warmup(spec_engine):
    """Speculation only changes how the fixed lanes are SPENT: no new
    shapes, no new programs, on any workload in this suite."""
    counts = spec_engine.compile_counts()
    assert counts == {"mixed": 1, "export": 0, "import": 0,
                      "adapter": 0}


# ------------------------------------------------- compile-event counter
def test_compile_counter_sees_forced_new_signature(lm):
    """The anti-vacuous regression: a genuinely new program signature
    MUST increment compile_counts (jax.monitoring backend-compile
    events attributed to the call, with the shape-signature floor)."""
    eng = ServeEngine(lm)
    eng.warmup()
    c0 = eng.compile_counts()["mixed"]
    assert c0 == 1
    c = eng.cache_cfg
    pool = KVPool.alloc(c)                     # a throwaway, donated
    t = 2                                      # not the mixed width
    z = jnp.zeros((t,), jnp.int32)
    pts = jnp.zeros((c.max_seqs, c.pages_per_seq), jnp.int32)
    eng._call_counted("mixed", eng._mixed_jit, eng.params, pool,
                      z, z, z, z, pts, z, jnp.ones((t,), jnp.int32), z,
                      z - 1, z)
    assert eng.compile_counts()["mixed"] == c0 + 1
    if eng._events_ok:   # jax.monitoring present: the EVENT path saw it
        assert eng._compiles["mixed"] == 2
