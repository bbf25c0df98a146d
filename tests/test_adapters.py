"""Multi-tenant LoRA adapter serving (serve/adapters.py).

Layered like tests/test_serve_v2.py:
  * pool — slot-state property test driving random register/acquire/
    release/evict churn against AdapterPool.check_invariants, plus the
    admission-block and re-registration contracts.
  * exactness — rank padding contributes exactly zero; a mixed-tenant
    batch (>= 3 adapters + base lanes in ONE step) is token-identical
    to each tenant's merged-weight reference, greedy and top_k=1,
    across arrival orders, under eviction pressure, and with zero
    recompiles.
  * tenancy — tenant-salted prefix keys are disjoint, so equal
    prompts under different adapters never share cache pages.
  * search — the cost model prices the adapter gather + matmuls and
    the pool's HBM term, and the cost-cache fingerprint misses when
    either adapter knob changes (stale pre-adapter rows cannot
    resurrect).
"""

import numpy as np
import pytest

from flexflow_tpu.config import FFConfig
from flexflow_tpu.serve.adapters import (
    AdapterConfig,
    AdapterPool,
    make_tenant_adapters,
    merge_adapter_params,
    tenant_prefix_salt,
)


def _pool_cfg(slots=4, rank=4):
    return AdapterConfig(num_layers=2, hidden=32, num_heads=4,
                         head_dim=8, ff_dim=64, rank=rank,
                         num_slots=slots + 1)


def _weights(rank=4, ff=64, seed=0):
    return make_tenant_adapters(num_layers=2, hidden=32, num_heads=4,
                                head_dim=8, ff_dim=ff, rank=rank,
                                tenants=1, seed=seed)[1][0]


# ------------------------------------------------------------- pool
def test_pool_lifecycle_hit_miss_evict():
    pool = AdapterPool(_pool_cfg(slots=2))
    pool.register(1, _weights(), scale=0.5)
    pool.register(2, _weights(seed=1), scale=0.5)
    pool.register(3, _weights(seed=2), scale=0.5)
    s1 = pool.acquire(1)                  # miss -> load
    assert s1 is not None and pool.take_pending() == [(s1, 1)]
    assert pool.acquire(1) == s1          # hit, refcount 2
    s2 = pool.acquire(2)                  # second slot
    assert s2 is not None and s2 != s1
    assert pool.acquire(3) is None        # both mapped: admission blocks
    assert pool.stats["blocked_admissions"] == 1
    pool.release(2)                       # slot 2 parks in the LRU
    s3 = pool.acquire(3)                  # evicts tenant 2's slot
    assert s3 == s2 and pool.stats["evictions"] == 1
    assert not pool.resident(2) and pool.resident(3)
    # the evicted-then-reassigned slot must load tenant 3, and ONLY 3
    assert pool.take_pending() == [(s3, 3)]
    pool.check_invariants()


def test_pool_register_contracts():
    pool = AdapterPool(_pool_cfg())
    with pytest.raises(ValueError):
        pool.register(0, _weights())      # tenant 0 is the base model
    pool.register(1, _weights(rank=2), scale=0.5)   # true rank <= pool
    with pytest.raises(ValueError):
        pool.register(2, _weights(rank=8))          # rank > pool rank
    s = pool.acquire(1)
    assert s is not None
    with pytest.raises(ValueError):
        pool.register(1, _weights(seed=3))  # resident: slab would stale
    pool.release(1)
    with pytest.raises(KeyError):
        pool.acquire(9)                   # unregistered tenant
    assert pool.registered() == (1,)


def test_pool_property_random_churn():
    """Seeded random interleaving of every pool operation; the
    free/cached/mapped partition, refcounts, and registry bijection
    must hold after each step (the PagedKVCache property-test
    idiom)."""
    rng = np.random.RandomState(1234)
    pool = AdapterPool(_pool_cfg(slots=3))
    live = []                             # acquired (tenant) multiset
    registered = set()
    next_tenant = 1
    for step in range(400):
        op = rng.randint(4)
        if op == 0 and len(registered) < 12:
            pool.register(next_tenant, _weights(seed=next_tenant),
                          scale=0.25)
            registered.add(next_tenant)
            next_tenant += 1
        elif op == 1 and registered:
            t = int(rng.choice(sorted(registered)))
            s = pool.acquire(t)
            if s is not None:
                live.append(t)
        elif op == 2 and live:
            t = live.pop(rng.randint(len(live)))
            pool.release(t)
        elif op == 3:
            pool.take_pending()
        pool.check_invariants()
    for t in live:
        pool.release(t)
    pool.check_invariants()


def test_pool_byte_budget_sizes_slots():
    cfg = FFConfig(adapter_rank=4, adapter_pool_mb=0.5,
                   serve_max_seqs=8)
    ac = AdapterConfig.from_ff(cfg, num_layers=2, hidden=32,
                               num_heads=4, head_dim=8, ff_dim=64)
    assert ac.usable_slots == int(0.5 * (1 << 20)) // ac.slot_device_bytes
    assert ac.pool_bytes == ac.num_slots * ac.slot_bytes
    # sharded pools hold more tenants at the same per-chip budget
    ac2 = AdapterConfig.from_ff(cfg, num_layers=2, hidden=32,
                                num_heads=4, head_dim=8, ff_dim=64,
                                tensor_parallel=2)
    assert ac2.usable_slots > ac.usable_slots


# --------------------------------------------------------- engine e2e
VOCAB = 89


@pytest.fixture(scope="module")
def base_setup():
    """One adapter-armed engine + 3 registered tenants + the shared
    base params every merged-weight reference folds from."""
    from flexflow_tpu.models.transformer import build_transformer_lm
    from flexflow_tpu.serve import ServeEngine
    cfg = FFConfig(batch_size=1, kv_page_size=8, kv_num_pages=73,
                   serve_max_seqs=8, serve_prefill_budget=48,
                   adapter_rank=4)
    lm = build_transformer_lm(cfg, vocab_size=VOCAB, max_seq_len=64,
                              hidden=32, num_heads=4, num_layers=2,
                              ff_dim=64)
    eng = ServeEngine(lm)
    eng.warmup()
    adapters = make_tenant_adapters(num_layers=2, hidden=32,
                                    num_heads=4, head_dim=8, ff_dim=64,
                                    rank=4, tenants=3, seed=7)
    for t, (w, sc) in adapters.items():
        eng.register_adapter(t, w, scale=sc)
    return eng, adapters


def _merged_refs(eng, adapters, prompts, tenants, max_new):
    """Per-request greedy streams from the per-tenant merged-weight
    oracle (what a weight-swap server would emit)."""
    base = eng.params
    out = []
    try:
        for p, t in zip(prompts, tenants):
            if t == 0:
                eng.params = base
            else:
                w, sc = adapters[t]
                eng.params = merge_adapter_params(base, w, sc)
            out.append(eng.generate_reference([p], [max_new])[0])
    finally:
        eng.params = base
    return out


def test_mixed_tenant_batch_matches_merged_references(base_setup):
    """>= 3 adapters + base lanes decode in ONE mixed step and every
    stream equals its tenant's merged-weight reference, with zero
    recompiles — the tentpole acceptance gate."""
    eng, adapters = base_setup
    rng = np.random.RandomState(11)
    prompts = [list(rng.randint(1, VOCAB, size=rng.randint(4, 20)))
               for _ in range(6)]
    tenants = [1, 2, 3, 0, 2, 1]
    before = eng.compile_counts()
    out = eng.generate(prompts, 6, tenant_ids=tenants)
    assert eng.compile_counts() == before, "adapter serving recompiled"
    assert out == _merged_refs(eng, adapters, prompts, tenants, 6)
    st = eng.last_stats["adapter_pool"]
    assert st["resident_tenants"] == 3 and st["loads"] >= 3
    eng.adapters.check_invariants()


def test_arrival_order_invariant_and_topk1(base_setup):
    """Shuffled arrival order changes nothing: same per-tenant streams,
    still zero recompiles; top_k=1 sampling (argmax by construction)
    matches the greedy oracle through the sampling path."""
    eng, adapters = base_setup
    rng = np.random.RandomState(13)
    prompts = [list(rng.randint(1, VOCAB, size=rng.randint(4, 16)))
               for _ in range(5)]
    tenants = [3, 0, 1, 2, 3]
    refs = _merged_refs(eng, adapters, prompts, tenants, 5)
    before = eng.compile_counts()
    order = [4, 2, 0, 3, 1]
    out = eng.generate([prompts[i] for i in order], 5,
                       tenant_ids=[tenants[i] for i in order])
    assert out == [refs[i] for i in order]
    sampled = eng.generate(prompts, 5, tenant_ids=tenants,
                           temperature=0.7, top_k=1, sample_seed=3)
    assert sampled == refs
    assert eng.compile_counts() == before


def test_prefix_hits_stay_tenant_local(base_setup):
    """Two tenants sharing a byte-identical prompt prefix must NOT
    share pages (adapted K/V differs), while a same-tenant repeat
    still hits — and every stream stays exact."""
    eng, adapters = base_setup
    rng = np.random.RandomState(17)
    prefix = list(rng.randint(1, VOCAB, size=24))
    prompts = [prefix + list(rng.randint(1, VOCAB, size=4))
               for _ in range(4)]
    tenants = [1, 1, 2, 0]
    out = eng.generate(prompts, 5, tenant_ids=tenants)
    assert out == _merged_refs(eng, adapters, prompts, tenants, 5)
    # same-tenant pair shares the prefix; cross-tenant pairs must not,
    # so hits stay strictly below the all-shared ceiling
    st = eng.last_stats
    assert 0 < st["prefix_hit_tokens"] <= 24


def test_eviction_pressure_and_preemption_stay_exact():
    """A 2-slot pool serving 4 tenants over a KV pool small enough to
    preempt: adapter slots churn (evictions + blocked admissions),
    requests bounce and resume, and every stream still matches its
    merged-weight reference with zero recompiles."""
    from flexflow_tpu.models.transformer import build_transformer_lm
    from flexflow_tpu.serve import ServeEngine
    cfg = FFConfig(batch_size=1, kv_page_size=4, kv_num_pages=18,
                   serve_max_seqs=4, serve_prefill_budget=16,
                   adapter_rank=4, adapter_pool_mb=0.03)
    lm = build_transformer_lm(cfg, vocab_size=61, max_seq_len=48,
                              hidden=32, num_heads=4, num_layers=2,
                              ff_dim=64)
    eng = ServeEngine(lm)
    assert eng.adapter_cfg.usable_slots == 2
    eng.warmup()
    adapters = make_tenant_adapters(num_layers=2, hidden=32,
                                    num_heads=4, head_dim=8, ff_dim=64,
                                    rank=4, tenants=4, seed=23)
    for t, (w, sc) in adapters.items():
        eng.register_adapter(t, w, scale=sc)
    rng = np.random.RandomState(29)
    prompts = [list(rng.randint(1, 61, size=rng.randint(6, 16)))
               for _ in range(8)]
    tenants = [1, 2, 3, 4, 1, 3, 4, 2]
    max_new = [int(rng.randint(4, 10)) for _ in range(8)]
    before = eng.compile_counts()
    out = eng.generate(prompts, max_new, tenant_ids=tenants)
    assert eng.compile_counts() == before
    base = eng.params
    for i, (p, t) in enumerate(zip(prompts, tenants)):
        w, sc = adapters[t]
        eng.params = merge_adapter_params(base, w, sc)
        assert out[i] == eng.generate_reference([p], [max_new[i]])[0]
    eng.params = base
    pool = eng.last_stats["adapter_pool"]
    assert pool["evictions"] > 0
    eng.adapters.check_invariants()


def test_rank_padding_exact(base_setup):
    """A true-rank-2 adapter registered into the rank-4 pool decodes
    identically to its (unpadded) rank-2 merged reference: the padded
    rows/columns of zeros contribute exactly nothing."""
    eng, _ = base_setup
    w, sc = make_tenant_adapters(num_layers=2, hidden=32, num_heads=4,
                                 head_dim=8, ff_dim=64, rank=2,
                                 tenants=1, seed=41)[1]
    eng.register_adapter(7, w, scale=sc)
    rng = np.random.RandomState(43)
    prompts = [list(rng.randint(1, VOCAB, size=10)) for _ in range(2)]
    out = eng.generate(prompts, 6, tenant_ids=[7, 0])
    base = eng.params
    eng.params = merge_adapter_params(base, w, sc)
    ref = eng.generate_reference([prompts[0]], [6])[0]
    eng.params = base
    assert out[0] == ref
    assert out[1] == eng.generate_reference([prompts[1]], [6])[0]


def test_unregistered_tenant_rejected_at_submit(base_setup):
    eng, _ = base_setup
    with pytest.raises(ValueError, match="no registered adapter"):
        eng.generate([[1, 2, 3]], 3, tenant_ids=[99])
    # the failed submit must not leak pool state
    eng.adapters.check_invariants()


# ----------------------------------------------------------- tenancy
def test_tenant_salt_disjoint_keys():
    from flexflow_tpu.serve import prefix_page_keys
    toks = list(range(1, 33))
    base = prefix_page_keys(toks, 8, 4)
    t1 = prefix_page_keys(toks, 8, 4, prev=tenant_prefix_salt(1))
    t2 = prefix_page_keys(toks, 8, 4, prev=tenant_prefix_salt(2))
    assert tenant_prefix_salt(0) == b""
    assert base == prefix_page_keys(toks, 8, 4,
                                    prev=tenant_prefix_salt(0))
    assert not (set(base) & set(t1)) and not (set(t1) & set(t2))


# ------------------------------------------------------------ search
def test_cost_model_prices_adapters():
    from flexflow_tpu.search.cost_model import ServeArch, \
        serve_step_tasks, serve_device_bytes
    from flexflow_tpu.search.machine_model import (
        MachineSpec, TPUMachineModel)
    mm = TPUMachineModel(spec=MachineSpec.v5e(8))
    base = ServeArch(num_layers=2, hidden=256, num_heads=8,
                     head_dim=32, ff_dim=1024, vocab=32000)
    armed = ServeArch(num_layers=2, hidden=256, num_heads=8,
                      head_dim=32, ff_dim=1024, vocab=32000,
                      adapter_rank=8, adapter_slots=16)
    t_base = serve_step_tasks(base, 1, mm, lanes=8)
    t_armed = serve_step_tasks(armed, 1, mm, lanes=8)
    names = {t.name for t in t_armed}
    assert "adapter_gather" in names
    assert "adapter_gather" not in {t.name for t in t_base}
    # the LoRA matmul flops fold into the existing layer tasks
    by_name = {t.name: t for t in t_base}
    for t in t_armed:
        if t.name in by_name and t.name.startswith("l0"):
            assert t.seconds >= by_name[t.name].seconds
    assert sum(t.seconds for t in t_armed) \
        > sum(t.seconds for t in t_base)
    # the pool's HBM term scales with slots and shrinks with sharding
    assert serve_device_bytes(armed, 1) > serve_device_bytes(base, 1)
    assert serve_device_bytes(armed, 1) > serve_device_bytes(armed, 4)


def test_fingerprint_misses_on_adapter_knobs():
    """Regression gate: the cost-cache fingerprint folds both adapter
    knobs, so rows priced pre-adapters (or at another pool size) can
    never resurrect."""
    from flexflow_tpu.search.cost_model import ServeArch
    from flexflow_tpu.search.serve_place import _serve_fingerprint
    from flexflow_tpu.search.machine_model import (
        MachineSpec, TPUMachineModel)
    mm = TPUMachineModel(spec=MachineSpec.v5e(8))
    kw = dict(num_layers=2, hidden=256, num_heads=8, head_dim=32,
              ff_dim=1024, vocab=32000)
    fp0 = _serve_fingerprint(mm, ServeArch(**kw))
    fp1 = _serve_fingerprint(mm, ServeArch(adapter_rank=8,
                                           adapter_slots=16, **kw))
    fp2 = _serve_fingerprint(mm, ServeArch(adapter_rank=8,
                                           adapter_slots=32, **kw))
    assert len({fp0, fp1, fp2}) == 3
    # signature() carries the knobs too — the per-row key side
    s0 = ServeArch(**kw).signature()
    s1 = ServeArch(adapter_rank=8, adapter_slots=16, **kw).signature()
    assert s0 != s1


# ----------------------------------------------------- observability
def test_serve_metrics_tenant_label_and_adapter_counters(base_setup):
    from flexflow_tpu.utils.telemetry import serve_metrics
    eng, adapters = base_setup
    rng = np.random.RandomState(47)
    prompts = [list(rng.randint(1, VOCAB, size=8)) for _ in range(3)]
    eng.generate(prompts, 4, tenant_ids=[1, 2, 0])
    st = eng.last_stats
    m = serve_metrics(st)
    assert m.counter("serve_adapter_loads_total") \
        == st["adapter_pool"]["loads"]
    assert m.counter("serve_adapter_evictions_total") \
        == st["adapter_pool"]["evictions"]
    assert m.gauge("serve_adapter_registered_tenants") \
        == st["adapter_pool"]["registered_tenants"]
    # the tenant label folds like role=/replica=: labeled series only,
    # no double-count of the unlabeled aggregates
    m2 = serve_metrics(st, registry=m, tenant="1")
    assert m2.counter("serve_tokens_generated_total", tenant="1") \
        == st["total_new_tokens"]
    assert m2.counter("serve_tokens_generated_total") \
        == st["total_new_tokens"]


def test_serve_report_renders_adapter_block(base_setup):
    from flexflow_tpu.utils.profiling import serve_report
    eng, _ = base_setup
    rng = np.random.RandomState(53)
    eng.generate([list(rng.randint(1, VOCAB, size=8))], 3,
                 tenant_ids=[1])
    text = serve_report(eng.last_stats)
    assert "adapter pool:" in text and "adapter churn:" in text
