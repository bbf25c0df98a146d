"""Tensor-parallel sharded serving (PR 9).

Layers:
  * engine — the head-sharded mixed program over a 4-device "tensor"
    mesh produces greedy outputs TOKEN-IDENTICAL to the single-device
    engine on f32 (per-head bit identity + exact psums + the one
    logits all-gather), through prefix hits, chunked prefill,
    preemption, speculation+rollback and quantized (int8) pages, with
    zero recompiles after warmup and clean invariants/scales per step.
  * pool — head-sharded per-device accounting: page bytes divide
    exactly by the tensor degree, a kv_pool_mb budget is per-DEVICE
    HBM (so a sharded pool holds ~t× pages at the same per-chip
    budget), watermark/ladder fractions stay per-device-identical.
  * search — the paper's loop closed for inference:
    serve_place.optimize_serve prices the serve program per tensor
    degree on the v5e machine model (>= 1.5x simulated decode step at
    t=4 for the production-scale arch — the acceptance gate), resolves
    --serve-mesh auto, and a placement/dtype flip is a guaranteed
    cost-cache miss.
"""

import dataclasses

import numpy as np
import pytest

import jax

from flexflow_tpu.config import FFConfig
from flexflow_tpu.models.transformer import build_transformer_lm
from flexflow_tpu.parallel.mesh import MachineSpec, serve_tensor_mesh
from flexflow_tpu.search.cost_model import ServeArch, serve_step_tasks
from flexflow_tpu.search.machine_model import TPUMachineModel
from flexflow_tpu.search.serve_place import (candidate_degrees,
                                             optimize_serve,
                                             price_placement)
from flexflow_tpu.search.simulator import (simulate_serve_step,
                                           simulate_serve_tasks)
from flexflow_tpu.serve import ServeEngine
from flexflow_tpu.serve.kv_cache import KVCacheConfig


# --------------------------------------------------------------- helpers
def _lm(kv_dtype="float32", *, page_size=4, pool_pages=None,
        kv_pool_mb=0.0, budget=32, max_seqs=4, max_seq_len=64,
        spec=True, **cfg_kw):
    cfg = FFConfig(
        batch_size=1, kv_page_size=page_size,
        kv_num_pages=pool_pages or (1 + 16 * max_seqs),
        kv_pool_mb=kv_pool_mb, kv_dtype=kv_dtype,
        serve_max_seqs=max_seqs, serve_prefill_budget=budget,
        serve_spec_decode=spec, **cfg_kw)
    # vocab 61 and ff_dim 72 deliberately do NOT divide by 4: the
    # sharded engine must pad them (zero ff columns, -inf vocab bias)
    # without perturbing a single token
    return build_transformer_lm(cfg, vocab_size=61,
                                max_seq_len=max_seq_len, hidden=32,
                                num_heads=4, num_layers=2, ff_dim=72)


def _prompts(rng, n, lo=4, hi=28):
    return [list(rng.randint(1, 61, size=rng.randint(lo, hi)))
            for _ in range(n)]


def _big_arch(**over):
    """The Gemma-31B-class serving arch the acceptance gate prices
    (PAPERS.md: the inference-placement decision that dominates TPU
    serving cost — too big for one v5e chip at bf16)."""
    kw = dict(num_layers=48, hidden=6144, num_heads=48, head_dim=128,
              ff_dim=24576, vocab=256128, decode_lanes=32,
              prefill_lanes=512, context=2048, kv_dtype="int8",
              kv_itemsize=1.0, kv_scales=True, act_itemsize=2.0,
              act_dtype="bfloat16", param_itemsize=2.0)
    kw.update(over)
    return ServeArch(**kw)


# --------------------------------------------------- sharded engine parity
def test_sharded_token_identity_f32():
    """The tentpole gate: tp=4 greedy outputs == single-device greedy
    outputs, token for token, on f32 pages — including a warm second
    pass (prefix-cache hits attach pages another pass committed) — with
    zero recompiles after warmup."""
    ff = _lm()
    e1 = ServeEngine(ff)
    e1.warmup()
    e4 = ServeEngine(ff, tensor_parallel=4)
    counts = e4.warmup()
    rng = np.random.RandomState(0)
    prompts = _prompts(rng, 6)
    out1 = e1.generate(prompts, 6)
    out4 = e4.generate(prompts, 6)
    assert out4 == out1
    # warm pass: prefix hits on the SHARDED pool must replay the same
    # head-sharded page content
    again = e4.generate(prompts, 6)
    assert again == out1
    assert e4.last_stats["prefix_hit_tokens"] > 0
    assert e4.compile_counts() == counts
    e4.cache.check_invariants()
    # and the reference oracle transfers unchanged
    assert out4 == e4.generate_reference(prompts, 6)


def test_sharded_chunking_preemption_speculation_identity():
    """Execution-path invariance under sharding: a tight pool (page
    pressure -> watermark blocking + preemption) with speculation on
    (rejected drafts -> rollbacks) and a small chunk budget must still
    produce the single-device engine's exact stream, invariants
    checked every step."""
    rng = np.random.RandomState(1)
    prompts = _prompts(rng, 8, lo=6, hi=30)
    base_eng = ServeEngine(_lm(spec=False), spec_tokens=0)
    base_eng.warmup()
    base = base_eng.generate(prompts, 8)
    eng = ServeEngine(_lm(pool_pages=1 + 30, budget=8), spec_tokens=3,
                      tensor_parallel=4)
    eng.warmup()

    def on_step(i):
        eng.cache.check_invariants()

    assert eng.generate(prompts, 8, on_step=on_step) == base
    assert eng.last_stats["compile_counts"]["mixed"] == 1


def test_sharded_int8_pages_bit_match_single_device():
    """Quantized pools under sharding: per-row quantization is
    per-head, so each device's int8 rows are the unsharded engine's
    bits for its heads — tp=4 int8 must equal single-device int8
    token for token, with live scale audits passing per step."""
    ff = _lm("int8")
    e1 = ServeEngine(ff)
    e1.warmup()
    e4 = ServeEngine(ff, tensor_parallel=4)
    e4.warmup()
    rng = np.random.RandomState(2)
    prompts = _prompts(rng, 6)
    out1 = e1.generate(prompts, 5)
    out4 = e4.generate(prompts, 5,
                       on_step=lambda s: e4.check_kv_scales())
    assert out4 == out1
    e4.check_kv_scales()   # post-run: prefix-parked pages
    e4.cache.check_invariants()
    # the relaxed quantized gate vs the reference transfers verbatim
    e4.assert_token_parity(prompts, out4,
                           e4.generate_reference(prompts, 5),
                           what="sharded int8 outputs")


def test_sharded_mesh_validation():
    ff = _lm()
    with pytest.raises(ValueError, match="divisible"):
        ServeEngine(ff, tensor_parallel=3)   # 4 heads % 3 != 0
    with pytest.raises(ValueError, match="tensor"):
        from flexflow_tpu.parallel.mesh import make_mesh
        ServeEngine(ff, mesh=make_mesh((2,), ("data",)))
    # an explicit 1-D tensor mesh is accepted
    eng = ServeEngine(ff, mesh=serve_tensor_mesh(2))
    assert eng.tp == 2


def test_serve_mesh_config_and_cli():
    ff = _lm(serve_mesh="2")
    eng = ServeEngine(ff)
    assert eng.tp == 2 and eng.tp_mesh is not None
    rng = np.random.RandomState(3)
    prompts = _prompts(rng, 3)
    eng.warmup()
    ref = ServeEngine(_lm())
    ref.warmup()
    assert eng.generate(prompts, 4) == ref.generate(prompts, 4)
    # CLI flag and validation
    cfg = FFConfig(argv=["--serve-mesh", "auto"])
    assert cfg.serve_mesh == "auto"
    with pytest.raises(ValueError, match="serve_mesh"):
        FFConfig(serve_mesh="three")
    with pytest.raises(ValueError, match="serve_mesh"):
        FFConfig(serve_mesh="0")


def test_serve_mesh_auto_resolves_through_search():
    """--serve-mesh auto closes the loop: the engine asks
    optimize_serve which degree minimizes the simulated decode step.
    For this test-sized LM the collectives dominate any compute win,
    so the search must keep it single-device — the same pricing that
    shards the 31B-class arch (test_optimize_serve_speedup_gate)."""
    eng = ServeEngine(_lm(serve_mesh="auto"))
    assert eng.serve_placement is not None
    assert eng.tp == eng.serve_placement.tensor_parallel
    assert eng.tp == 1   # tiny model: sharding cannot pay
    assert 1 in eng.serve_placement.decode_by_degree


# ----------------------------------------------------- per-device pool math
def test_head_sharded_pool_accounting():
    c = KVCacheConfig(num_layers=2, num_heads=4, head_dim=8,
                      page_size=4, num_pages=33, max_seqs=2,
                      max_seq_len=32, tensor_parallel=4)
    assert c.heads_per_device == 1
    assert c.page_device_bytes * 4 == c.page_bytes
    assert c.pool_device_bytes * 4 == c.pool_bytes
    c.validate()
    with pytest.raises(ValueError, match="divisible"):
        KVCacheConfig(num_layers=2, num_heads=4, head_dim=8,
                      page_size=4, num_pages=33, max_seqs=2,
                      max_seq_len=32, tensor_parallel=3).validate()
    # quantized pages shard their scale rows on the same head axis:
    # device bytes still divide exactly
    q = KVCacheConfig(num_layers=2, num_heads=4, head_dim=8,
                      page_size=4, num_pages=33, max_seqs=2,
                      max_seq_len=32, kv_dtype="int8",
                      tensor_parallel=2)
    assert q.page_device_bytes * 2 == q.page_bytes


def test_kv_pool_mb_is_per_device_budget():
    """The watermark satellite: kv_pool_mb is per-DEVICE HBM, so the
    same budget holds ~t× the pages under head sharding — and every
    page-count-fraction threshold (admission watermark, ladder rungs)
    fires at the same relative per-device pressure."""
    def cfg_for(tp):
        c = FFConfig(kv_page_size=8, kv_pool_mb=0.5)
        return KVCacheConfig.from_ff(c, num_layers=2, num_heads=4,
                                     head_dim=8, max_seq_len=128,
                                     tensor_parallel=tp)
    c1, c4 = cfg_for(1), cfg_for(4)
    assert c4.usable_pages >= 4 * c1.usable_pages - 4
    # per-device bytes never exceed the budget
    assert c4.pool_device_bytes <= 0.5 * (1 << 20) + c4.page_device_bytes
    from flexflow_tpu.serve.kv_cache import PagedKVCache
    from flexflow_tpu.serve.scheduler import ContinuousBatchingScheduler
    s1 = ContinuousBatchingScheduler(PagedKVCache(c1),
                                     admit_watermark=0.1)
    s4 = ContinuousBatchingScheduler(PagedKVCache(c4),
                                     admit_watermark=0.1)
    # watermark pages scale WITH the pool: same relative pressure
    assert s4.watermark_pages >= 4 * s1.watermark_pages - 4


def test_sharding_stats_and_report():
    from flexflow_tpu.utils.profiling import serve_report
    eng = ServeEngine(_lm(), tensor_parallel=2)
    eng.warmup()
    rng = np.random.RandomState(4)
    eng.generate(_prompts(rng, 3), 3)
    sh = eng.last_stats["sharding"]
    for key in ("mesh", "tensor_parallel", "heads_per_device",
                "kv_pool_device_bytes", "collective_bytes_per_step"):
        assert key in sh, key
    assert sh["tensor_parallel"] == 2 and sh["heads_per_device"] == 2
    assert sh["kv_pool_device_bytes"] * 2 == eng.cache_cfg.pool_bytes
    assert "sharding: mesh" in serve_report(eng.last_stats)
    # single-device engines carry no sharding block
    e1 = ServeEngine(_lm())
    e1.warmup()
    e1.generate(_prompts(rng, 2), 2)
    assert e1.last_stats["sharding"] is None


# ------------------------------------------------- placement search / cost
def test_serve_step_tasks_structure():
    arch = _big_arch()
    mm = TPUMachineModel(spec=MachineSpec.v5e(8))
    t1 = serve_step_tasks(arch, 1, mm, lanes=arch.decode_lanes)
    t4 = serve_step_tasks(arch, 4, mm, lanes=arch.decode_lanes)
    assert not any(t.kind == "collective" for t in t1)
    # t>1: 2 all-reduces per layer + the embed psum + ONE all-gather
    colls = [t for t in t4 if t.kind == "collective"]
    assert len(colls) == 2 * arch.num_layers + 2
    assert sum(t.name == "logits_gather" for t in colls) == 1
    # the serve chain's critical path == its sum (strictly sequential)
    assert simulate_serve_tasks(t4) == pytest.approx(
        sum(t.seconds for t in t4))
    # compute time strictly shrinks with the degree
    c1 = sum(t.seconds for t in t1 if t.kind == "compute")
    c4 = sum(t.seconds for t in t4 if t.kind == "compute")
    assert c4 < c1 / 2


def test_optimize_serve_speedup_gate():
    """The acceptance criterion: on the v5e machine model the
    placement search's simulated decode step at t=4 is >= 1.5x better
    than t=1 for the production-scale arch, and the returned placement
    is at least as good as every degree it priced."""
    mm = TPUMachineModel(spec=MachineSpec.v5e(8))
    place = optimize_serve(_big_arch(), 8, mm=mm)
    table = place.decode_by_degree
    assert set(candidate_degrees(_big_arch(), 8)) <= set(table)
    assert table[1] / table[4] >= 1.5
    assert place.tensor_parallel > 1
    assert place.decode_step_s <= min(table.values()) + 1e-12
    assert place.speedup_vs_single() >= table[1] / table[4]


def test_optimize_serve_axis_assignment():
    """With physical torus dims on the spec, the search may lay the
    serve axis over multiple link sets — and must never return an
    assignment worse than the flat ring it also priced."""
    spec = dataclasses.replace(MachineSpec.v5e(16),
                               ici_torus_dims=(4, 4))
    mm = TPUMachineModel(spec=spec)
    arch = _big_arch(num_heads=64)
    place = optimize_serve(arch, 16, mm=mm)
    flat = simulate_serve_step(arch, place.tensor_parallel, mm)
    assert place.decode_step_s <= flat + 1e-12
    if place.tensor_parallel == 16:
        assert place.axis_dims in ((4, 4), ())


def test_serve_placement_cost_cache_miss_on_flip(tmp_path):
    """Guaranteed-miss acceptance: a placement flip changes the entry
    key, a KV/activation dtype flip changes the serve fingerprint —
    cached serve costs can never cross either boundary."""
    from flexflow_tpu.search.cost_cache import CostCache
    from flexflow_tpu.search.serve_place import _serve_fingerprint
    mm = TPUMachineModel(spec=MachineSpec.v5e(8))
    arch = _big_arch()
    # a private store: other tests in this process share the default
    # path and would have pre-warmed these very entries
    cache = CostCache.open(str(tmp_path / "serve_costcache.json"))
    fp = _serve_fingerprint(mm, arch)
    h0, m0 = cache.hits, cache.misses
    d1, p1 = price_placement(arch, 4, mm, cache=cache, fingerprint=fp)
    assert cache.misses == m0 + 1
    d2, p2 = price_placement(arch, 4, mm, cache=cache, fingerprint=fp)
    assert (d2, p2) == (d1, p1) and cache.hits == h0 + 1
    # placement flip: entry-key miss
    price_placement(arch, 8, mm, cache=cache, fingerprint=fp)
    assert cache.misses == m0 + 2
    # dtype flip: fingerprint miss (and a distinct fingerprint)
    arch_f32 = dataclasses.replace(arch, kv_dtype="float32",
                                   kv_itemsize=4.0, kv_scales=False)
    fp2 = _serve_fingerprint(mm, arch_f32)
    assert fp2 != fp
    price_placement(arch_f32, 4, mm, cache=cache, fingerprint=fp2)
    assert cache.misses == m0 + 3


def test_memory_penalty_prices_hbm_fit():
    """What makes a too-big model shard itself: at t=1 the 31B-class
    bf16 weights exceed one v5e chip's HBM, so the simulated step
    carries the reference's 1ms/MB penalty; at t=8 it fits clean."""
    from flexflow_tpu.search.cost_model import serve_device_bytes
    arch = _big_arch()
    spec = MachineSpec.v5e(8)
    assert serve_device_bytes(arch, 1) > spec.hbm_capacity
    assert serve_device_bytes(arch, 8) < spec.hbm_capacity
    mm = TPUMachineModel(spec=spec)
    assert simulate_serve_step(arch, 1, mm) > 100 * \
        simulate_serve_step(arch, 8, mm)
