"""Disaggregated prefill/decode serving (PR 12).

Layers:
  * handoff — PagedKVCache.export_pages/import_pages move whole-page
    chain-keyed content between pools refcount-correctly (imported
    pages park hashed/refcount-0/matchable; dedupe by key; invariants
    extended to imported pages), and ServeEngine.export_kv/import_kv
    ship the device rows (+ scale rows on quantized pools) through ONE
    fixed-shape program each.
  * cluster — DisaggCluster (prefill role -> page handoff -> decode
    role) is token-identical to the unified engine through prefix
    hits, chunked prefill, preemption pressure, speculation+rollback,
    and int8/fp8 pages (bounded-error + greedy-tie-parity gates
    transfer), with zero recompiles after warmup and check_invariants
    on BOTH roles' pools after every step. Backpressure (the
    degradation-ladder watermark) skips imports instead of squeezing
    a loaded pool, degrading to recompute — still exact.
  * search — serve_step_tasks prices the page-transfer link on the
    host link (a KV-dtype flip changes the priced transfer cost and
    is a guaranteed cost-cache miss), and optimize_serve(...,
    disaggregated=True) returns the prefill:decode ratio table with a
    >= 1.3x simulated TPOT reduction for the production-scale arch.
"""

import dataclasses

import numpy as np
import pytest

from flexflow_tpu.config import FFConfig
from flexflow_tpu.models.transformer import build_transformer_lm
from flexflow_tpu.parallel.mesh import MachineSpec
from flexflow_tpu.search.cost_model import (ServeArch,
                                            kv_handoff_bytes,
                                            serve_step_tasks)
from flexflow_tpu.search.machine_model import TPUMachineModel
from flexflow_tpu.search.serve_place import (DisaggPlacement,
                                             optimize_serve,
                                             optimize_serve_disagg,
                                             price_disagg_candidate)
from flexflow_tpu.search.simulator import (simulate_serve_step,
                                           simulate_serve_tasks)
from flexflow_tpu.serve import DisaggCluster, ServeEngine
from flexflow_tpu.serve.kv_cache import PagedKVCache, prefix_page_keys


# --------------------------------------------------------------- helpers
def _lm(kv_dtype="float32", *, page_size=4, pool_pages=None,
        budget=32, max_seqs=4, max_seq_len=64, **cfg_kw):
    cfg = FFConfig(
        batch_size=1, kv_page_size=page_size,
        kv_num_pages=pool_pages or (1 + 16 * max_seqs),
        kv_dtype=kv_dtype, serve_max_seqs=max_seqs,
        serve_prefill_budget=budget, **cfg_kw)
    return build_transformer_lm(cfg, vocab_size=61,
                                max_seq_len=max_seq_len, hidden=32,
                                num_heads=4, num_layers=2, ff_dim=72)


def _prompts(rng, n, lo=4, hi=28):
    return [list(rng.randint(1, 61, size=rng.randint(lo, hi)))
            for _ in range(n)]


def _big_arch(**over):
    kw = dict(num_layers=48, hidden=6144, num_heads=48, head_dim=128,
              ff_dim=24576, vocab=256128, decode_lanes=32,
              prefill_lanes=512, context=2048, decode_tokens=128,
              kv_dtype="int8", kv_itemsize=1.0, kv_scales=True,
              act_itemsize=2.0, act_dtype="bfloat16",
              param_itemsize=2.0)
    kw.update(over)
    return ServeArch(**kw)


def _per_step_invariants(cluster):
    def hook(role, w, step):
        cluster.check_invariants()
    return hook


# ------------------------------------------------------- pool-level handoff
def test_export_import_pages_refcount_correct():
    """Host bookkeeping round trip: exported full pages re-register on
    the importer as parked (hashed, refcount-0, matchable) pages; the
    partial tail never crosses; invariants hold on both pools."""
    from flexflow_tpu.serve.kv_cache import KVCacheConfig
    cfg = KVCacheConfig(num_layers=2, num_heads=4, head_dim=8,
                        page_size=4, num_pages=33, max_seqs=2,
                        max_seq_len=64)
    src = PagedKVCache(cfg)
    dst = PagedKVCache(cfg)
    tokens = list(range(1, 12))          # 11 tokens: 2 full pages + tail
    slot = src.alloc_slot()
    src.ensure_capacity(slot, len(tokens))
    src.advance(slot, len(tokens))
    pages, keys, ntok = src.export_pages(slot, tokens)
    assert len(pages) == 2 and ntok == 8
    assert keys == prefix_page_keys(tokens, 4, 2)
    todo = dst.import_pages(keys)
    assert [i for i, _ in todo] == [0, 1]
    assert dst.imported_pages() == tuple(sorted(p for _, p in todo))
    # parked state: refcount 0, hashed, matchable
    for _, p in todo:
        assert dst.ref(p) == 0
    assert dst.match_prefix(keys) == [p for _, p in todo]
    src.check_invariants()
    dst.check_invariants()
    # re-import dedupes fully
    assert dst.import_pages(keys) == []
    assert dst.stats["import_dedup_pages"] == 2
    # attach to a slot, free it, and the invariants/imported set survive
    s2 = dst.alloc_slot()
    dst.attach_prefix(s2, [p for _, p in todo], 8)
    dst.check_invariants()
    dst.free_slot(s2)
    dst.check_invariants()
    # eviction drops the key AND the imported marking atomically
    dst.shrink_lru(0)
    assert dst.imported_pages() == ()
    dst.check_invariants()


def test_import_pages_requires_prefix_cache():
    from flexflow_tpu.serve.kv_cache import KVCacheConfig
    cfg = KVCacheConfig(num_layers=1, num_heads=2, head_dim=4,
                        page_size=4, num_pages=17, max_seqs=1,
                        max_seq_len=32)
    pool = PagedKVCache(cfg, prefix_cache=False)
    with pytest.raises(RuntimeError, match="prefix cache"):
        pool.import_pages([b"k" * 32])


def test_engine_export_import_rows_bit_equal():
    """Device rows survive the hop bit-for-bit: export from a prefill
    engine mid-serve, import into a fresh engine, and the destination
    pool's rows at the imported pages equal the source's."""
    rng = np.random.RandomState(0)
    ff = _lm()
    src = ServeEngine(ff, spec_tokens=0)
    src.warmup()
    dst = ServeEngine(ff, spec_tokens=0)
    dst.warmup()
    dst.warmup_handoff()
    prompt = list(rng.randint(1, 61, size=13))
    ships = []
    src.generate([prompt], 1,
                 on_finish=lambda r: ships.append(
                     src.export_kv(r.slot, r.context)))
    (ship,) = ships
    assert ship is not None and ship.num_pages == len(prompt) // 4
    written = dst.import_kv(ship)
    assert written == ship.num_pages
    dst.cache.check_invariants(dst.pool)
    got = dst.pool.rows(
        np.asarray([dst.cache._page_of_hash[k] for k in ship.keys]))
    np.testing.assert_array_equal(np.asarray(got.k), ship.k_rows)
    np.testing.assert_array_equal(np.asarray(got.v), ship.v_rows)
    # geometry mismatch is rejected loudly
    bad = dataclasses.replace(ship, page_size=8)
    with pytest.raises(ValueError, match="geometry"):
        dst.import_kv(bad)


def test_export_import_sharded_tp2():
    """The shard_map handoff path: head-sharded (t=2) engines round-
    trip page rows bit-exactly and a sharded cluster stays token-
    identical to the sharded unified engine, zero recompiles."""
    rng = np.random.RandomState(10)
    ff = _lm(serve_mesh="2")
    src = ServeEngine(ff, spec_tokens=0)
    assert src.tp == 2
    src.warmup()
    dst = ServeEngine(ff, spec_tokens=0)
    dst.warmup()
    dst.warmup_handoff()
    prompt = list(rng.randint(1, 61, size=14))
    ships = []
    src.generate([prompt], 1,
                 on_finish=lambda r: ships.append(
                     src.export_kv(r.slot, r.context)))
    (ship,) = ships
    assert dst.import_kv(ship) == ship.num_pages
    got = dst.pool.rows(
        np.asarray([dst.cache._page_of_hash[k] for k in ship.keys]))
    np.testing.assert_array_equal(np.asarray(got.k), ship.k_rows)
    dst.cache.check_invariants(dst.pool)
    # sharded cluster == sharded unified engine, token for token
    uni = ServeEngine(ff, spec_tokens=0)
    uni.warmup()
    prompts = _prompts(rng, 6, hi=40)
    ref = uni.generate(prompts, 5)
    cl = DisaggCluster(ff, spec_tokens=0)
    counts = cl.warmup()
    assert all(e.tp == 2 for _, e in cl.engines())
    out = cl.generate(prompts, 5)
    assert out == ref
    assert cl.compile_counts() == counts
    cl.check_invariants()


# ------------------------------------------------------- cluster exactness
def test_disagg_token_identity_f32():
    """The acceptance gate: a disaggregated cluster is token-identical
    to the unified engine (and the no-cache reference) on f32 pages,
    zero recompiles after warmup on both roles, invariants on both
    pools after every step."""
    rng = np.random.RandomState(1)
    ff = _lm()
    uni = ServeEngine(ff)
    uni.warmup()
    prompts = _prompts(rng, 8, hi=50)
    ref = uni.generate(prompts, 6)
    cl = DisaggCluster(ff)
    counts = cl.warmup()
    out = cl.generate(prompts, 6, on_step=_per_step_invariants(cl))
    assert out == ref
    assert out == uni.generate_reference(prompts, 6)
    assert cl.compile_counts() == counts
    assert cl.stats["handoff_requests"] > 0
    # every role's pool drained clean
    for _, eng in cl.engines():
        assert eng.cache.free_pages == eng.cache_cfg.usable_pages


def test_disagg_prefix_hits_and_dedup():
    """Shared prompt prefixes cross the link ONCE: the second batch's
    imports dedupe against resident keys, and the decode role admits
    handed-off requests as prefix hits (near-zero recomputed prefill
    beyond tail chunks)."""
    rng = np.random.RandomState(2)
    ff = _lm()
    cl = DisaggCluster(ff)
    cl.warmup()
    prefix = list(rng.randint(1, 61, size=24))
    prompts = [prefix + list(rng.randint(1, 61, size=4))
               for _ in range(6)]
    uni = ServeEngine(ff)
    uni.warmup()
    ref = uni.generate(prompts, 4)
    out = cl.generate(prompts, 4)
    assert out == ref
    assert cl.stats["handoff_dedup_pages"] > 0
    dec = cl.last_stats["roles"]["decode"][0]
    # the decode role prefix-matched the imported pages: computed far
    # fewer prefill tokens than the prompts carry
    assert dec["prefix_hit_tokens"] > 0
    assert dec["prefill_tokens_computed"] < dec["prompt_tokens_total"]


def test_disagg_speculation_and_eos():
    """Speculation+rollback on the decode role and eos termination on
    BOTH sides of the split stay token-identical to the unified
    engine."""
    rng = np.random.RandomState(3)
    ff = _lm()
    uni = ServeEngine(ff, spec_tokens=0)
    uni.warmup()
    prompts = _prompts(rng, 6, hi=40)
    eos = 7
    ref = uni.generate(prompts, 10, eos_token=eos)
    cl = DisaggCluster(ff, spec_tokens=3)
    counts = cl.warmup()
    out = cl.generate(prompts, 10, eos_token=eos,
                      on_step=_per_step_invariants(cl))
    assert out == ref
    assert cl.compile_counts() == counts
    # max_new=1 requests never reach the decode role
    out1 = cl.generate(prompts, 1, eos_token=eos)
    assert out1 == [r[:1] for r in ref]


def test_disagg_preemption_pressure_exact():
    """A pool tight enough to churn admissions/preemptions on the
    decode role: outputs still identical, pools still clean."""
    rng = np.random.RandomState(4)
    ff = _lm(pool_pages=1 + 16 * 2, max_seq_len=64)
    uni = ServeEngine(ff, spec_tokens=0)
    uni.warmup()
    prompts = _prompts(rng, 10, lo=20, hi=55)
    ref = uni.generate(prompts, 5)
    cl = DisaggCluster(ff, spec_tokens=0)
    cl.warmup()
    out = cl.generate(prompts, 5, on_step=_per_step_invariants(cl))
    assert out == ref
    cl.check_invariants()


def test_disagg_backpressure_skips_not_breaks():
    """With the admission watermark raised past a shipment's headroom,
    the cluster SKIPS imports (counted) instead of squeezing the pool
    — and the decode role recomputes, keeping outputs exact."""
    rng = np.random.RandomState(5)
    ff = _lm(pool_pages=17, max_seq_len=64,
             serve_admit_watermark=0.5)  # wm > post-import headroom
    uni = ServeEngine(ff, spec_tokens=0)
    uni.warmup()
    prompts = _prompts(rng, 4, lo=40, hi=55)
    ref = uni.generate(prompts, 3)
    cl = DisaggCluster(ff, spec_tokens=0)
    cl.warmup()
    out = cl.generate(prompts, 3, on_step=_per_step_invariants(cl))
    assert out == ref
    assert cl.stats["handoff_skipped"] > 0
    assert cl.metrics.counter("kv_handoff_skipped_total") > 0


@pytest.mark.parametrize("kv_dtype", ["int8", "float8_e4m3"])
def test_disagg_quantized_pages(kv_dtype):
    """Quantized pools ship their int8/fp8 rows + f32 scale rows
    bit-exactly: the cluster equals the unified engine token-for-token
    (transfer is lossless over already-quantized content), and the
    no-cache reference comparison holds through the usual tie-margin
    gate."""
    rng = np.random.RandomState(6)
    ff = _lm(kv_dtype)
    uni = ServeEngine(ff, spec_tokens=0)
    uni.warmup()
    prompts = _prompts(rng, 6, lo=8, hi=40)
    ref_q = uni.generate(prompts, 5)
    cl = DisaggCluster(ff, spec_tokens=0)
    counts = cl.warmup()
    out = cl.generate(prompts, 5, on_step=_per_step_invariants(cl))
    assert out == ref_q, "disagg diverged from unified on " + kv_dtype
    assert cl.compile_counts() == counts
    for _, eng in cl.engines():
        eng.check_kv_scales()
    oracle = uni.generate_reference(prompts, 5)
    uni.assert_token_parity(prompts, out, oracle,
                            what=f"disagg {kv_dtype} outputs")


def test_disagg_sampled_streams_survive_the_split():
    """Seeded sampling crosses the prefill->decode handoff (the PR-12
    follow-up): draws key on the stream-id carried with the request /
    PageShipment — NOT the local scheduler's rid/token index — with
    the decode role resuming at offset 1, so unified and disaggregated
    token streams are identical at one seed for temperature/top-k
    sampling (the mixes that used to be refused loudly)."""
    rng = np.random.RandomState(3)
    ff = _lm()
    uni = ServeEngine(ff, spec_tokens=0)
    uni.warmup()
    cl = DisaggCluster(ff, spec_tokens=0)
    cl.warmup()
    prompts = _prompts(rng, 6, hi=24)
    # mixed per-request sampling: greedy, top_k=1, and real top-k
    # temperature streams in one batch, crossing 2 decode waves
    temps = [0.0, 0.7, 0.9, 0.8, 1.3, 0.6]
    tks = [None, 1, 5, 8, 3, None]
    for seed in (0, 7):
        ref = uni.generate(prompts, 6, temperature=temps, top_k=tks,
                           sample_seed=seed)
        out = cl.generate(prompts, 6, temperature=temps, top_k=tks,
                          sample_seed=seed)
        assert out == ref, (
            f"disagg sampled streams diverged from unified at seed "
            f"{seed}")
    # a DIFFERENT seed must move the sampled streams (the equality
    # above is not vacuous greedy collapse)
    alt = cl.generate(prompts, 6, temperature=temps, top_k=tks,
                      sample_seed=11)
    assert alt != out
    # eos emitted mid-stream by a SAMPLED request truncates identically
    eos = int(ref[2][1]) if len(ref[2]) > 1 else 7
    assert cl.generate(prompts, 6, temperature=temps, top_k=tks,
                       sample_seed=0, eos_token=eos) == \
        uni.generate(prompts, 6, temperature=temps, top_k=tks,
                     sample_seed=0, eos_token=eos)
    # the unified engine's submit contract still holds up front
    with pytest.raises(ValueError, match="max_new_tokens"):
        cl.generate([[1, 2], [3, 4]], [4, 0])
    assert cl.stats["handoff_requests"] > 0


def test_disagg_per_request_args_slice_per_wave():
    """Per-request lists survive the wave split: a batch whose decode
    wave is a proper subset (one max_new=1 request) with per-request
    greedy args and 2 prefill engines must serve, identically."""
    rng = np.random.RandomState(9)
    ff = _lm()
    uni = ServeEngine(ff, spec_tokens=0)
    uni.warmup()
    prompts = _prompts(rng, 5, hi=30)
    mnt = [6, 1, 6, 1, 6]
    ref = uni.generate(prompts, mnt, temperature=[0.0] * 5,
                       top_k=[1] * 5)
    cl = DisaggCluster(ff, prefill_engines=2, spec_tokens=0)
    cl.warmup()
    out = cl.generate(prompts, mnt, temperature=[0.0] * 5,
                      top_k=[1] * 5)
    assert out == ref
    # done-at-first-token requests ship nothing: only the 3 decoding
    # requests' shipments crossed the link
    assert cl.stats["handoff_requests"] <= 3


def test_disagg_ratio_and_cli_config():
    """serve_disagg_ratio parses/validates; from_config builds the
    requested engine counts; engine_for consumes --serve-disagg; the
    decode-budget floor is enforced."""
    from flexflow_tpu.serve import engine_for
    ff = _lm(serve_disagg_ratio="2:1")
    cl = DisaggCluster.from_config(ff)
    assert (len(cl.prefill), len(cl.decode)) == (2, 1)
    # the config-driven entry point: --serve-disagg picks the cluster
    assert isinstance(engine_for(_lm()), ServeEngine)
    srv = engine_for(_lm(serve_disagg=True, serve_disagg_ratio="1:2"))
    assert isinstance(srv, DisaggCluster)
    assert (len(srv.prefill), len(srv.decode)) == (1, 2)
    # "auto" resolves through the ratio search and keeps the winning
    # placement on the cluster
    cla = DisaggCluster.from_config(
        _lm(serve_disagg_ratio="auto", serve_disagg_decode_budget=24),
        num_devices=2)
    assert cla.placement is not None
    assert (len(cla.prefill) == cla.placement.prefill_engines
            and len(cla.decode) == cla.placement.decode_engines)
    assert cla.decode_budget == 24
    cfg = FFConfig(argv=["--serve-disagg", "--serve-disagg-ratio",
                         "3:2", "--serve-disagg-decode-budget", "64"])
    assert cfg.serve_disagg and cfg.serve_disagg_ratio == "3:2"
    assert cfg.serve_disagg_decode_budget == 64
    with pytest.raises(ValueError, match="serve_disagg_ratio"):
        FFConfig(serve_disagg_ratio="0:2")
    with pytest.raises(ValueError, match="decode_budget"):
        DisaggCluster(_lm(), decode_budget=2)  # < one page


def test_disagg_report_and_metrics_split():
    """The per-role TTFT/TPOT split renders from the cluster's own
    exported registry (the no-drift rule) and the handoff counters
    land in it."""
    from flexflow_tpu.utils.profiling import disagg_report
    rng = np.random.RandomState(7)
    ff = _lm()
    cl = DisaggCluster(ff)
    cl.warmup()
    cl.generate(_prompts(rng, 6), 6)
    m = cl.metrics
    assert m.hist_count("serve_tpot_seconds", role="decode") > 0
    assert m.hist_count("serve_ttft_seconds", role="prefill") > 0
    assert m.counter("kv_transfer_pages_total") > 0
    assert m.counter("kv_transfer_bytes_total") > 0
    assert m.counter("kv_handoff_requests_total") > 0
    rep = disagg_report(cl.last_stats, m)
    assert "prefill role (lifetime):" in rep \
        and "decode role (lifetime):" in rep
    assert "kv handoff:" in rep
    # rebuilding the fold from the stats dict gives the same split
    rep2 = disagg_report(cl.last_stats, None)
    assert "decode role:" in rep2
    # last_stats carries THIS call's handoff delta (self.stats is
    # lifetime): a fully-deduped second call ships 0 pages
    first_pages = cl.last_stats["handoff"]["handoff_pages"]
    assert first_pages > 0
    cl.generate(_prompts(np.random.RandomState(7), 6), 6)
    assert cl.last_stats["handoff"]["handoff_pages"] == 0
    assert cl.stats["handoff_pages"] == first_pages


def test_disagg_memory_ledger_covers_both_roles():
    """The cluster ledger sums BOTH roles' pools (the
    don't-undercount satellite): cluster totals equal the per-role
    sums and every role's kv pool is accounted."""
    ff = _lm()
    cl = DisaggCluster(ff, prefill_engines=1, decode_engines=2)
    cl.warmup()
    led = cl.memory_ledger()
    roles = led["roles"]
    assert len(roles) == 3
    assert led["kv_pool_bytes"] == pytest.approx(
        sum(r["kv_pool_bytes"] for r in roles.values()))
    assert led["params_bytes"] == pytest.approx(
        sum(r["params_bytes"] for r in roles.values()))
    assert led["total_bytes"] > max(
        r["total_bytes"] for r in roles.values())


def test_disagg_telemetry_spans_and_gauges():
    """With a live bus: kv_handoff spans land on the cluster track,
    transfer counters on the registry, and the role-labeled HBM
    gauges cover the cluster."""
    from flexflow_tpu.utils.telemetry import Telemetry
    rng = np.random.RandomState(8)
    tel = Telemetry()
    ff = _lm()
    cl = DisaggCluster(ff, telemetry=tel)
    cl.warmup()
    cl.generate(_prompts(rng, 4, lo=8, hi=30), 4)
    names = {(ev[1], ev[2]) for ev in tel.events}
    assert (("serve", "cluster"), "kv_handoff") in names, names
    assert tel.metrics.counter("kv_transfer_bytes_total") > 0
    cl.memory_ledger()
    assert tel.metrics.gauge("serve_hbm_bytes", component="kv_pool",
                             role="cluster") > 0


# ------------------------------------------------------- search pricing
def test_transfer_link_priced_and_dtype_sensitive():
    """The page-transfer link: kv_handoff_bytes follows the storage
    itemsize (f32 -> int8 is the 4x byte lever, minus scale rows), the
    transfer task rides BESIDE the chain (makespan = max, not sum),
    and simulate_serve_step grows only when the link dominates."""
    arch = _big_arch()
    f32 = dataclasses.replace(arch, kv_dtype="float32",
                              kv_itemsize=4.0, kv_scales=False)
    assert kv_handoff_bytes(f32) > 3.5 * kv_handoff_bytes(arch)
    mm = TPUMachineModel(spec=MachineSpec.v5e(16))
    tasks = serve_step_tasks(arch, 8, mm, lanes=arch.decode_lanes,
                             transfer_tokens=arch.context)
    (xfer,) = [t for t in tasks if t.kind == "transfer"]
    assert xfer.name == "kv_handoff" and not xfer.deps
    chain = sum(t.seconds for t in tasks if t.kind != "transfer")
    assert simulate_serve_tasks(tasks) == pytest.approx(
        max(chain, xfer.seconds))
    base = simulate_serve_step(arch, 8, mm)
    small = simulate_serve_step(arch, 8, mm, transfer_tokens=8)
    assert small == pytest.approx(base)   # link hidden behind compute
    huge = simulate_serve_step(arch, 8, mm,
                               transfer_tokens=64 * arch.context)
    assert huge > base                    # link became the bottleneck


def test_disagg_placement_ratio_table_and_gate():
    """optimize_serve(..., disaggregated=True) returns the ratio
    table; the winner beats every tabled ratio; simulated TPOT
    reduction >= 1.3x for the production arch (the ci.sh 1m simulated
    half)."""
    mm = TPUMachineModel(spec=MachineSpec.v5e(16))
    place = optimize_serve(_big_arch(), 16, mm=mm, disaggregated=True)
    assert isinstance(place, DisaggPlacement)
    assert place.ratio in place.ratio_table
    assert place.prefill_engines >= 1 and place.decode_engines >= 1
    assert (place.prefill_engines * place.prefill_tensor
            + place.decode_engines * place.decode_tensor) <= 16
    assert min(place.ratio_table.values()) <= place.bottleneck_s * (
        1 + 1e-9)
    assert place.tpot_reduction_vs_unified() >= 1.3
    # the decode step never pays the prefill budget's lanes
    assert place.decode_step_s < place.prefill_step_s


def test_disagg_transfer_cost_cache_miss_on_dtype_flip(tmp_path):
    """The acceptance regression: a KV-dtype flip (f32 -> int8)
    changes the priced transfer cost AND is a guaranteed cost-cache
    miss (different fingerprint + different entry key)."""
    from flexflow_tpu.search.cost_cache import CostCache
    from flexflow_tpu.search.serve_place import _serve_fingerprint
    mm = TPUMachineModel(spec=MachineSpec.v5e(16))
    arch_q = _big_arch()
    arch_f = dataclasses.replace(arch_q, kv_dtype="float32",
                                 kv_itemsize=4.0, kv_scales=False)
    cache = CostCache(str(tmp_path / "cc.json"))
    fp_q = _serve_fingerprint(mm, arch_q)
    fp_f = _serve_fingerprint(mm, arch_f)
    assert fp_q != fp_f
    pre_q, dec_q, xfer_q = price_disagg_candidate(
        arch_q, 8, 8, mm, cache=cache, fingerprint=fp_q)
    pre_f, dec_f, xfer_f = price_disagg_candidate(
        arch_f, 8, 8, mm, cache=cache, fingerprint=fp_f)
    assert xfer_f > 3.5 * xfer_q          # the 4x byte lever
    # cached rows round-trip under their own fingerprints
    assert price_disagg_candidate(
        arch_q, 8, 8, mm, cache=cache,
        fingerprint=fp_q) == (pre_q, dec_q, xfer_q)
    # the f32 row cannot be served for the int8 arch: its key lives
    # under a different fingerprint AND a different signature
    key_q = cache.entry_key("serve_disagg", (8, 8),
                            extra=arch_q.signature())
    key_f = cache.entry_key("serve_disagg", (8, 8),
                            extra=arch_f.signature())
    assert key_q != key_f
    assert cache.get(fp_q, key_f) is None


# =======================================================================
# continuous pipelining + cross-process transport (wall-clock fabric)
# =======================================================================
def test_disagg_pipelined_token_identity_and_hook_arities():
    """generate_pipelined drives BOTH roles' steppable sessions from
    one event loop (no batch wave barrier) yet stays token-identical
    to the phased path and the unified engine — only WHEN steps run
    changes, never what they compute. Both on_step arities work via
    normalize_on_step; a 2-arg hook is rejected at arming time."""
    from flexflow_tpu.serve import normalize_on_step
    rng = np.random.RandomState(11)
    ff = _lm(pool_pages=64)
    prompts = _prompts(rng, 8)
    max_new = [int(x) for x in rng.randint(1, 8, size=8)]
    temps = [0.8 if i % 3 == 0 else None for i in range(8)]
    tks = [3 if i % 3 == 0 else None for i in range(8)]
    uni = ServeEngine(_lm(pool_pages=64))
    ref = uni.generate(prompts, max_new, temperature=temps,
                       top_k=tks, sample_seed=5)
    uni.close()
    with DisaggCluster(ff, prefill_engines=2, decode_engines=2) as cl:
        phased = cl.generate(prompts, max_new, temperature=temps,
                             top_k=tks, sample_seed=5)
        assert phased == ref
        assert cl.last_stats["pipelined"] is False
        steps = []
        piped = cl.generate_pipelined(
            prompts, max_new, temperature=temps, top_k=tks,
            sample_seed=5, on_step=lambda role, w, s: (
                steps.append((role, w)), cl.check_invariants()))
        assert piped == ref
        assert cl.last_stats["pipelined"] is True
        assert cl.last_stats["handoff"]["handoff_requests"] == 8
        assert {r for r, _ in steps} == {"prefill", "decode"}
        # 1-arg hook through the same adapter
        one = []
        piped2 = cl.generate_pipelined(prompts, max_new,
                                       temperature=temps, top_k=tks,
                                       sample_seed=5,
                                       on_step=lambda s: one.append(1))
        assert piped2 == ref and len(one) > 0
        # max_new == 1 everywhere: pipelined must not submit empty
        # decode work (prefill emits the only token)
        assert cl.generate_pipelined(prompts, 1, sample_seed=5) \
            == cl.generate(prompts, 1, sample_seed=5)
        cl.check_invariants()
        for _, eng in cl.engines():
            assert eng.cache.free_pages == eng.cache_cfg.usable_pages
    with pytest.raises(TypeError, match="on_step"):
        normalize_on_step(lambda a, b: None)
    assert normalize_on_step(None) is None


def test_disagg_tcp_transport_token_identity():
    """--transport tcp: shipments really cross a loopback socket
    (length-prefixed frames, CRC, synchronous acks) and the cluster
    stays token-identical to the in-process handoff on BOTH the
    phased and pipelined paths — including quantized pages with
    scale rows."""
    rng = np.random.RandomState(13)
    prompts = _prompts(rng, 6)
    max_new = [int(x) for x in rng.randint(2, 7, size=6)]
    temps = [0.8 if i % 2 == 0 else None for i in range(6)]
    tks = [3 if i % 2 == 0 else None for i in range(6)]
    with DisaggCluster(_lm(pool_pages=64)) as cl:
        ref = cl.generate(prompts, max_new, temperature=temps,
                          top_k=tks, sample_seed=2)
        assert cl.last_stats["transport"] == "inproc"
    ff = _lm(pool_pages=64, serve_transport="tcp")
    with DisaggCluster(ff) as cl:
        assert cl._receiver is not None and cl._sender is not None
        out = cl.generate(prompts, max_new, temperature=temps,
                          top_k=tks, sample_seed=2)
        assert out == ref
        assert cl.last_stats["transport"] == "tcp"
        frames0 = cl._receiver.stats["frames"]
        assert frames0 > 0
        assert cl._receiver.stats["accepted"] == frames0
        assert cl._receiver.stats["wire_errors"] == 0
        piped = cl.generate_pipelined(prompts, max_new,
                                      temperature=temps, top_k=tks,
                                      sample_seed=2)
        assert piped == ref
        assert cl._receiver.stats["frames"] > frames0
        cl.check_invariants()
    # quantized pages cross the socket bit-exactly (scale rows ride
    # in the same frame)
    with DisaggCluster(_lm("int8", pool_pages=64)) as cl:
        ref_q = cl.generate(prompts, max_new, sample_seed=2)
    ffq = _lm("int8", pool_pages=64, serve_transport="tcp")
    with DisaggCluster(ffq) as cl:
        assert cl.generate(prompts, max_new, sample_seed=2) == ref_q
        assert cl._receiver.stats["wire_errors"] == 0
