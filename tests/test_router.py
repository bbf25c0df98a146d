"""Multi-replica serving tier (PR 14): prefix-affinity router, SLO
traffic harness, telemetry-driven autoscaler.

Layers:
  * session — ServeSession is the steppable form of generate(): same
    tokens whether requests are submitted up front or mid-stream
    (sampling keys on stream ids, not submission interleaving).
  * traffic — seeded synthesis is deterministic, heavy-tailed,
    multi-tenant, and validated.
  * router — affinity routes to the LONGEST chain-hash prefix match
    (block-boundary exact), tenant-sticky falls back, load spills off
    rung/occupancy pressure, routing is deterministic at one seed,
    and a cancel (even mid-QUEUE) reclaims the affinity pin.
  * autoscaler — decisions read only exported gauges, scale on SLO
    pressure, never flap on steady load, and replay exactly.
  * chaos — a seeded cancel+sampling storm over the pool holds
    cluster-wide check_invariants after EVERY replica step, full page
    reclamation, zero recompiles, and single-replica token exactness.
"""

import numpy as np
import pytest

from flexflow_tpu.config import FFConfig
from flexflow_tpu.models.transformer import build_transformer_lm
from flexflow_tpu.serve import (Autoscaler, ReplicaPool, ServeEngine,
                                TrafficRequest, TrafficSpec,
                                make_traffic)
from flexflow_tpu.serve.scheduler import RequestOutcome
from flexflow_tpu.serve.traffic import tenant_prefixes
from flexflow_tpu.utils.profiling import router_report
from flexflow_tpu.utils.telemetry import Telemetry


# --------------------------------------------------------------- helpers
def _lm(*, page_size=4, pool_pages=48, budget=8, max_seqs=4,
        max_seq_len=96, **cfg_kw):
    cfg = FFConfig(batch_size=1, kv_page_size=page_size,
                   kv_num_pages=1 + pool_pages,
                   serve_max_seqs=max_seqs,
                   serve_prefill_budget=budget,
                   serve_spec_decode=False, **cfg_kw)
    return build_transformer_lm(cfg, vocab_size=61,
                                max_seq_len=max_seq_len, hidden=32,
                                num_heads=4, num_layers=2, ff_dim=72)


def _traffic(n=16, seed=0, **over):
    kw = dict(requests=n, seed=seed, rate_rps=2000.0, tenants=3,
              prefix_tokens=24, tail_mean=4.0, output_mean=4.0,
              max_prompt=48, max_new_cap=8, vocab=61)
    kw.update(over)
    return make_traffic(TrafficSpec(**kw))


def _drain(replica):
    while replica.session.step() is not None:
        pass


# =======================================================================
# traffic harness
# =======================================================================
def test_traffic_deterministic_and_shaped():
    spec = TrafficSpec(requests=64, seed=5, tenants=4,
                       prefix_tokens=24, max_prompt=48,
                       cancel_frac=0.2, sample_frac=0.3, vocab=61)
    a = make_traffic(spec)
    b = make_traffic(spec)
    assert [(t.t_arrival, t.prompt, t.max_new, t.cancel_after_tokens,
             t.temperature) for t in a] == \
        [(t.t_arrival, t.prompt, t.max_new, t.cancel_after_tokens,
          t.temperature) for t in b]
    # a different seed moves everything
    c = make_traffic(TrafficSpec(requests=64, seed=6, tenants=4,
                                 prefix_tokens=24, max_prompt=48,
                                 vocab=61))
    assert [t.prompt for t in a] != [t.prompt for t in c]
    # arrivals strictly ordered, stream ids in arrival order
    ts = [t.t_arrival for t in a]
    assert ts == sorted(ts) and [t.stream_id for t in a] == list(
        range(64))
    # every prompt = its tenant's shared prefix + a nonempty tail,
    # admissible under the cap
    prefixes = tenant_prefixes(spec)
    for t in a:
        assert t.prompt[:24] == prefixes[t.tenant]
        assert 24 < len(t.prompt) <= 48
        assert 1 <= t.max_new <= spec.max_new_cap
        if t.cancel_after_tokens is not None:
            assert 1 <= t.cancel_after_tokens < t.max_new
    # heavy tails actually produce outliers and cancels/samples fire
    tails = [len(t.prompt) - 24 for t in a]
    assert max(tails) >= 3 * (sum(tails) / len(tails)) * 0.8
    assert any(t.cancel_after_tokens for t in a)
    assert any(t.sampled for t in a)
    # Zipf skew: tenant 0 dominates
    counts = np.bincount([t.tenant for t in a], minlength=4)
    assert counts[0] == max(counts)


def test_traffic_bursty_and_validation():
    base = dict(requests=64, seed=1, prefix_tokens=24, max_prompt=48,
                vocab=61)
    po = make_traffic(TrafficSpec(arrival="poisson", **base))
    bu = make_traffic(TrafficSpec(arrival="bursty", burst_factor=8.0,
                                  **base))
    # bursty inter-arrival gaps are MORE dispersed at a comparable
    # mean (coefficient of variation strictly above poisson's)
    def cv(tr):
        gaps = np.diff([t.t_arrival for t in tr])
        return float(np.std(gaps) / np.mean(gaps))
    assert cv(bu) > cv(po)
    with pytest.raises(ValueError, match="arrival"):
        make_traffic(TrafficSpec(arrival="nope", **base))
    with pytest.raises(ValueError, match="prefix_tokens"):
        make_traffic(TrafficSpec(requests=4, prefix_tokens=48,
                                 max_prompt=48, vocab=61))
    with pytest.raises(ValueError, match="rate_rps"):
        make_traffic(TrafficSpec(requests=4, rate_rps=0.0,
                                 prefix_tokens=8, max_prompt=48))


# =======================================================================
# sessions (the engine hook)
# =======================================================================
def test_session_mid_stream_submit_matches_generate():
    """Tokens are a function of (prompt, sampling stream), not of
    WHEN a request was submitted: half the batch submitted up front,
    half after a few steps, must equal one generate() over the same
    stream ids."""
    ff = _lm()
    eng = ServeEngine(ff)
    eng.warmup()
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(1, 61, size=rng.randint(4, 24)))
               for _ in range(6)]
    ref = eng.generate(prompts, 5, temperature=[0, 0.8, 0, 0.8, 0, 0],
                       top_k=[None, 4, None, 4, None, None],
                       sample_seed=3, stream_ids=list(range(6)))
    temps = [0, 0.8, 0, 0.8, 0, 0]
    tks = [None, 4, None, 4, None, None]
    session = eng.start_session()
    reqs = []
    for i in range(3):
        sp = eng._sample_params(temps[i], tks[i], 3, 1,
                                eng.topk_cap)[0]
        reqs.append(session.submit(prompts[i], 5, sample=sp,
                                   stream_id=i))
    for _ in range(2):
        session.step()
    for i in range(3, 6):
        sp = eng._sample_params(temps[i], tks[i], 3, 1,
                                eng.topk_cap)[0]
        reqs.append(session.submit(prompts[i], 5, sample=sp,
                                   stream_id=i))
    while session.step() is not None:
        pass
    session.close()
    assert [list(r.out_tokens) for r in reqs] == ref
    eng.cache.check_invariants()
    assert eng.cache.free_pages == eng.cache_cfg.usable_pages


def test_session_exclusive():
    ff = _lm()
    eng = ServeEngine(ff)
    s = eng.start_session()
    with pytest.raises(RuntimeError, match="live ServeSession"):
        eng.start_session()
    s.close()
    eng.start_session().close()   # reopens after close


# =======================================================================
# routing
# =======================================================================
def test_longest_prefix_wins_across_block_boundaries():
    ff = _lm(page_size=4)
    pool = ReplicaPool(ff, 2, policy="affinity")
    base = list(range(1, 41))          # 40 shared tokens = 10 pages
    # replica 0 serves (and commits) 17 tokens -> 4 full pages;
    # replica 1 serves 33 tokens -> 8 full pages of the same chain
    r0, r1 = pool.replicas
    r0.session.submit(base[:17], 1)
    _drain(r0)
    r1.session.submit(base[:33], 1)
    _drain(r1)
    target, info = pool.route(base[:40] + [55, 56])
    assert target.idx == 1 and info["affinity_hit"]
    assert info["matched_tokens"] == 32     # 8 full pages
    # a prompt agreeing only through 1.5 pages matches ONE full page:
    # the chain key of page 2 commits to tokens 4..7, so a flip at
    # token 6 must kill every key from page 2 on
    probe = base[:6] + [59, 60] + base[8:20]
    target2, info2 = pool.route(probe)
    assert info2["matched_tokens"] == 4
    # a total miss falls back tenant-sticky, deterministically
    miss = [58] * 12
    t_a, info_a = pool.route(miss, tenant=7)
    t_b, info_b = pool.route(miss, tenant=7)
    assert info_a["fallback"] and t_a.idx == t_b.idx
    pool.close()


def test_router_pending_pins_colocate_before_commit():
    """Two same-tenant requests arriving back-to-back route together
    even though the first has not COMMITTED its pages yet — the
    router's pending-pin table covers the gap."""
    ff = _lm(page_size=4)
    pool = ReplicaPool(ff, 2, policy="affinity")
    prompt = list(range(1, 30))
    tr0 = TrafficRequest(stream_id=0, t_arrival=0.0, tenant=1,
                        prompt=prompt, max_new=2)
    tr1 = TrafficRequest(stream_id=1, t_arrival=0.0, tenant=1,
                        prompt=list(prompt) + [33], max_new=2)
    a = pool.submit(tr0)
    b = pool.submit(tr1)
    assert b["replica"] == a["replica"]
    assert b["affinity_hit"] and b["matched_tokens"] > 0
    pool.close()


def test_spill_under_rung_pressure():
    """An affinity hit pointing at a saturated replica spills to the
    least-loaded one instead of queueing (the degradation ladder /
    occupancy as the backpressure signal)."""
    ff = _lm(page_size=4, pool_pages=40)
    pool = ReplicaPool(ff, 2, policy="affinity",
                       spill_occupancy=0.5)
    prefix = list(range(1, 26))
    r0 = pool.replicas[0]
    # park the prefix AND enough live residency on replica 0 to push
    # occupancy past the spill ceiling (requests mid-flight: submit,
    # step once so pages map, don't drain)
    rng = np.random.RandomState(1)
    for k in range(3):
        r0.session.submit(prefix + list(rng.randint(40, 61, size=30)),
                          8)
    for _ in range(40):
        if r0.occupancy() >= 0.5:
            break
        assert r0.session.step() is not None
    assert r0.occupancy() >= 0.5
    target, info = pool.route(prefix + [59, 60])
    assert target.idx == 1 and info["spilled"]
    # with spill disabled (ceiling 1.0 + rung far) the hit sticks
    pool.spill_occupancy = 1.01
    target2, info2 = pool.route(prefix + [59, 60])
    assert target2.idx == 0 and not info2["spilled"]
    _drain(r0)
    pool.close()


def test_routing_deterministic_at_one_seed():
    ff = _lm()
    traffic = _traffic(n=20, seed=4, cancel_frac=0.1,
                       sample_frac=0.25)
    outs = []
    for _ in range(2):
        pool = ReplicaPool(ff, 2, policy="affinity")
        res = pool.run(traffic, slo_ttft_s=1.0, slo_tpot_s=1.0)
        outs.append([(r["stream_id"], r["replica"], r["outcome"],
                      tuple(r["tokens"])) for r in res["requests"]])
        pool.check_drained()
        pool.close()
    assert outs[0] == outs[1]


def test_cancel_mid_queue_reclaims_affinity_pin():
    ff = _lm()
    pool = ReplicaPool(ff, 2, policy="affinity")
    tr = TrafficRequest(stream_id=0, t_arrival=0.0, tenant=0,
                        prompt=list(range(1, 20)), max_new=4)
    tracked = pool.submit(tr)
    ridx = tracked["replica"]
    assert pool._pins[ridx], "submit did not pin the prefix"
    # cancelled while still WAITING in the scheduler queue (no step
    # has run): the pin must reclaim IMMEDIATELY so routing stops
    # steering this tenant at pages that will never commit
    assert pool.cancel(0)
    assert not pool._pins[ridx], "cancel left the affinity pin"
    _drain(pool.replicas[ridx])
    assert tracked["req"].outcome == RequestOutcome.CANCELLED
    pool.check_drained()
    # double-cancel / unknown stream are clean no-ops
    assert not pool.cancel(0)
    assert not pool.cancel(99)
    pool.close()


def test_round_robin_policy_cycles():
    ff = _lm()
    pool = ReplicaPool(ff, 3, policy="round_robin")
    seen = [pool.route([1, 2, 3])[0].idx for _ in range(6)]
    assert seen == [0, 1, 2, 0, 1, 2]
    pool.close()


# =======================================================================
# pool runs: exactness, labels, report
# =======================================================================
def test_pool_tokens_match_single_replica_and_labels():
    ff = _lm()
    traffic = _traffic(n=18, seed=2, sample_frac=0.3, tenants=2)
    tel = Telemetry()
    pool = ReplicaPool(ff, 2, policy="affinity", telemetry=tel)
    res = pool.run(traffic, slo_ttft_s=1.0, slo_tpot_s=1.0,
                   sample_seed=9)
    pool.assert_zero_recompiles()
    pool.check_drained()
    eng = ServeEngine(ff)
    eng.warmup()
    ref = eng.generate([t.prompt for t in traffic],
                       [t.max_new for t in traffic],
                       temperature=[t.temperature for t in traffic],
                       top_k=[t.top_k for t in traffic],
                       sample_seed=9,
                       stream_ids=[t.stream_id for t in traffic])
    for rec, r in zip(res["requests"], ref):
        assert rec["outcome"] == "completed" and rec["tokens"] == r
    # per-replica LABELED fold (the serve_metrics replica= satellite):
    # TTFT histograms and token counters split per replica without
    # double-counting the unlabeled aggregate
    m = pool.metrics
    per = [m.counter("serve_tokens_generated_total",
                     replica=str(i)) for i in (0, 1)]
    assert all(v > 0 for v in per)
    assert m.counter("serve_tokens_generated_total") == sum(per)
    assert m.hist_count("serve_ttft_seconds", replica="0") > 0
    assert m.counter("router_requests_total", replica="0") > 0
    assert m.counter("router_affinity_hits_total") > 0
    # router spans landed on the router track
    tracks = {ev[1] for ev in tel.events}
    assert ("serve", "router") in tracks
    # the report renders without error and carries the headline
    rep = router_report(res, m)
    assert "goodput-under-SLO" in rep and "affinity hits" in rep
    pool.close()


# =======================================================================
# autoscaler
# =======================================================================
def _scaler(pool, price, **over):
    kw = dict(slo_ttft_s=6 * price, slo_tpot_s=2 * price,
              min_replicas=1, max_replicas=3, interval_s=20 * price,
              up_patience=2, down_patience=6, cooldown_s=40 * price,
              decode_table={1: price}, tensor_parallel=1,
              decode_lanes=4)
    kw.update(over)
    return Autoscaler(pool.metrics, **kw)


def test_autoscaler_scales_up_and_replays():
    ff = _lm(pool_pages=40, max_seq_len=128)
    probe = ReplicaPool(ff, 1)
    price = probe.price_probe(64)
    probe.close()
    traffic = _traffic(n=40, seed=3, arrival="bursty",
                       rate_rps=0.2 / price, burst_factor=6.0,
                       tenants=5, prefix_tokens=40, max_prompt=64,
                       output_mean=8.0, max_new_cap=12)
    runs = []
    for _ in range(2):
        tel = Telemetry()
        pool = ReplicaPool(ff, 1, telemetry=tel)
        res = pool.run(traffic, slo_ttft_s=6 * price,
                       slo_tpot_s=2 * price,
                       autoscaler=_scaler(pool, price))
        pool.assert_zero_recompiles()
        pool.check_drained()
        runs.append([(e["t"], e["direction"], e["replica"])
                     for e in res["scale_events"]])
        pool.close()
    assert runs[0] and runs[0] == runs[1]
    assert runs[0][0][1] == "up"
    # every decision is visible as a telemetry SPAN with its reason
    spans = [e for e in tel.events
             if e[0] == "X" and e[2].startswith("scale_")]
    assert len(spans) == len(runs[0])
    assert all(e[6].get("reason") for e in spans)


def test_autoscaler_no_flap_on_steady_load():
    """Hysteresis: a comfortably-served steady stream produces ZERO
    scale decisions — and even under pressure, cooldown forbids an
    up/down flip-flop inside the dead time."""
    ff = _lm(pool_pages=48, max_seq_len=128)
    probe = ReplicaPool(ff, 2)
    price = probe.price_probe(64)
    probe.close()
    traffic = _traffic(n=30, seed=6, rate_rps=0.02 / price,
                       tenants=2, prefix_tokens=16, max_prompt=40,
                       output_mean=4.0)
    pool = ReplicaPool(ff, 2)
    scaler = _scaler(pool, price, min_replicas=2, max_replicas=4,
                     # generous SLOs: steady load sits well inside
                     slo_ttft_s=50 * price, slo_tpot_s=20 * price,
                     occ_lo=0.0)   # never "cold" either
    res = pool.run(traffic, slo_ttft_s=50 * price,
                   slo_tpot_s=20 * price, autoscaler=scaler)
    assert res["scale_events"] == []
    assert res["replicas_end"] == 2
    pool.close()
    # cooldown property on any event stream the bursty test produced:
    # consecutive decisions are separated by >= cooldown_s
    ff2 = _lm(pool_pages=40, max_seq_len=128)
    pool2 = ReplicaPool(ff2, 1)
    traffic2 = _traffic(n=40, seed=3, arrival="bursty",
                        rate_rps=0.2 / price, burst_factor=6.0,
                        tenants=5, prefix_tokens=40, max_prompt=64,
                        output_mean=8.0, max_new_cap=12)
    res2 = pool2.run(traffic2, slo_ttft_s=6 * price,
                     slo_tpot_s=2 * price,
                     autoscaler=_scaler(pool2, price,
                                        cooldown_s=40 * price))
    ts = [e["t"] for e in res2["scale_events"]]
    assert all(b - a >= 40 * price - 1e-12
               for a, b in zip(ts, ts[1:]))
    pool2.close()


def test_autoscaler_reads_only_gauges_and_prices_target():
    """The decision function sees nothing but the exported registry:
    rigged gauges alone drive it, and the decode-table pricing turns
    demand into a target count."""
    from flexflow_tpu.utils.telemetry import MetricsRegistry
    m = MetricsRegistry()
    a = Autoscaler(m, slo_ttft_s=0.1, slo_tpot_s=0.01,
                   min_replicas=1, max_replicas=4, interval_s=1.0,
                   up_patience=2, down_patience=2,
                   decode_table={1: 0.001}, tensor_parallel=1,
                   decode_lanes=4)   # capacity = 4000 tok/s
    assert a.target_replicas(9000.0) == 3
    m.set("serve_pool_replicas_live", 1)
    m.set("serve_pool_ttft_p99_window_s", 0.5)   # SLO blown
    m.set("serve_pool_occupancy_mean", 0.5)
    assert a.evaluate(1.0) is None                # patience 1/2
    d = a.evaluate(2.0)
    assert d is not None and d["direction"] == "up"
    assert "ttft" in d["reason"]
    # demand above priced capacity scales up even with latency OK
    b = Autoscaler(m, slo_ttft_s=0.0, slo_tpot_s=0.0,
                   min_replicas=1, max_replicas=4, interval_s=1.0,
                   up_patience=1, decode_table={1: 0.001},
                   tensor_parallel=1, decode_lanes=4)
    m.set("serve_pool_ttft_p99_window_s", 0.0)
    m.set("serve_pool_occupancy_mean", 0.2)
    m.set("serve_pool_decode_tokens_per_s_window", 9000.0)
    d2 = b.evaluate(1.0)
    assert d2 is not None and d2["direction"] == "up" \
        and d2["priced_target"] == 3
    # and a scale-down is REFUSED while the target needs the fleet
    m.set("serve_pool_replicas_live", 3)
    m.set("serve_pool_occupancy_mean", 0.0)
    m.set("serve_pool_queue_depth", 0.0)
    c = Autoscaler(m, slo_ttft_s=0.0, slo_tpot_s=0.0,
                   min_replicas=1, max_replicas=4, interval_s=1.0,
                   down_patience=1, decode_table={1: 0.001},
                   tensor_parallel=1, decode_lanes=4)
    assert c.evaluate(1.0) is None   # priced target 3 == live 3
    m.set("serve_pool_decode_tokens_per_s_window", 100.0)
    d3 = c.evaluate(2.0)
    assert d3 is not None and d3["direction"] == "down"


def test_autoscaler_validation_and_config():
    from flexflow_tpu.utils.telemetry import MetricsRegistry
    m = MetricsRegistry()
    with pytest.raises(ValueError, match="min_replicas"):
        Autoscaler(m, min_replicas=3, max_replicas=2)
    with pytest.raises(ValueError, match="interval"):
        Autoscaler(m, interval_s=0.0)
    cfg = FFConfig(batch_size=1, serve_replicas=2, slo_ttft_ms=5.0,
                   slo_tpot_ms=2.0, serve_autoscale=True)
    a = Autoscaler.from_config(cfg, m)
    assert a.slo_ttft_s == 0.005 and a.slo_tpot_s == 0.002
    assert a.max_replicas == 4   # 2x serve_replicas default


# =======================================================================
# chaos
# =======================================================================
def test_seeded_chaos_invariants_every_step():
    """A seeded storm — cancels (router-driven mid-generation AND
    external mid-queue), sampling, bursty arrivals — holds
    check_invariants on EVERY replica after EVERY step, reclaims all
    pages, never recompiles, and every surviving stream matches the
    single-replica reference."""
    ff = _lm(pool_pages=40)
    traffic = _traffic(n=24, seed=8, arrival="bursty",
                       rate_rps=3000.0, cancel_frac=0.25,
                       sample_frac=0.3, tenants=4)
    pool = ReplicaPool(ff, 2, policy="affinity")
    external_cancel = {5, 11}

    def on_step(replica, ev):
        for r in pool.replicas:
            r.engine.cache.check_invariants()
        for sid in list(external_cancel):
            if sid in pool._inflight:
                pool.cancel(sid)
                external_cancel.discard(sid)

    res = pool.run(traffic, slo_ttft_s=1.0, slo_tpot_s=1.0,
                   on_step=on_step)
    pool.assert_zero_recompiles()
    pool.check_drained()
    assert res["cancelled"] > 0
    eng = ServeEngine(ff)
    eng.warmup()
    ref = eng.generate([t.prompt for t in traffic],
                       [t.max_new for t in traffic],
                       temperature=[t.temperature for t in traffic],
                       top_k=[t.top_k for t in traffic],
                       sample_seed=0,
                       stream_ids=[t.stream_id for t in traffic])
    for rec, r in zip(res["requests"], ref):
        if rec["outcome"] == "completed":
            assert rec["tokens"] == r
        else:
            assert rec["tokens"] == r[:len(rec["tokens"])]
    # no pin survives the run
    assert all(not pins for pins in pool._pins)
    pool.close()


def test_pool_rerun_does_not_double_count_metrics():
    """run() twice on one pool: sessions recycle per run, so the
    end-of-run registry fold covers THIS run only — counters after
    two identical runs are exactly 2x one run's, not 3x (the
    session-lifetime re-fold bug)."""
    ff = _lm()
    traffic = _traffic(n=8, seed=12)
    pool = ReplicaPool(ff, 2)
    r1 = pool.run(traffic, slo_ttft_s=1.0, slo_tpot_s=1.0)
    after1 = pool.metrics.counter("serve_tokens_generated_total")
    assert after1 == r1["tokens_total"] > 0
    r2 = pool.run(traffic, slo_ttft_s=1.0, slo_tpot_s=1.0)
    after2 = pool.metrics.counter("serve_tokens_generated_total")
    assert after2 == after1 + r2["tokens_total"] == 2 * after1
    # the second run reproduces the first (same traffic, fresh rids)
    assert [r["tokens"] for r in r2["requests"]] == \
        [r["tokens"] for r in r1["requests"]]
    # ...and reports PER-RUN routing/scale accounting, not the pool
    # lifetime (routed == this run's requests; self.stats keeps the
    # lifetime totals, the DisaggCluster idiom)
    assert r2["routing"]["routed"] == len(traffic)
    assert pool.stats["routed"] == 2 * len(traffic)
    assert r2["scale_events"] == []
    pool.check_drained()
    pool.close()
    # round-robin placement also restarts per run (reused pool ==
    # fresh pool, deterministically)
    ff_rr = _lm()
    pool_rr = ReplicaPool(ff_rr, 2, policy="round_robin")
    a = pool_rr.run(traffic, slo_ttft_s=1.0, slo_tpot_s=1.0)
    b = pool_rr.run(traffic, slo_ttft_s=1.0, slo_tpot_s=1.0)
    assert [r["replica"] for r in a["requests"]] == \
        [r["replica"] for r in b["requests"]]
    pool_rr.close()


def test_autoscale_flag_arms_config_autoscaler():
    """--autoscale is a LIVE knob: run() with no explicit autoscaler
    builds one from the config flags."""
    ff = _lm(serve_autoscale=True, slo_ttft_ms=1000.0,
             slo_tpot_ms=1000.0, serve_autoscale_max=2)
    traffic = _traffic(n=6, seed=13)
    pool = ReplicaPool(ff, 1)
    res = pool.run(traffic)
    assert res["autoscaled"]
    pool.close()
    ff2 = _lm()
    pool2 = ReplicaPool(ff2, 1)
    assert not pool2.run(traffic)["autoscaled"]
    pool2.close()


# =======================================================================
# config / CLI
# =======================================================================
def test_router_config_flags_and_validation():
    cfg = FFConfig(batch_size=1, argv=[
        "--serve-replicas", "3", "--router-policy", "round_robin",
        "--slo-ttft-ms", "5", "--slo-tpot-ms", "1.5", "--autoscale",
        "--autoscale-max", "6"])
    assert cfg.serve_replicas == 3
    assert cfg.router_policy == "round_robin"
    assert cfg.slo_ttft_ms == 5.0 and cfg.slo_tpot_ms == 1.5
    assert cfg.serve_autoscale and cfg.serve_autoscale_max == 6
    with pytest.raises(ValueError, match="router_policy"):
        FFConfig(batch_size=1, router_policy="random")
    with pytest.raises(ValueError, match="serve_replicas"):
        FFConfig(batch_size=1, serve_replicas=0)
    with pytest.raises(ValueError, match="slo_ttft_ms"):
        FFConfig(batch_size=1, slo_ttft_ms=-1.0)
    # from_config picks the flags up
    ff = _lm(serve_replicas=2, router_policy="round_robin")
    pool = ReplicaPool.from_config(ff)
    assert len(pool.replicas) == 2 and pool.policy == "round_robin"
    pool.close()


# =======================================================================
# wall-clock fabric
# =======================================================================
def test_wall_clock_token_identity_both_modes():
    """The fabric's core contract: the SAME traffic serves
    token-identically on the virtual clock, the threaded wall clock,
    and the single-threaded wall baseline — sampling keys on stream
    ids, never on the clock (cancel-free traffic: abandon points are
    clock-dependent by design)."""
    traffic = _traffic(n=14, seed=4, sample_frac=0.3, tenants=2,
                       cancel_frac=0.0, rate_rps=300.0)

    def toks(res):
        return {r["stream_id"]: r["tokens"] for r in res["requests"]}

    pool = ReplicaPool(_lm(), 2, policy="affinity")
    virt = pool.run(traffic, sample_seed=3)
    assert all(r["outcome"] == "completed" for r in virt["requests"])
    pool.close()

    pool = ReplicaPool(_lm(), 2, policy="affinity")
    wall = pool.run(traffic, sample_seed=3, wall_clock=True,
                    time_scale=0.2, dwell_s=0.002)
    assert toks(wall) == toks(virt)
    assert wall["clock"] == "wall" and wall["wall_threads"]
    # one coherent clock: every record's stamps are ordered and the
    # makespan covers them (satellite: no wall/virtual mixing)
    for rec in wall["requests"]:
        assert rec["t_arrival"] <= rec["t_finish"] \
            <= wall["makespan_s"] + 1e-9
        if rec["ttft_s"] is not None:
            assert rec["ttft_s"] >= 0.0
    # wall runs label their OWN histogram series; the virtual series
    # stays untouched on this pool
    assert pool.metrics.hist_count(
        "serve_router_ttft_wall_seconds") > 0
    assert pool.metrics.hist_count(
        "serve_router_ttft_virtual_seconds") == 0
    assert any(p["busy_wall_s"] > 0 for p in wall["per_replica"])
    pool.assert_zero_recompiles()
    pool.check_drained()
    # the same pool replays VIRTUAL after a wall run, identically
    virt2 = pool.run(traffic, sample_seed=3)
    assert toks(virt2) == toks(virt)
    pool.close()

    pool = ReplicaPool(_lm(), 2, policy="affinity")
    single = pool.run(traffic, sample_seed=3, wall_clock=True,
                      wall_threads=False, time_scale=0.2,
                      dwell_s=0.002)
    assert toks(single) == toks(virt)
    assert single["clock"] == "wall" and not single["wall_threads"]
    pool.close()


def test_wall_clock_attribution_sums_to_measured_latency():
    """Satellite bugfix gate: explain_request must still sum exactly
    to measured latency when the run is wall-clock — every span and
    the request stamps live on ONE clock (time.perf_counter)."""
    from flexflow_tpu.utils.telemetry import REQUEST_COMPONENTS
    tel = Telemetry()
    pool = ReplicaPool(_lm(), 2, policy="affinity", telemetry=tel)
    traffic = _traffic(n=10, seed=6, cancel_frac=0.0,
                       rate_rps=300.0)
    res = pool.run(traffic, sample_seed=1, wall_clock=True,
                   time_scale=0.2, dwell_s=0.002)
    att = res["attribution"]
    assert set(att) == set(REQUEST_COMPONENTS)
    for rec in res["requests"][:4]:
        b = pool.explain_request(rec["stream_id"])
        assert b["replica"] == rec["replica"]
        assert abs(sum(b["components"].values()) - b["latency_s"]) \
            <= 1e-9 + 0.01 * b["latency_s"]
    pool.close()


def test_wall_clock_refuses_autoscaler_and_reads_config():
    traffic = _traffic(n=4, seed=0, cancel_frac=0.0)
    pool = ReplicaPool(_lm(), 2)
    price = pool.price_probe(64)
    with pytest.raises(ValueError, match="virtual clock"):
        pool.run(traffic, wall_clock=True,
                 autoscaler=_scaler(pool, price))
    pool.close()
    # --wall-clock dispatches run() to the wall loop via config
    ff = _lm(serve_wall_clock=True)
    pool = ReplicaPool(ff, 2)
    res = pool.run(traffic, sample_seed=0, time_scale=0.1)
    assert res["clock"] == "wall"
    pool.close()
    cfg = FFConfig(batch_size=1, argv=["--wall-clock", "--transport",
                                       "tcp", "--transport-port",
                                       "0"])
    assert cfg.serve_wall_clock and cfg.serve_transport == "tcp"
    with pytest.raises(ValueError, match="serve_transport"):
        FFConfig(batch_size=1, serve_transport="udp")
    with pytest.raises(ValueError, match="mutually exclusive"):
        FFConfig(batch_size=1, serve_wall_clock=True,
                 serve_autoscale=True)


def test_rescale_arrivals_preserves_identity_fields():
    from flexflow_tpu.serve import rescale_arrivals
    traffic = _traffic(n=8, seed=2, cancel_frac=0.2, sample_frac=0.3)
    fast = rescale_arrivals(traffic, 0.25)
    assert [t.t_arrival * 0.25 for t in traffic] == \
        [t.t_arrival for t in fast]
    assert [(t.stream_id, t.prompt, t.max_new, t.temperature,
             t.cancel_after_tokens) for t in traffic] == \
        [(t.stream_id, t.prompt, t.max_new, t.temperature,
          t.cancel_after_tokens) for t in fast]
    assert traffic[0] is not fast[0]    # copies, originals untouched
    with pytest.raises(ValueError, match="scale"):
        rescale_arrivals(traffic, 0.0)
