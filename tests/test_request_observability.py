"""End-to-end request observability (the serving-tier tentpole;
docs/observability.md "Trace-id propagation" / "Per-request latency
attribution" / "SLO burn-rate monitor" / "Failure flight recorder").

Layered like the subsystem:
  * trace propagation — ONE trace id minted at the first tier rides
    the Request / ServeSession / PageShipment, so a routed (and
    disagg-routed) request's spans reconstruct one causally-linked,
    time-ordered timeline across router/replica/role tracks on the
    shared trace clock.
  * attribution — explain_request folds a request's spans into an
    additive queue/routing/prefill/transfer/decode/preempt_stall/
    retry/other breakdown summing to its measured latency (within 1%
    by gate, exactly by construction), with the pool-level aggregate
    fold landing in the exported registry.
  * SLO burn monitor — error-budget counters from the pool, windowed
    fast/slow burn rates, deterministic fire/clear transitions that
    replay at one seed, alert spans + gauges.
  * flight recorder — chaos-aborted runs leave a loadable,
    schema-valid post-mortem bundle (fault-abort / deadline-storm /
    explicit triggers), bounded, with the engine serving on.
  * endpoints — the aggregated ReplicaPool/DisaggCluster /metrics
    endpoint survives CONCURRENT scrapes during a live run and goes
    down cleanly on close().
"""

import functools
import glob
import json
import os
import sys
import threading
import urllib.request

import numpy as np
import pytest

from flexflow_tpu.config import FFConfig
from flexflow_tpu.serve import ServeEngine
from flexflow_tpu.serve.disagg import DisaggCluster
from flexflow_tpu.serve.router import ReplicaPool
from flexflow_tpu.serve.traffic import TrafficSpec, make_traffic
from flexflow_tpu.utils.slo import SLOBurnMonitor
from flexflow_tpu.utils.telemetry import (REQUEST_COMPONENTS,
                                          MetricsRegistry, Telemetry,
                                          attribute_request,
                                          fold_attribution,
                                          next_trace_id)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "tools"))

VOCAB = 89


def _lm(**over):
    from flexflow_tpu.models.transformer import build_transformer_lm
    kw = dict(batch_size=1, kv_page_size=8, kv_num_pages=73,
              serve_max_seqs=8, serve_prefill_budget=48,
              serve_retry_backoff_s=0.0)
    kw.update(over)
    cfg = FFConfig(**kw)
    return build_transformer_lm(cfg, vocab_size=VOCAB, max_seq_len=64,
                                hidden=32, num_heads=4, num_layers=2,
                                ff_dim=64)


def _small_lm(**over):
    """Router-sized model: tiny pages force interesting schedules."""
    from flexflow_tpu.models.transformer import build_transformer_lm
    kw = dict(batch_size=1, kv_page_size=4, kv_num_pages=48,
              serve_max_seqs=4, serve_prefill_budget=8,
              serve_retry_backoff_s=0.0, serve_spec_decode=False)
    kw.update(over)
    cfg = FFConfig(**kw)
    return build_transformer_lm(cfg, vocab_size=VOCAB, max_seq_len=48,
                                hidden=32, num_heads=4, num_layers=2,
                                ff_dim=64)


def _prompts(rng, n, lo=4, hi=28):
    return [list(rng.randint(1, VOCAB, size=rng.randint(lo, hi)))
            for _ in range(n)]


def _traffic(n=12, seed=0, **over):
    kw = dict(requests=n, seed=seed, tenants=3, prefix_tokens=8,
              tail_mean=4, output_mean=4, max_prompt=24,
              max_new_cap=6, vocab=VOCAB)
    kw.update(over)
    return make_traffic(TrafficSpec(**kw))


# ------------------------------------------------- trace propagation
def test_trace_ids_unique_and_minted_at_submit():
    a, b = next_trace_id(), next_trace_id()
    assert isinstance(a, int) and b > a
    tel = Telemetry()
    eng = ServeEngine(_lm(), telemetry=tel)
    eng.warmup()
    rng = np.random.RandomState(0)
    eng.generate(_prompts(rng, 4), 4)
    rows = eng.last_stats["requests"]
    tids = [r["trace_id"] for r in rows]
    assert len(set(tids)) == len(tids) and all(t > b for t in tids)


def test_engine_timeline_causally_linked():
    """Every lifecycle span of one request carries its trace id and
    the timeline is time-ordered on the shared clock."""
    tel = Telemetry()
    eng = ServeEngine(_lm(), telemetry=tel)
    eng.warmup()
    rng = np.random.RandomState(1)
    eng.generate(_prompts(rng, 6), 5)
    for row in eng.last_stats["requests"]:
        evs = tel.request_events(row["trace_id"])
        names = {e[2] for e in evs}
        assert "queue_wait" in names
        assert "prefill" in names
        # the queue_wait 'b' precedes every chunk span's start
        qb = min(e[3] for e in evs if e[0] == "b")
        chunk_starts = [e[3] for e in evs if e[0] == "X"]
        assert chunk_starts and all(qb <= t for t in chunk_starts)
        # no foreign rid ever shares the trace id
        rids = {e[6]["rid"] for e in evs if e[6] and "rid" in e[6]}
        assert rids == {row["rid"]}


def test_routed_request_one_timeline():
    """The acceptance gate's first clause: a routed request's router
    decision, queue wait and chunk spans land on ONE causally-linked
    timeline (one merged clock across the pool's replica tracks)."""
    tel = Telemetry()
    pool = ReplicaPool(_small_lm(), 2, policy="affinity",
                       telemetry=tel)
    pool.run(_traffic(10))
    recs = pool.last_stats["requests"]
    assert recs
    for rec in recs:
        evs = tel.request_events(rec["trace_id"])
        names = {e[2] for e in evs}
        assert {"routing", "route"} <= names
        assert "queue_wait" in names
        assert "prefill" in names or "decode" in names
        # routing happens before the first chunk span — one clock
        t_route = min(e[3] for e in evs if e[2] == "routing")
        chunk_ts = [e[3] for e in evs
                    if e[0] == "X" and e[2] != "routing"]
        assert chunk_ts and all(t_route <= t for t in chunk_ts)
        # spans recorded on the replica's OWN track group
        procs = {e[1][0] for e in evs if e[0] == "X"
                 and e[2] in ("prefill", "decode", "spec_decode")}
        assert procs == {f"replica{rec['replica']}"}
    pool.close()


def test_disagg_request_one_timeline_with_transfer():
    """A disagg-routed request: prefill-role spans, the kv_handoff
    transfer span (trace id crossed inside the PageShipment) and
    decode-role spans share one trace id; attribution shows a
    transfer component and sums to the cross-role latency."""
    tel = Telemetry()
    cl = DisaggCluster(_lm(), prefill_engines=1, decode_engines=1,
                       telemetry=tel)
    cl.warmup()
    rng = np.random.RandomState(2)
    prompts = [list(rng.randint(1, VOCAB, size=rng.randint(12, 30)))
               for _ in range(4)]
    out = cl.generate(prompts, 6)
    assert out == cl.generate_reference(prompts, 6)
    crossed = 0
    for i in range(len(prompts)):
        tid, pre, dec = cl._last_traces[i]
        evs = tel.request_events(tid)
        names = {e[2] for e in evs}
        assert "prefill" in names and "queue_wait" in names
        b = cl.explain_request(i)
        assert abs(sum(b["components"].values()) - b["latency_s"]) \
            <= 1e-9 + 0.01 * b["latency_s"]
        if b["crossed_link"]:
            crossed += 1
            assert "kv_handoff" in names and "decode" in names
            assert b["components"]["transfer"] > 0.0
    assert crossed > 0
    cl.close()


def test_shipment_carries_trace_id():
    tel = Telemetry()
    eng = ServeEngine(_lm(), telemetry=tel)
    eng.warmup()
    got = {}

    def grab(req):
        got["ship"] = eng.export_kv(req.slot, req.context,
                                    trace_id=req.trace_id)

    rng = np.random.RandomState(3)
    eng.generate([list(rng.randint(1, VOCAB, size=20))], 1,
                 on_finish=grab)
    ship = got["ship"]
    assert ship is not None
    assert ship.trace_id == eng.last_stats["requests"][0]["trace_id"]


# ------------------------------------------------- attribution
def test_attribute_request_partition_rules():
    """Unit check of the interval sweep: overlaps resolve by priority,
    async pairs close, retry carves out of compute, and the components
    sum to the window exactly."""
    evs = [
        ("b", ("p", "q"), "queue_wait", 0.0, 0.0, 7, {"trace": 7}),
        ("e", ("p", "q"), "queue_wait", 2.0, 0.0, 7, None),
        # prefill overlapping the queue tail: compute wins the overlap
        ("X", ("p", "s"), "prefill", 1.0, 1.5, None, {"trace": 7}),
        ("X", ("p", "s"), "decode", 3.0, 2.0, None, {"trace": 7}),
        # retry backoff inside the decode span (no trace arg)
        ("X", ("p", "e"), "retry_backoff", 3.5, 0.5, None, None),
        # a foreign request's span never contributes
        ("X", ("p", "s"), "decode", 3.0, 2.0, None, {"trace": 8}),
        ("X", ("p", "c"), "kv_handoff", 5.5, 0.25, None, {"trace": 7}),
    ]
    b = attribute_request(evs, 7, t_submit=0.0, t_finish=6.0)
    c = b["components"]
    assert abs(sum(c.values()) - 6.0) < 1e-12
    assert c["queue"] == pytest.approx(1.0)       # [0, 1): pre-prefill
    assert c["prefill"] == pytest.approx(1.5)     # [1, 2.5)
    assert c["decode"] == pytest.approx(1.5)      # [3, 5) minus retry
    assert c["retry"] == pytest.approx(0.5)       # [3.5, 4)
    assert c["transfer"] == pytest.approx(0.25)
    assert c["other"] == pytest.approx(6.0 - 1.0 - 1.5 - 1.5 - 0.5
                                       - 0.25)


def test_explain_request_sums_and_errors():
    tel = Telemetry()
    eng = ServeEngine(_lm(), telemetry=tel)
    eng.warmup()
    rng = np.random.RandomState(4)
    eng.generate(_prompts(rng, 6), 6)
    for row in eng.last_stats["requests"]:
        b = eng.explain_request(row["rid"])
        assert set(b["components"]) == set(REQUEST_COMPONENTS)
        lat = b["latency_s"]
        assert abs(sum(b["components"].values()) - lat) \
            <= 1e-9 + 0.01 * lat
        assert b["components"]["prefill"] > 0.0
        assert b["components"]["decode"] > 0.0
        assert b["attributed_s"] <= lat + 1e-9
    with pytest.raises(KeyError):
        eng.explain_request(999)
    eng_off = ServeEngine(_lm())
    with pytest.raises(RuntimeError):
        eng_off.explain_request(0)


@functools.lru_cache(maxsize=None)
def _preempting_run():
    """One run under injected page pressure (the PR-6 chaos site makes
    the evictions deterministic). -> the engine, and the rids that
    sat out a step: no chunk span in some step between their first
    and their last."""
    from flexflow_tpu.utils.faults import FaultInjector
    tel = Telemetry()
    inj = FaultInjector("serve.page_pressure:exhaust:0.9@4-8", seed=0)
    eng = ServeEngine(_lm(kv_num_pages=17, serve_max_seqs=4,
                          serve_prefill_budget=24,
                          serve_spec_decode=False),
                      telemetry=tel, faults=inj)
    eng.warmup()
    rng = np.random.RandomState(5)
    prompts = _prompts(rng, 8, lo=10, hi=26)
    eng.generate(prompts, 8)
    steps, chunks = [], {}
    for ph, track, name, ts, _dur, _ident, args in tel.events:
        if ph != "X":
            continue
        if name == "step":
            steps.append(ts)
        elif name in ("prefill", "decode"):
            chunks.setdefault(args["rid"], set()).add(ts)
    sat_out = {rid for rid, at in chunks.items()
               if any(min(at) < ts < max(at) and ts not in at
                      for ts in steps)}
    return eng, sat_out


def test_preempted_request_attributes_stall():
    """Preemption leaves a preempt_stall component (the requeue_wait
    async span) in EVERY request that sat out a step for it, and the
    sum contract survives the adversarial path."""
    eng, sat_out = _preempting_run()
    preempted = [r for r in eng.last_stats["requests"]
                 if r["preemptions"] > 0]
    assert preempted, "tiny pool should force preemption"
    assert sat_out & {r["rid"] for r in preempted}
    for row in preempted:
        b = eng.explain_request(row["rid"])
        if row["rid"] in sat_out:
            assert b["components"]["preempt_stall"] > 0.0
        lat = b["latency_s"]
        assert abs(sum(b["components"].values()) - lat) \
            <= 1e-9 + 0.01 * lat


def test_same_plan_evict_readmit_stalls_no_step():
    """A request evicted and re-admitted by ONE plan (the pages its
    eviction freed cover the needy request and its own return) holds a
    chunk in every step: each eviction still leaves its `preempt`
    instant and a closed `requeue_wait`, which ends where the
    re-admitting step's chunk span starts — so the stall is the host's
    planning time where the step landed first, and nothing where the
    step before was still running (its chunk span covers the wait).
    What the eviction cost is the re-prefill, under `prefill`."""
    eng, sat_out = _preempting_run()
    never = [r for r in eng.last_stats["requests"]
             if r["preemptions"] > 0 and r["rid"] not in sat_out]
    assert never, "the injected pressure evicts the oldest in place"
    evs = list(eng.telemetry.events)
    for row in never:
        rid = row["rid"]
        begun = {ident: ts for ph, _t, name, ts, _d, ident, args in evs
                 if ph == "b" and name == "requeue_wait"
                 and args["rid"] == rid}
        ended = {ident: ts for ph, _t, name, ts, _d, ident, _a in evs
                 if ph == "e" and name == "requeue_wait"
                 and ident in begun}
        assert len(begun) == len(ended) == row["preemptions"]
        assert all(ended[i] >= begun[i] for i in begun)
        instants = [args for ph, _t, name, _ts, _d, _i, args in evs
                    if ph == "i" and name == "preempt"
                    and args["rid"] == rid]
        assert len(instants) == row["preemptions"]
        b = eng.explain_request(rid)
        waits = sum(ended[i] - begun[i] for i in begun)
        assert 0.0 <= b["components"]["preempt_stall"] <= waits + 1e-9
        assert b["components"]["prefill"] > 0.0


def test_fold_attribution_registry_series():
    m = MetricsRegistry()
    fold_attribution({"latency_s": 2.0,
                      "components": {"queue": 0.5, "decode": 1.0,
                                     "other": 0.5}}, m)
    fold_attribution({"latency_s": 2.0,
                      "components": {"queue": 1.0, "decode": 0.5,
                                     "other": 0.5}}, m)
    assert m.counter("serve_latency_attributed_requests_total") == 2
    assert m.counter("serve_latency_attribution_seconds_total",
                     component="queue") == pytest.approx(1.5)
    assert m.gauge("serve_latency_attribution_fraction",
                   component="decode") == pytest.approx(1.5 / 4.0)


def test_pool_run_folds_attribution_into_registry():
    tel = Telemetry()
    pool = ReplicaPool(_small_lm(), 2, telemetry=tel)
    st = pool.run(_traffic(8, seed=1))
    att = st["attribution"]
    assert set(att) == set(REQUEST_COMPONENTS)
    assert sum(att.values()) > 0
    n = pool.metrics.counter("serve_latency_attributed_requests_total")
    assert n > 0
    # per-request explain by stream id agrees with the records
    rec = st["requests"][0]
    b = pool.explain_request(rec["stream_id"])
    assert b["replica"] == rec["replica"]
    assert abs(sum(b["components"].values()) - b["latency_s"]) \
        <= 1e-9 + 0.01 * b["latency_s"]
    pool.close()


# ------------------------------------------------- SLO burn monitor
def _drive_monitor(mon, history):
    for t, total, viol in history:
        mon.registry.counter_set("serve_slo_requests_total", total)
        mon.registry.counter_set("serve_slo_violations_total", viol)
        mon.observe(t)


def test_burn_monitor_fires_and_clears_deterministically():
    def history():
        out, total, viol = [], 0, 0
        for t in range(1, 120):
            total += 10
            if 40 <= t < 60:
                viol += 5
            out.append((float(t), total, viol))
        return out

    runs = []
    for _ in range(2):
        mon = SLOBurnMonitor(MetricsRegistry(), error_budget=0.01,
                             fast_window_s=10, slow_window_s=40,
                             interval_s=1.0)
        _drive_monitor(mon, history())
        runs.append(list(mon.events))
    assert runs[0] == runs[1]
    states = [e["state"] for e in runs[0]]
    assert states == ["firing", "ok"]
    assert 40 <= runs[0][0]["t"] < 60


def test_burn_monitor_gauges_spans_and_validation():
    tel = Telemetry()
    mon = SLOBurnMonitor(tel.metrics, error_budget=0.01,
                         fast_window_s=5, slow_window_s=20,
                         interval_s=1.0, telemetry=tel)
    hist = [(float(t), 10 * t, 5 * t if t > 3 else 0)
            for t in range(1, 30)]
    _drive_monitor(mon, hist)
    m = tel.metrics
    assert m.gauge("slo_burn_rate", window="fast") > 0
    assert m.gauge("slo_budget_remaining", 1.0) < 1.0
    assert mon.state == "firing"
    mon.finish(29.0)
    names = [e[2] for e in tel.events]
    assert "slo_alert_fire" in names and "slo_alert" in names
    assert "slo_burn_rate" in m.to_prometheus()
    with pytest.raises(ValueError):
        SLOBurnMonitor(MetricsRegistry(), error_budget=0.0)
    with pytest.raises(ValueError):
        SLOBurnMonitor(MetricsRegistry(), fast_window_s=10,
                       slow_window_s=5)
    with pytest.raises(ValueError):
        SLOBurnMonitor(MetricsRegistry(), interval_s=0)


def test_pool_exports_slo_counters_and_alerts_replay():
    """The pool's error-budget counters + auto-armed monitor: alert
    transitions are part of last_stats and replay exactly at one
    seed across two fresh pools."""
    runs = []
    for _ in range(2):
        pool = ReplicaPool(_small_lm(), 2, telemetry=Telemetry())
        price = pool.price_probe(16)
        # impossible TPOT target: every completed request violates
        st = pool.run(_traffic(10, seed=3),
                      slo_ttft_s=price * 200, slo_tpot_s=price * 1e-3)
        tot = pool.metrics.counter("serve_slo_requests_total")
        viol = pool.metrics.counter("serve_slo_violations_total")
        assert tot > 0 and viol > 0
        assert pool.metrics.counter("serve_slo_violations_total",
                                    slo="tpot") > 0
        assert 0.0 <= st["slo_attainment_budget"] <= 1.0
        runs.append([(round(e["t"], 9), e["state"])
                     for e in st["slo_alerts"]])
        pool.close()
    assert runs[0] == runs[1]
    assert runs[0] and runs[0][0][1] == "firing"


def test_no_slo_monitor_flag_disarms():
    cfg_lm = _small_lm(slo_monitor=False)
    pool = ReplicaPool(cfg_lm, 1, telemetry=Telemetry())
    price = pool.price_probe(16)
    st = pool.run(_traffic(4, seed=4), slo_ttft_s=price * 200,
                  slo_tpot_s=price * 1e-3)
    assert st["slo_alerts"] == []
    # counters still export (the monitor is the consumer, not the
    # producer)
    assert pool.metrics.counter("serve_slo_requests_total") > 0
    # the call-level disarm spelling works too (and a telemetry-off
    # engine's fold returns zeros without touching the shared
    # disabled registry)
    st2 = pool.run(_traffic(4, seed=7), slo_ttft_s=price * 200,
                   slo_tpot_s=price * 1e-3, slo_monitor=False)
    assert st2["slo_alerts"] == []
    eng_off = ServeEngine(_lm())
    eng_off.warmup()
    eng_off.generate([[1, 2, 3]], 2)
    assert all(v == 0.0 for v in eng_off.fold_attribution().values())
    assert not eng_off.telemetry.metrics.counters
    pool.close()


# ------------------------------------------------- flight recorder
def test_fault_abort_leaves_loadable_bundle(tmp_path):
    """The acceptance gate's last clause: a fault-aborted run leaves a
    loadable post-mortem bundle — under the PR-6 chaos harness, with
    invariants intact and the engine serving on."""
    from postmortem import validate
    pmdir = str(tmp_path / "pm")
    eng = ServeEngine(_lm(postmortem_dir=pmdir,
                          fault_spec="serve.mixed:fatal@4"))
    assert eng.telemetry.enabled  # postmortem_dir implies telemetry
    eng.warmup()
    rng = np.random.RandomState(6)
    prompts = _prompts(rng, 6)
    with pytest.raises(Exception):
        eng.generate(prompts, 8)
    found = glob.glob(os.path.join(pmdir,
                                   "postmortem-fault_abort-*.json"))
    assert len(found) == 1
    with open(found[0]) as f:
        bundle = json.load(f)
    assert validate(bundle) == []
    assert bundle["reason"] == "fault_abort"
    assert bundle["detail"]["failed_inflight"] > 0
    assert len(bundle["events"]) > 0
    assert len(bundle["events"]) <= eng.postmortem_events
    assert "serve.mixed" in bundle["faults"]["fired"]
    # the engine recovered and the pool is clean
    eng.cache.check_invariants()
    out = eng.generate(prompts[:2], 4)
    assert all(len(o) == 4 for o in out)


def test_deadline_storm_and_rate_limit(tmp_path):
    pmdir = str(tmp_path / "pm")
    eng = ServeEngine(_lm(postmortem_dir=pmdir))
    eng.warmup()
    rng = np.random.RandomState(7)
    prompts = _prompts(rng, 6)
    eng.generate(prompts, 8, deadline_s=1e-4)
    storms = glob.glob(
        os.path.join(pmdir, "postmortem-deadline_storm-*.json"))
    assert len(storms) == 1
    # a second storm inside the rate-limit window dumps NOTHING new
    eng.generate(prompts, 8, deadline_s=1e-4)
    assert len(glob.glob(os.path.join(pmdir, "postmortem-*.json"))) \
        == 1
    # explicit dumps bypass the limiter
    p = eng.dump_postmortem(reason="manual")
    assert os.path.exists(p)


def test_rejection_triggers_bundle(tmp_path):
    """Rung-4 rejection (injected page-pool exhaustion hides the whole
    pool from planning — the PR-6 chaos site) black-boxes: the
    scheduler state in the bundle shows the rejection."""
    from flexflow_tpu.utils.faults import FaultInjector
    from postmortem import validate
    pmdir = str(tmp_path / "pm")
    inj = FaultInjector("serve.page_pressure:exhaust:1.0@1-50", seed=0)
    eng = ServeEngine(_lm(postmortem_dir=pmdir), faults=inj)
    eng.warmup()
    rng = np.random.RandomState(8)
    big = list(rng.randint(1, VOCAB, size=30))
    out = eng.generate([big], 2)
    assert out[0] == []  # rejected, not raised
    found = glob.glob(os.path.join(pmdir,
                                   "postmortem-rejection-*.json"))
    assert len(found) == 1
    with open(found[0]) as f:
        bundle = json.load(f)
    assert validate(bundle) == []
    assert bundle["scheduler"]["stats"]["rejected"] >= 1


def test_bundle_write_is_atomic(tmp_path):
    """No partially-written bundle is ever visible: the tmp file is
    gone and the artifact parses."""
    eng = ServeEngine(_lm(telemetry=True))
    eng.warmup()
    rng = np.random.RandomState(9)
    eng.generate(_prompts(rng, 2), 3)
    path = str(tmp_path / "bundle.json")
    got = eng.dump_postmortem(path=path, reason="manual")
    assert got == path and os.path.exists(path)
    assert not glob.glob(path + ".tmp.*")
    with open(path) as f:
        json.load(f)


# ------------------------------------------------- endpoints
def _scrape(port, path="/metrics"):
    return urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=5)


def test_pool_endpoint_concurrent_scrape_during_run():
    """Satellite gate: the ReplicaPool's ONE aggregated /metrics
    endpoint serves concurrent scrapes while run() is folding into
    the registry from the serving thread — every scrape 200 + parses,
    and close() takes the endpoint down."""
    import re
    lm = _small_lm(metrics_port=0)
    pool = ReplicaPool(lm, 2, telemetry=Telemetry())
    assert pool.metrics_server is not None
    port = pool.metrics_server.port
    results = {"scrapes": 0, "errors": []}
    stop = threading.Event()
    line_re = re.compile(
        r'^(# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* '
        r'(counter|gauge|summary)'
        r'|[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? -?[0-9eE.+-]+'
        r'|)$')

    def scraper():
        while not stop.is_set():
            try:
                with _scrape(port) as resp:
                    assert resp.status == 200
                    text = resp.read().decode()
                for line in text.splitlines():
                    assert line_re.match(line), line
                results["scrapes"] += 1
            except Exception as e:   # pragma: no cover - failure path
                results["errors"].append(repr(e))
                return

    threads = [threading.Thread(target=scraper, daemon=True)
               for _ in range(2)]
    for t in threads:
        t.start()
    try:
        price = pool.price_probe(16)
        pool.run(_traffic(16, seed=5), slo_ttft_s=price * 50,
                 slo_tpot_s=price * 4)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
    assert not results["errors"], results["errors"]
    assert results["scrapes"] > 0
    # the aggregated page carries router + SLO + attribution series
    with _scrape(port) as resp:
        page = resp.read().decode()
    assert "router_requests_total" in page
    assert "serve_pool_slo_attainment" in page
    assert "serve_latency_attribution_seconds_total" in page
    with _scrape(port, "/healthz") as resp:
        assert resp.status == 200
    pool.close()
    with pytest.raises(Exception):
        _scrape(port, "/healthz")


def test_cluster_endpoint_scrape_and_close():
    """The DisaggCluster's aggregated endpoint: one port serves both
    roles' fold + handoff counters; close() is clean + idempotent."""
    lm = _lm(metrics_port=0)
    cl = DisaggCluster(lm, prefill_engines=1, decode_engines=1)
    assert cl.metrics_server is not None
    # role engines own NO endpoint — the cluster aggregates
    for _role, eng in cl.engines():
        assert eng.metrics_server is None
    cl.warmup()
    rng = np.random.RandomState(10)
    prompts = [list(rng.randint(1, VOCAB, size=rng.randint(12, 28)))
               for _ in range(3)]
    cl.generate(prompts, 5)
    port = cl.metrics_server.port
    with _scrape(port) as resp:
        page = resp.read().decode()
    assert 'serve_ttft_seconds{quantile="0.5",role="prefill"}' in page \
        or 'role="prefill"' in page
    assert "kv_transfer_bytes_total" in page
    cl.close()
    cl.close()   # idempotent
    with pytest.raises(Exception):
        _scrape(port, "/healthz")


# ------------------------------------------------- contracts / CLI
def test_telemetry_on_off_tokens_identical_with_traces():
    """The PR-10 contract holds through the tentpole: trace minting,
    attribution stash and flight-recorder arming change NO tokens and
    compile NOTHING."""
    lm = _lm()
    rng = np.random.RandomState(11)
    prompts = _prompts(rng, 6)
    eng_off = ServeEngine(lm)
    eng_off.warmup()
    out_off = eng_off.generate(prompts, 6)
    tel = Telemetry()
    eng_on = ServeEngine(lm, telemetry=tel)
    counts = eng_on.warmup()
    out_on = eng_on.generate(prompts, 6)
    assert out_on == out_off
    assert eng_on.compile_counts() == counts
    # explicit trace ids are observability-only
    out_tid = eng_on.generate(prompts, 6,
                              trace_ids=[next_trace_id()
                                         for _ in prompts])
    assert out_tid == out_off
    assert eng_on.compile_counts() == counts


def test_config_flags_and_validation():
    cfg = FFConfig(argv=["--postmortem-dir", "/tmp/pm",
                         "--postmortem-events", "512",
                         "--slo-error-budget", "0.05",
                         "--no-slo-monitor"])
    assert cfg.postmortem_dir == "/tmp/pm"
    assert cfg.postmortem_events == 512
    assert cfg.slo_error_budget == 0.05
    assert cfg.slo_monitor is False
    with pytest.raises(ValueError):
        FFConfig(postmortem_events=0)
    with pytest.raises(ValueError):
        FFConfig(slo_error_budget=0.0)
    with pytest.raises(ValueError):
        FFConfig(slo_error_budget=1.5)
    # trace_ids length validation
    eng = ServeEngine(_lm())
    eng.warmup()
    with pytest.raises(ValueError):
        eng.generate([[1, 2, 3]], 2, trace_ids=[1, 2])


def test_router_report_renders_slo_and_attribution():
    from flexflow_tpu.utils.profiling import router_report
    tel = Telemetry()
    pool = ReplicaPool(_small_lm(), 2, telemetry=tel)
    price = pool.price_probe(16)
    st = pool.run(_traffic(10, seed=6), slo_ttft_s=price * 200,
                  slo_tpot_s=price * 1e-3)
    text = router_report(st, metrics=pool.metrics)
    assert "slo budget: attainment" in text
    assert "burn fast=" in text
    assert "latency attribution:" in text
    if st["slo_alerts"]:
        assert "slo alert -> firing" in text
    pool.close()
