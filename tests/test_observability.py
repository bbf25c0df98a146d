"""Explainable placement search + memory ledger + metrics endpoint
(PR 11 tentpole; docs/observability.md).

Layered like the subsystem:
  * schedule — simulated-trace round-trip: the exported Perfetto JSON
    loads, every event is schema-valid, the critical-path chain is
    time-contiguous, per-resource tracks never overlap, and the
    trace's exact end time equals Simulator.simulate's returned
    makespan BIT-exactly (train) / simulate_serve_step's (serve).
  * search trace — tracing is pure observation (bit-identical results
    at the same seed, on vs off), deterministic event streams, the
    bounded ring, and the serve-placement walk's trace.
  * attribution — per-task-class drift folding: breakdown accounting,
    the share fold, the least-squares alignment recovering a rigged
    per-class scale, and the report table.
  * ledger — serve + train memory ledgers vs the actual nbytes of the
    live device buffers; explain_placement component sums exact.
  * endpoint — /metrics scrape parses, /healthz lives, close() is
    clean and idempotent.
"""

import json
import urllib.request

import numpy as np
import pytest

from flexflow_tpu.config import FFConfig
from flexflow_tpu.parallel.mesh import make_mesh
from flexflow_tpu.parallel.pconfig import Strategy
from flexflow_tpu.search.cost_model import ServeArch
from flexflow_tpu.search.simulator import (Simulator,
                                           export_serve_schedule,
                                           serve_step_breakdown,
                                           simulate_serve_step)
from flexflow_tpu.search.trace import SearchTrace
from flexflow_tpu.utils.telemetry import Telemetry

VOCAB = 89


def _model(layers=2):
    from flexflow_tpu.models.transformer import build_transformer
    cfg = FFConfig(batch_size=8)
    cfg.enable_parameter_parallel = True
    cfg.enable_sequence_parallel = True
    return build_transformer(cfg, batch_size=8, seq_len=64, hidden=128,
                             num_heads=4, num_layers=layers, ff_dim=256,
                             num_classes=10)


def _mesh():
    return make_mesh((2, 2, 2), ("data", "model", "seq"))


def _lm(**cfg_kw):
    from flexflow_tpu.models.transformer import build_transformer_lm
    cfg = FFConfig(batch_size=1, kv_page_size=8, kv_num_pages=73,
                   serve_max_seqs=8, serve_prefill_budget=48,
                   serve_retry_backoff_s=0.0)
    for k, v in cfg_kw.items():
        setattr(cfg, k, v)
    return build_transformer_lm(cfg, vocab_size=VOCAB, max_seq_len=64,
                                hidden=32, num_heads=4, num_layers=2,
                                ff_dim=64)


# --------------------------------------------------------- schedule
def _load_spans(path):
    with open(path) as f:
        doc = json.load(f)
    for ev in doc["traceEvents"]:
        assert isinstance(ev.get("ph"), str) and ev.get("name"), ev
        assert isinstance(ev.get("pid"), int) \
            and isinstance(ev.get("tid"), int), ev
        if ev["ph"] != "M":
            assert isinstance(ev["ts"], (int, float)), ev
        if ev["ph"] == "X":
            assert isinstance(ev["dur"], (int, float)) \
                and ev["dur"] >= 0, ev
    return doc, [e for e in doc["traceEvents"] if e["ph"] == "X"]


def test_train_schedule_trace_round_trip(tmp_path):
    ff = _model()
    mesh = _mesh()
    sim = Simulator(ff, mesh)
    strat = Strategy()
    path = str(tmp_path / "sched.json")
    summary = sim.export_schedule(strat, path)
    full = sim.simulate(strat)
    doc, spans = _load_spans(path)
    # exact end-time equality with the priced step time
    assert summary["makespan_s"] == full
    assert doc["metadata"]["makespan_s"] == full
    assert max(e["args"]["t_end_s"] for e in spans) == full
    # per-resource tracks never overlap (resource exclusivity is the
    # event loop's contract) and stay within [0, makespan]
    by_track = {}
    for e in spans:
        by_track.setdefault((e["pid"], e["tid"]), []).append(e)
    assert len(by_track) >= 2  # compute + ici at least
    for es in by_track.values():
        es.sort(key=lambda e: (e["args"]["t_start_s"],
                               e["args"]["t_end_s"]))
        for a, b in zip(es, es[1:]):
            assert a["args"]["t_end_s"] <= b["args"]["t_start_s"]
        for e in es:
            assert 0.0 <= e["args"]["t_start_s"] \
                <= e["args"]["t_end_s"] <= full
    # the critical path chains contiguously (each start bit-equals the
    # previous crit task's end) and reaches the event-loop end
    crit = sorted((e for e in spans if e["args"].get("crit")),
                  key=lambda e: e["args"]["t_start_s"])
    assert crit and summary["critical_tasks"] == len(crit)
    for a, b in zip(crit, crit[1:]):
        assert a["args"]["t_end_s"] == b["args"]["t_start_s"]


def test_train_schedule_trace_scaled_and_penalized(tmp_path):
    """Calibration scale, dispatch overhead and an HBM penalty all
    fold into the trace's exact end time."""
    ff = _model()
    mesh = _mesh()
    sim = Simulator(ff, mesh)
    sim.time_scale = 3.7
    sim.step_overhead = 1.25e-4
    # force a memory penalty by shrinking HBM below the model
    import dataclasses
    spec = dataclasses.replace(sim.mm.spec, hbm_capacity=1024.0)
    sim.mm = dataclasses.replace(sim.mm, spec=spec)
    sim.invalidate()
    strat = Strategy()
    path = str(tmp_path / "sched.json")
    summary = sim.export_schedule(strat, path)
    full = sim.simulate(strat)
    assert summary["hbm_penalty_s"] > 0
    assert summary["makespan_s"] == full
    _, spans = _load_spans(path)
    assert max(e["args"]["t_end_s"] for e in spans) == full
    names = {e["name"] for e in spans}
    assert "hbm_penalty" in names and "step_overhead" in names


def test_serve_schedule_trace_round_trip(tmp_path):
    arch = ServeArch(num_layers=4, hidden=512, num_heads=8,
                     head_dim=64, ff_dim=2048, vocab=32000)
    path = str(tmp_path / "serve_sched.json")
    summary = export_serve_schedule(arch, 4, path)
    ref = simulate_serve_step(arch, 4)
    doc, spans = _load_spans(path)
    assert summary["makespan_s"] == ref
    assert doc["metadata"]["makespan_s"] == ref
    assert max(e["args"]["t_end_s"] for e in spans) == ref
    # the serve chain is serial: task durations + penalty sum to the
    # makespan (chain accumulation, tight tolerance)
    total = sum(e["dur"] for e in spans) / 1e6
    assert total == pytest.approx(ref, rel=1e-9)
    # per-class breakdown sums exactly to the simulated step
    bd = serve_step_breakdown(arch, 4)
    assert sum(bd.values()) == pytest.approx(ref, rel=1e-12)
    assert bd["collective"] > 0 and bd["attention"] > 0
    # t=1 prices no collectives
    bd1 = serve_step_breakdown(arch, 1)
    assert bd1["collective"] == 0.0


# ------------------------------------------------------ search trace
def test_search_trace_determinism_and_purity():
    """Tracing on vs off at one seed: bit-identical strategies; two
    traced runs: identical event streams."""
    from flexflow_tpu.search.mcmc import optimize
    ff = _model()
    mesh = _mesh()

    def run(traced, seed=5):
        ff.config.search_trace = traced
        s = optimize(ff, budget=120, mesh=mesh, seed=seed,
                     use_native=False, chains=2)
        t = (ff.search_stats or {}).get("trace")
        return {k: dict(v.axis_map)
                for k, v in s.op_strategies.items()}, t

    s_on, t_on = run(True)
    s_off, t_off = run(False)
    s_on2, t_on2 = run(True)
    ff.config.search_trace = True
    assert s_on == s_off, "tracing changed the search result"
    assert t_off is None and t_on and t_on2
    assert t_on["proposals"] == 120 and t_on2["proposals"] == 120
    assert t_on == t_on2, "traced runs are not deterministic"
    assert t_on["accepts"] == sum(
        p["accepts"] for p in t_on["acceptance_by_phase"])
    assert sum(d["proposals"] for d in t_on["by_path"].values()) == 120
    # the best-cost curve is monotone decreasing
    curve = [c["cost_s"] for c in t_on["best_cost_curve"]]
    assert curve == sorted(curve, reverse=True)


def test_search_trace_ring_bounded():
    tr = SearchTrace(budget=100, max_events=32)
    for i in range(100):
        tr.record(i, 0, "rewrite", "op", 0.0, True, 1.0, "delta")
    s = tr.summary()
    assert s["events_recorded"] == 32 and s["events_dropped"] == 68
    assert s["proposals"] == 100 and s["accepts"] == 100
    assert [p["proposals"] for p in s["acceptance_by_phase"]] \
        == [34, 33, 33]
    assert len(tr.events_list()) == 32


def test_serve_place_trace():
    from flexflow_tpu.search.serve_place import optimize_serve
    arch = ServeArch(num_layers=4, hidden=512, num_heads=8,
                     head_dim=64, ff_dim=2048, vocab=32000)
    p1 = optimize_serve(arch, 4, budget=32, seed=7)
    p2 = optimize_serve(arch, 4, budget=32, seed=7)
    assert p1.trace and p1.trace["proposals"] > 0
    assert p1.tensor_parallel == p2.tensor_parallel
    assert p1.trace == p2.trace  # deterministic walk
    cfg = FFConfig()
    cfg.search_trace = False
    assert optimize_serve(arch, 4, budget=8, seed=7,
                          config=cfg).trace is None


def test_search_report_renders_trace():
    from flexflow_tpu.search.mcmc import optimize
    from flexflow_tpu.utils.profiling import search_report
    ff = _model()
    optimize(ff, budget=60, mesh=_mesh(), seed=1, use_native=False,
             chains=1)
    rep = search_report(ff.search_stats)
    assert "trace:" in rep and "accepted" in rep
    assert "best-cost curve" in rep


# ------------------------------------------------------- attribution
def test_task_drift_share_fold():
    tel = Telemetry()
    tel.record_drift("d", "r1", 1.0, 2.0,
                     breakdown={"a": 0.5, "b": 0.5})
    snap = tel.task_drift_snapshot()["d"]
    assert snap["regimes"] == 1
    # one regime: both classes inherit the regime's 2x ratio
    assert snap["classes"]["a"]["ratio"] == pytest.approx(2.0)
    assert snap["classes"]["b"]["ratio"] == pytest.approx(2.0)
    # regimes without breakdowns never participate
    tel2 = Telemetry()
    tel2.record_drift("d", "r1", 1.0, 2.0)
    assert tel2.task_drift_snapshot() == {}


def test_task_drift_lstsq_recovers_rigged_scales():
    """Two classes, rigged so class `a` runs 2x its prediction and
    class `b` exactly as predicted: with enough distinct regime mixes
    the alignment recovers the per-class factors — the 'which term is
    off' answer a per-regime ratio cannot give."""
    tel = Telemetry()
    mixes = [(1.0, 0.1), (0.1, 1.0), (0.5, 0.5), (0.8, 0.3)]
    for i, (pa, pb) in enumerate(mixes):
        measured = 2.0 * pa + 1.0 * pb
        tel.record_drift("d", f"regime{i}", pa + pb, measured,
                         breakdown={"a": pa, "b": pb})
    snap = tel.task_drift_snapshot()["d"]
    assert snap["method"] == "lstsq"
    assert snap["classes"]["a"]["ratio"] == pytest.approx(2.0)
    assert snap["classes"]["b"]["ratio"] == pytest.approx(1.0)
    rep = tel.drift_report()
    assert "task class" in rep and "lstsq" in rep
    assert "regime0" in rep  # named regime keys render as-is


def test_train_step_breakdown_classes():
    ff = _model()
    sim = Simulator(ff, _mesh())
    bd = sim.step_breakdown(Strategy())
    assert set(bd) == set(sim.TRAIN_TASK_CLASSES)
    assert bd["fwd"] > 0 and bd["bwd"] > 0


# ------------------------------------------------------------ ledger
def test_serve_memory_ledger_matches_live_buffers():
    from flexflow_tpu.serve import ServeEngine
    eng = ServeEngine(_lm(telemetry=True))
    eng.warmup()
    led = eng.memory_ledger()
    assert led["pools_live"]
    # ledger params + kv accounting vs the actual nbytes of the live
    # device buffers: every array is unsharded here, so the comparison
    # is exact (ci.sh gates <= 5% to leave room for real meshes)
    live = float(sum(
        np.prod(x.shape) * x.dtype.itemsize
        for x in [*__import__("jax").tree_util.tree_leaves(
            (eng._step_params, eng.pool))]))
    assert led["live_bytes"] == pytest.approx(live, rel=1e-9)
    assert led["params_bytes"] + led["kv_pool_bytes"] \
        == pytest.approx(live, rel=0.05)
    assert led["total_bytes"] > led["params_bytes"]
    assert led["sim_hbm_input_bytes"] > 0
    # components exported as gauges on the engine registry
    m = eng.telemetry.metrics
    for comp in ("params", "kv_pool", "total", "live"):
        assert m.gauge("serve_hbm_bytes", component=comp) > 0
    eng.close()


def test_train_memory_ledger():
    import jax
    ff = _model()
    ff.compile()
    ff.init_layers()
    led = ff.memory_ledger()
    params = float(sum(x.nbytes for x in
                       jax.tree_util.tree_leaves(ff.state.params)))
    assert led["params_bytes"] == pytest.approx(params, rel=1e-9)
    assert led["live_bytes"] >= led["params_bytes"]
    assert led["sim_hbm_input_bytes"] is not None


def test_explain_placement_components_sum_exact():
    from flexflow_tpu.search.explain import (explain_placement,
                                             explain_report)
    ff = _model()
    mesh = _mesh()
    info = explain_placement(ff, mesh=mesh, strategy=Strategy(),
                             top_k=3)
    assert info["ops"]
    searchable = 0
    for o in info["ops"]:
        assert sum(o["components"].values()) == o["total_s"]
        for a in o["alternatives"]:
            assert sum(a["components"].values()) == a["total_s"]
            assert a["delta_s"] == a["total_s"] - o["total_s"]
        searchable += bool(o["alternatives"])
    assert searchable > 0  # linear/attention ops have alternatives
    rep = explain_report(info)
    assert "rejected" in rep and "hbm:" in rep
    assert info["memory"]["sim_bytes_per_device"] > 0


# ---------------------------------------------------------- endpoint
def test_metrics_endpoint_scrape_and_close():
    from flexflow_tpu.serve import ServeEngine
    eng = ServeEngine(_lm(metrics_port=0))
    assert eng.telemetry.enabled  # metrics_port implies telemetry
    port = eng.metrics_server.port
    rng = np.random.RandomState(0)
    eng.generate([list(rng.randint(1, VOCAB, size=8))
                  for _ in range(2)], 4)
    h = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/healthz", timeout=10)
    assert h.status == 200 and h.read() == b"ok\n"
    page = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
    assert "serve_tokens_generated_total" in page
    for ln in page.strip().splitlines():
        if not ln.startswith("#"):
            float(ln.rpartition(" ")[2])  # every sample parses
    assert urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=10).status == 200
    eng.close()
    eng.close()  # idempotent
    with pytest.raises(Exception):
        urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz",
                               timeout=2)


def test_metrics_port_validation():
    with pytest.raises(ValueError):
        FFConfig(metrics_port=70000)
    cfg = FFConfig(argv=["--metrics-port", "0"])
    assert cfg.metrics_port == 0
    assert FFConfig().metrics_port is None


def test_schedule_trace_flag_exports_through_optimize(tmp_path):
    from flexflow_tpu.search.mcmc import optimize
    ff = _model()
    path = str(tmp_path / "sched.json")
    ff.config.schedule_trace_file = path
    optimize(ff, budget=40, mesh=_mesh(), seed=0, use_native=False,
             chains=1)
    summary = ff.search_stats["schedule_trace"]
    doc, spans = _load_spans(path)
    assert doc["metadata"]["makespan_s"] == summary["makespan_s"]
