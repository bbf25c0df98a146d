"""Unified-telemetry suite (utils/telemetry.py, docs/observability.md).

Layered like the subsystem:
  * bus — ring-buffer bounding, metrics registry semantics, the
    nearest-rank quantile definition, Prometheus text parseability.
  * serve — telemetry on vs off is bit-identical tokens with ZERO
    recompiles (recording is pure host-side observation); the Chrome
    trace-event export is schema-valid (ts/dur/pid/tid well-formed,
    X spans nest per thread) with per-request-slot and per-engine-step
    tracks; lifecycle events survive preemption, speculation, retry,
    cancel and deadline — chaos runs stay traceable.
  * train — fit() with telemetry on trains to a bit-identical loss
    history; dispatch/fetch spans and the train drift sample land.
  * drift — the calibrator's predicted/measured accounting against a
    rigged cost model, threshold flagging both directions, and the
    regime cap.
  * reports — serve_report/train_report render FROM the canonical
    metrics fold, so the string numbers equal the exported snapshot.
  * profiling.trace — configurable log dir, returns the path, and
    raises when jax.profiler will not start.
"""

import json
import os

import numpy as np
import pytest

from flexflow_tpu.config import FFConfig
from flexflow_tpu.serve import ServeEngine
from flexflow_tpu.utils.telemetry import (MetricsRegistry, Telemetry,
                                          pct, pow2_bucket,
                                          serve_metrics, telemetry_for)

VOCAB = 89


# --------------------------------------------------------------- bus
def test_ring_buffer_bounds_under_long_run():
    tel = Telemetry(max_events=64)
    for i in range(1000):
        tel.span(("p", "t"), f"s{i}", 0.0, 1.0)
        tel.metrics.inc("steps_total")
    assert len(tel.events) == 64
    assert tel.dropped_events == 1000 - 64
    # aggregates are NEVER dropped with events
    assert tel.metrics.counter("steps_total") == 1000


def test_disabled_telemetry_records_nothing():
    tel = Telemetry(enabled=False)
    tel.span(("p", "t"), "s", 0.0, 1.0)
    tel.instant(("p", "t"), "i")
    tel.counter(("p", "t"), "c", 1.0)
    tel.record_drift("d", "r", 1.0, 2.0)
    with tel.timed(("p", "t"), "x"):
        pass
    assert len(tel.events) == 0 and not tel.drift_snapshot()


def test_metrics_registry_semantics():
    m = MetricsRegistry()
    m.inc("a_total")
    m.inc("a_total", 2)
    m.inc("a_total", 5, site="x")
    m.set("g", 3.5)
    m.counter_set("abs_total", 7)
    m.counter_set("abs_total", 9)          # absolute, not additive
    for v in range(1, 101):
        m.observe("h_seconds", v / 100.0)
    assert m.counter("a_total") == 3
    assert m.counter("a_total", site="x") == 5
    assert m.gauge("g") == 3.5
    assert m.counter("abs_total") == 9
    assert m.hist_count("h_seconds") == 100
    # nearest-rank over the window — the shared pct() definition
    win = sorted(v / 100.0 for v in range(1, 101))
    assert m.quantile("h_seconds", 50) == pct(win, 50)
    assert m.quantile("h_seconds", 99) == pct(win, 99)
    snap = m.snapshot()
    assert snap["histograms"]["h_seconds"]["count"] == 100
    assert snap["histograms"]["h_seconds"]["p99"] == pct(win, 99)


def test_metrics_thread_safety_hammer():
    """The wall-clock fabric's contract: counter/gauge/histogram
    mutation and ring-buffer emission are lock-guarded — N threads
    hammering the SAME telemetry bus lose no counts, and concurrent
    snapshot/scrape reads never see a mid-iteration mutation."""
    import threading

    tel = Telemetry(max_events=256)
    m = tel.metrics
    n_threads, n_iter = 8, 400
    stop = threading.Event()
    read_errs = []

    def reader():
        # concurrent scrapes (the MetricsServer's live behavior):
        # any "dict changed size during iteration" lands here
        while not stop.is_set():
            try:
                m.snapshot()
                m.to_prometheus()
                tel.drift_snapshot()
            except Exception as e:
                read_errs.append(e)
                return

    def writer(t):
        for i in range(n_iter):
            m.inc("hammer_total")
            m.inc("hammer_total", 2, thread=str(t))
            m.set("hammer_gauge", float(i), thread=str(t))
            m.observe("hammer_seconds", i / n_iter)
            tel.span(("p", f"t{t}"), "s", 0.0, 1.0)
            tel.record_drift("hammer", "r", 1.0, 1.0 + i % 3)

    rt = threading.Thread(target=reader, daemon=True)
    rt.start()
    threads = [threading.Thread(target=writer, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60.0)
    stop.set()
    rt.join(timeout=10.0)
    assert not read_errs, read_errs
    # no lost counts, anywhere
    assert m.counter("hammer_total") == n_threads * n_iter
    for t in range(n_threads):
        assert m.counter("hammer_total", thread=str(t)) == 2 * n_iter
    assert m.hist_count("hammer_seconds") == n_threads * n_iter
    # ring stayed bounded, and drops were accounted exactly
    assert len(tel.events) == 256
    assert tel.dropped_events == n_threads * n_iter - 256
    d = tel.drift_snapshot()["hammer"]["r"]
    assert d["count"] == n_threads * n_iter


def test_prometheus_text_parses():
    import re
    m = MetricsRegistry()
    m.inc("serve_tokens_total", 42)
    m.inc("fault_fired_total", 2, site="serve.mixed", kind="transient")
    m.set("serve_tokens_per_sec", 123.4)
    for v in (0.1, 0.2, 0.3):
        m.observe("serve_ttft_seconds", v)
    text = m.to_prometheus()
    line_re = re.compile(
        r'^(# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* '
        r'(counter|gauge|summary)'
        r'|[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? -?[0-9eE.+-]+)$')
    for line in text.splitlines():
        if line:
            assert line_re.match(line), line
    assert "serve_tokens_total 42" in text
    assert 'fault_fired_total{kind="transient",site="serve.mixed"} 2' \
        in text
    assert 'serve_ttft_seconds{quantile="0.5"}' in text
    assert "serve_ttft_seconds_count 3" in text


def test_pow2_bucket():
    assert [pow2_bucket(n) for n in (0, 1, 2, 3, 4, 5, 63, 64, 65)] \
        == [0, 1, 2, 4, 4, 8, 64, 64, 128]


def test_telemetry_for_config_resolution():
    assert not telemetry_for(None).enabled
    assert not telemetry_for(FFConfig()).enabled
    t = telemetry_for(FFConfig(telemetry=True,
                               telemetry_buffer_events=128,
                               telemetry_drift_threshold=0.25))
    assert t.enabled and t.max_events == 128 \
        and t.drift_threshold == 0.25
    # --trace-out alone also enables
    assert telemetry_for(FFConfig(trace_out="/tmp/t.json")).enabled
    # each enabled resolution is a FRESH bus; disabled is shared
    assert telemetry_for(FFConfig(telemetry=True)) is not t
    assert telemetry_for(FFConfig()) is telemetry_for(FFConfig())


def test_config_cli_flags():
    cfg = FFConfig(argv=["--telemetry", "--trace-out", "/tmp/x.json",
                         "--trace-dir", "/tmp/prof",
                         "--telemetry-buffer", "512",
                         "--drift-threshold", "0.75"])
    assert cfg.telemetry and cfg.trace_out == "/tmp/x.json"
    assert cfg.trace_dir == "/tmp/prof"
    assert cfg.telemetry_buffer_events == 512
    assert cfg.telemetry_drift_threshold == 0.75
    with pytest.raises(ValueError):
        FFConfig(telemetry_buffer_events=0)
    with pytest.raises(ValueError):
        FFConfig(telemetry_drift_threshold=-0.1)


# --------------------------------------------------------------- serve
@pytest.fixture(scope="module")
def lm():
    from flexflow_tpu.models.transformer import build_transformer_lm
    cfg = FFConfig(batch_size=1, kv_page_size=8, kv_num_pages=73,
                   serve_max_seqs=8, serve_prefill_budget=48,
                   serve_retry_backoff_s=0.0)
    return build_transformer_lm(cfg, vocab_size=VOCAB, max_seq_len=64,
                                hidden=32, num_heads=4, num_layers=2,
                                ff_dim=64)


def _prompts(rng, n, lo=4, hi=28):
    return [list(rng.randint(1, VOCAB, size=rng.randint(lo, hi)))
            for _ in range(n)]


def test_serve_on_off_identical_zero_recompiles(lm):
    """The observability contract: telemetry is pure observation —
    bit-identical tokens, zero recompiles, no state left behind."""
    rng = np.random.RandomState(0)
    prompts = _prompts(rng, 8)
    eng_off = ServeEngine(lm)
    eng_off.warmup()
    out_off = eng_off.generate(prompts, 6)
    tel = Telemetry()
    eng_on = ServeEngine(lm, telemetry=tel)
    counts = eng_on.warmup()
    out_on = eng_on.generate(prompts, 6)
    assert out_on == out_off
    assert eng_on.compile_counts() == counts
    assert len(tel.events) > 0
    # a second batch ACCUMULATES counters in the engine registry
    toks1 = tel.metrics.counter("serve_tokens_generated_total")
    out2 = eng_on.generate(prompts, 6)
    assert out2 == eng_off.generate(prompts, 6)
    assert tel.metrics.counter("serve_tokens_generated_total") > toks1
    assert eng_on.compile_counts() == counts


def _span_nesting_ok(events):
    """On each (pid, tid), X spans must be disjoint or properly
    nested — the Chrome trace model."""
    by_tid = {}
    for ev in events:
        if ev["ph"] == "X":
            by_tid.setdefault((ev["pid"], ev["tid"]), []).append(
                (ev["ts"], ev["ts"] + ev["dur"]))
    for spans in by_tid.values():
        spans.sort()
        stack = []
        for s, e in spans:
            while stack and s >= stack[-1] - 1e-6:
                stack.pop()
            assert not stack or e <= stack[-1] + 1e-6, (
                "spans overlap without nesting")
            stack.append(e)
    return True


def test_chrome_trace_schema_and_tracks(lm, tmp_path):
    tel = Telemetry()
    eng = ServeEngine(lm, telemetry=tel)
    eng.warmup()
    rng = np.random.RandomState(1)
    eng.generate(_prompts(rng, 6), 5)
    path = tel.export_chrome_trace(str(tmp_path / "trace.json"))
    with open(path) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    assert evs and doc["displayTimeUnit"] == "ms"
    for ev in evs:
        assert ev["ph"] in ("X", "i", "M", "C", "b", "e")
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        assert isinstance(ev["name"], str) and ev["name"]
        if ev["ph"] != "M":
            assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
        if ev["ph"] == "X":
            assert isinstance(ev["dur"], (int, float)) \
                and ev["dur"] >= 0
    assert _span_nesting_ok(evs)
    threads = {ev["args"]["name"] for ev in evs
               if ev["ph"] == "M" and ev["name"] == "thread_name"}
    # one track per engine step stream + one per request slot + queue
    assert "engine" in threads and "queue" in threads
    assert any(t.startswith("slot ") for t in threads)
    names = {ev["name"] for ev in evs}
    assert {"step", "queue_wait"} <= names
    assert "prefill" in names or "decode" in names


def test_spans_through_preempt_spec_retry_cancel(lm):
    """Lifecycle events stay correct through the adversarial paths —
    and everything keeps working under fault injection (chaos runs are
    traceable)."""
    from flexflow_tpu.utils.faults import FaultInjector
    # tiny pool forces preemption; injected transients force retries
    cfg = FFConfig(batch_size=1, kv_page_size=8, kv_num_pages=17,
                   serve_max_seqs=4, serve_prefill_budget=24,
                   serve_retry_backoff_s=0.0)
    from flexflow_tpu.models.transformer import build_transformer_lm
    ff = build_transformer_lm(cfg, vocab_size=VOCAB, max_seq_len=40,
                              hidden=32, num_heads=4, num_layers=2,
                              ff_dim=64)
    tel = Telemetry()
    inj = FaultInjector("serve.mixed:transient@3,5", seed=0)
    eng = ServeEngine(ff, telemetry=tel, faults=inj, spec_tokens=4)
    eng.warmup()
    rng = np.random.RandomState(2)
    prompts = _prompts(rng, 8, lo=12, hi=30)
    deadlines = [None] * 8
    deadlines[3] = 1e-9

    def on_step(step):
        if step == 1:
            eng.cancel(2)  # rid 2: third submission of this batch

    out = eng.generate(prompts, 8, deadline_s=deadlines,
                       on_step=on_step)
    assert len(out) == 8
    st = eng.last_stats
    names = [e[2] for e in tel.events]
    if st["preemptions"]:
        assert "preempt" in names
        # a re-admitted victim emits a preempt->readmit span, NOT a
        # duplicate of its original queue_wait
        assert "requeue_wait" in names
    qb = [e for e in tel.events if e[0] == "b" and e[2] == "queue_wait"]
    idents = [e[5] for e in qb]
    assert len(idents) == len(set(idents)), (
        "duplicate queue_wait spans for one request")
    assert st["retries"] >= 1 and "retry" in names
    assert st["cancelled"] == 1 and "cancel" in names
    assert st["deadline_expired"] == 1 and "deadline_expired" in names
    if st["spec_drafted_tokens"]:
        assert "spec_verify" in names
    # fault observability satellite: fired sites land in the registry
    assert tel.metrics.counter("fault_fired_total", site="serve.mixed",
                               kind="transient") >= 2
    assert tel.metrics.counter("fault_site_hits_total",
                               site="serve.mixed") > 0
    # rung histogram exported per rung
    assert tel.metrics.counter("serve_rung_steps_total", rung=0) > 0
    # abort outcomes in the requests counter
    assert tel.metrics.counter("serve_requests_total",
                               outcome="cancelled") == 1
    assert tel.metrics.counter("serve_requests_total",
                               outcome="deadline_expired") == 1


def test_serve_drift_report_against_rigged_cost_model(lm, monkeypatch):
    """Rig the engine's per-regime predictor to a constant so the
    drift ratio is measured/constant exactly — and the flag fires on
    the configured threshold."""
    tel = Telemetry(drift_threshold=0.5)
    eng = ServeEngine(lm, telemetry=tel)
    eng.warmup()
    monkeypatch.setattr(  # 1 s/step predicted, no breakdown
        ServeEngine, "_drift_predicted",
        lambda self, *key: (1.0, None))
    rng = np.random.RandomState(3)
    eng.generate(_prompts(rng, 4), 4)
    snap = tel.drift_snapshot()
    assert snap.get("serve"), "no serve drift regimes"
    for reg, d in snap["serve"].items():
        assert d["predicted_ms_per_step"] == pytest.approx(1000.0)
        # CPU steps are milliseconds, so measured/predicted << 1/1.5
        assert d["ratio"] < 1.0 and d["flagged"]
        assert d["ratio"] == pytest.approx(
            d["measured_ms_per_step"] / d["predicted_ms_per_step"])
    rep = tel.drift_report()
    assert "DRIFT" in rep and "serve" in rep


def test_drift_threshold_flags_both_directions():
    tel = Telemetry(drift_threshold=0.5)
    tel.record_drift("d", "slow", predicted_s=1.0, measured_s=2.0)
    tel.record_drift("d", "fast", predicted_s=2.0, measured_s=1.0)
    tel.record_drift("d", "ok", predicted_s=1.0, measured_s=1.2)
    snap = tel.drift_snapshot()["d"]
    assert snap["slow"]["flagged"] and snap["fast"]["flagged"]
    assert not snap["ok"]["flagged"]
    # caller-supplied threshold overrides construction-time
    assert not tel.drift_snapshot(threshold=2.0)["d"]["slow"]["flagged"]
    assert tel.drift_report(threshold=2.0).count("DRIFT") == 0


def test_drift_regime_cap():
    tel = Telemetry()
    for i in range(Telemetry.MAX_DRIFT_REGIMES + 10):
        tel.record_drift("d", f"r{i}", 1.0, 1.0)
    assert len(tel.drift_snapshot()["d"]) == Telemetry.MAX_DRIFT_REGIMES
    assert tel.drift_regimes_dropped == 10
    assert "dropped" in tel.drift_report()


# --------------------------------------------------------------- train
def _fit_transformer(telemetry: bool):
    from flexflow_tpu import SGDOptimizer
    from flexflow_tpu.models.transformer import build_transformer
    cfg = FFConfig(batch_size=8)
    cfg.telemetry = telemetry
    ff = build_transformer(cfg, batch_size=8, seq_len=16, hidden=32,
                           num_heads=4, num_layers=2, ff_dim=64,
                           num_classes=10)
    ff.compile(optimizer=SGDOptimizer(lr=0.01),
               loss_type="sparse_categorical_crossentropy", metrics=[])
    rng = np.random.RandomState(0)
    x = {"input": rng.randn(48, 16, 32).astype(np.float32)}
    y = rng.randint(0, 10, (48,)).astype(np.int32)
    hist = ff.fit(x, y, epochs=2, verbose=False)
    return ff, hist


def test_train_on_off_identical_with_spans_and_drift():
    ff_off, h_off = _fit_transformer(False)
    ff_on, h_on = _fit_transformer(True)
    assert [h["loss"] for h in h_on] == [h["loss"] for h in h_off]
    assert not ff_off.telemetry.enabled
    tel = ff_on.telemetry
    assert tel.enabled and len(tel.events) > 0
    names = [e[2] for e in tel.events]
    assert "dispatch" in names and "fetch_wait" in names
    assert any(n.startswith("epoch") for n in names)
    # train metrics folded into the registry train_report reads
    assert tel.metrics.counter("train_dispatches_total") == \
        ff_on.last_train_stats["dispatches"]
    # the train drift sample: measured wall/step vs the overlap graph.
    # Epoch 0 contains the cold jit compile and records NO sample
    # (compile seconds are not step time) — only epoch 1 lands.
    drift = tel.drift_snapshot().get("train", {})
    assert drift, "no train drift regime recorded"
    for d in drift.values():
        assert d["count"] == 1 and d["measured_ms_per_step"] > 0


def test_fit_trace_out_writes_chrome_trace(tmp_path):
    from flexflow_tpu import SGDOptimizer
    from flexflow_tpu.models.transformer import build_transformer
    path = str(tmp_path / "train_trace.json")
    cfg = FFConfig(batch_size=8)
    cfg.trace_out = path  # --trace-out implies telemetry
    ff = build_transformer(cfg, batch_size=8, seq_len=16, hidden=32,
                           num_heads=4, num_layers=2, ff_dim=64,
                           num_classes=10)
    ff.compile(optimizer=SGDOptimizer(lr=0.01),
               loss_type="sparse_categorical_crossentropy", metrics=[])
    rng = np.random.RandomState(0)
    x = {"input": rng.randn(32, 16, 32).astype(np.float32)}
    y = rng.randint(0, 10, (32,)).astype(np.int32)
    ff.fit(x, y, epochs=1, verbose=False)
    with open(path) as f:
        doc = json.load(f)
    assert any(ev["name"] == "dispatch"
               for ev in doc["traceEvents"] if ev["ph"] == "X")


# --------------------------------------------------------------- reports
def test_serve_report_renders_from_metrics(lm):
    """The string report and the exported snapshot share one source:
    the percentile line is exactly the histogram's quantiles, the
    totals exactly the counters."""
    from flexflow_tpu.utils.profiling import serve_percentiles, \
        serve_report
    eng = ServeEngine(lm)
    eng.warmup()
    rng = np.random.RandomState(4)
    eng.generate(_prompts(rng, 6), 6)
    stats = eng.last_stats
    m = serve_metrics(stats)
    rep = serve_report(stats)
    p50 = m.quantile("serve_tpot_seconds", 50)
    p99 = m.quantile("serve_tpot_seconds", 99)
    assert f"p50={p50*1e3:.3f} ms" in rep
    assert f"p99={p99*1e3:.3f} ms" in rep
    assert (f"total: {m.counter('serve_tokens_generated_total'):.0f} "
            f"tokens") in rep
    assert serve_percentiles(stats) == {50: p50, 99: p99}
    # and the same fold feeds the Prometheus page
    assert "serve_tokens_per_sec" in m.to_prometheus()


def test_train_report_renders_from_metrics():
    from flexflow_tpu.utils.profiling import train_report
    from flexflow_tpu.utils.telemetry import train_metrics
    ff, _ = _fit_transformer(False)
    st = ff.last_train_stats
    m = train_metrics(st)
    rep = train_report(st)
    assert (f"train: {m.counter('train_dispatches_total'):.0f} "
            f"dispatches") in rep
    assert train_report({}) == "train: no stats recorded"


# --------------------------------------------------------------- trace()
def test_profiling_trace_resolves_dir_and_raises(tmp_path, monkeypatch):
    import jax

    from flexflow_tpu.utils import profiling
    started = []
    monkeypatch.setattr(jax.profiler, "start_trace", started.append)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    with profiling.trace(str(tmp_path / "t")) as got:
        assert got == str(tmp_path / "t")
    # config-resolved dir (the --trace-dir satellite)
    cfg = FFConfig(trace_dir=str(tmp_path / "cfg_dir"))
    with profiling.trace(config=cfg) as got:
        assert got == str(tmp_path / "cfg_dir")
    # default when nothing is configured: inside the checkout's
    # ignored .scratch/, never /tmp
    with profiling.trace() as got:
        assert got == profiling.DEFAULT_TRACE_DIR
        assert got.endswith(os.path.join(".scratch", "trace"))
    assert started == [str(tmp_path / "t"), str(tmp_path / "cfg_dir"),
                       profiling.DEFAULT_TRACE_DIR]

    # a profiler that will not start is an error, not a no-op: a run
    # asked to trace must not silently come back without one
    def boom(path):
        raise RuntimeError("no profiler on this backend")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    with pytest.raises(RuntimeError, match="no profiler"):
        with profiling.trace(str(tmp_path / "t")):
            pass


def test_profiling_trace_real_backend(tmp_path):
    """On the CPU backend jax.profiler works: the trace directory is
    created and the path returned."""
    from flexflow_tpu.utils import profiling
    d = str(tmp_path / "real")
    with profiling.trace(d) as got:
        assert got == d
    assert os.path.isdir(d)


# --------------------------------------------------------------- chaos
def test_chaos_run_emits_trace_and_fault_metrics(lm, tmp_path):
    """docs/robustness.md: chaos runs emit traces — the full seeded
    chaos interleaving with telemetry on stays token-correct for the
    survivors and leaves an inspectable trace + fault registry."""
    from flexflow_tpu.utils.faults import FaultInjector
    tel = Telemetry()
    inj = FaultInjector(
        "serve.mixed:transient@2,4;serve.page_pressure:exhaust:0.8@2-6",
        seed=7)
    eng = ServeEngine(lm, telemetry=tel, faults=inj)
    eng.warmup()
    rng = np.random.RandomState(5)
    prompts = _prompts(rng, 6)
    out = eng.generate(prompts, 5, on_step=lambda s:
                       eng.cache.check_invariants())
    ref = ServeEngine(lm).generate_reference(prompts, 5)
    st = eng.last_stats
    for o, r, rec in zip(out, ref, st["requests"]):
        if rec["outcome"] == "completed":
            assert o == r
    assert st["retries"] >= 1
    assert tel.metrics.counter("fault_fired_total", site="serve.mixed",
                               kind="transient") >= 1
    assert tel.metrics.counter("fault_fired_total",
                               site="serve.page_pressure",
                               kind="exhaust") >= 1
    path = tel.export_chrome_trace(str(tmp_path / "chaos.json"))
    with open(path) as f:
        doc = json.load(f)
    assert any(ev["name"] == "retry" for ev in doc["traceEvents"])


def test_unwritable_trace_out_does_not_fail_generate(lm, tmp_path):
    """An unwritable --trace-out path must not fail a generate() that
    already produced tokens (the same promise fit() makes)."""
    tel = Telemetry()
    eng = ServeEngine(lm, telemetry=tel)
    eng.warmup()
    eng.trace_out = str(tmp_path / "no_such_dir" / "trace.json")
    rng = np.random.RandomState(11)
    prompts = _prompts(rng, 4)
    out = eng.generate(prompts, 4)
    assert out == ServeEngine(lm).generate_reference(prompts, 4)


def test_fault_aborted_generate_still_flushes_trace(lm, tmp_path):
    """A run a fatal fault kills mid-flight still leaves the Chrome
    trace and the fault registry behind — the failing chaos replay is
    inspectable post-hoc (docs/robustness.md)."""
    from flexflow_tpu.utils.faults import FaultInjector, InjectedFault
    tel = Telemetry()
    inj = FaultInjector("serve.mixed:fatal@2", seed=0)
    eng = ServeEngine(lm, telemetry=tel)
    eng.warmup()
    eng.faults = inj  # armed after warmup: step 1 runs, step 2 dies
    path = str(tmp_path / "aborted.json")
    eng.trace_out = path
    rng = np.random.RandomState(12)
    with pytest.raises(InjectedFault):
        eng.generate(_prompts(rng, 4), 6)
    with open(path) as f:
        doc = json.load(f)
    assert any(ev["name"] == "step" for ev in doc["traceEvents"])
    assert tel.metrics.counter("fault_fired_total", site="serve.mixed",
                               kind="fatal") == 1
