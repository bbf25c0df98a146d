"""Where a start goes (ISSUE 54, docs/observability.md "Set-up
phases"): the phases a model and an engine record of their own set-up
through `Telemetry.timed(..., keep=)`, by name, order and nesting; kept
with the bus off, on track (proc, "setup") with it on; what the
process-wide listener counted inside each; `boot_stats`; and
tools/setup_phases.py's table."""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.config import FFConfig
from flexflow_tpu.core import programs
from flexflow_tpu.serve import ServeEngine
from flexflow_tpu.utils import telemetry as T

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 89
COUNTERS = {"backend_compiles", "backend_compile_s", "cache_hits",
            "trace_s", "lower_s"}


def _lm(**kw):
    from flexflow_tpu.models.transformer import build_transformer_lm
    cfg = FFConfig(batch_size=1, kv_page_size=8, kv_num_pages=73,
                   serve_max_seqs=4, serve_prefill_budget=48, **kw)
    return build_transformer_lm(cfg, vocab_size=VOCAB, max_seq_len=64,
                                hidden=32, num_heads=4, num_layers=2,
                                ff_dim=64)


def _trainer(telemetry=None, **kw):
    from flexflow_tpu import AdamOptimizer
    from flexflow_tpu.models.transformer import build_transformer_lm
    cfg = FFConfig(batch_size=2, seed=3, **kw)
    m = build_transformer_lm(cfg, vocab_size=VOCAB, max_seq_len=16,
                             hidden=32, num_heads=4, num_layers=2,
                             ff_dim=64)
    m.telemetry = telemetry
    m.compile(optimizer=AdamOptimizer(lr=1e-3),
              loss_type="sparse_categorical_crossentropy", metrics=[])
    return m


def _train_batch(m):
    toks = np.random.RandomState(0).randint(
        0, VOCAB, size=(2, 16)).astype(np.int32)
    batch = {m.input_tensors[0].name: toks,
             "label": np.roll(toks, -1, axis=1)}
    if len(m.input_tensors) > 1:
        batch[m.input_tensors[1].name] = np.broadcast_to(
            np.arange(16, dtype=np.int32), (2, 16)).copy()
    return batch


def _edges(phases):
    """(parent, name) in start order."""
    return [(r[1], r[0]) for r in sorted(phases,
                                         key=lambda r: (r[2], -r[3]))]


def _by_name(phases, name):
    return [r for r in phases if r[0] == name]


@pytest.fixture(scope="module")
def engine():
    t0 = time.perf_counter()
    eng = ServeEngine(_lm())
    eng.warmup()
    return eng, t0, time.perf_counter()


# ------------------------------------------------------ the entry point
def test_timed_keeps_the_span_with_the_bus_off():
    tel = T.telemetry_for()
    assert not tel.enabled
    keep = T.PhaseList()
    args = {"k": 1}
    t0 = time.perf_counter()
    with tel.timed(("p", "setup"), "outer", args, keep=keep):
        with tel.timed(("p", "setup"), "inner", keep=keep):
            time.sleep(0.002)
        args["late"] = 2          # added before the phase ends: kept
    t1 = time.perf_counter()
    (iname, iparent, it0, idur, iargs), (name, parent, ot0, dur, oargs) = keep
    assert (iname, iparent, name, parent) == ("inner", "outer", "outer",
                                              None)
    assert oargs == {"k": 1, "late": 2} and iargs is None
    assert t0 <= ot0 <= it0 and it0 + idur <= ot0 + dur <= t1
    assert idur >= 0.002 and keep.open == []
    assert len(tel.events) == 0


def test_timed_keep_carries_the_totals_difference_and_survives_a_raise():
    n = {"v": 10}
    keep = T.PhaseList(totals=lambda: {"things": n["v"]})
    tel = T.Telemetry()
    with pytest.raises(RuntimeError):
        with tel.timed(("p", "setup"), "fails", {"a": 1}, keep=keep):
            n["v"] += 3
            raise RuntimeError("in the phase")
    (name, parent, _, _, args), = keep
    assert (name, parent, args) == ("fails", None, {"a": 1, "things": 3})
    assert keep.open == []
    # the same span, with the same args, on the bus
    (ph, track, bname, _, _, _, bargs), = list(tel.events)
    assert (ph, track, bname, bargs) == ("X", ("p", "setup"), "fails", args)


def test_timed_t_start_backdates_a_phase():
    keep = T.PhaseList()
    t0 = time.perf_counter() - 1.5
    with T.telemetry_for().timed(("p", "setup"), "early", keep=keep,
                                 t_start=t0):
        pass
    (_, _, start, dur, _), = keep
    assert start == t0 and 1.5 <= dur < 2.5


# ------------------------------------------------------------- serving
def test_engine_phases_by_name_order_and_nesting(engine):
    eng, _, _ = engine
    assert _edges(eng.boot_stats["phases"]) == [
        (None, "model_compile"),
        ("model_compile", "lower_strategy"),
        ("model_compile", "build_step"),
        ("model_compile", "init_state"),
        (None, "engine_init"),
        ("engine_init", "read_arch"),
        ("engine_init", "shard_params"),
        (None, "warmup"),
        ("warmup", "alloc_pool"),
        ("warmup", "first_dispatch"),
        ("first_dispatch", "compile:mixed"),
    ]


def test_children_lie_inside_parents_and_roots_inside_the_wall_time(engine):
    eng, t0, t1 = engine
    phases = eng.boot_stats["phases"]
    eps = 1e-6
    for name, parent, start, dur, _ in phases:
        if parent is None:
            assert t0 - eps <= start and start + dur <= t1 + eps, name
            continue
        # the enclosing record of that name
        ps = [p for p in _by_name(phases, parent)
              if p[2] - eps <= start and start + dur <= p[2] + p[3] + eps]
        assert len(ps) == 1, (name, parent)
    roots = [r for r in phases if r[1] is None]
    assert eng.boot_stats["setup_s"] == pytest.approx(
        sum(r[3] for r in roots))
    assert eng.boot_stats["setup_s"] <= t1 - t0
    # roots do not overlap
    roots.sort(key=lambda r: r[2])
    for a, b in zip(roots, roots[1:]):
        assert a[2] + a[3] <= b[2] + eps


def test_phases_are_kept_with_the_bus_off(engine):
    eng, _, _ = engine
    assert not eng.telemetry.enabled and len(eng.telemetry.events) == 0
    assert eng.model.telemetry is None
    assert len(eng.boot_stats["phases"]) == 11


def test_every_phase_carries_the_listeners_difference_and_its_own_args(
        engine):
    eng, _, _ = engine
    phases = eng.boot_stats["phases"]
    for name, _, _, _, args in phases:
        assert COUNTERS <= set(args), name
    own = {name: set(args) - COUNTERS for name, _, _, _, args in phases}
    assert own["init_state"] == {"leaves", "bytes"}
    assert own["shard_params"] == {"bytes"}
    assert own["alloc_pool"] == {"pool_bytes", "pages"}
    assert own["compile:mixed"] == {"fingerprint", "source"}
    init, = _by_name(phases, "init_state")
    leaves = jax.tree_util.tree_leaves(eng.model.state)
    assert init[4]["leaves"] == len(leaves)
    assert init[4]["bytes"] == sum(x.nbytes for x in leaves)
    pool, = _by_name(phases, "alloc_pool")
    assert pool[4]["pages"] == eng.cache_cfg.num_pages
    assert pool[4]["pool_bytes"] == sum(
        x.nbytes for x in jax.tree.leaves(eng.pool))
    mixed, = _by_name(phases, "compile:mixed")
    assert mixed[4]["fingerprint"] == eng.programs.fp_hash
    assert mixed[4]["source"] == "compiled"
    assert mixed[4]["backend_compiles"] == 1
    # a parent's counts are at least its children's
    warm, = _by_name(phases, "warmup")
    assert warm[4]["backend_compiles"] >= (pool[4]["backend_compiles"]
                                           + mixed[4]["backend_compiles"])
    # the eager initializer programs no registry sees are counted here
    # (none where an earlier test of this process compiled them all)
    assert init[4]["backend_compiles"] >= 0
    assert (init[4]["backend_compile_s"] > 0) == (
        init[4]["backend_compiles"] > 0)


def test_boot_stats_keeps_its_older_keys(engine):
    eng, _, _ = engine
    rec = eng.boot_stats
    assert {"fingerprint", "restored", "compiles", "compile_s", "families",
            "boot_s", "warm", "attn_impl", "scan_impl",
            "expert_impl"} <= set(rec)
    assert rec["compiles"] == 1 and rec["restored"] == 0
    assert rec["warm"] is False and rec["restore_s"] == 0.0
    assert rec["families"]["mixed"]["compiles"] == 1
    warm, = _by_name(rec["phases"], "warmup")
    assert rec["boot_s"] == pytest.approx(warm[3], abs=0.05)
    assert rec["compile_s"] <= warm[3]


def test_the_same_spans_lie_on_the_setup_track_with_the_bus_on():
    tel = T.Telemetry()
    lm = _lm()
    lm.telemetry = tel
    eng = ServeEngine(lm, telemetry=tel)
    eng.warmup()
    spans = [(track, name, dur, args)
             for ph, track, name, _, dur, _, args in tel.events
             if ph == "X" and track[1] == T.SETUP_THREAD]
    phases = eng.boot_stats["phases"]
    assert [(n, d, a) for _, n, d, a in spans] == [
        (r[0], r[3], r[4]) for r in phases]
    assert {t for t, _, _, _ in spans} == {("model", "setup"),
                                           ("serve", "setup")}
    # a pool's replica writes on its own process's track
    eng.set_track_process("replica3")
    with eng.setup_phase("probe"):
        pass
    assert list(tel.events)[-1][1] == ("replica3", "setup")


def test_setup_spans_are_in_the_chrome_trace(tmp_path):
    tel = T.Telemetry()
    eng = ServeEngine(_lm(), telemetry=tel)
    eng.warmup()
    path = tel.export_chrome_trace(str(tmp_path / "t.json"))
    doc = json.load(open(path))
    threads = {(e["pid"], e["tid"]): e["args"]["name"]
               for e in doc["traceEvents"] if e["name"] == "thread_name"}
    names = [e["name"] for e in doc["traceEvents"] if e["ph"] == "X"
             and threads[(e["pid"], e["tid"])] == "setup"]
    assert names[-4:] == ["alloc_pool", "compile:mixed", "first_dispatch",
                          "warmup"]
    warm = next(e for e in doc["traceEvents"] if e["name"] == "warmup")
    assert warm["args"]["backend_compiles"] >= 1


def test_a_second_engine_over_a_program_cache_reads_its_step(tmp_path):
    cold = ServeEngine(_lm(program_cache_dir=str(tmp_path)))
    cold.warmup()
    assert not _by_name(cold.boot_stats["phases"], "load_programs") \
        or _by_name(cold.boot_stats["phases"],
                    "load_programs")[0][4]["restored"] == 0
    eng = ServeEngine(_lm(program_cache_dir=str(tmp_path)))
    eng.warmup()
    phases = eng.boot_stats["phases"]
    load, = _by_name(phases, "load_programs")
    assert load[1] == "engine_init"
    assert load[4]["restored"] == 1 and load[4]["store_bytes"] > 0
    mixed, = _by_name(phases, "compile:mixed")
    assert mixed[1] == "load_programs"
    assert mixed[4]["source"] == "restored"
    warm, = _by_name(phases, "warmup")
    assert warm[4]["backend_compiles"] == 0 and warm[4]["cache_hits"] == 0
    assert eng.boot_stats["warm"] is True
    assert eng.boot_stats["restore_s"] == pytest.approx(load[3], abs=0.05)
    assert eng.boot_stats["restore_s"] > 0
    assert eng.boot_stats["compiles"] == 0


# ------------------------------------------------------------ training
def test_trainer_phases_after_one_train_batch():
    m = _trainer()
    assert _edges(m.boot_stats["phases"]) == [
        (None, "model_compile"),
        ("model_compile", "lower_strategy"),
        ("model_compile", "build_step"),
        ("model_compile", "init_state"),
    ]
    assert "compiles" not in m.boot_stats       # no step has run
    float(m.train_batch(_train_batch(m))["loss"])
    rec = m.boot_stats
    assert _edges(rec["phases"])[4:] == [(None, "compile:train_step")]
    step, = _by_name(rec["phases"], "compile:train_step")
    assert step[4]["source"] == "compiled"
    assert step[4]["fingerprint"] == rec["fingerprint"]
    assert step[4]["backend_compiles"] >= 1 and step[4]["trace_s"] > 0
    assert rec["compiles"] == 1 and rec["restored"] == 0
    assert rec["families"]["train_step"]["compiles"] == 1
    assert rec["compile_s"] == pytest.approx(step[3], abs=0.05)
    assert rec["setup_s"] == pytest.approx(
        sum(r[3] for r in rec["phases"] if r[1] is None))
    # a second step writes nothing
    float(m.train_batch(_train_batch(m))["loss"])
    assert len(m.boot_stats["phases"]) == 5


def test_a_search_is_a_phase_of_the_compile_that_runs_it():
    from flexflow_tpu import make_mesh
    from flexflow_tpu.models.transformer import build_transformer_lm
    from flexflow_tpu.search import mcmc
    cfg = FFConfig(batch_size=8, seed=3, search_budget=6)
    mesh = make_mesh((2, 2), ("data", "model"), jax.devices()[:4])
    m = build_transformer_lm(cfg, vocab_size=VOCAB, max_seq_len=16,
                             hidden=32, num_heads=4, num_layers=2,
                             ff_dim=64, mesh=mesh)
    m.compile(loss_type="sparse_categorical_crossentropy", metrics=[])
    edges = _edges(m.boot_stats["phases"])
    assert edges[:3] == [(None, "model_compile"),
                         ("model_compile", "search"),
                         ("model_compile", "lower_strategy")]
    search, = _by_name(m.boot_stats["phases"], "search")
    assert search[4]["budget"] == 6
    assert search[4]["engine"] == m.search_stats["engine"]
    # called by hand, as the benchmark's trainer does: a root
    mcmc.optimize(m, budget=4, mesh=mesh, seed=1)
    assert _edges(m.boot_stats["phases"])[-1] == (None, "search")


# ---------------------------------------------------- the process's own
def test_the_listener_keeps_the_seconds_of_a_forced_compile():
    assert programs.CompileEvents.install()
    keep = programs.boot_phases()
    salt = time.perf_counter()      # a constant no cache has seen
    x = jax.block_until_ready(jnp.asarray(np.ones((7, 3), np.float32)))
    before = programs.CompileEvents.totals()
    with T.telemetry_for().timed(("p", "setup"), "forced", keep=keep):
        jax.block_until_ready(jax.jit(
            lambda x: jnp.sin(x) * salt + 54.0)(x))
    after = programs.CompileEvents.totals()
    assert after["backend_compiles"] == before["backend_compiles"] + 1
    assert after["backend_compile_s"] > before["backend_compile_s"]
    assert after["trace_s"] > before["trace_s"]
    assert after["lower_s"] > before["lower_s"]
    (_, _, _, dur, args), = keep
    assert args["backend_compiles"] == 1
    assert 0 < args["backend_compile_s"] <= dur
    assert args["backend_compile_s"] == pytest.approx(
        after["backend_compile_s"] - before["backend_compile_s"])
    assert set(before) == COUNTERS
    # the older readers' counter is the same number
    assert programs.CompileEvents.count == after["backend_compiles"]


def test_the_packages_import_is_a_phase_of_the_process():
    edges = _edges(programs.PROCESS_PHASES)
    assert edges[:2] == [(None, "import"), ("import", "jax_import")]
    imp, = _by_name(programs.PROCESS_PHASES, "import")
    jimp, = _by_name(programs.PROCESS_PHASES, "jax_import")
    # conftest.py imports JAX before any test imports the package
    assert imp[4]["jax_preloaded"] is True
    assert imp[2] == jimp[2] and jimp[3] <= imp[3]
    assert imp[4]["backend_compiles"] == 0


# ------------------------------------------------------------- the tool
def _tool():
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import setup_phases
    finally:
        sys.path.pop(0)
    return setup_phases


def test_tree_rows_nest_by_parent_and_take_children_from_self():
    rows = _tool().tree_rows([
        ("b", "a", 1.0, 2.0, {"x": 1}), ("c", "b", 1.5, 0.5, None),
        ("a", None, 0.0, 4.0, None), ("a", None, 5.0, 1.0, None),
        ("b", "a", 5.2, 0.3, None), ("d", None, 4.0, 1.0, None)])
    assert [(d, n, s, round(ss, 6)) for d, n, s, ss, _ in rows] == [
        (0, "a", 4.0, 2.0), (1, "b", 2.0, 1.5), (2, "c", 0.5, 0.5),
        (0, "d", 1.0, 1.0), (0, "a", 1.0, 0.7), (1, "b", 0.3, 0.3)]
    assert rows[1][4] == {"x": 1} and rows[0][4] == {}


@pytest.mark.parametrize("cell", ["chat-steady", "pretrain-1chip"])
def test_the_tools_table_sums_to_its_wall_time(cell, tmp_path):
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "setup_phases.py"),
         "--workload", cell, "--rehearse-cpu", "--seed", "2147483659"],
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 JAX_COMPILATION_CACHE_DIR=str(tmp_path)),
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    doc = json.loads(out.stdout.strip().splitlines()[-1])
    assert doc["rehearsal"] is True and doc["workload"] == cell
    roots = [r for r in doc["rows"] if r["depth"] == 0]
    assert doc["covered_s"] == pytest.approx(sum(r["s"] for r in roots))
    assert 0.9 * doc["wall_s"] <= doc["covered_s"] <= doc["wall_s"]
    # every row's self time is its own less its children's
    for i, r in enumerate(doc["rows"]):
        kids, j = 0.0, i + 1
        while j < len(doc["rows"]) and doc["rows"][j]["depth"] > r["depth"]:
            if doc["rows"][j]["depth"] == r["depth"] + 1:
                kids += doc["rows"][j]["s"]
            j += 1
        assert r["self_s"] == pytest.approx(r["s"] - kids, abs=1e-9)
        assert r["self_s"] >= -1e-6
    names = [r["name"] for r in roots]
    last = "warmup" if cell == "chat-steady" else "first_step"
    assert names[:3] == ["import", "backend_init", "import_driver"]
    assert names[-1] == last and "model_compile" in names
    assert ("search" in names) == (cell == "pretrain-1chip")
    lines = out.stdout.splitlines()
    assert lines[0].split()[:3] == ["phase", "s", "self"]
    assert any(ln.startswith("no phase covers") for ln in lines)
