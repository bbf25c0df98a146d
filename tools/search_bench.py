"""Strategy-search throughput benchmark: delta simulation vs full
simulation (the perf-trajectory file for the search subsystem).

Runs the Python MCMC engine on the small-transformer config twice —
full simulation per proposal (the pre-delta baseline path,
--no-delta-sim) and delta simulation (Simulator.simulate_delta) — and
records proposals/sec for both, the speedup, and a delta-vs-full
makespan equivalence sweep (the same property tests/test_search_delta.py
asserts: the delta replay is exact, so max relative error must be ~0).

    python tools/search_bench.py            # full bench -> BENCH_search.json
    python tools/search_bench.py --smoke    # CI gate: 200-iteration
        search; FAILS (exit 1) if delta speedup < 2x or if delta/full
        makespans diverge beyond float tolerance

The JSON carries the machine-model fingerprint (search/cost_cache.py)
so committed numbers are attributable to one machine + cost-model state.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _platform import select_platform  # noqa: E402

_plat = select_platform("SEARCH_BENCH_PLATFORM")
if _plat == "cpu" and "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    # the bench mesh is (2, 2, 2): give the virtual CPU platform 8
    # devices (must land before the first backend init)
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                               + os.environ.get("XLA_FLAGS", ""))

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")

EQUIV_TOL = 1e-9  # delta replay is exact; anything above is a bug


def build_model():
    """Small-transformer search config (the acceptance-criteria graph)."""
    from flexflow_tpu import FFConfig
    from flexflow_tpu.models.transformer import build_transformer

    cfg = FFConfig(batch_size=8)
    cfg.enable_parameter_parallel = True
    cfg.enable_sequence_parallel = True
    cfg.enable_propagation = True
    return build_transformer(cfg, batch_size=8, seq_len=64, hidden=128,
                             num_heads=4, num_layers=4, ff_dim=256,
                             num_classes=10)


def run_search(ff, mesh, budget, delta: bool, chains: int = 1,
               seed: int = 0):
    from flexflow_tpu.search.mcmc import optimize

    ff.config.search_delta_sim = delta
    t0 = time.perf_counter()
    strat = optimize(ff, budget=budget, mesh=mesh, seed=seed,
                     use_native=False, chains=chains)
    wall = time.perf_counter() - t0
    # proposals_per_sec comes from the annealing loop itself (stashed
    # on model.search_stats) — the fixed per-search setup (simulator
    # build, candidate enumeration, the interleaved-upgrade pricing) is
    # identical for both legs and would drown a short smoke run
    stats = dict(ff.search_stats)
    stats["optimize_wall_s"] = wall
    return strat, stats


def equivalence_sweep(ff, mesh, moves: int = 200, seed: int = 0):
    """Random rewrite walk asserting simulate_delta == simulate per
    move; returns the max relative makespan error observed."""
    import random

    from flexflow_tpu.parallel.pconfig import OpStrategy, Strategy
    from flexflow_tpu.search.mcmc import candidate_maps
    from flexflow_tpu.search.simulator import Simulator

    ff.config.search_delta_sim = True
    sim = Simulator(ff, mesh)
    cands = {op.name: candidate_maps(op, mesh, ff.config, i)
             for i, op in enumerate(ff.ops)}
    searchable = [op for op in ff.ops if len(cands[op.name]) > 1]
    cur = Strategy()
    for op in ff.ops:
        cur.set(op.name, cur.for_op(op.name).copy())
    assert sim.delta_rebase(cur), "delta template must apply here"
    rng = random.Random(seed)
    max_rel = 0.0
    for _ in range(moves):
        op = rng.choice(searchable)
        cur.set(op.name, OpStrategy(dict(rng.choice(cands[op.name]))))
        tok = sim.simulate_delta(cur, (op.name,))
        full = sim.simulate(cur)
        if tok is None:
            sim.delta_rebase(cur)
            continue
        max_rel = max(max_rel, abs(tok.cost - full) / max(full, 1e-30))
    return max_rel


def main():
    import jax

    from flexflow_tpu import make_mesh
    from flexflow_tpu.utils.cache_dirs import arm_compile_cache
    arm_compile_cache()
    from flexflow_tpu.search.cost_cache import machine_fingerprint
    from flexflow_tpu.search.simulator import Simulator
    from flexflow_tpu.utils.profiling import search_report

    smoke = "--smoke" in sys.argv
    budget = 200 if smoke else 4000
    gate = 2.0 if smoke else None

    ff = build_model()
    mesh = make_mesh((2, 2, 2), ("data", "model", "seq"))

    # warm the cost caches so both legs price from the same state
    run_search(ff, mesh, 50, delta=True)

    # alternate the legs and take best-of-N per leg: the 2-core CI
    # hosts are shared, and a noisy neighbor mid-leg would skew a
    # single-shot ratio either way (observed 2x wall swings on
    # otherwise-idle containers)
    reps = 2 if smoke else 3
    full_runs, delta_runs = [], []
    for _ in range(reps):
        _, fs = run_search(ff, mesh, budget, delta=False)
        full_runs.append(fs)
        _, ds = run_search(ff, mesh, budget, delta=True)
        delta_runs.append(ds)
    full_stats = max(full_runs, key=lambda s: s["proposals_per_sec"])
    delta_stats = max(delta_runs, key=lambda s: s["proposals_per_sec"])
    max_rel = equivalence_sweep(ff, mesh,
                                moves=(60 if smoke else 200))

    pps_full = full_stats["proposals_per_sec"]
    pps_delta = delta_stats["proposals_per_sec"]
    speedup = pps_delta / pps_full if pps_full > 0 else 0.0
    sim = Simulator(ff, mesh)
    fingerprint = machine_fingerprint(sim.mm, mesh,
                                      precision=sim._precision(),
                                      overlap=sim.overlap_sig())
    records = [{
        "metric": "search_delta_speedup",
        "value": round(speedup, 2),
        "unit": "x",
        "extra": {
            "config": "small-transformer b8 s64 h128 4L, mesh d2xm2xs2",
            "platform": jax.default_backend(),
            "budget": budget,
            "proposals_per_sec_full": round(pps_full, 1),
            "proposals_per_sec_delta": round(pps_delta, 1),
            "runs_full": [round(s["proposals_per_sec"], 1)
                          for s in full_runs],
            "runs_delta": [round(s["proposals_per_sec"], 1)
                           for s in delta_runs],
            "delta_vs_full_max_rel_err": max_rel,
            "delta_stats": {k: v for k, v in delta_stats.items()
                            if isinstance(v, (int, float))},
            "fingerprint": fingerprint,
        },
    }]
    # search-trace convergence diagnostics (search/trace.SearchTrace):
    # acceptance rate (overall + by annealing phase), proposals/sec by
    # delta-vs-full simulation path, and the best-cost-curve tail
    trace = delta_stats.get("trace") or {}
    if trace:
        records.append({
            "metric": "search_trace",
            "value": round(trace.get("acceptance_rate", 0.0), 4),
            "unit": "acceptance_rate",
            "extra": {
                "platform": jax.default_backend(),
                "budget": budget,
                "acceptance_by_phase": [
                    round(p["rate"], 4)
                    for p in trace.get("acceptance_by_phase", [])],
                "by_path": trace.get("by_path", {}),
                "proposals_per_sec": {
                    "delta": round(pps_delta, 1),
                    "full": round(pps_full, 1)},
                "best_cost_curve_tail": trace.get(
                    "best_cost_curve", [])[-8:],
                "improvements": trace.get("improvements", 0),
                "events_recorded": trace.get("events_recorded", 0),
                "fingerprint": fingerprint,
            },
        })
    print(search_report(delta_stats))
    print(f"full: {pps_full:,.0f} proposals/s | "
          f"delta: {pps_delta:,.0f} proposals/s | "
          f"speedup {speedup:.2f}x | max rel err {max_rel:.2e}")

    if not smoke:
        path = os.path.join(ROOT, "BENCH_search.json")
        write_records(path, records)
        print(f"wrote {os.path.normpath(path)}")

    if gate is not None:
        ok = True
        if speedup < gate:
            print(f"FAIL: delta speedup {speedup:.2f}x < {gate}x gate")
            ok = False
        if max_rel > EQUIV_TOL:
            print(f"FAIL: delta/full makespans diverge "
                  f"(max rel err {max_rel:.2e} > {EQUIV_TOL})")
            ok = False
        if not trace:
            print("FAIL: search ran without a trace "
                  "(search_trace diagnostics missing)")
            ok = False
        if not ok:
            return 1
        print(f"smoke OK: speedup {speedup:.2f}x >= {gate}x, "
              f"delta == full within {EQUIV_TOL}, trace "
              f"{trace.get('proposals', 0)} proposals at "
              f"{trace.get('acceptance_rate', 0.0):.1%} acceptance")
    return 0


def write_records(path: str, records) -> None:
    """Merge-by-metric JSONL through the shared artifact writer
    (tools/_bench_io.py — serve_bench writes BENCH_serve.json through
    the same code): a partial run refreshes ITS records without
    clobbering others', tolerating individually corrupt lines in the
    old artifact. (Pre-PR-11 BENCH_search.json was one whole-file
    dict — such a line has no "metric" key and is simply
    superseded.)"""
    from _bench_io import write_records as _write
    _write(path, records)


if __name__ == "__main__":
    sys.exit(main())
